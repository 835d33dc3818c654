"""One run of one benchmark cell, from device check to the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; everything else is found by name:

    configs/<config>.json      registry name, overrides, engine sizes,
                               the published sizes and the check's limit
    models/<model>.py          seeded weights + the plain reference
    traffic/<mix>.json         arrival kind, lengths, rate or clients
    arrivals/<kind>.py         the generator for that arrival kind
    metrics/<metric>.py        one reader per per-layer metric

so a later change adds a configuration, a mix, an arrival kind or a
metric as new files. A run:

1. checks for the chips the cell asks for (no CPU fallback), turns the
   persistent compilation cache on, draws the weights on the device from
   the seed and builds ``LLM.from_config(..., backend="paged")``;
2. sets up: fills the page pool's free list with cached prompt pages, as
   a long-running server's pool is, then serves the generator's warm-up
   requests (which compiles every program the traffic uses); the time
   from process start to here is ``setup_s``;
3. drives the traffic for ``seconds`` through ``LLM.submit``/``tick``
   and stamps every token on the host clock; in an open loop it then
   keeps the load on until every request due in the window has its
   first token;
4. reads the device's peak memory, frees the program, and checks against
   the reference every request still in a lane at the close (the tokens
   it was served so far) and a seeded sample of the finished ones, the
   longest among them: the widest gap by which a served token's logit
   lies below the reference's best, at every served position.

With ``trace`` the window runs under the JAX profiler, with benchmark
spans around the calls into the program (``bench.*``), and the result
holds the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from benchmarks.chip import counts, trace_reduce, traffic

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
DRAIN_LIMIT_S = 60.0
# names the trace gives the program's compiled code
PAGED_KERNEL = r"tpu_custom_call"      # the one Pallas kernel in decode
DECODE_PROGRAM = r"decode"
UNNAMED_PROGRAM = r"jit__unknown"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_module(path: pathlib.Path):
    """Import one file found by name (arrival kinds, metric readers,
    models); file names may hold dots, so not through the package."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(kind: str, name: str, bench_dir: pathlib.Path = BENCH):
    """The file of one named piece: ``traffic`` mixes are JSON data;
    ``arrivals`` kinds, ``metrics`` readers and ``models`` are Python
    modules, returned imported."""
    if kind == "traffic":
        return json.loads((bench_dir / kind / f"{name}.json").read_text())
    return load_module(bench_dir / kind / f"{name}.py")


def load_cell(name: str, root: pathlib.Path = ROOT):
    """(benchmark, cell, configuration, traffic mix) for cell ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    return bench, cell, config, find("traffic", cell["traffic"])


def metric_names(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


class CompileMeter:
    """XLA compile count and time, from JAX's monitoring events (a
    persistent-cache hit counts as its load time)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Live:
    """One request as the benchmark sees it; times are host seconds."""
    req: traffic.Req
    rid: int
    prompt: np.ndarray
    due: float
    submitted: float
    n_seen: int = 0
    first: Optional[float] = None
    last: Optional[float] = None        # stamp of the newest token
    win_n: int = 0
    done: Optional[float] = None
    outcome: Optional[str] = None
    out: Optional[list] = None


class Probe:
    """What the traced run records at the program's seams: lanes and
    context lengths of each decode step, and real tokens and past lengths
    of each batched prefill wave."""

    def __init__(self):
        self.decodes: list[tuple[float, list[int]]] = []
        self.waves: list[tuple[float, list[tuple[int, int]]]] = []

    def install(self, llm) -> None:
        import jax
        ann = jax.profiler.TraceAnnotation
        eng = llm.engine
        be = getattr(eng, "backend", None)

        def wrap(obj, name, span, record=None):
            fn = getattr(obj, name, None)
            if fn is None:
                log(f"probe: {type(obj).__name__}.{name} not found; "
                    f"its span and records are left out")
                return

            def wrapped(*a, **k):
                if record is not None:
                    record(*a, **k)
                with ann(span):
                    return fn(*a, **k)
            setattr(obj, name, wrapped)

        def on_decode(slots, tables, lengths, *_, **__):
            self.decodes.append((time.perf_counter(),
                                 [int(lengths[s]) + 1 for s in slots]))

        def on_wave(flat, seg, pos, past_len, last_index, lanes, *_, **__):
            seg = np.asarray(seg)
            rows = []
            for lane in lanes:
                s = lane["slot"]
                first = int(np.argmax(seg == s))
                rows.append((int(last_index[s]) - first + 1,
                             int(past_len[s])))
            self.waves.append((time.perf_counter(), rows))

        if be is not None:
            wrap(be, "decode_step", "bench.decode_step", on_decode)
            wrap(be, "dispatch_wave", "bench.dispatch_wave", on_wave)
            wrap(be, "_page_state", "bench.page_state")
        wrap(eng, "exec_admit", "bench.admit")


class Driver:
    """Submits the generator's requests and stamps each token after the
    tick that produced it; ``gaps`` holds every gap between two
    consecutive tokens of a request that were both stamped in the
    window (tokens a tick hands over together are 0 apart)."""

    def __init__(self, llm, arrivals, seed: int, vocab: int):
        self.llm = llm
        self.arr = arrivals
        self.seed = seed
        self.vocab = vocab
        self.live: dict[int, Live] = {}
        self.finished: list[Live] = []
        self.all: list[Live] = []
        self.pending: list[traffic.Req] = []
        self.lateness: list[float] = []
        self.gaps: list[float] = []
        self.t0 = self.t1 = None
        self.ticks = self.window_ticks = 0

    def prompt(self, idx: int, n: int) -> np.ndarray:
        """Tokens of request ``idx`` (negative: the pool-filling ones)."""
        key = [self.seed, 3, idx] if idx >= 0 else [self.seed, 4, -idx]
        rng = np.random.default_rng(key)
        return rng.integers(0, self.vocab, n, dtype=np.int32)

    def submit(self, req: traffic.Req, due: float) -> Live:
        prompt = self.prompt(req.idx, req.prompt_len)
        now = time.perf_counter()
        h = self.llm.submit(prompt, max_tokens=req.max_tokens)
        lv = Live(req, h.rid, prompt, due, now)
        self.live[h.rid] = lv
        self.all.append(lv)
        return lv

    def tick(self) -> None:
        self.llm.tick()
        self.ticks += 1
        self.poll(time.perf_counter())
        self.submit_pending()

    def submit_pending(self) -> None:
        """Closed-loop clients send their next request at once."""
        for r in self.pending:
            self.submit(r, time.perf_counter())
        self.pending = []

    def poll(self, now: float) -> None:
        in_win = self.t0 is not None and self.t1 is None
        for rid in list(self.live):
            lv = self.live[rid]
            rec = self.llm.records[rid]
            n = len(rec.req.out or ())
            if n > lv.n_seen:
                if lv.first is None:
                    lv.first = now
                if in_win:
                    if lv.last is not None and lv.last >= self.t0:
                        self.gaps.append(now - lv.last)
                        self.gaps += [0.0] * (n - lv.n_seen - 1)
                    lv.win_n += n - lv.n_seen
                lv.last = now
                lv.n_seen = n
            if rec.done_t is not None:
                lv.done = now
                lv.outcome = rec.outcome or "done"
                lv.out = list(rec.req.out or ())
                del self.live[rid]
                self.finished.append(lv)
                if lv.req.idx >= 0:
                    self.pending += self.arr.on_done(lv.req, now)

    def snapshot(self) -> None:
        """The tokens served so far to every request still in a lane."""
        for rid, lv in self.live.items():
            lv.out = list(self.llm.records[rid].req.out or ())

    def serve_until(self, cond: Callable[[], bool]) -> None:
        while not cond():
            if not self.llm.has_work():
                raise RuntimeError("engine idle before the set-up ended")
            self.tick()


def _p(xs, q) -> Optional[float]:
    return float(np.percentile(xs, q)) if len(xs) else None


def build(config: dict, seed: int):
    """The program's model configuration, the seeded weights and the LLM."""
    import jax
    from repro.configs import get_config
    from repro.serving import LLM, PagedEngineCfg

    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config["overrides"])
    m = config["model"]
    for key in ("d_model", "n_layers", "n_heads", "n_kv", "d_ff", "vocab",
                "qkv_bias", "rope_fraction"):
        if getattr(cfg, key) != m[key]:
            raise SystemExit(f"configuration {config['registry']}: program "
                             f"has {key}={getattr(cfg, key)}, the file "
                             f"says {m[key]}")
    model = find("models", config["reference"])
    wseed = int(np.random.default_rng([seed, 0]).integers(2 ** 31))
    params = model.make_params(m, cfg.vocab_padded,
                               jax.random.PRNGKey(wseed))
    jax.block_until_ready(params)
    e = config["engine"]
    ecfg = PagedEngineCfg(max_batch=e["max_batch"], page_size=e["page_size"],
                          n_pages=e["n_pages"], hot_pages=e["hot_pages"],
                          batch_past_pages=e["batch_past_pages"], eos_id=-1)
    llm = LLM.from_config(cfg, backend="paged", params=params,
                          engine_cfg=ecfg)
    return cfg, model, params, llm


def set_up(drv: Driver, config: dict, mix: dict) -> dict:
    """Fill the pool's free list with cached prompt pages, then serve the
    warm-up requests; returns what was done."""
    e = config["engine"]
    page = e["page_size"]
    warm = drv.arr.warmup()
    # leave free only half the pages the warm-up prompts take, so their
    # prefill already evicts cached pages (as every allocation will)
    keep = sum(-(-r.prompt_len // page) for r in warm) // 2 \
        if drv.arr.closed else 0
    free = e["n_pages"] - 1 - keep
    plen = int(mix["prompt"]["hi"])
    n_fill = max(0, -(-free // (plen // page)))
    fill = [traffic.Req(-1 - i, -1.0, plen, 1) for i in range(n_fill)]
    t = time.perf_counter()
    lvs = [drv.submit(r, t) for r in fill]
    drv.serve_until(lambda: all(lv.done for lv in lvs))
    t_fill = time.perf_counter() - t
    t = time.perf_counter()
    lvs = [drv.submit(r, t) for r in warm]
    if drv.arr.closed:      # clients keep their first requests running
        drv.serve_until(lambda: all(lv.first for lv in lvs)
                        and any(lv.n_seen > 1 for lv in lvs))
    else:
        drv.serve_until(lambda: all(lv.done for lv in lvs))
    drv.finished.clear()
    drv.all = list(drv.live.values())
    return {"fill_requests": n_fill, "fill_s": t_fill,
            "warm_requests": len(warm), "warm_s": time.perf_counter() - t}


def window(drv: Driver, seconds: float, trace_dir: Optional[str]):
    """Drive the traffic for ``seconds``; returns the traced ``Trace``."""
    import jax
    ann = jax.profiler.TraceAnnotation
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    closed = drv.arr.closed
    win_span = ann(trace_reduce.WINDOW_SPAN)
    win_span.__enter__()
    drv.t0 = time.perf_counter()
    t_end = drv.t0 + seconds
    if not closed:
        drv.arr.start(seconds)

    def step(now: float, t_stop: float) -> None:
        with ann("bench.submit"):
            drv.submit_pending()
            for r in drv.arr.due(now - drv.t0):
                due = drv.t0 + r.due_t
                drv.lateness.append(now - due)
                drv.submit(r, due)
        if drv.llm.has_work():
            w0, c0 = time.perf_counter(), time.process_time()
            with ann("bench.tick"):
                drv.llm.tick()
            w, c = time.perf_counter() - w0, time.process_time() - c0
            if w > longest[0]:
                longest[:] = [w, c, w0 - drv.t0]
            drv.ticks += 1
            with ann("bench.poll"):
                drv.poll(time.perf_counter())
        elif not closed:
            with ann("bench.wait"):
                nxt = min(drv.t0 + drv.arr.next_due(), t_stop)
                time.sleep(max(0.0, nxt - time.perf_counter()))

    gc_s = [0.0, 0, 0.0]            # seconds, collections, open since

    def on_gc(phase, _info):
        if phase == "start":
            gc_s[2] = time.perf_counter()
        else:
            gc_s[0] += time.perf_counter() - gc_s[2]
            gc_s[1] += 1

    gc.callbacks.append(on_gc)
    longest = [0.0, 0.0, 0.0]       # longest tick: wall s, CPU s, at
    cpu0 = time.process_time()
    now = drv.t0
    ticks0 = drv.ticks
    while now < t_end:
        step(now, t_end)
        now = time.perf_counter()
    drv.t1 = now
    drv.window_ticks = drv.ticks - ticks0
    gc.callbacks.remove(on_gc)
    log(f"host in the window: process CPU {time.process_time() - cpu0:.3f} "
        f"s of {now - drv.t0:.3f} s, {gc_s[1]} garbage collections "
        f"{gc_s[0]:.3f} s; longest tick {longest[0]:.3f} s "
        f"({longest[1]:.3f} s CPU) at {longest[2]:.3f} s")
    win_span.__exit__(None, None, None)
    if not closed:      # keep the load on until the window's requests start
        due_in = [lv for lv in drv.all if drv.t0 <= lv.due < t_end]
        limit = drv.t1 + DRAIN_LIMIT_S
        while (any(lv.first is None and lv.done is None for lv in due_in)
               and time.perf_counter() < limit):
            step(time.perf_counter(), limit)
    drv.snapshot()
    if not trace_dir:
        return None
    jax.profiler.stop_trace()
    return trace_reduce.load(trace_reduce.latest_xplane(trace_dir))


def end_to_end(drv: Driver, seconds: float) -> tuple[dict, int, int]:
    """End-to-end metrics, requests attempted and failed."""
    t0, t1 = drv.t0, drv.t1
    t_end = t0 + seconds
    if drv.arr.closed:
        attempted = [lv for lv in drv.all if lv.win_n or lv.submitted >= t0]
    else:
        attempted = [lv for lv in drv.all if t0 <= lv.due < t_end]
    failed = [lv for lv in attempted
              if (lv.outcome not in (None, "done"))
              or (not drv.arr.closed and lv.first is None)]
    ttft = [lv.first - lv.due for lv in attempted if lv.first is not None]
    tokens = sum(lv.win_n for lv in drv.all)
    out = {"ttft_p90_ms": (_p(ttft, 90), "ms"),
           "itl_p95_ms": (_p(drv.gaps, 95), "ms"),
           "out_tok_s": (tokens / (t1 - t0), "tokens/s")}
    out = {k: (None if v is None else (v * 1e3 if u == "ms" else v), u)
           for k, (v, u) in out.items()}
    backlog = sum(1 for lv in attempted if lv.first is None or lv.first > t1)
    log(f"window {t1 - t0:.3f} s: {len(attempted)} requests attempted, "
        f"{len(failed)} failed, {tokens} tokens, {drv.window_ticks} "
        f"ticks; {len(ttft)} first tokens, {backlog} of them after the "
        f"window; {len(drv.gaps)} gaps between tokens, p50 "
        f"{_p(drv.gaps, 50)} s, max {max(drv.gaps, default=None)} s; "
        f"ttft p50 {_p(ttft, 50)} s")
    return out, len(attempted), len(failed)


def check(drv: Driver, model, m: dict, params, mix: dict, seed: int,
          *, fp8: bool = False) -> dict:
    """Reference gaps over every request still in a lane at the close
    (so each lane is checked) and a seeded sample of ``check_sample``
    finished ones, the longest among them: ``gap`` is the widest gap by
    which a served token's logit lies below the reference's best; with
    ``fp8`` also ``control_gap``, the same for the token the fp8 control
    ranks first at each of those positions."""
    done = [lv for lv in drv.finished if lv.outcome == "done"]
    short = [lv for lv in done if len(lv.out) != lv.req.max_tokens]
    sample = [lv for lv in drv.live.values() if lv.out]
    if done:
        done.sort(key=lambda lv: (len(lv.prompt) + len(lv.out), lv.rid))
        rest = done[:-1]
        rng = np.random.default_rng([seed, 5])
        k = min(len(rest), int(mix["check_sample"]) - 1)
        sample += [done[-1]] + [rest[i] for i in sorted(
            rng.choice(len(rest), k, replace=False))]
    gap = ctl = 0.0
    n_tok = 0
    for lv in sample:
        toks = np.concatenate([lv.prompt, np.asarray(lv.out[:-1], np.int32)])
        pos = np.arange(len(lv.prompt) - 1, len(toks))
        served = np.asarray(lv.out)
        ref = model.logits_at(m, params, toks, pos)
        best = ref.max(axis=1)
        rows = np.arange(len(pos))
        gap = max(gap, float((best - ref[rows, served]).max()))
        if fp8:
            low = model.logits_at(m, params, toks, pos, fp8=True)
            ctl = max(ctl, float((best - ref[rows, low.argmax(axis=1)])
                                 .max()))
        n_tok += len(pos)
    out = {"checked": len(sample), "tokens": n_tok,
           "gap": gap if sample else None, "short": len(short)}
    if fp8:
        out["control_gap"] = ctl if sample else None
    return out


def passes(chk: dict, gap: Optional[float], failed: int,
           limit: float) -> bool:
    """The verdict, for the program's tokens and the control's alike."""
    return (failed == 0 and chk["checked"] > 0 and chk["short"] == 0
            and gap is not None and gap <= limit)


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, *,
             seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, peak: Optional[dict] = None,
             tamper: Optional[Callable] = None,
             control: bool = False) -> Optional[dict]:
    """One run; returns the result object, or None (after a message on
    standard error) when the chips are not there. The tests run it on
    the CPU with ``require_tpu=False``, a stand-in ``peak`` table and a
    ``tamper`` hook that breaks the served path. With ``control`` the
    fp8 control's tokens take the program's place in the verdict, on the
    same sample, and ``program`` holds the program's own verdict and gap
    (``control.py``)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    if require_tpu and device["platform"] != "tpu":
        print("bench: no TPU found; this benchmark never times on "
              f"{device['platform']}", file=sys.stderr)
        return None
    if device["count"] < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} chips, "
              f"JAX sees {device['count']}", file=sys.stderr)
        return None
    peak = counts.peaks(device["kind"]) if peak is None else peak

    if require_tpu:     # every program, however small, from the cache
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()

    cfg, model, params, llm = build(config, seed)
    if tamper is not None:
        tamper(llm)
    probe = Probe() if trace else None
    if probe:
        probe.install(llm)
    arr = find("arrivals", mix["arrivals"]).Arrivals(
        mix, seed, max_batch=config["engine"]["max_batch"])
    drv = Driver(llm, arr, seed, config["model"]["vocab"])
    done = set_up(drv, config, mix)
    setup_s = time.perf_counter() - t_start
    c0 = (meter.compiles, meter.seconds)
    log(f"set-up {setup_s:.3f} s: pool fill {done['fill_requests']} "
        f"requests {done['fill_s']:.3f} s, warm-up {done['warm_requests']} "
        f"requests {done['warm_s']:.3f} s; {meter.compiles} compiles "
        f"{meter.seconds:.3f} s ({meter.cache_hits} cache hits)")

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        tr = window(drv, seconds, tdir if trace else None)
    n_comp = meter.compiles - c0[0]
    log(f"compiles inside the window and after it: {n_comp} "
        f"({meter.seconds - c0[1]:.3f} s)")
    if drv.lateness:
        lat = np.asarray(drv.lateness) * 1e3
        log(f"generator lateness over {len(lat)} arrivals: p50 "
            f"{np.percentile(lat, 50):.3f} ms, p99 "
            f"{np.percentile(lat, 99):.3f} ms, max {lat.max():.3f} ms")
    e2e, attempted, failed = end_to_end(drv, seconds)
    st = llm.stats()
    sched = st.get("sched")
    log(f"engine: preemptions={getattr(sched, 'preemptions', None)} "
        f"fault_retries={getattr(sched, 'fault_retries', None)} "
        f"decode_compiles={st.get('decode_compiles')} pool={st.get('pool')}")
    mem = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in mem)

    drv.llm = llm = None
    gc.collect()
    t = time.perf_counter()
    chk = check(drv, model, config["model"], params, mix, seed,
                fp8=control)
    limit = float(config["check"]["logit_gap_limit"])
    gap = chk["control_gap"] if control else chk["gap"]
    correct = passes(chk, gap, failed, limit)
    log(f"check: {chk['checked']} requests ({len(drv.live)} still in a "
        f"lane), {chk['tokens']} served tokens against the float32 "
        f"reference in {time.perf_counter() - t:.3f} s; {chk['short']} "
        f"finished short" + (" [fp8 control in the program's place]"
                             if control else ""))

    if trace:
        ctx = MetricContext(config["model"], peak, tr, probe, drv, seconds)
        metrics = {}
        for spec in metric_names(bench, cell["name"], "per_layer"):
            val = find("metrics", spec["name"]).read(ctx)
            if val is not None:
                metrics[spec["name"]] = {"value": val, "unit": spec["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
    else:
        e2e["setup_s"] = (setup_s, "s")
        metrics = {}
        for spec in metric_names(bench, cell["name"], "end_to_end"):
            val, unit = e2e[spec["name"]]
            if val is not None:
                metrics[spec["name"]] = {"value": val, "unit": unit}
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0},
              "short_answers": {"value": chk["short"], "limit": 0}}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    if control:
        result["program"] = {"correct": passes(chk, chk["gap"], failed,
                                               limit),
                             "logit_gap": chk["gap"]}
    result["checks"] = checks
    return result


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read: the model's sizes, the
    chip's peaks, the reduced trace, the seam records and the requests.
    ``in_window(t)`` tells whether a host time falls in the window."""
    model: dict
    peak: dict
    trace: trace_reduce.Trace
    probe: Probe
    driver: Driver
    seconds: float

    def in_window(self, t: float) -> bool:
        return self.driver.t0 <= t <= self.driver.t1

    def decodes(self) -> list[list[int]]:
        return [c for t, c in self.probe.decodes if self.in_window(t)]

    def waves(self) -> list[list[tuple[int, int]]]:
        return [w for t, w in self.probe.waves if self.in_window(t)]

    def decode_flops(self) -> float:
        return sum(counts.decode_flops(self.model, c)
                   for c in self.decodes())

    def prefill_flops(self) -> float:
        return sum(counts.prefill_flops(self.model, n, past)
                   for w in self.waves() for n, past in w)

    def decode_program_s(self) -> float:
        """Device seconds of the decode program: the one named for
        decode, or, while the program leaves its jits unnamed
        (``jit__unknown``), the one that runs the paged-decode kernel."""
        t = self.trace
        return (t.program_s(DECODE_PROGRAM)
                or t.program_s(UNNAMED_PROGRAM, holding=PAGED_KERNEL))

    def idle_share(self) -> Optional[float]:
        if self.trace.window_s <= 0 or not self.trace.ops:
            return None
        return 100.0 * (1.0 - self.trace.busy_s() / self.trace.window_s)


def print_result(result: dict) -> None:
    """The checks as the last lines of standard error, then the result
    as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
