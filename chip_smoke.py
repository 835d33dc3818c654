"""Serve OLMo-1B at its published widths on a TPU through ``LLM``.

The quickest proof that the serving system starts on the chip:

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # four chips: spatial backend only

One chip: a Pallas paged-decode kernel check against the XLA gather
reference, then full-width ``olmo_1b`` (16 layers, d 2048, 16 MHA heads,
vocab 50304, bf16, STAR as the config declares it) served by the paged
backend — 8 requests with prompts of 128 to 1536 tokens and 32 new tokens
each, which exercises chunked prefill, DLZS hot-page decode past 256
tokens and one decode compile. Four chips: the same model with
``star=None`` served by the sequence-sharded spatial backend over the four
devices, compared with the paged backend on one chip in the same process.

Weights are random, made from ``--seed``; no checkpoint is read. The times
printed are smoke numbers, not benchmark results. Any failed check exits
nonzero. Only when every check passed is the last line of standard output
one JSON object: ``{"ok": true, "device": {"platform": "tpu", ...}}``.
Without a TPU the script exits nonzero before serving anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

PROMPT_LENS = (128, 320, 512, 704, 896, 1088, 1280, 1536)
NEW_TOKENS = 32
PAGE = 16
N_PAGES = 1024            # ~2 GiB of bf16 KV at olmo_1b widths
# Pallas kernel vs XLA gather, same bf16 inputs, fp32 accumulation in both:
# the gather rounds the softmax weights to bf16 before the V product and
# both round the output to bf16, so they agree to a few bf16 ulps.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# Spatial vs paged greedy tokens are equal, or at the first divergence both
# tokens lie within repro.serving.parity.TIE_ULPS bf16 ulps of the top logit
# of a reference prefill: random weights give near-flat logits, so ties flip
# on the reduction order of the two backends.
SPATIAL_PROMPT_LENS = (128, 512, 1024, 1536)
SPATIAL_NEW_TOKENS = 16


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Sums XLA backend compile time (a persistent-cache hit counts as
    its load time) from JAX's monitoring events."""

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

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def kernel_check(seed: int, *, b=8, g=16, r=1, d=128, n_pages=64, w=16):
    """Pallas paged decode (compiled on a TPU) against the XLA gather at
    olmo_1b decode widths, reading the second layer of a two-layer pool.
    Returns (max abs error, within tolerance)."""
    import jax
    import jax.numpy as jnp

    from repro.kvcache import paged_attention as pa

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, g * r, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (2, n_pages, PAGE, g, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (2, n_pages, PAGE, g, d), jnp.bfloat16)
    phys = np.full((b, w), -1, np.int32)
    logical = np.full((b, w), -1, np.int32)
    kv_len = np.zeros((b,), np.int32)
    for i in range(b):
        n = int(rng.integers(1, w + 1))            # some rows half empty
        phys[i, :n] = rng.choice(np.arange(1, n_pages), n, replace=False)
        logical[i, :n] = np.arange(n)
        kv_len[i] = (n - 1) * PAGE + int(rng.integers(1, PAGE + 1))
    args = (q, kp, vp, jnp.int32(1), jnp.asarray(phys), jnp.asarray(logical),
            jnp.asarray(kv_len))
    kern = jax.jit(lambda *a: pa.paged_decode(*a, n_kv=g,
                                              backend="pallas"))(*args)
    ref = jax.jit(lambda *a: pa.paged_decode(*a, n_kv=g,
                                             backend="xla"))(*args)
    kern = np.asarray(kern, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(kern - ref)))
    ok = bool(np.all(np.abs(kern - ref)
                     <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)))
    return err, ok


def decode_runs_kernel(backend) -> bool:
    """True when the engine's compiled decode step holds a Mosaic kernel
    (a Pallas call lowered for the TPU, not interpreted)."""
    import jax
    import jax.numpy as jnp

    b, w = backend.max_batch, backend.hot_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    page_state = {"phys": i32(b, w), "logical": i32(b, w),
                  "write_page": i32(b), "write_off": i32(b)}
    lowered = backend._decode.lower(backend.params, backend.last_token,
                                    backend.cache, page_state)
    return "tpu_custom_call" in lowered.as_text()


def serve_burst(llm, vocab: int, lens, new_tokens: int, rng):
    """Submit one request per prompt length and serve them to the end."""
    handles = [llm.submit(rng.integers(0, vocab, size=n, dtype=np.int32),
                          max_tokens=new_tokens) for n in lens]
    t0 = time.perf_counter()
    llm.run_until_done()
    return handles, time.perf_counter() - t0


def serve_check(cfg, seed: int, meter: CompileMeter, *, lens=PROMPT_LENS,
                new_tokens=NEW_TOKENS, n_pages=N_PAGES) -> list[str]:
    """Serve ``cfg`` through ``LLM`` on the paged backend; returns the
    failed checks (empty when all passed)."""
    import jax

    from repro.kvcache import paged_attention as pa
    from repro.models import lm
    from repro.serving import LLM, PagedEngineCfg, SchedulerCfg

    params = lm.init(jax.random.PRNGKey(seed), cfg)
    # chunks must stay whole STAR q-tiles; the batched prefill's past
    # window only has to hold the longest prompt
    chunk = max(1, (cfg.star.block_q // PAGE) if cfg.star else 4)
    max_pages = -(-(max(lens) + new_tokens) // PAGE)
    llm = LLM.from_config(
        cfg, backend="paged", params=params,
        engine_cfg=PagedEngineCfg(max_batch=len(lens), page_size=PAGE,
                                  n_pages=n_pages, hot_pages=16, eos_id=-1,
                                  batch_past_pages=max_pages),
        sched_cfg=SchedulerCfg(chunk_pages=chunk))
    rng = np.random.default_rng(seed)
    c0 = meter.snapshot()
    cold, cold_s = serve_burst(llm, cfg.vocab, lens, new_tokens, rng)
    c1 = meter.snapshot()
    warm, warm_s = serve_burst(llm, cfg.vocab, lens, new_tokens, rng)
    c2 = meter.snapshot()

    st = llm.stats()
    sched = st["sched"]
    n_warm = sum(len(h.tokens) for h in warm)
    log(f"serve: {len(lens)} requests x {new_tokens} new tokens, prompts "
        f"{min(lens)}-{max(lens)}, pool {n_pages} pages of {PAGE}")
    log(f"smoke numbers (not benchmark results): cold burst "
        f"{cold_s:.3f} s with {c1[1] - c0[1]} compiles "
        f"{c1[0] - c0[0]:.3f} s ({c1[2] - c0[2]} cache hits); warm burst "
        f"{warm_s:.3f} s, {n_warm / warm_s:.1f} tok/s with "
        f"{c2[1] - c1[1]} compiles {c2[0] - c1[0]:.3f} s")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"smoke peak HBM {stats['peak_bytes_in_use'] / 2**30:.3f} GiB "
            f"of {stats.get('bytes_limit', 0) / 2**30:.3f} GiB")
    log(f"sched: fault_retries={sched.fault_retries} "
        f"quarantines={sched.quarantines} preemptions={sched.preemptions}; "
        f"decode_compiles={st['decode_compiles']}")

    failed = []
    for h in cold + warm:
        if h.outcome != "done" or len(h.tokens) != new_tokens:
            failed.append(f"request {h.rid}: outcome {h.outcome}, "
                          f"{len(h.tokens)} of {new_tokens} tokens")
    if sched.fault_retries or sched.quarantines:
        failed.append(f"faults: {sched.fault_retries} retries, "
                      f"{sched.quarantines} quarantines")
    if st["decode_compiles"] != 1:
        failed.append(f"decode compiled {st['decode_compiles']} times")
    backend = llm.engine.backend
    kernel = pa.default_backend() == "pallas" and decode_runs_kernel(backend)
    log(f"decode attention: backend={pa.default_backend()} "
        f"compiled_kernel={kernel}")
    if not kernel:
        failed.append("decode did not run the compiled Pallas kernel")
    return failed


def spatial_check(cfg, seed: int, shards: int, *,
                  lens=SPATIAL_PROMPT_LENS,
                  new_tokens=SPATIAL_NEW_TOKENS) -> list[str]:
    """Serve ``cfg`` (dense attention) on the spatial backend over
    ``shards`` devices and on the paged backend on one device; compare
    greedy tokens tie-aware. Returns the failed checks."""
    import jax

    from repro.models import lm
    from repro.serving import LLM, PagedEngineCfg, SchedulerCfg, parity
    from repro.spatial import SpatialEngineCfg

    cfg = dataclasses.replace(cfg, star=None)
    params = lm.init(jax.random.PRNGKey(seed), cfg)
    max_pages = -(-(max(lens) + new_tokens) // PAGE)
    local = -(-max_pages // shards)
    # hot windows cover every page of the longest request: both exact
    spatial = LLM.from_config(
        cfg, backend="spatial", shards=shards, params=params,
        engine_cfg=SpatialEngineCfg(
            n_shards=shards, max_batch=len(lens), page_size=PAGE,
            n_pages_local=2 * local * len(lens), hot_pages_local=local,
            eos_id=-1, batch_past_pages=local),
        sched_cfg=SchedulerCfg(chunk_pages=4))
    paged = LLM.from_config(
        cfg, backend="paged", params=params,
        engine_cfg=PagedEngineCfg(
            max_batch=len(lens), page_size=PAGE,
            n_pages=2 * max_pages * len(lens), hot_pages=max_pages,
            eos_id=-1, batch_past_pages=max_pages),
        sched_cfg=SchedulerCfg(chunk_pages=4))

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in lens]
    got, want = [], []
    for llm, out in ((spatial, got), (paged, want)):
        hs = [llm.submit(p, max_tokens=new_tokens) for p in prompts]
        t0 = time.perf_counter()
        llm.run_until_done()
        dt = time.perf_counter() - t0
        out.extend(hs)
        log(f"{type(llm.engine.backend).__name__}: {len(hs)} requests in "
            f"{dt:.3f} s (smoke number, compiles included)")

    failed = []
    leaves = jax.tree.leaves(spatial.engine.backend.cache["layers"])
    for leaf in leaves:
        devs = {s.device for s in leaf.addressable_shards}
        if len(devs) != shards or any(
                s.data.shape[0] != 1 for s in leaf.addressable_shards):
            failed.append(f"pool leaf {leaf.shape} not split one shard per "
                          f"device: {sorted(str(d) for d in devs)}")
            break
    log(f"spatial pool: {len(leaves)} slabs, each split over "
        f"{len({s.device for s in leaves[0].addressable_shards})} devices")

    exact = ties = 0
    for p, hs, hp in zip(prompts, got, want):
        for h in (hs, hp):
            if h.outcome != "done" or len(h.tokens) != new_tokens:
                failed.append(f"request {h.rid}: outcome {h.outcome}, "
                              f"{len(h.tokens)} tokens")
        if hs.tokens == hp.tokens:
            exact += 1
            continue
        # the reference prefill pads to multiples of 128: one compile
        div = parity.divergence(params, cfg, p, hs.tokens, hp.tokens,
                                pad_to=128)
        log(f"prompt {len(p)} (spatial vs paged): "
            f"{div.describe() if div else 'length mismatch'} "
            f"(tie tolerance {parity.TIE_ULPS} ulps)")
        if div is None or not div.is_tie():
            failed.append(f"prompt {len(p)}: spatial and paged tokens "
                          f"differ beyond a tie")
        else:
            ties += 1
    log(f"spatial vs paged: {exact} of {len(prompts)} requests "
        f"token-identical, {ties} diverge at a tie")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: paged serving + kernel check on one chip; "
                         "4: spatial backend on four chips vs paged")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script never falls back to "
              "the CPU", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, SRC)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    cfg = get_config("olmo_1b")
    if args.chips == 4:
        failed = spatial_check(cfg, args.seed, 4)
    else:
        err, ok = kernel_check(args.seed)
        log(f"pallas paged kernel vs XLA gather: max abs err {err:.6f} "
            f"(tolerance {KERNEL_ATOL} + {KERNEL_RTOL}*|ref|)")
        failed = [] if ok else [f"kernel mismatch {err:.6f}"]
        failed += serve_check(cfg, args.seed, meter)
    secs, n, hits = meter.snapshot()
    log(f"smoke numbers: whole process {n} compiles, {secs:.3f} s "
        f"({hits} persistent-cache hits)")
    if failed:
        for f in failed:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
