"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Drives the unified serving front door (``repro.serving.api.LLM``) over
one of the three backends:

* ``--engine paged``   — the default: the paged KV-cache engine with
  chunked prefill and the preemption scheduler (batched varlen prefill
  with the ``prefill_tokens="auto"`` budget controller by default).
* ``--engine spatial`` — the sequence-sharded multi-device runtime
  (``--shards N``): context length scales with device count. With
  ``JAX_PLATFORMS=cpu`` and fewer devices than shards it re-executes
  itself with ``xla_force_host_platform_device_count`` set (the
  fake-device harness); on an accelerator it needs N chips.
* ``--engine dense``   — the retired slot-based engine, kept as the
  parity oracle and footprint baseline (tests/benchmarks); serve it
  only to compare against the pool-backed engines.

``--disagg`` serves through the prefill/decode-disaggregated router
instead (``repro.serving.disagg``, docs/disaggregation.md): submits
land on a prefill-tuned instance of the chosen backend and the
KVTransfer fabric hands each request to a paged decode-tuned instance
at the phase boundary.

Requests carry an SLA class (``--sla-mix`` cycles interactive / standard
/ batch) that the scheduler maps onto priorities: interactive traffic is
admitted first and preempted last. ``--sla-deadlines`` enforces the
SLA-tier default TTFT/end-to-end budgets and ``--shed-watermarks HIGH
LOW`` turns on hysteresis admission shedding of low-priority traffic
under backlog (see docs/serving.md, "Robustness"). ``--full`` serves the
architecture at its published widths with seeded random weights; without
it the smoke config serves. The process exits nonzero when any request
ends ``failed`` (a backend fault that exhausted its retries).

Telemetry (``repro.obs``, see docs/observability.md) is on by default:

* ``--trace PATH`` exports a Perfetto/Chrome trace of the run
  (``.jsonl`` suffix streams JSONL, anything else writes Chrome JSON)
  and prints the per-phase time table (``tools/trace_summary.py``);
* ``--metrics TARGET`` writes the Prometheus text exposition of the
  run's ``MetricsRegistry`` — ``-`` for stdout, else a file path (point
  a node_exporter textfile collector at it);
* ``--no-telemetry`` serves with the no-op ``NULL_TELEMETRY`` (the
  library default), dropping per-token timestamps and the surfaces
  above.
"""

from __future__ import annotations

import argparse
import sys
import time

SLA_CYCLE = ("interactive", "standard", "batch")


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--engine", default="paged",
                    choices=("dense", "paged", "spatial"))
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation: serve through "
                         "a (prefill-tuned, decode-tuned) instance pair "
                         "of --engine joined by the KVTransfer fabric "
                         "(paged/spatial)")
    ap.add_argument("--shards", type=int, default=2,
                    help="sequence shards (spatial engine)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=64,
                    help="pool pages (paged: total; spatial: per shard)")
    ap.add_argument("--sla-mix", action="store_true",
                    help="cycle requests through interactive/standard/"
                         "batch SLA classes")
    ap.add_argument("--sla-deadlines", action="store_true",
                    help="enforce the SLA-tier default TTFT/e2e deadline "
                         "budgets (paged/spatial; expired requests end "
                         "with outcome 'expired')")
    ap.add_argument("--shed-watermarks", nargs=2, type=int, default=None,
                    metavar=("HIGH", "LOW"),
                    help="enable admission shedding (paged/spatial): shed "
                         "sheddable waiting requests when the backlog "
                         "crosses HIGH, until it is back at LOW")
    ap.add_argument("--shed-below-priority", type=int, default=0,
                    help="with --shed-watermarks: only requests below "
                         "this priority are sheddable (0 sheds 'batch' "
                         "but never 'standard'/'interactive')")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="export a Perfetto/Chrome trace of the run "
                         "(.jsonl streams JSONL) and print the per-phase "
                         "time table")
    ap.add_argument("--metrics", metavar="TARGET", default=None,
                    help="Prometheus text exposition after the run: "
                         "'-' for stdout, else a file path")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="serve with the no-op telemetry (the library "
                         "default); --trace/--metrics are ignored")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)

    if args.engine == "spatial":
        # the XLA device count is fixed at first jax init: on the CPU
        # grow it in a child process, on a chip fail clearly
        from repro.spatial import require_devices
        require_devices(args.shards, ["-m", "repro.launch.serve"]
                        + (argv if argv is not None else sys.argv[1:]))

    import dataclasses
    import pathlib

    import jax
    import numpy as np

    from repro import obs
    from repro.configs import ARCHS, get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.serving import (LLM, AdmissionCfg, EngineCfg,
                               PagedEngineCfg, SchedulerCfg)
    from repro.spatial import SpatialEngineCfg

    enable_compile_cache()
    if args.arch not in ARCHS:
        raise SystemExit(f"unknown arch {args.arch}; choose from "
                         f"{sorted(ARCHS)}")
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    if cfg.enc_layers or cfg.embeds_input:
        raise SystemExit(f"{args.arch}: frontend-stub archs serve via "
                         "examples/ drivers")
    if args.engine == "spatial" and cfg.star is not None:
        cfg = dataclasses.replace(cfg, star=None)
    params = lm.init(jax.random.PRNGKey(0), cfg)

    if args.engine == "dense":
        engine_cfg = EngineCfg(max_batch=args.slots, max_len=args.max_len,
                               eos_id=-1)
    elif args.engine == "paged":
        engine_cfg = PagedEngineCfg(
            max_batch=args.slots, page_size=args.page_size,
            n_pages=args.pages, hot_pages=args.max_len // args.page_size,
            eos_id=-1)
    else:
        engine_cfg = SpatialEngineCfg(
            n_shards=args.shards, max_batch=args.slots,
            page_size=args.page_size, n_pages_local=args.pages,
            hot_pages_local=args.max_len // args.page_size, eos_id=-1)
    sched_cfg = None
    if args.sla_deadlines or args.shed_watermarks:
        if args.engine == "dense":
            print("[serve] --sla-deadlines/--shed-watermarks ignored on "
                  "the dense engine (no scheduler; per-request deadlines "
                  "still apply via submit())")
        else:
            admission = None
            if args.shed_watermarks:
                high, low = args.shed_watermarks
                admission = AdmissionCfg(
                    high_watermark=high, low_watermark=low,
                    shed_below_priority=args.shed_below_priority)
            sched_cfg = SchedulerCfg(prefill_tokens="auto",
                                     sla_deadlines=args.sla_deadlines,
                                     admission=admission)
    tel = None if args.no_telemetry else obs.Telemetry(
        {"launcher": "repro.launch.serve", "engine": args.engine,
         "arch": args.arch, "disagg": args.disagg})
    if args.disagg:
        if args.engine == "dense":
            raise SystemExit("--disagg needs a pool-backed engine "
                             "(paged/spatial)")
        from repro.serving import DisaggRouter
        llm = DisaggRouter.from_config(
            cfg, backend="paged", prefill_backend=args.engine,
            params=params, shards=args.shards,
            prefill_engine_cfg=engine_cfg if args.engine != "paged"
            else None,
            prefill_sched_cfg=sched_cfg, telemetry=tel)
    else:
        llm = LLM.from_config(cfg, backend=args.engine, params=params,
                              shards=args.shards, engine_cfg=engine_cfg,
                              sched_cfg=sched_cfg, telemetry=tel)

    rng = np.random.default_rng(0)
    t0 = time.time()
    handles = [llm.submit(rng.integers(0, cfg.vocab, size=args.prompt_len,
                                       dtype=np.int32),
                          max_tokens=args.max_tokens,
                          sla=SLA_CYCLE[i % len(SLA_CYCLE)]
                          if args.sla_mix else None)
               for i in range(args.requests)]
    done = llm.run_until_done()
    rep = llm.metrics()
    n_tok = rep.get("tokens", sum(len(v) for v in done.values()))
    extra = ""
    if rep.get("requests"):
        extra = f", ttft_p50={rep['ttft_p50_ms']}ms"
        if rep.get("occupancy") is not None:
            extra += f", occupancy={rep['occupancy']}"
        if args.sla_mix:
            extra += "".join(
                f", {k}={v['ttft_mean_ms']}ms"
                for k, v in rep["per_sla"].items()
                if v["ttft_mean_ms"] is not None)
        abnormal: dict = {}
        for v in rep.get("per_sla", {}).values():
            for outcome, n in v.get("outcomes", {}).items():
                if outcome != "done":
                    abnormal[outcome] = abnormal.get(outcome, 0) + n
        if abnormal:
            extra += ", " + ", ".join(
                f"{k}={n}" for k, n in sorted(abnormal.items()))
    if args.disagg:
        tr = llm.transfer.stats()
        extra += (f", transfers={tr['n_transfers']}"
                  f", transfer_bytes={tr['bytes_total']}")
    dt = time.time() - t0
    shards = f", {args.shards} shards" if args.engine == "spatial" else ""
    mode = ", disagg" if args.disagg else ""
    print(f"[serve] {args.arch} ({'full' if args.full else 'smoke'}, "
          f"{args.engine}{shards}{mode}): "
          f"{len(done)} requests, {n_tok} tokens, "
          f"{n_tok / dt:.1f} tok/s, star={'on' if cfg.star else 'off'}"
          f"{extra}")

    if args.trace:
        if tel is None:
            print("[serve] --trace ignored (telemetry disabled)")
        else:
            path = pathlib.Path(args.trace)
            if path.parent != pathlib.Path("."):
                path.parent.mkdir(parents=True, exist_ok=True)
            if path.suffix == ".jsonl":
                tel.tracer.export_jsonl(str(path))
            else:
                tel.tracer.export_chrome(str(path))
            print(obs.format_table(obs.phase_summary(tel.tracer.events),
                                   title=args.engine))
            print(f"[serve] trace -> {path} "
                  f"(load at https://ui.perfetto.dev)")

    if args.metrics:
        if tel is None:
            print("[serve] --metrics ignored (telemetry disabled)")
        else:
            text = tel.metrics.render_prometheus()
            if args.metrics == "-":
                sys.stdout.write(text)
            else:
                pathlib.Path(args.metrics).write_text(text)
                print(f"[serve] metrics -> {args.metrics} "
                      f"({len(text.splitlines())} lines)")

    # a backend exception is isolated to its requests (retry, then
    # quarantine): the run still fails if any request ended failed
    failed = [h.rid for h in handles if h.outcome == "failed"]
    if failed:
        raise SystemExit(f"[serve] {len(failed)} of {len(handles)} "
                         f"requests failed (rids {failed}); see the "
                         f"fault warnings above")


if __name__ == "__main__":
    main()
