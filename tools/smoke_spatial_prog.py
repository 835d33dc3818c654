"""Subprocess program for the CI spatial smoke: 2-shard fake-device mesh.

Launched by tools/smoke_serve.py (the XLA device count is fixed at first
jax init, so the parent cannot host the mesh itself). Small and fast —
everything drives the unified ``LLM`` front door:

* token parity: SpatialServingEngine(2 shards) == PagedServingEngine on a
  small mixed-length batch, one decode compilation;
* capacity: a prompt that overflows one shard's pool is rejected by the
  single-pool engine and served by the 2-shard engine;
* lazy shed: under per-shard pool pressure with ``lazy_swap`` the shared
  EngineCore path sheds DLZS-cold ref-1 pages with zero full preemptions;
* front-door overhead: LLM-driven throughput within 5% of the directly
  driven engine (both warmed) — reported as ``SPATIAL_TOKS direct=..
  llm=..`` for the parent's BENCH_serving.json ``engine_core`` entry.

With ``--trace PATH`` it instead runs ONE small traced batched-prefill
workload on the 2-shard engine, exports a Chrome/Perfetto trace to PATH,
asserts shard-tagged events made it in, and prints SPATIAL_TRACE_OK.

Prints SPATIAL_OK on success; any assertion exits non-zero.
"""

import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import (LLM, PagedEngineCfg, PagedServingEngine,
                           SchedulerCfg)
from repro.spatial import SpatialEngineCfg, SpatialServingEngine

cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
params = lm.init(jax.random.PRNGKey(0), cfg)

if len(sys.argv) >= 3 and sys.argv[1] == "--trace":
    from repro import obs
    trace_path = sys.argv[2]
    tel = obs.Telemetry({"backend": "spatial", "n_shards": 2})
    llm = LLM(SpatialServingEngine(cfg, params, SpatialEngineCfg(
        n_shards=2, max_batch=2, page_size=16, n_pages_local=24,
        hot_pages_local=4, eos_id=-1),
        SchedulerCfg(chunk_pages=1, prefill_tokens=48)),
        telemetry=tel)
    for i, l in enumerate((6, 18, 35)):
        llm.submit((np.arange(l, dtype=np.int32) * 5 + i) % cfg.vocab,
                   max_tokens=4, rid=i)
    done = llm.run_until_done(max_steps=20_000)
    assert all(len(v) == 4 for v in done.values()), done
    tel.tracer.export_chrome(trace_path)
    events = obs.load_trace(trace_path)
    shard_tagged = [e for e in events
                    if (e.get("args") or {}).get("shard") is not None]
    assert shard_tagged, "no shard-tagged events in spatial trace"
    ticks = [e for e in events if e.get("name") == "tick"]
    assert ticks, "no tick spans in spatial trace"
    print(f"SPATIAL_TRACE_OK events={len(events)} "
          f"shard_tagged={len(shard_tagged)} ticks={len(ticks)}")
    sys.exit(0)


def submit_all(llm, lengths, max_tokens=4):
    for i, l in enumerate(lengths):
        llm.submit((np.arange(l, dtype=np.int32) * 5 + i) % cfg.vocab,
                   max_tokens=max_tokens, rid=i)
    return llm.run_until_done(max_steps=20_000)


# 1. parity through the front door
mixed = (6, 18, 35)
paged = LLM(PagedServingEngine(cfg, params, PagedEngineCfg(
    max_batch=2, page_size=16, n_pages=24, hot_pages=4, eos_id=-1),
    SchedulerCfg(chunk_pages=1, prefill_tokens=16)))
want = submit_all(paged, mixed)
sp = LLM(SpatialServingEngine(cfg, params, SpatialEngineCfg(
    n_shards=2, max_batch=2, page_size=16, n_pages_local=24,
    hot_pages_local=4, eos_id=-1),
    SchedulerCfg(chunk_pages=1, prefill_tokens=16)))
got = submit_all(sp, mixed)
assert got == want, f"2-shard parity broke:\n{got}\n{want}"
assert sp.stats()["decode_compiles"] == 1

# 2. capacity: overflow prompt only the sharded engine admits
long_prompt = (np.arange(150, dtype=np.int32) * 3 + 7) % cfg.vocab
small = LLM(PagedServingEngine(cfg, params, PagedEngineCfg(
    max_batch=2, page_size=16, n_pages=8, hot_pages=12, eos_id=-1)))
try:
    small.submit(long_prompt, max_tokens=4)
    raise SystemExit("single-pool engine admitted the overflow prompt")
except ValueError:
    pass
sp_small = LLM(SpatialServingEngine(cfg, params, SpatialEngineCfg(
    n_shards=2, max_batch=2, page_size=16, n_pages_local=8,
    hot_pages_local=12, eos_id=-1),
    SchedulerCfg(chunk_pages=2, prefill_tokens=32)))
h = sp_small.submit(long_prompt, max_tokens=4, rid=9)
done = sp_small.run_until_done(max_steps=20_000)
assert len(done[9]) == 4 and all(0 <= t < cfg.vocab for t in done[9])

# 3. lazy cold-page shed on the sharded pools (shared EngineCore path)
shed = LLM(SpatialServingEngine(cfg, params, SpatialEngineCfg(
    n_shards=2, max_batch=2, page_size=16, n_pages_local=6,
    hot_pages_local=2, recent_pages=2, eos_id=-1),
    SchedulerCfg(chunk_pages=1, prefill_tokens=16, swap=True,
                 lazy_swap=True)))
for i in range(2):
    shed.submit((np.arange(80, dtype=np.int32) + i) % cfg.vocab,
                max_tokens=48, rid=i)
done = shed.run_until_done(max_steps=20_000)
st = shed.stats()
assert all(len(v) == 48 for v in done.values())
assert st["sched"].sheds > 0 and st["sched"].preemptions == 0, \
    (st["sched"].sheds, st["sched"].preemptions)

# 4. front-door overhead: direct engine vs LLM, both warmed, same config
TP_LENGTHS = (40, 64, 28, 52)


def mk_engine():
    return SpatialServingEngine(cfg, params, SpatialEngineCfg(
        n_shards=2, max_batch=4, page_size=16, n_pages_local=32,
        hot_pages_local=8, eos_id=-1),
        SchedulerCfg(chunk_pages=2, prefill_tokens=96))


def reqs(seed):
    rng = np.random.default_rng(seed)
    from repro.serving.engine import Request
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=l,
                                               dtype=np.int32),
                    max_tokens=16) for i, l in enumerate(TP_LENGTHS)]


direct = mk_engine()
direct.run(reqs(7))                              # warmup
llm = LLM(mk_engine())
for r in reqs(7):
    llm.submit(r.prompt, max_tokens=r.max_tokens, rid=r.rid)
llm.run_until_done(max_steps=20_000)             # warmup
llm.clear_finished()

for attempt in range(3):                         # shared-CPU noise guard
    t0 = time.perf_counter()
    d_done = direct.run(reqs(1))
    d_tok_s = sum(len(v) for v in d_done.values()) \
        / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for r in reqs(1):
        llm.submit(r.prompt, max_tokens=r.max_tokens, rid=100 + r.rid)
    l_done = llm.run_until_done(max_steps=20_000)
    l_tok_s = sum(len(v) for v in l_done.values()) \
        / (time.perf_counter() - t0)
    llm.clear_finished()
    if l_tok_s >= 0.95 * d_tok_s:
        break
assert l_tok_s >= 0.95 * d_tok_s, \
    f"LLM front door lost spatial throughput: {l_tok_s:.1f} vs " \
    f"{d_tok_s:.1f} tok/s"
print(f"SPATIAL_TOKS direct={d_tok_s:.1f} llm={l_tok_s:.1f}")

print(f"SPATIAL_OK parity={len(want)} long_prompt={len(long_prompt)} "
      f"sheds={st['sched'].sheds} shards=2")
