"""Subprocess program: SpatialServingEngine spatial-SPECIFIC acceptance
on N fake devices. (The backend-agnostic scenarios — pressure/swap
parity, batched-prefill parity, lazy shed, admission — live in the
shared conformance suite: tests/spatial_progs/conformance_prog.py.)

argv[1] = shard count. Asserts, on a smoke LM:
  1. token-for-token parity with PagedServingEngine on a mixed-length
     batch under chunked prefill, with ONE decode compilation — the
     cross-BACKEND exactness claim (partial (m,l,o) psum merge == the
     single-pool gather+softmax);
  2. a prompt longer than a single shard's page pool is rejected by the
     paged engine but admitted AND served by the spatial engine;
  3. cross-shard prefix sharing: same-prefix prompts share pages inside
     each shard's pool.
Prints ALL_OK on success.
"""

import os
import sys

N_SHARDS = int(sys.argv[1]) if len(sys.argv) > 1 else 2
os.environ["XLA_FLAGS"] = \
    f"--xla_force_host_platform_device_count={N_SHARDS}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import dataclasses

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import (PagedEngineCfg, PagedServingEngine, Request,
                           SchedulerCfg)
from repro.spatial import SpatialEngineCfg, SpatialServingEngine

cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
params = lm.init(jax.random.PRNGKey(1), cfg)


def reqs(lengths, max_tokens=5):
    return [Request(rid=i, prompt=(np.arange(l, dtype=np.int32) * 7 + i)
                    % cfg.vocab, max_tokens=max_tokens)
            for i, l in enumerate(lengths)]


# 1. mixed-length token parity vs the paged engine (chunked prefill on)
mixed = (5, 8, 17, 33, 40)
paged = PagedServingEngine(cfg, params, PagedEngineCfg(
    max_batch=2, page_size=16, n_pages=32, hot_pages=4, recent_pages=2,
    eos_id=-1), SchedulerCfg(chunk_pages=1, prefill_tokens=16))
want = paged.run(reqs(mixed))
sp = SpatialServingEngine(cfg, params, SpatialEngineCfg(
    n_shards=N_SHARDS, max_batch=2, page_size=16, n_pages_local=32,
    hot_pages_local=4, recent_pages=2, eos_id=-1),
    SchedulerCfg(chunk_pages=1, prefill_tokens=16))
got = sp.run(reqs(mixed))
assert got == want, f"mixed-length parity broke:\n{got}\n{want}"
assert sp.stats()["decode_compiles"] == 1, sp.stats()["decode_compiles"]
print(f"parity[{N_SHARDS} shards]: OK")

# 2. ultra-long prompt: overflows one shard's pool, stripes across N
small = 8                                     # 7 usable pages per shard
long_prompt = (np.arange(150, dtype=np.int32) * 3 + 11) % cfg.vocab
pg_small = PagedServingEngine(cfg, params, PagedEngineCfg(
    max_batch=2, page_size=16, n_pages=small, hot_pages=12, eos_id=-1),
    SchedulerCfg(chunk_pages=2, prefill_tokens=32))
try:
    pg_small.submit(Request(rid=0, prompt=long_prompt, max_tokens=4))
    raise SystemExit("paged engine admitted an over-capacity prompt")
except ValueError:
    pass
sp_small = SpatialServingEngine(cfg, params, SpatialEngineCfg(
    n_shards=N_SHARDS, max_batch=2, page_size=16, n_pages_local=small,
    hot_pages_local=12, eos_id=-1),
    SchedulerCfg(chunk_pages=2, prefill_tokens=32))
done = sp_small.run([Request(rid=0, prompt=long_prompt, max_tokens=4)])
assert len(done[0]) == 4 and all(0 <= t < cfg.vocab for t in done[0]), done
print(f"long-context[{N_SHARDS} shards]: OK {done[0]}")

# 3. cross-shard prefix sharing
shared = np.arange(32, dtype=np.int32)        # 2 full pages
sreqs = [Request(rid=i, prompt=np.concatenate(
            [shared, np.full((4 + i,), 100 + i, np.int32)]), max_tokens=4)
         for i in range(2)]
before = sp.stats()["pools"]["shared_hits"]
sp.run(sreqs)
hits = sp.stats()["pools"]["shared_hits"] - before
assert hits >= 2, f"expected >= 2 prefix hits, got {hits}"
print(f"prefix-share[{N_SHARDS} shards]: OK ({hits} hits)")

print("ALL_OK")
