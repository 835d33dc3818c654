"""Subprocess program: decode-time DLZS sparsity parity on an N-shard
fake-device mesh (tests/test_decode_sparse.py drives it; the parent's
XLA device count is fixed at first jax init, hence the subprocess).

The spatial leg of the parity matrix:

* ``decode_hot_width=None`` + quant off — token-identical to the dense
  oracle (the sparse plumbing must be invisible);
* bounded per-shard width — first token exact (prefill is
  width-independent), greedy top-1 agreement above a floor, exactly one
  decode compile, and the pages-skipped telemetry populated;
* ``kv_quant="int8"`` at the minimal width — hot = {newest local, sink
  local} per shard is never quantized and is all the gather reads, so
  tokens must be identical to the same width without the tier while
  cold pages demonstrably quantize.

argv[1] = shard count. Prints DECODE_SPARSE_OK on success.
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
from repro.serving import (EngineCfg, LLM, SchedulerCfg, ServingEngine)
from repro.spatial import SpatialEngineCfg, SpatialServingEngine

LENGTHS = (5, 21, 40, 64)
GEN = 24

cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
params = lm.init(jax.random.PRNGKey(1), cfg)
prompts = [(np.arange(l, dtype=np.int32) * 7 + i) % cfg.vocab
           for i, l in enumerate(LENGTHS)]


def run(llm):
    handles = [llm.submit(p, max_tokens=GEN, rid=i)
               for i, p in enumerate(prompts)]
    done = llm.run_until_done(max_steps=10_000)
    assert all(h.done for h in handles)
    return done


def spatial(width=None, kv_quant=None):
    scfg = SchedulerCfg(chunk_pages=1, prefill_tokens=16,
                        decode_hot_width=width, kv_quant=kv_quant)
    return LLM(SpatialServingEngine(cfg, params, SpatialEngineCfg(
        n_shards=N_SHARDS, max_batch=2, page_size=16, n_pages_local=24,
        hot_pages_local=8, recent_pages=2, eos_id=-1), scfg))


def agreement(got, want):
    fr = []
    for rid in want:
        n = 0
        for x, y in zip(got[rid], want[rid]):
            if x != y:
                break
            n += 1
        fr.append(n / max(len(want[rid]), 1))
    return sum(fr) / len(fr)


want = run(LLM(ServingEngine(cfg, params,
                             EngineCfg(max_batch=2, max_len=128,
                                       eos_id=-1))))

# 1. width=None: bit-identical to the dense oracle
llm = spatial()
got = run(llm)
assert got == want, f"width=None changed tokens:\n{got}\n{want}"
assert llm.stats()["decode_compiles"] == 1
print(f"[{N_SHARDS} shards] width=None: exact")

# 2. bounded per-shard width: first-token exactness + agreement floor
llm = spatial(width=2)
got = run(llm)
for rid in want:
    assert got[rid][0] == want[rid][0], f"rid {rid} first token"
agr = agreement(got, want)
assert agr >= 0.5, f"width=2 agreement {agr:.3f} < 0.5"
st = llm.stats()
assert st["decode_compiles"] == 1
assert st["hot_width"] == 2
spars = llm.engine.backend.decode_sparsity
assert spars is not None and spars["pages_hot"] <= spars["pages_total"]
print(f"[{N_SHARDS} shards] width=2: agreement {agr:.3f}")

# 3. int8 tier at minimal width: token-exact, cold pages quantized
base = run(spatial(width=2))
llm = spatial(width=2, kv_quant="int8")
got = run(llm)
assert got == base, "unread int8 tier perturbed the fp gather"
kq = llm.stats()["kv_quant"]
assert kq["quantize_events"] > 0, "no cold page ever quantized"
assert kq["bytes_per_page_int8"] < kq["bytes_per_page_fp"]
print(f"[{N_SHARDS} shards] width=2+int8: exact, "
      f"{kq['quantize_events']} quantize events")

print("DECODE_SPARSE_OK")
