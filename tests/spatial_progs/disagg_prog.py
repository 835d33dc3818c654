"""Subprocess program: disaggregated serving with a sequence-sharded
spatial PREFILL instance handing off into a single-pool paged DECODE
instance — the backend-uniform flat-payload wire format crossing
backend kinds. Runs the shared router parity scenario plus the
transfer-seam chaos scenario (tests/disagg_scenarios.py) on a
fake-device mesh.

argv[1] = shard count for the spatial prefill instance (default 2).
Prints DISAGG_OK on success."""

import os
import sys

N_SHARDS = int(sys.argv[1]) if len(sys.argv) > 1 else 2
os.environ["XLA_FLAGS"] = \
    f"--xla_force_host_platform_device_count={N_SHARDS}"
_HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(_HERE, ".."))               # scenarios
sys.path.insert(0, os.path.join(_HERE, "..", "..", "src"))

import dataclasses

import jax

import disagg_scenarios as dscen
import engine_core_scenarios as scen
from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import (DisaggRouter, LLM, PagedEngineCfg,
                           PagedServingEngine, SchedulerCfg)
from repro.serving import parity
from repro.spatial import SpatialEngineCfg, SpatialServingEngine

cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
params = lm.init(jax.random.PRNGKey(1), cfg)


def _decode(scfg=None):
    return PagedServingEngine(
        cfg, params,
        PagedEngineCfg(max_batch=4, page_size=16, n_pages=64,
                       hot_pages=4, eos_id=-1),
        scfg or SchedulerCfg(chunk_pages=1))


def make_router(*, fault_plan=None, staging="device",
                transfer_retries=2, tel=None):
    pre = SpatialServingEngine(
        cfg, params,
        SpatialEngineCfg(n_shards=N_SHARDS, max_batch=2, page_size=16,
                         n_pages_local=32, hot_pages_local=4, eos_id=-1),
        SchedulerCfg(chunk_pages=1, prefill_tokens=48))
    return DisaggRouter(pre, _decode(), telemetry=tel,
                        fault_plan=fault_plan, staging=staging,
                        transfer_retries=transfer_retries)


def make_single():
    # parity reference: a single instance of the DECODE backend
    return LLM(_decode())


def _tie(prompt, got, want):
    # sharded prefill sums in another order than the paged decode
    # instance's own replay: audit greedy argmax ties at the divergence
    # point like the spatial chaos conformance
    return scen._greedy_tie(cfg, params, prompt, got, want,
                            parity.TIE_ULPS)


print(f"[{N_SHARDS}-shard spatial -> paged] "
      + dscen.scenario_disagg_parity(make_router, make_single, cfg,
                                     greedy_tie=_tie)
      + " OK")
print(f"[{N_SHARDS}-shard spatial -> paged] "
      + dscen.scenario_disagg_chaos(make_router, make_single, cfg,
                                    greedy_tie=_tie)
      + " OK")
print("DISAGG_OK")
