"""Spatial serving runtime: sequence-sharded ultra-long-context engine.

Design note
===========

PRs 1-2 built a single-device paged serving stack: one page pool caps
the longest servable prompt at one device's memory. This package deploys
that stack onto a multi-device mesh the way the paper's Spatial-STAR
deployment maps the STAR pipeline onto a multi-core spatial
architecture:

* ``topology``     — the shard ring: mesh construction (fake-device
                     friendly via ``xla_force_host_platform_device_count``),
                     striped page -> shard ownership, and the MRCA-derived
                     neighbor schedule that realizes the partial-state
                     ring on a wrap-around-free mesh NoC.
* ``sharded_pool`` — one ``kvcache`` page pool per shard behind a
                     global-logical-page interface: prefix sharing,
                     DLZS-scored eviction and hot-page retention all run
                     per shard; capacity = n_shards x local pool.
* ``engine``       — ``SpatialServingEngine``: ultra-long prompts
                     prefill shard-locally in page-aligned chunks with
                     the causal cross-shard part merged as partial
                     softmax (m, l, o) states (DRAttention's combination
                     as a psum tree); decode broadcasts the query, each
                     shard attends over its local pages via the paged
                     gather, and the partials merge to the owner. One
                     decode compilation, exact numerics.
The serve loop lives in the backend-agnostic ``repro.serving.api.LLM``
front door (QoS/SLA submission, tick driving, streaming, TTFT/latency
metrics); the engine here is a thin ``Backend`` under the shared
``serving.engine_core.EngineCore`` executor, so chunked/batched prefill,
lazy cold-page shedding and preempt/swap are literally the paged
engine's code paths, shard-tagged. (The old ``Orchestrator`` entry point
was removed after its one-PR deprecation window — construct ``LLM``
directly.)

Context length scales with device count: a prompt that overflows one
shard's pool (rejected by ``PagedServingEngine.submit``) stripes across
the mesh and serves normally — the acceptance workload in
``tests/test_spatial.py`` and ``benchmarks/serving.py --spatial``.
"""

from repro.spatial.engine import (SpatialBackend, SpatialEngineCfg,
                                  SpatialServingEngine)
from repro.spatial.sharded_pool import ShardedPagePools, ShardPoolExhausted
from repro.spatial.topology import (ShardTopology, ensure_host_devices,
                                    require_devices)

__all__ = ["ShardPoolExhausted", "ShardTopology",
           "ShardedPagePools", "SpatialBackend", "SpatialEngineCfg",
           "SpatialServingEngine", "ensure_host_devices", "require_devices"]
