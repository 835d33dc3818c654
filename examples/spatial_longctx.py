"""Serve an ultra-long prompt by sequence-sharding it across a device
mesh — the spatial deployment story end to end, through the unified
``LLM`` front door.

A prompt that overflows a single device's KV page pool is striped
page-by-page over 4 shards (fake host devices here; real accelerators on
hardware): each shard prefills the chunks against its resident pages with
the cross-shard causal part merged as partial-softmax states, and every
decode step broadcasts the query, attends shard-locally, and merges the
partial (m, l, o) back — DRAttention's combination as a psum tree. Next
to it, a handful of normal requests with mixed SLA classes show the
front door's QoS path on the same mesh. The request mix comes from the
same scenario builder the spatial benchmark uses
(``repro.serving.scenarios.longctx_mix``).

Run:  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/spatial_longctx.py
(relaunches itself with xla_force_host_platform_device_count=4; on a host
with 4 chips it runs on them directly)
"""

N_SHARDS = 4


def main():
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving import LLM, PagedEngineCfg, SchedulerCfg
    from repro.serving.scenarios import longctx_mix
    from repro.spatial import SpatialEngineCfg

    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(0), cfg)

    pages_local = 12                        # 11 usable pages per shard
    # one 500-token interactive prompt + 3 mixed-SLA shorts — the shared
    # scenario builder the spatial benchmark drives too
    mix = longctx_mix(cfg.vocab, long_tokens=500, long_max_tokens=16,
                      n_short=3, short_tokens=24, short_max_tokens=16)

    # a single-pool engine with the same per-device budget cannot admit it
    single = LLM.from_config(cfg, backend="paged", params=params,
                             engine_cfg=PagedEngineCfg(
                                 max_batch=4, page_size=16,
                                 n_pages=pages_local, hot_pages=8,
                                 eos_id=-1))
    try:
        single.submit(mix[0]["prompt"], max_tokens=mix[0]["max_tokens"])
        raise AssertionError("single pool admitted the long prompt?!")
    except ValueError as e:
        print(f"single device: {e}")

    llm = LLM.from_config(
        cfg, backend="spatial", params=params,
        engine_cfg=SpatialEngineCfg(
            n_shards=N_SHARDS, max_batch=4, page_size=16,
            n_pages_local=pages_local, hot_pages_local=10, eos_id=-1),
        sched_cfg=SchedulerCfg(chunk_pages=2, prefill_tokens=32))
    handles = [llm.submit(**r) for r in mix]
    done = llm.run_until_done()
    rep = llm.metrics()

    eng = llm.engine
    st = llm.stats()
    print(f"\n{N_SHARDS} shards x {pages_local - 1} pages "
          f"({(pages_local - 1) * 16} tokens/shard) served a "
          f"{len(mix[0]['prompt'])}-token prompt + {len(done) - 1} "
          f"mixed-SLA requests:")
    print(f"  {rep['tokens']} tokens in {rep['wall_s']}s "
          f"({rep['tok_s']} tok/s), ttft p50 {rep['ttft_p50_ms']} ms, "
          f"occupancy {rep['occupancy']}")
    for sla, m in rep["per_sla"].items():
        print(f"  {sla:12s} ttft {m['ttft_mean_ms']} ms")
    print(f"  pools: {st['pools']['live']} live / "
          f"{st['pools']['capacity']} pages aggregate, "
          f"{st['pools']['shared_hits']} prefix hits; "
          f"decode compiled {st['decode_compiles']}x")
    cost = eng.topo.exchange_cost()
    print(f"  NoC exchange (MRCA vs forced ring): "
          f"{cost['mrca']['latency_ns']:.0f} vs "
          f"{cost['naive_ring']['latency_ns']:.0f} ns/rotation")
    long_handle = handles[0]
    print(f"  long-prompt output head: {long_handle.tokens[:8]}...")
    assert long_handle.done and len(long_handle.tokens) == 16


if __name__ == "__main__":
    from repro.spatial import require_devices
    require_devices(N_SHARDS, [__file__])
    main()
