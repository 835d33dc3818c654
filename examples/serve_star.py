"""Serve a small LM through the paged continuous-batching engine via the
unified ``LLM`` front door. Decode sparsity is page-granular: DLZS
scores over the int8 LZ prediction cache decide which KV pages each step
gathers (attention is exact within them), and identical prompt prefixes
share pages copy-on-write. STAR's tile-granular pipeline still runs at
prefill.

Run:  PYTHONPATH=src python examples/serve_star.py
"""

import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import LLM, PagedEngineCfg, SchedulerCfg


def main():
    cfg = get_smoke_config("star_paper")   # STAR sparse decode enabled
    params = lm.init(jax.random.PRNGKey(0), cfg)
    # page_size == star.block_q so full prefix pages never split a prefill
    # tile (keeps prefix sharing exact); hot_pages*page_size = 256-token
    # decode working set regardless of how long a request grows.
    llm = LLM.from_config(
        cfg, backend="paged", params=params,
        engine_cfg=PagedEngineCfg(
            max_batch=4, page_size=cfg.star.block_q, n_pages=32,
            hot_pages=4, recent_pages=2, eos_id=-1),
        # chunk boundaries must stay STAR q-tile aligned
        sched_cfg=SchedulerCfg(chunk_pages=1))

    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab, size=cfg.star.block_q,
                          dtype=np.int32)  # shared "system prompt" page
    t0 = time.time()
    for i in range(10):
        llm.submit(np.concatenate(
            [system, rng.integers(0, cfg.vocab, size=8 + 4 * i,
                                  dtype=np.int32)]), max_tokens=16)
    done = llm.run_until_done()
    dt = time.time() - t0
    n_tok = sum(len(v) for v in done.values())
    st = llm.stats()
    pool = st["pool"]
    print(f"served {len(done)} requests / {n_tok} tokens through "
          f"{llm.engine.pcfg.max_batch} continuous-batching slots in "
          f"{dt:.1f}s ({n_tok / dt:.1f} tok/s on CPU)")
    print(f"pool: peak {pool.peak_live}/{pool.capacity} pages live, "
          f"{pool.shared_hits} prefix-share hits, "
          f"{pool.evictions} DLZS evictions; working set "
          f"{st['working_set_bytes'] / 2**20:.1f} MiB "
          f"({st['bytes_per_page'] / 2**20:.2f} MiB/page), "
          f"decode compiled {st['decode_compiles']}x")
    for rid in sorted(done)[:3]:
        print(f"  req {rid}: {done[rid][:8]}...")
    assert len(done) == 10


if __name__ == "__main__":
    main()
