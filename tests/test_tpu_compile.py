"""Ahead-of-time TPU v5e compiles of the Pallas kernels at real widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and raises what the chip's compiler
would raise (tile-rule violations, VMEM overflow). Interpret-mode tests
cannot see those faults. Nothing here runs a kernel.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dlzs import dlzs_block_scores
from repro.kernels.flash import flash_attention
from repro.kernels.paged import paged_decode_attention
from repro.kernels.sufa import sufa_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


# (batch, kv heads G, q heads per kv head R, hot window W, pool pages):
# OLMo-1B decode (16 MHA heads), a grouped-query shape, and the two chip
# benchmark cells' decode shapes, where a block of pages that overflows
# VMEM would fail here before a chip run
@pytest.mark.parametrize("b,g,r,w,n_pages", [
    (8, 16, 1, 16, 1024), (4, 8, 4, 16, 1024),
    (16, 16, 1, 128, 2049), (16, 2, 16, 512, 8193)],
    ids=["olmo_1b_G16_R1", "gqa_G8_R4", "olmo_1b.decode_batch",
         "chatglm3_6b.decode_batch"])
def test_paged_decode_compiles(one_chip, b, g, r, w, n_pages):
    d, page = 128, 16
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(lambda q, k, v, ph, lg, kl, nv: paged_decode_attention(
        q, k, v, ph, lg, kl, nv, scale=d ** -0.5, interpret=False),
        one_chip, ((b, g, r, d), bf), ((n_pages, page, g, d), bf),
        ((n_pages, page, g, d), bf), ((b, w), i32), ((b, w), i32),
        ((b,), i32), ((b,), i32))


def test_flash_compiles(one_chip):
    shape = ((16, 2048, 128), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             one_chip, shape, shape, shape)


def test_dlzs_block_scores_compiles(one_chip):
    shape = ((16, 2048, 128), jnp.bfloat16)
    _compile(lambda q, k: dlzs_block_scores(q, k, interpret=False),
             one_chip, shape, shape)


def test_sufa_compiles(one_chip):
    bh, t, d, keep, blk = 16, 2048, 128, 4, 128
    n_qt = t // blk
    tiles = ((bh, n_qt, keep, blk, d), jnp.bfloat16)
    _compile(lambda q, kg, vg, m: sufa_attention(q, kg, vg, m,
                                                 interpret=False),
             one_chip, ((bh, t, d), jnp.bfloat16), tiles, tiles,
             ((bh, n_qt, keep, blk, blk), jnp.bool_))


# OLMoE's dropless expert layer at the chip cell's shapes: one decode step
# of 24 lanes and one 384-token prefill wave over 16 held experts of the
# published widths. Its grouped matmuls must lower to the ragged-dot
# kernels whose op names the benchmark's expert roofline reads.
@pytest.mark.parametrize("tokens", [24, 384], ids=["decode", "wave"])
def test_moe_dropless_compiles_to_ragged_dot(one_chip, tokens):
    from repro.models import moe
    cfg = moe.MoECfg(n_experts=64, top_k=8, d_model=2048, d_ff=1024)
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda x, wg, w1, w2, w3: moe.apply_dropless(
            {"wg": wg, "w1": w1, "w2": w2, "w3": w3}, cfg, x, held=16)
    ).lower(*[jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((tokens, 2048), bf), ((2048, 64), jnp.float32),
        ((16, 2048, 1024), bf), ((16, 1024, 2048), bf),
        ((16, 2048, 1024), bf))]).compile()
    assert compiled.as_text().count("%ragged-dot-none") >= 3


# OLMoE-1B-7B's whole decode step, as the paged engine compiles it, at the
# chip cell's engine (24 lanes, 1,921 pages of 16, an 80-page window, 16
# held experts): it fits one v5e's HBM. At the 32 lanes and 2,561 pages
# first planned it does not, for the decode program's whole-pool
# temporaries (ROADMAP 1.3), which is why the cell serves 24 lanes; once
# that case compiles, the cell can go back to 32.
@pytest.mark.parametrize("lanes,n_pages,fits", [
    (24, 1921, True), (32, 2561, False)],
    ids=["olmoe_1b_7b.decode_batch", "olmoe_1b_7b_32_lanes"])
def test_olmoe_decode_step_fits_one_chip(one_chip, monkeypatch, lanes,
                                         n_pages, fits):
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import paged as kpaged
    from repro.models import lm
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "pallas")
    monkeypatch.setattr(kpaged, "resolve_interpret", lambda i: False)
    cfg = dataclasses.replace(get_config("olmoe_1b_7b"), star=None,
                              experts_held=16)
    page, window = 16, 80
    sds = lambda t: jax.tree.map(                          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    probe = jax.eval_shape(lambda p: lm.prefill(
        p, cfg, {"tokens": jnp.zeros((1, page), jnp.int32)},
        last_index=jnp.zeros((1,), jnp.int32)), params)[1]["layers"]
    cache = {"layers": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((a.shape[0], n_pages) + a.shape[2:],
                                       a.dtype), probe),
             "lengths": jax.ShapeDtypeStruct((lanes,), jnp.int32)}
    state = {k: jax.ShapeDtypeStruct((lanes, window), jnp.int32)
             for k in ("phys", "logical")}
    state.update({k: jax.ShapeDtypeStruct((lanes,), jnp.int32)
                  for k in ("write_page", "write_off")})
    lowered = jax.jit(lambda p, t, c, s: lm.decode_step_paged(p, cfg, t, c, s),
                      donate_argnums=(2,)).lower(
        sds(params), sds(jax.ShapeDtypeStruct((lanes, 1), jnp.int32)),
        sds(cache), sds(state))
    if fits:
        assert "%paged_decode" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="Ran out of memory in memory "
                                            "space hbm"):
            lowered.compile()
