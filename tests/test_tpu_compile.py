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
