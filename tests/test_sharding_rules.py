"""Logical-axis rule tests (single-device mesh: specs only, no collectives)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.shardlib import rules as shr


def _mesh(shape=(1, 1), names=("data", "model")):
    return jax.sharding.AbstractMesh(tuple(shape), tuple(names))


def _mesh11():
    return _mesh()


def test_logical_spec_basic():
    with shr.axis_rules(_mesh11()):
        assert shr.logical_spec(("batch", "seq", "embed")) == P("data")
        assert shr.logical_spec(("embed_w", "mlp")) == P("data", "model")


def test_divisibility_drops_mapping():
    with shr.axis_rules(_mesh((2, 2))):
        # kv_heads=3 not divisible by model=2 -> replicated
        spec = shr.logical_spec(("batch", "seq", "kv_heads", "head_dim"),
                                (4, 8, 3, 16))
        assert spec == P("data")
        spec2 = shr.logical_spec(("batch", "seq", "kv_heads", "head_dim"),
                                 (4, 8, 4, 16))
        assert spec2 == P("data", None, "model")


def test_duplicate_mesh_axis_first_wins():
    with shr.axis_rules(_mesh((2, 2)),
                        kv_seq="model"):
        spec = shr.logical_spec(
            ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
            (4, 4, 8, 2, 16))
        # kv_seq takes 'model'; kv_heads (also ->model) must be dropped
        assert spec == P(None, "data", "model")


def test_missing_mesh_axis_dropped():
    # single-pod mesh has no 'pod' axis; batch=('pod','data') degrades
    # (a single surviving axis is emitted bare, not as a 1-tuple — older
    # PartitionSpec does not normalize the two forms as equal)
    with shr.axis_rules(_mesh11()):
        assert shr.logical_spec(("batch",)) == P("data")
    mesh3 = _mesh((1, 1, 1), ("pod", "data", "model"))
    with shr.axis_rules(mesh3):
        assert shr.logical_spec(("batch",)) == P(("pod", "data"))


def test_no_context_is_noop():
    assert shr.logical_spec(("batch", "embed")) == P()
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert shr.shd(x, "batch", "embed") is x


def test_overrides():
    with shr.axis_rules(_mesh11(), embed="model"):
        assert shr.logical_spec(("embed",)) == P("model")
    with shr.axis_rules(_mesh11()):
        assert shr.logical_spec(("embed",)) == P()


def test_axis_size():
    with shr.axis_rules(_mesh((4, 2))):
        assert shr.axis_size("batch") == 4
        assert shr.axis_size("mlp") == 2
        assert shr.axis_size("seq") == 1
