"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names ("batch", "heads",
"mlp", ...); a per-run rule table maps logical names onto the physical mesh
axes ("pod", "data", "model"). The same model definition then runs unmodified
on the single-pod (16,16) mesh, the multi-pod (2,16,16) mesh, a 1x1 test mesh,
or no mesh at all (plain CPU unit tests — constraints become no-ops).

Rules are held in a context (``with axis_rules(mesh, rules): ...``) so that
layer code can call ``shd(x, "batch", "seq", "embed")`` without threading a
mesh object through every signature.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, tuple]

# Default logical -> physical mapping for the production meshes.
DEFAULT_RULES: dict[str, AxisVal] = {
    "batch": ("pod", "data"),      # data parallel (hierarchical over pods)
    "seq": None,                   # sequence kept local by default
    "seq_shard": ("pod", "data"),  # explicit sequence parallelism (long ctx)
    "act_seq": "model",            # residual-stream sequence dim (Megatron
    #                                SP: activations sharded across TP ranks)
    "embed": None,                 # d_model replicated (activations)
    "embed_w": ("pod", "data"),    # weight contracting dim — FSDP/ZeRO-3:
    #                                2-D (data x model) weight sharding
    "mlp": "model",                # FFN hidden — tensor parallel
    "heads": "model",              # attention query heads — tensor parallel
    "kv_heads": "model",           # GQA KV heads when divisible by TP degree
    #                                (shape check auto-drops -> replicated)
    "head_dim": None,
    "qkv": None,
    "vocab": "model",              # output-head vocab — tensor parallel
    "embed_tp": "model",           # embedding-table hidden dim — TP
    "experts": "data",             # expert parallelism (MoE dispatch axis)
    "expert_mlp": "model",         # TP inside each expert
    "layers": None,                # scan-stacked layer dim
    "conv": None,
    "state": None,                 # SSM / mLSTM recurrent state feature dim
    "heads_ssm": "model",          # SSM heads — tensor parallel
    "kv_seq": None,                # KV-cache sequence dim (decode: may shard)
}

_CTX = threading.local()


class _RuleContext:
    def __init__(self, mesh: Optional[Mesh], rules: Mapping[str, AxisVal]):
        self.mesh = mesh
        self.rules = dict(rules)


def _get() -> Optional[_RuleContext]:
    return getattr(_CTX, "ctx", None)


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh],
               rules: Optional[Mapping[str, AxisVal]] = None,
               **overrides: AxisVal):
    """Activate a mesh + logical-rule table for the enclosed region."""
    merged = dict(DEFAULT_RULES if rules is None else rules)
    merged.update(overrides)
    prev = _get()
    _CTX.ctx = _RuleContext(mesh, merged)
    try:
        yield _CTX.ctx
    finally:
        _CTX.ctx = prev


def current_mesh() -> Optional[Mesh]:
    ctx = _get()
    return ctx.mesh if ctx else None


def current_rules() -> Mapping[str, AxisVal]:
    ctx = _get()
    return ctx.rules if ctx else DEFAULT_RULES


def _resolve_one(logical: Optional[str], mesh: Mesh,
                 rules: Mapping[str, AxisVal]):
    """Logical name -> mesh axis (or tuple), dropping axes absent from mesh."""
    if logical is None:
        return None
    val = rules.get(logical, None)
    if val is None:
        return None
    if isinstance(val, str):
        val = (val,)
    present = tuple(a for a in val if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def logical_spec(logical: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> P:
    """Build a PartitionSpec from logical axis names using the active rules.

    If ``shape`` is given, any mapping whose mesh-axis product does not divide
    the dimension is dropped (replicate) — keeps shard_map/memory estimates
    honest instead of relying on GSPMD padding.
    """
    ctx = _get()
    if ctx is None or ctx.mesh is None:
        return P()
    mesh = ctx.mesh
    entries = []
    used: set = set()
    for i, name in enumerate(logical):
        ax = _resolve_one(name, mesh, ctx.rules)
        if ax is not None:
            # a mesh axis may appear at most once per spec: first dim wins
            axes = tuple(a for a in ((ax,) if isinstance(ax, str) else ax)
                         if a not in used)
            ax = None if not axes else (axes if len(axes) > 1 else axes[0])
        if ax is not None and shape is not None:
            axes = (ax,) if isinstance(ax, str) else ax
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if shape[i] % size != 0:
                ax = None
        if ax is not None:
            used.update((ax,) if isinstance(ax, str) else ax)
        entries.append(ax)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shd(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint (no-op outside a rules context)."""
    ctx = _get()
    if ctx is None or ctx.mesh is None:
        return x
    spec = logical_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def batch_axes() -> tuple:
    """The physical mesh axes backing the logical 'batch' axis (for psums)."""
    ctx = _get()
    if ctx is None or ctx.mesh is None:
        return ()
    ax = _resolve_one("batch", ctx.mesh, ctx.rules)
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def mesh_axis(logical: str):
    """Resolve one logical name to a mesh axis name (or None)."""
    ctx = _get()
    if ctx is None or ctx.mesh is None:
        return None
    return _resolve_one(logical, ctx.mesh, ctx.rules)


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes backing a logical axis (1 if unmapped)."""
    ctx = _get()
    if ctx is None or ctx.mesh is None:
        return 1
    ax = _resolve_one(logical, ctx.mesh, ctx.rules)
    if ax is None:
        return 1
    axes = (ax,) if isinstance(ax, str) else ax
    size = 1
    for a in axes:
        size *= ctx.mesh.shape[a]
    return size


def tree_shardings(mesh: Mesh, axes_tree, rules=None):
    """Map a tree of logical-axes tuples to a tree of NamedShardings."""
    rules = dict(DEFAULT_RULES if rules is None else rules)

    def one(axes):
        with axis_rules(mesh, rules):
            return NamedSharding(mesh, logical_spec(axes))

    return jax.tree.map(one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings_shaped(mesh: Mesh, axes_tree, shape_tree, rules=None):
    """Like tree_shardings but drops non-divisible mappings using shapes."""
    rules = dict(DEFAULT_RULES if rules is None else rules)

    def one(axes, sds):
        with axis_rules(mesh, rules):
            return NamedSharding(mesh, logical_spec(axes, sds.shape))

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple))
