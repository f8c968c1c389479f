"""Logical-axis sharding rules (MaxText-style) for every architecture family.

Models annotate activations/params with *logical* axis names ('batch',
'seq', 'heads', 'embed', 'mlp', 'experts', 'vocab', 'table_rows', ...).
An `AxisRules` table maps logical names to physical mesh axes; the same
model code then runs

  * unsharded on 1 CPU device (smoke tests) — rules context unset, every
    constraint is a no-op;
  * sharded on the (data, model) single-pod mesh or the (pod, data, model)
    multi-pod mesh (dry-run / production) — constraints resolve to
    NamedSharding and GSPMD inserts the collectives.

The rules below encode the distribution design of DESIGN.md §4:
  - LM dense:  DP over (pod, data) + FSDP (params sharded over data) +
    TP over model (heads / d_ff / vocab).
  - LM MoE:    experts over model (EP) + FSDP elsewhere.
  - RecSys:    embedding-table rows over model, batch over (pod, data).
  - GNN:       nodes/edges over (pod, data), weights replicated.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

_ctx = threading.local()


@dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> mesh axis name(s) (or None = replicate)."""

    rules: Mapping[str, Any] = field(default_factory=dict)

    def resolve(self, *logical: str | None) -> P:
        """Logical axis names (one per tensor dim; None = replicated dim) ->
        PartitionSpec. Unknown names replicate."""
        return P(*[self.rules.get(name) if name else None for name in logical])

    def override(self, **updates) -> "AxisRules":
        """New AxisRules with some logical names remapped — per-shape-cell
        specialization (e.g. long_500k re-shards the KV cache sequence
        axis instead of the batch axis)."""
        merged = dict(self.rules)
        merged.update(updates)
        return AxisRules(merged)


# Physical axes: single-pod mesh ('data', 'model'); multi-pod adds 'pod'.
# Writing ('pod', 'data') in a rule is safe for the single-pod mesh ONLY if
# filtered; `_filter_spec` drops axes the mesh does not have.


def _filter_entry(entry, mesh_axes: frozenset[str]):
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if entry in mesh_axes else None
    kept = tuple(a for a in entry if a in mesh_axes)
    return kept if kept else None


def filter_rules(rules: AxisRules, mesh: Mesh) -> AxisRules:
    axes = frozenset(mesh.axis_names)
    return AxisRules({k: _filter_entry(v, axes) for k, v in rules.rules.items()})


# --------------------------------------------------------------------------
# Per-family rule tables
# --------------------------------------------------------------------------

LM_RULES = AxisRules({
    # activations
    "batch": ("pod", "data"),
    "seq": None,                 # sequence replicated by default (SP optional)
    "seq_shard": "model",        # KV-cache sequence sharding (decode cells)
    "seq_attn": "model",         # attention-score q-seq sharding (dense path)
    "heads": "model",
    "kv_heads": "model",
    "embed": None,               # activation d_model dim replicated
    "mlp": "model",              # activation d_ff dim (TP)
    "kv_batch": ("pod", "data"),
    # params: FSDP shards the non-TP dim over 'data'; TP dim over 'model'
    "embed_fsdp": "data",
    "vocab": "model",
    "qkv_in": "data",            # wq/wk/wv input dim (FSDP all-gather per layer)
    "qkv_out": "model",          # column parallel
    "o_in": "model",             # row parallel
    "o_out": "data",
    "ffn_in": "data",
    "ffn_out": "model",
    "ffn_down_in": "model",
    "ffn_down_out": "data",
    "experts": "model",          # EP
    # FSDP inside each expert shard. ('pod','data') = ZeRO-3 across pods:
    # required to FIT 1T-param optimizer state (DESIGN.md §4 records the
    # DCN cost; the single-pod mesh simply filters 'pod' away).
    "expert_in": ("pod", "data"),
    "expert_out": None,
    # dispatch-buffer capacity axis (sort-dispatch §Perf variant)
    "expert_cap": ("pod", "data"),
    "layers": None,              # scan-stacked leading dim, never sharded
    "norm": None,
})

RECSYS_RULES = AxisRules({
    "batch": ("pod", "data"),
    "seq": None,
    "heads": None,
    "embed": None,
    "mlp": None,
    "table_rows": "model",       # the tables ARE the model: row-sharded
    "table_dim": None,
    "candidates": ("data", "model"),  # retrieval: 10^6 candidates sharded
    "dense_in": None,            # small dense MLP replicated
    "dense_out": None,
    "norm": None,
    "layers": None,
    "vocab": "model",
})

GNN_RULES = AxisRules({
    "nodes": ("pod", "data"),
    "edges": ("pod", "data"),
    "batch": ("pod", "data"),
    "feat": None,
    "mlp_in": None,
    "mlp_out": None,
    "norm": None,
    "layers": None,
})

PAPER_RULES = AxisRules({
    # The ranking-under-constraints serving fleet (DESIGN.md §4): users over
    # (pod, data); the KNN train-user DB and item catalog over 'model'.
    "batch": ("pod", "data"),
    "users_db": "model",
    "items": "model",
    "covariates": None,
    "constraints": None,
    "embed": None,
    "mlp": None,
    "norm": None,
    "layers": None,
})

RULES_BY_FAMILY = {
    "lm": LM_RULES,
    "recsys": RECSYS_RULES,
    "gnn": GNN_RULES,
    "paper": PAPER_RULES,
}


# --------------------------------------------------------------------------
# Context + constraint helpers
# --------------------------------------------------------------------------

@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh | None, rules: AxisRules | None):
    """Activate (mesh, rules) for `logical_shard` within the block."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, filter_rules(rules, mesh)) if (mesh and rules) else None
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        _ctx.state = prev


def current_mesh() -> Mesh | None:
    st = getattr(_ctx, "state", None)
    return st[0] if st else None


def current_rules() -> AxisRules | None:
    st = getattr(_ctx, "state", None)
    return st[1] if st else None


def logical_spec(*logical: str | None) -> P:
    rules = current_rules()
    if rules is None:
        return P()
    return rules.resolve(*logical)


def _axis_prod(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape[entry]
    n = 1
    for a in entry:
        n *= mesh.shape[a]
    return n


def drop_nondivisible(spec: P, shape, mesh: Mesh) -> P:
    """Relax a PartitionSpec: replicate any dim whose size is not an exact
    multiple of its mesh-axis product (e.g. 40 heads on a 16-way 'model'
    axis, or batch 1), and drop repeated mesh axes (a spec may map each
    axis to at most one dim — keep the first use). GSPMD remains free to
    choose a sharding for relaxed dims; we just do not constrain them."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    used: set = set()
    for dim, entry in zip(shape, entries):
        if dim % _axis_prod(mesh, entry) != 0:
            entry = None
        if entry is not None:
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            if any(a in used for a in axes):
                entry = None
            else:
                used.update(axes)
        out.append(entry)
    return P(*out)


def logical_shard(x: Array, *logical: str | None) -> Array:
    """with_sharding_constraint against the active (mesh, rules); identity
    when no context is active (single-device smoke tests). Constraints on
    non-divisible dims are dropped rather than erroring (see
    drop_nondivisible)."""
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = drop_nondivisible(rules.resolve(*logical), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*logical: str | None) -> NamedSharding | None:
    st = getattr(_ctx, "state", None)
    if st is None:
        return None
    mesh, rules = st
    return NamedSharding(mesh, rules.resolve(*logical))


# --------------------------------------------------------------------------
# Param shardings from logical-axis annotations
# --------------------------------------------------------------------------

def param_shardings(
    logical_axes: Any, mesh: Mesh, rules: AxisRules
) -> Any:
    """Map a pytree of per-dim logical-axis tuples to NamedShardings.

    `logical_axes` mirrors the params pytree; each leaf is a tuple of
    logical axis names (or None), one per tensor dimension.
    """
    rules = filter_rules(rules, mesh)

    def leaf(axes):
        return NamedSharding(mesh, rules.resolve(*axes))

    return jax.tree.map(
        leaf, logical_axes, is_leaf=lambda x: isinstance(x, tuple)
    )


def eval_shape_with_sharding(fn, logical_axes_fn, mesh, rules, *args):
    """jax.eval_shape + attach shardings (dry-run param stand-ins)."""
    shapes = jax.eval_shape(fn, *args)
    axes = logical_axes_fn()
    shardings = param_shardings(axes, mesh, rules)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes,
        shardings,
    )
