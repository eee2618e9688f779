"""Logical-axis sharding rules (flax-linen style, dependency-free).

Model code annotates tensors and parameters with *logical* axis names
("batch", "embed", "heads", "ffn", "vocab", "experts", "kv_seq", ...).
A rule set maps logical names to physical mesh axes; the mapping is
resolved lazily against the mesh that is active at trace time, so the
same model code runs on a single CPU device (rules inactive -> no-op),
the single-pod (data, model) mesh, and the multi-pod (pod, data, model)
mesh without modification.

Divisibility guard: a logical dim is only bound to a mesh axis if the
dim size is divisible by the product of the mapped axis sizes;
otherwise it silently falls back to replication for that dim. This is
what lets e.g. a 4-head model and a 64-head model share one rule set.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

# Logical axis name -> mesh axis name(s). Values may be a string, a tuple of
# strings (sharded over the product of those axes), or None (replicated).
AxisRules = Mapping[str, Any]

# The default production rule set. "pod" and "data" jointly form the
# DP/FSDP domain; "model" is the TP/EP domain.
DEFAULT_RULES: AxisRules = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),            # FSDP shard dim for params (largest dim)
    "fsdp_big": ("pod", "data"),  # FSDP over pods too (>=60B models)
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "qkv": ("model",),            # flattened head*dim projections
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ffn": ("model",),     # fallback when expert count not divisible
    "moe_cap": ("data",),         # expert-capacity dim of dispatch buffers
    "seq": None,
    "res_seq": None,              # residual-stream seq; ("model",) = seq-parallel
    "kv_seq": None,               # bound to ("data",) for long-context decode
    "conv": None,
    "state": None,
    "layers": None,
}


class _RulesState(threading.local):
    def __init__(self):
        self.rules: AxisRules | None = None


_STATE = _RulesState()


@contextlib.contextmanager
def axis_rules(rules: AxisRules | None):
    """Activate a logical->physical rule set for the enclosed trace."""
    prev = _STATE.rules
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def current_rules() -> AxisRules | None:
    return _STATE.rules


def _active_mesh() -> AbstractMesh | None:
    """The mesh set by ``jax.set_mesh`` for the current trace, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _resolve_one(name: str | None, dim: int | None, mesh, rules: AxisRules):
    """Map one logical axis name to mesh axes, with divisibility guard."""
    if name is None:
        return None
    target = rules.get(name, None)
    if target is None:
        return None
    if isinstance(target, str):
        target = (target,)
    sizes = dict(mesh.shape)
    # keep only axes present in this mesh
    axes = tuple(a for a in target if a in sizes)
    if not axes:
        return None
    if dim is not None:
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if dim % prod != 0:
            # try progressively shorter prefixes (e.g. drop "pod")
            while axes:
                axes = axes[1:]
                prod = 1
                for a in axes:
                    prod *= sizes[a]
                if axes and dim % prod == 0:
                    break
            if not axes:
                return None
    if len(axes) == 1:
        return axes[0]
    return axes


def logical_to_pspec(
    logical: Sequence[str | None],
    shape: Sequence[int] | None = None,
    mesh=None,
    rules: AxisRules | None = None,
) -> P:
    """Resolve a tuple of logical axis names to a PartitionSpec."""
    rules = rules if rules is not None else (_STATE.rules or DEFAULT_RULES)
    mesh = mesh if mesh is not None else _active_mesh()
    if mesh is None:
        return P(*([None] * len(logical)))
    used: set[str] = set()
    out = []
    for i, name in enumerate(logical):
        dim = None if shape is None else shape[i]
        r = _resolve_one(name, dim, mesh, rules)
        # a physical mesh axis may appear at most once in a PartitionSpec
        if r is not None:
            raxes = (r,) if isinstance(r, str) else tuple(r)
            if any(a in used for a in raxes):
                r = None
            else:
                used.update(raxes)
        out.append(r)
    return P(*out)


def shard_as(x, *logical: str | None):
    """with_sharding_constraint by logical axis names; no-op off-mesh."""
    if _STATE.rules is None:
        return x
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = logical_to_pspec(logical, shape=x.shape, mesh=mesh)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def spec_tree_to_shardings(spec_tree, shape_tree, mesh: Mesh, rules: AxisRules | None = None):
    """Map a tree of logical-axis tuples (+ matching ShapeDtypeStructs) to
    NamedShardings on ``mesh``. Used to build pjit in_shardings."""
    rules = rules or DEFAULT_RULES

    def one(logical, sds):
        pspec = logical_to_pspec(logical, shape=sds.shape, mesh=mesh, rules=rules)
        return NamedSharding(mesh, pspec)

    return jax.tree.map(one, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))
