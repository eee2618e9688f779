"""Elastic restart: restore a checkpoint onto a DIFFERENT mesh.

At 1000+-node scale, restarts rarely come back with the same topology
(failed hosts drained, pods resized). Checkpoints here are stored
mesh-agnostic (full logical arrays per leaf), so elasticity reduces to
recomputing shardings against the new mesh and device_put-ing — this
module packages that with the logical-axis rules so a training driver
can do it in one call, and verifies divisibility up front (falling back
to replication per the rules' guard rather than crashing mid-restore).
"""

from __future__ import annotations

import jax

from repro.distributed.sharding import DEFAULT_RULES, spec_tree_to_shardings
from repro.models.common import shape_tree, spec_tree
from repro.training.checkpoint import CheckpointManager


def shardings_for_mesh(model, mesh, rules=None, dtype=None):
    """Param NamedShardings for ``mesh`` from the model's logical specs."""
    defs = model.param_defs()
    shapes = shape_tree(defs, dtype or model.cfg.pdtype())
    return spec_tree_to_shardings(spec_tree(defs), shapes, mesh, rules or DEFAULT_RULES)


def elastic_restore(ckpt: CheckpointManager, model, mesh, *, step=None, rules=None):
    """Load the latest (or given) checkpoint and lay it out on ``mesh``,
    whatever shape that mesh has. Returns (params, aux, step)."""
    defs = model.param_defs()
    like = shape_tree(defs, model.cfg.pdtype())
    shardings = shardings_for_mesh(model, mesh, rules)
    tree, aux, step = ckpt.restore(step, {"params": like}, shardings=None)
    params = tree["params"]
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shardings)
    return params, aux, step
