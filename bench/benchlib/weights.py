"""Random weights drawn by the benchmark from the seed, on the device, in
one jitted call, in the dtype they are served in.

The tree follows the layout that the configuration's reference module
declares (``weight_spec(m)`` in ``bench/reference/<reference>.py``: the
names the program's model reads, and the reference reads the same): the
program is handed these arrays and the reference uses the benchmark's
own copy, so the reference takes nothing the program made. A new model
family brings its layout in its reference module; nothing here changes.

Kinds: "normal" draws N(0, scale^2), "gain" 1 + N(0, scale^2). The
layouts give matrices N(0, 1/fan_in) (output projections also divided
by sqrt(2 * n_layers)), embeddings N(0, 1), norm gains and gate biases
1 + N(0, 0.1), so that every leaf is exercised.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


@partial(jax.jit, static_argnums=(0, 2))
def _draw(leaves: tuple, key, dtype):
    out = []
    for i, (shape, kind, scale) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        v = z * scale if kind == "normal" else 1.0 + z * scale
        out.append(v.astype(dtype))
    return out


def make(layout: dict, dtype, seed: int):
    """The weight tree of ``layout`` ({path: (shape, kind, scale)}, nested
    like the parameter tree) for ``seed``, in ``dtype``, drawn in one
    jitted call on the default device. Leaf i of the flattened layout
    draws from ``fold_in(key, i)``."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return treedef.unflatten(_draw(tuple(leaves), key, jnp.dtype(dtype)))


def same_layout(params, shapes) -> list[str]:
    """Paths where ``params`` and the program's ``shapes`` (from
    ``jax.eval_shape`` of its init) disagree in structure, shape or
    dtype; empty when they match."""
    a = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    b = dict(jax.tree_util.tree_flatten_with_path(shapes)[0])
    bad = [jax.tree_util.keystr(k) for k in set(a) ^ set(b)]
    for k in set(a) & set(b):
        if a[k].shape != b[k].shape or a[k].dtype != b[k].dtype:
            bad.append(f"{jax.tree_util.keystr(k)}: {a[k].shape} {a[k].dtype}"
                       f" vs {b[k].shape} {b[k].dtype}")
    return sorted(bad)
