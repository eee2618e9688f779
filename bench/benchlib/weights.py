"""Random weights drawn by the benchmark from the seed, on the device, in
one jitted call, in the dtype they are served in.

The tree follows the served model's parameter layout (the names the
program's model reads), and the reference reads the same names: the
program is handed these arrays and the reference uses the benchmark's
own copy, so the reference takes nothing the program made.

Scales: matrices are N(0, 1/fan_in) (output projections also divided by
sqrt(2 * n_layers)), embeddings N(0, 1), norm gains and gate biases
1 + N(0, 0.1), so that every leaf is exercised.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def _dense_spec(m: dict) -> dict:
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    vp = -(-m["vocab_size"] // m["vocab_pad_to"]) * m["vocab_pad_to"]
    out = 1.0 / math.sqrt(2 * L)
    spec = {
        "embed": {"w": ((vp, d), "normal", 1.0)},
        "blocks": {
            "ln1": {"w": ((L, d), "gain", 0.1)},
            "ln2": {"w": ((L, d), "gain", 0.1)},
            "attn": {"wq": ((L, d, q), "normal", d ** -0.5),
                     "wk": ((L, d, kv), "normal", d ** -0.5),
                     "wv": ((L, d, kv), "normal", d ** -0.5),
                     "wo": ((L, q, d), "normal", q ** -0.5 * out)},
            "mlp": {"w1": ((L, d, f), "normal", d ** -0.5),
                    "w3": ((L, d, f), "normal", d ** -0.5),
                    "w2": ((L, f, d), "normal", f ** -0.5 * out)},
        },
        "ln_f": {"w": ((d,), "gain", 0.1)},
    }
    if not m.get("tie_embeddings"):
        spec["lm_head"] = {"w": ((vp, d), "normal", d ** -0.5)}
    return spec


def spec(m: dict) -> dict:
    """{path: (shape, kind, scale)} nested like the parameter tree."""
    if m["family"] == "dense":
        return _dense_spec(m)
    raise ValueError(f"no weight layout for family {m['family']!r}")


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


def make(m: dict, seed: int):
    """The weight tree of model dict ``m`` for ``seed``, in its
    ``param_dtype``, drawn in one jitted call on the default device."""
    leaves, treedef = jax.tree.flatten(spec(m), is_leaf=_is_leaf)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return treedef.unflatten(_draw(tuple(leaves), key,
                                   jnp.dtype(m["param_dtype"])))


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
