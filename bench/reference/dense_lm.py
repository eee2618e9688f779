"""Plain reference of the served dense decoder (Mistral-7B's block as
its published configuration states it): token embedding; per block a pre-norm GQA
self-attention with rotate-half RoPE on queries and keys, then a
pre-norm gated SiLU MLP, each added to the residual stream; a final
RMSNorm and an untied output projection.

Whole-sequence causal attention in float32 at the highest matmul
precision, block by block (one block's weights widened at a time, so
it runs beside the served weights on one chip). Imports nothing of the
program.

Besides the forward pass, the module states what the benchmark needs of
this family: ``weight_spec``, the weight layout it reads (drawn by
``benchlib.weights``), and ``decode_flops`` / ``prefill_flops``, the
operations a token costs (read by ``step_mfu``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchlib import costs
from reference.common import F32, HIGHEST, linear, rmsnorm


def weight_spec(m: dict) -> dict:
    """{path: (shape, kind, scale)} nested like the parameter tree; the
    kinds and scales are ``benchlib.weights``'s."""
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


# Operations of the dense GQA decoder: ``benchlib.costs``'s arithmetic.
decode_flops = costs.decode_flops
prefill_flops = costs.prefill_flops


def _rope(x, theta):
    """x (B, T, H, Dh) at positions 0..T-1, rotate-half convention."""
    T, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv                 # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _block(x, blocks, l, *, dims, quant):
    H, Hkv, Dh, eps, theta = dims
    p = jax.tree.map(lambda a: a[l], blocks)
    B, T, _ = x.shape
    h = rmsnorm(x, p["ln1"]["w"], eps)
    q = _rope(linear(h, p["attn"]["wq"], quant).reshape(B, T, H, Dh), theta)
    k = _rope(linear(h, p["attn"]["wk"], quant).reshape(B, T, Hkv, Dh), theta)
    v = linear(h, p["attn"]["wv"], quant).reshape(B, T, Hkv, Dh)
    G = H // Hkv
    q = q.reshape(B, T, Hkv, G, Dh)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HIGHEST) / jnp.sqrt(F32(Dh))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, T, H * Dh)
    x = x + linear(a, p["attn"]["wo"], quant)
    h = rmsnorm(x, p["ln2"]["w"], eps)
    g = jax.nn.silu(linear(h, p["mlp"]["w1"], quant)) * linear(h, p["mlp"]["w3"], quant)
    return x + linear(g, p["mlp"]["w2"], quant)


@partial(jax.jit, static_argnames=("eps",))
def _final(x, w, *, eps):
    return rmsnorm(x, w, eps)


def final_hidden(params, m: dict, ids, quant=None):
    """ids (B, T) int32 -> normalized final hidden states (B, T, D) f32."""
    x = params["embed"]["w"][ids].astype(F32)
    dims = (m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"],
            m["rope_theta"])
    for l in range(m["n_layers"]):
        x = _block(x, params["blocks"], jnp.asarray(l, jnp.int32),
                   dims=dims, quant=quant)
    return _final(x, params["ln_f"]["w"], eps=m["norm_eps"])


def head_weights(params, m: dict):
    return params["embed"]["w"] if m.get("tie_embeddings") else params["lm_head"]["w"]
