"""Pieces shared by the plain references: float32 math at the highest
matmul precision, and the fp8 path that serves as the control.

``quant=None`` is the reference. The control, ``quant="fp8"``, rounds
both operands of every projection one precision below the bf16 that the
served configuration computes in: the weight per output channel and the
activation per token to float8 e4m3, scaled to its largest finite value,
multiplied as float32 (a v5e has no fp8 unit, so the rounding is what is
emulated).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _f8(x, axis):
    """float8 e4m3 over ``axis``, scaled so the largest magnitude is
    e4m3's largest finite value, returned dequantized in float32."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def linear(x, w, quant=None):
    """x (..., K) @ w (K, N) in float32 at the highest precision, or in
    a control's lower precision."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    if quant != "fp8":
        raise ValueError(f"no control precision {quant!r}")
    return jnp.matmul(_f8(x, -1), _f8(w, 0), precision=HIGHEST)


def vocab_block(v: int, cap: int = 32768) -> int:
    """The largest divisor of ``v`` that is at most ``cap``: the vocabulary
    is read in equal blocks, so one compiled program serves them all."""
    for b in range(min(cap, v), 0, -1):
        if v % b == 0:
            return b
    return 1


@partial(jax.jit, static_argnames=("blk", "quant"))
def _head_block(x, w, off, ids, *, blk, quant):
    wb = jax.lax.dynamic_slice_in_dim(w, off, blk, 0)            # (blk, D)
    logits = linear(x, wb.T, quant)                               # (B, T, blk)
    bmax = logits.max(-1)
    barg = logits.argmax(-1).astype(jnp.int32) + off
    local = ids - off
    inside = (local >= 0) & (local < blk)
    at = jnp.take_along_axis(logits, jnp.clip(local, 0, blk - 1), axis=-1)
    return bmax, barg, jnp.where(inside, at, -jnp.inf)


def vocab_stats(x, w, vocab: int, ids, quant=None):
    """Over the first ``vocab`` rows of the output projection ``w``
    (Vp, D) and final hidden states ``x`` (B, T, D): the largest logit
    (B, T), the token that has it (B, T), and the logits of the token
    ids ``ids`` (B, T, k)."""
    blk = vocab_block(vocab)
    best = arg = at = None
    for off in range(0, vocab, blk):
        bmax, barg, bat = _head_block(x, w, jnp.asarray(off, jnp.int32),
                                      ids, blk=blk, quant=quant)
        if best is None:
            best, arg, at = bmax, barg, bat
        else:
            arg = jnp.where(bmax > best, barg, arg)
            best = jnp.maximum(best, bmax)
            at = jnp.maximum(at, bat)
    return best, arg, at
