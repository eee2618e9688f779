"""Flash-decoding style single-token GQA attention — Pallas TPU.

One query token per sequence against a long (possibly partially
filled) KV cache. Grid: (B*Hkv, S/block_k); each program handles the
whole G = Hq/Hkv query-head group at once so the score matmul is
(G, D) x (D, bk) — MXU-shaped even for MQA. KV-length masking uses a
per-batch kv_len vector (positions >= kv_len are dead cache slots).
Online softmax carry in VMEM scratch across the sequential k dim.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, block_k, soft_cap, n_kv_heads):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = kvlen_ref[pl.program_id(0) // n_kv_heads]
    k_start = ik * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (G, D)
        k = k_ref[0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if soft_cap > 0.0:
            s = soft_cap * jnp.tanh(s / soft_cap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, *, kv_len, scale=None, logit_soft_cap=0.0,
                     interpret=False, block_k=256):
    """q (B,Hq,1,D); k,v (B,Hkv,S,D); kv_len scalar or (B,) -> (B,Hq,1,D)."""
    B, Hq, _, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bk = min(block_k, S)
    assert S % bk == 0

    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)

    kernel = functools.partial(_dec_kernel, scale=scale, block_k=bk,
                               soft_cap=logit_soft_cap, n_kv_heads=Hkv)
    # per-slot lengths are scalar-prefetched into SMEM (a rank-1 VMEM
    # block of one int32 is below the TPU's 128-lane tiling)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, ik, kl: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, kl: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, kl: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bh, ik, kl: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        interpret=interpret,
    )(kv_len, qf, kf, vf)
    return out.reshape(B, Hq, D)[:, :, None, :]
