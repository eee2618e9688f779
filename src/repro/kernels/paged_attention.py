"""Paged decode attention over a shared KV page pool — Pallas TPU.

vLLM-style PagedAttention: each decode slot's KV lives in fixed-size
pages scattered across a pool buffer ``(P, Hkv, page, D)``; a per-slot
block table maps token-page index -> pool page id. The kernel gathers
the pages *inside the kernel*: the block table and per-slot lengths are
scalar-prefetched (``pltpu.PrefetchScalarGridSpec``) so the k/v
BlockSpec index maps can steer each grid step's DMA straight to the
right pool page — no host-side gather, no contiguous copy of the cache.

Grid: (B * Hkv, n_pages). Like decode_attention, each program handles
the whole G = Hq/Hkv query-head group at once so the score matmul is
(G, D) x (D, page) — MXU-shaped even for MQA. The kv-page grid axis is
sequential per (slot, head): the online-softmax carry (acc/m/l) lives
in VMEM scratch across it, and pages past the slot's kv_len are skipped
entirely (``pl.when``) — dead pool pages are never touched.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, kvlen_ref, posoff_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, page, n_kv_heads, soft_cap,
                  ks_ref=None, vs_ref=None):
    bh = pl.program_id(0)
    ip = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pos_offset = tokens rolled out of the slot's window; the block
    # table maps only the surviving pages, so the slot-space KV length
    # is the absolute length minus the offset and rolled-out pages are
    # skipped by the same masked-page path as unwritten ones.
    b = bh // n_kv_heads
    kv_len = kvlen_ref[b] - posoff_ref[b]
    k_start = ip * page

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if ks_ref is not None:
            # quantized pool page: a per-position scale of k row j is a
            # scale of score column j, so dequant rides the (1, page)
            # lane row of the sidecar — the pool is never widened in HBM
            s = s * ks_ref[0, 0]
        if soft_cap > 0.0:
            s = soft_cap * jnp.tanh(s / soft_cap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        # likewise v's per-position scales fold into p's columns
        pv = p if vs_ref is None else p * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ip == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel_quant(bt_ref, kvlen_ref, posoff_ref, q_ref, k_ref, v_ref,
                        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    _paged_kernel(bt_ref, kvlen_ref, posoff_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, ks_ref=ks_ref, vs_ref=vs_ref, **kw)


def paged_attention(q, k_pages, v_pages, *, block_tables, kv_len, scale=None,
                    logit_soft_cap=0.0, interpret=False, pos_offset=None,
                    k_scales=None, v_scales=None):
    """q (B,Hq,1,D); k_pages,v_pages (P,Hkv,page,D);
    block_tables (B,n_pages) int32; kv_len scalar or (B,);
    pos_offset optional scalar or (B,) rolled-out token counts;
    k_scales,v_scales optional (P,Hkv,page) float32 sidecars for
    quantized pools (dequant happens inside the page loop)
    -> (B,Hq,1,D)."""
    B, Hq, _, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    if pos_offset is None:
        pos_offset = jnp.zeros((B,), jnp.int32)
    pos_offset = jnp.broadcast_to(
        jnp.asarray(pos_offset, jnp.int32).reshape(-1), (B,))
    bt = jnp.asarray(block_tables, jnp.int32).reshape(-1)   # (B*n_pages,)
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)

    def q_map(bh, ip, bt_ref, kvlen_ref, posoff_ref):
        return (bh, 0, 0)

    def kv_map(bh, ip, bt_ref, kvlen_ref, posoff_ref):
        pid = bt_ref[(bh // Hkv) * n_pages + ip]
        return (pid, bh % Hkv, 0, 0)

    def scale_map(bh, ip, bt_ref, kvlen_ref, posoff_ref):
        pid = bt_ref[(bh // Hkv) * n_pages + ip]
        return (pid, bh % Hkv, 0, 0)

    quant = k_scales is not None
    in_specs = [
        pl.BlockSpec((1, G, D), q_map),
        pl.BlockSpec((1, 1, page, D), kv_map),
        pl.BlockSpec((1, 1, page, D), kv_map),
    ]
    operands = [qf, k_pages, v_pages]
    if quant:
        # the scale sidecar rides the same scalar-prefetched block-table
        # steering as the pages themselves. Viewed as (P, Hkv, 1, page),
        # each grid step's block is one (1, page) row whose dims equal
        # the array's last two, as the TPU tiling rule requires
        in_specs += [pl.BlockSpec((1, 1, 1, page), scale_map),
                     pl.BlockSpec((1, 1, 1, page), scale_map)]
        operands += [k_scales.astype(jnp.float32).reshape(P, Hkv, 1, page),
                     v_scales.astype(jnp.float32).reshape(P, Hkv, 1, page)]
    kernel = functools.partial(
        _paged_kernel_quant if quant else _paged_kernel,
        scale=scale, page=page, n_kv_heads=Hkv, soft_cap=logit_soft_cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * Hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, D), q_map),
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
    )(bt, kv_len, pos_offset, *operands)
    return out.reshape(B, Hq, D)[:, :, None, :]
