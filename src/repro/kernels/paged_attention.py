"""Paged decode attention over a shared KV page pool — Pallas TPU.

vLLM-style PagedAttention: each decode slot's KV lives in fixed-size
pages scattered across a pool buffer ``(P, Hkv, page, D)``; a per-slot
block table maps token-page index -> pool page id. The kernel gathers
the pages *inside the kernel*: the flattened block table, the per-slot
lengths and the per-slot window offsets are scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``), and the pools stay in HBM
(``memory_space=pl.ANY``) — no host-side gather, no contiguous copy of
the cache.

Grid: (B,), one program per decode slot, holding every kv head and its
G = Hq/Hkv query group. The pool layout keeps one page's heads
contiguous, so one DMA per page id brings that page for all heads. The
program walks the slot's pages in blocks of ``pages_per_block``: while
block j is computed, block j+1's pages are in flight into the other
half of a VMEM double buffer (``pltpu.make_async_copy``, steered by the
prefetched table). The block loop runs ``cdiv(live pages, pages per
block)`` times, a bound read from the prefetched lengths, and no DMA
reads a page past the slot's live length: blocks past the last live
page are neither copied nor computed, and the last block copies only
its live pages.

Per block and kv head the score matmul is (G, D) x (D, pages*page),
then an online-softmax update of the float32 carry (acc/m/l). Positions
at or past the slot's length (the tail of its last live page, and the
buffer rows of a last block's uncopied pages) are masked out of both
matmuls, so they contribute exactly 0 whatever the buffer holds.
``pages_per_block`` is derived from the shapes: blocks of about
``_BLOCK_BYTES`` of K (a few hundred KB), capped by the table width.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BLOCK_BYTES = 256 * 1024   # K bytes one block aims to move (V as many)


def pages_per_block(page_bytes: int, n_pages: int) -> int:
    """Pages one DMA block moves: about ``_BLOCK_BYTES`` of one pool
    (``page_bytes`` is one page of all kv heads), at least one page and
    no more than the table. The K and V double buffers thus hold at
    most four times the larger of ``_BLOCK_BYTES`` and one page."""
    return max(1, min(_BLOCK_BYTES // page_bytes, n_pages))


def _scale_row(buf, slot, h, page):
    """Head h's per-position scales of the block in ``buf[slot]``, each
    page a (1, Hkv*page) lane row, as one (1, pages*page) row: the
    pages' slices side by side (Mosaic reshapes no lanes, but concats)."""
    rows = buf[slot]                                   # (pages, 1, lanes)
    return jnp.concatenate([rows[i][:, h * page:(h + 1) * page]
                            for i in range(rows.shape[0])], axis=-1)


def _paged_kernel(bt_ref, kvlen_ref, posoff_ref, q_ref, k_hbm, v_hbm, *rest,
                  scale, page, ppb, n_pages, soft_cap, quant):
    if quant:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
         acc_ref, m_ref, l_ref) = rest
        pools = ((k_hbm, kbuf), (v_hbm, vbuf), (ks_hbm, ksbuf),
                 (vs_hbm, vsbuf))
    else:
        o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref = rest
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    n_kv_heads, D = kbuf.shape[2], kbuf.shape[4]
    G = q_ref.shape[2]
    T = ppb * page
    b = pl.program_id(0)
    # pos_offset = tokens rolled out of the slot's window; the block
    # table maps only the surviving pages, so the slot-space KV length
    # is the absolute length minus the offset
    kv_len = kvlen_ref[b] - posoff_ref[b]
    live = jnp.minimum(pl.cdiv(kv_len, page), n_pages)
    n_blocks = pl.cdiv(live, ppb)

    def dma(j, slot, op):
        # one copy per live page of block j, all kv heads at once (none
        # for a block past the last); the wait walks the same pages, so
        # every copy started is awaited
        def page_copy(i, carry):
            pid = bt_ref[b * n_pages + j * ppb + i]
            for src, buf in pools:
                cp = pltpu.make_async_copy(src.at[pid], buf.at[slot, i],
                                           sem.at[slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, live - j * ppb), page_copy, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    dma(0, 0, "start")

    def block(j, carry):
        slot = j % 2
        dma(j + 1, 1 - slot, "start")
        dma(j, slot, "wait")
        start = j * T
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
        col_ok = kpos < kv_len
        row_ok = start + jax.lax.broadcasted_iota(jnp.int32, (T, D), 0) < kv_len
        for h in range(n_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)                    # (G, D)
            k = kbuf[slot, :, h].astype(jnp.float32).reshape(T, D)
            v = vbuf[slot, :, h].astype(jnp.float32).reshape(T, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if quant:
                # quantized pool: a per-position scale of k row t is a
                # scale of score column t, so dequant rides a (1, T)
                # lane row of the sidecar — the pool is never widened
                s = s * _scale_row(ksbuf, slot, h, page)
            if soft_cap > 0.0:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(col_ok, s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            # likewise v's per-position scales fold into p's columns;
            # masked columns and rows are zeroed, not just down-weighted,
            # so nothing a dead buffer row holds (NaN included) leaks in
            pv = p if not quant else jnp.where(
                col_ok, p * _scale_row(vsbuf, slot, h, page), 0.0)
            v = jnp.where(row_ok, v, 0.0)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, *, block_tables, kv_len, scale=None,
                    logit_soft_cap=0.0, interpret=False, pos_offset=None,
                    k_scales=None, v_scales=None):
    """q (B,Hq,1,D); k_pages,v_pages (P,Hkv,page,D);
    block_tables (B,n_pages) int32; kv_len scalar or (B,);
    pos_offset optional scalar or (B,) rolled-out token counts;
    k_scales,v_scales optional (P,Hkv,page) float32 sidecars for
    quantized pools (dequant happens inside the block loop)
    -> (B,Hq,1,D)."""
    B, Hq, _, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    ppb = pages_per_block(Hkv * page * D * k_pages.dtype.itemsize, n_pages)

    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    if pos_offset is None:
        pos_offset = jnp.zeros((B,), jnp.int32)
    pos_offset = jnp.broadcast_to(
        jnp.asarray(pos_offset, jnp.int32).reshape(-1), (B,))
    bt = jnp.asarray(block_tables, jnp.int32).reshape(-1)   # (B*n_pages,)
    qf = q.reshape(B, Hkv, G, D)

    def q_map(b, bt_ref, kvlen_ref, posoff_ref):
        return (b, 0, 0, 0)

    quant = k_scales is not None
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, Hkv, G, D), q_map), hbm, hbm]
    operands = [qf, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, ppb, Hkv, page, D), k_pages.dtype),
               pltpu.VMEM((2, ppb, Hkv, page, D), v_pages.dtype)]
    if quant:
        # one page's scales for all heads as one lane row, padded to
        # whole 128-lane tiles (a DMA from HBM moves whole tiles); they
        # ride the K/V pages' DMAs
        lanes = -(-Hkv * page // 128) * 128

        def rows(sc):
            sc = sc.astype(jnp.float32).reshape(P, Hkv * page)
            sc = jnp.pad(sc, ((0, 0), (0, lanes - Hkv * page)))
            return sc.reshape(P, 1, lanes)

        in_specs += [hbm, hbm]
        operands += [rows(k_scales), rows(v_scales)]
        scratch += [pltpu.VMEM((2, ppb, 1, lanes), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((Hkv, G, D), jnp.float32),
        pltpu.VMEM((Hkv, G, 1), jnp.float32),
        pltpu.VMEM((Hkv, G, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _paged_kernel, scale=scale, page=page, ppb=ppb, n_pages=n_pages,
        soft_cap=logit_soft_cap, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, D), q_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(bt, kv_len, pos_offset, *operands)
    return out.reshape(B, Hq, D)[:, :, None, :]
