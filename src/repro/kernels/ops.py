"""Jit-ready kernel entry points with impl dispatch.

Every op takes ``impl``:
  - "ref":     pure-jnp oracle (CPU dry-run / GSPMD path)
  - "pallas":  Pallas TPU kernel (compiled for TPU; interpret-mode on CPU
               is used by the test suite only)
  - "auto":    pallas on TPU backends, ref elsewhere

Models call these, never ``pl.pallas_call`` directly, so flipping a
single config bit moves the whole model between paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


# -- rmsnorm ---------------------------------------------------------------

def rmsnorm(x, w, *, eps=1e-5, gemma_style=False, impl="ref", interpret=False):
    if _resolve(impl) == "ref":
        return _ref.rmsnorm(x, w, eps=eps, gemma_style=gemma_style)
    from repro.kernels import rmsnorm as _k
    return _k.rmsnorm(x, w, eps=eps, gemma_style=gemma_style, interpret=interpret)


# -- attention -------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, scale=None, logit_soft_cap=0.0,
                    impl="ref", interpret=False, block_q=128, block_k=128,
                    chunk_q=None):
    """Prefill attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D)."""
    if _resolve(impl) == "ref":
        if q.shape[2] > 1024:  # flash-style memory without the kernel
            import os
            cq = chunk_q or int(os.environ.get("REPRO_ATTN_CHUNK_Q", "512"))
            return _ref.mha_chunked(q, k, v, causal=causal, scale=scale,
                                    logit_soft_cap=logit_soft_cap, chunk_q=cq)
        return _ref.mha(q, k, v, causal=causal, scale=scale,
                        logit_soft_cap=logit_soft_cap)
    from repro.kernels import flash_attention as _k
    return _k.flash_attention(q, k, v, causal=causal, scale=scale,
                              logit_soft_cap=logit_soft_cap,
                              interpret=interpret, block_q=block_q, block_k=block_k)


def chunk_attention(q, k, v, *, q_offset, kv_len, scale=None, logit_soft_cap=0.0,
                    impl="ref", interpret=False):
    """Chunked-prefill attention: q (B,Hq,Sq,D) is a prompt chunk whose
    first query sits at absolute position ``q_offset``; k,v are the
    full-size cache buffers with ``kv_len`` valid positions (the chunk's
    own K/V already written in). Causal across the chunk, full across
    the cached prefix. The Pallas flash kernel has no offset/len masking
    yet, so both impls lower to the reference path."""
    del impl, interpret
    return _ref.mha(q, k, v, causal=True, kv_len=kv_len, q_offset=q_offset,
                    scale=scale, logit_soft_cap=logit_soft_cap)


def decode_attention(q, k, v, *, kv_len, scale=None, logit_soft_cap=0.0,
                     impl="ref", interpret=False, block_k=256):
    """Decode attention: q (B,Hq,1,D) vs cache k,v (B,Hkv,S,D), valid < kv_len."""
    if _resolve(impl) == "ref":
        return _ref.decode_attention(q, k, v, kv_len=kv_len, scale=scale,
                                     logit_soft_cap=logit_soft_cap)
    from repro.kernels import decode_attention as _k
    return _k.decode_attention(q, k, v, kv_len=kv_len, scale=scale,
                               logit_soft_cap=logit_soft_cap,
                               interpret=interpret, block_k=block_k)


def paged_attention(q, k_pages, v_pages, *, block_tables, kv_len, scale=None,
                    logit_soft_cap=0.0, impl="ref", interpret=False,
                    pos_offset=None, k_scales=None, v_scales=None):
    """Paged decode attention: q (B,Hq,1,D) against pooled KV pages
    (P,Hkv,page,D) addressed through per-slot block tables (B,n_pages).
    The ref path gathers the pages into a contiguous view. The Pallas
    path runs one program per slot and DMAs the slot's live pages, all
    kv heads per page, in double-buffered blocks steered by the scalar-
    prefetched table; its block loop stops at the slot's last live page,
    so pages past kv_len are neither copied nor computed.
    ``pos_offset`` (scalar or (B,)) is the per-slot count of tokens
    rolled out of the window: the block table maps only surviving
    pages, so the slot-space KV length is kv_len - pos_offset.
    ``k_scales``/``v_scales`` ((P,Hkv,page) float32) mark the pool as
    quantized: both impls dequantize per page position in the attention
    math (in-register, per block, for the Pallas path)."""
    if _resolve(impl) == "ref":
        return _ref.paged_attention(q, k_pages, v_pages,
                                    block_tables=block_tables, kv_len=kv_len,
                                    scale=scale, logit_soft_cap=logit_soft_cap,
                                    pos_offset=pos_offset,
                                    k_scales=k_scales, v_scales=v_scales)
    from repro.kernels import paged_attention as _k
    return _k.paged_attention(q, k_pages, v_pages, block_tables=block_tables,
                              kv_len=kv_len, scale=scale,
                              logit_soft_cap=logit_soft_cap, interpret=interpret,
                              pos_offset=pos_offset,
                              k_scales=k_scales, v_scales=v_scales)


def gather_kv_pages(pages, block_tables):
    """Pool pages (P,H,page,D) or (P,page,r) + tables (B,n) -> the
    contiguous per-slot view (B,H,n*page,D) / (B,n*page,r). Used by the
    chunked-prefill and MLA paged paths, which reuse the contiguous
    attention math on the gathered view."""
    return _ref.gather_kv_pages(pages, block_tables)


def gather_dequant_kv_pages(pages, scales, block_tables):
    """Quantized-pool variant of :func:`gather_kv_pages`: gathers pages
    and their per-position scale sidecar, returns the dequantized
    float32 contiguous view."""
    return _ref.gather_dequant_kv_pages(pages, scales, block_tables)


def kv_qmax(dtype):
    """Max magnitude representable by a quantized-KV dtype (None if the
    dtype is not a quantized KV format)."""
    return _ref.kv_qmax(dtype)


def quantize_kv(x, dtype):
    """Symmetric amax quantization over the last axis -> (q, scale)."""
    return _ref.quantize_kv(x, dtype)


def dequantize_kv(q, scale):
    """Inverse of quantize_kv -> float32."""
    return _ref.dequantize_kv(q, scale)


# -- mamba2 ssd ------------------------------------------------------------

def ssd(x, dt, A, B, C, D, *, chunk=64, h0=None, impl="ref", interpret=False):
    if _resolve(impl) == "ref":
        return _ref.ssd(x, dt, A, B, C, D, chunk=chunk, h0=h0)
    from repro.kernels import ssm_scan as _k
    return _k.ssd(x, dt, A, B, C, D, chunk=chunk, h0=h0, interpret=interpret)


def ssd_step(x, dt, A, B, C, D, h):
    return _ref.ssd_step(x, dt, A, B, C, D, h)  # O(1) update; no kernel needed


# -- quantized matmul ------------------------------------------------------

def awq_matmul(x, qw, scales, zeros, *, bits=4, group_size=128,
               impl="ref", interpret=False, block_m=128, block_n=128):
    if _resolve(impl) == "ref":
        return _ref.awq_matmul(x, qw, scales, zeros, bits=bits, group_size=group_size)
    from repro.kernels import awq_matmul as _k
    return _k.awq_matmul(x, qw, scales, zeros, bits=bits, group_size=group_size,
                         interpret=interpret, block_m=block_m, block_n=block_n)
