"""Pure-jnp oracles for every Pallas kernel.

These are the *semantic ground truth*: the Pallas kernels are validated
against these in interpret mode, and the models run these on CPU (the
dry-run lowers this path; TPU deployments flip ``use_kernels``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


def rmsnorm(x, w, *, eps: float = 1e-5, gemma_style: bool = False):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if gemma_style else w.astype(jnp.float32)
    return (y * scale).astype(dt)


# ---------------------------------------------------------------------------
# attention (shared GQA core; prefill and decode are masks over the same math)
# ---------------------------------------------------------------------------


def mha(q, k, v, *, causal: bool = True, kv_len=None, q_offset=None, scale=None,
        logit_soft_cap: float = 0.0):
    """Grouped-query attention reference.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.
    kv_len: optional (B,) or scalar — positions >= kv_len are masked out
            (decode with a partially-filled cache).
    q_offset: optional scalar or (B,) — absolute position of q[0] for causal
            masking against a longer kv (prefill continuation / decode; the
            vector form is a speculative verify window at per-slot positions).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = scale if scale is not None else (1.0 / np.sqrt(D))

    qf = q.astype(jnp.float32).reshape(B, Hkv, G, Sq, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * scale
    if logit_soft_cap > 0.0:
        logits = logit_soft_cap * jnp.tanh(logits / logit_soft_cap)

    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        off = jnp.asarray(q_offset if q_offset is not None else (Skv - Sq))
        if off.ndim:                             # per-slot query offsets
            qpos = jnp.arange(Sq)[None, :, None] + off[:, None, None]
            kpos = jnp.arange(Skv)[None, None, :]
            mask = kpos <= qpos                  # (B, Sq, Skv)
        else:
            qpos = jnp.arange(Sq)[:, None] + off
            kpos = jnp.arange(Skv)[None, :]
            mask = kpos <= qpos
    if mask.ndim == 2:
        mask = jnp.broadcast_to(mask, (B, 1, 1, Sq, Skv))
    else:
        mask = mask[:, None, None]               # (B, 1, 1, Sq, Skv)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        kv_len = kv_len.reshape(-1, 1, 1, 1, 1) if kv_len.ndim else kv_len
        mask = mask & (jnp.arange(Skv).reshape(1, 1, 1, 1, Skv) < kv_len)

    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def decode_attention(q, k, v, *, kv_len, scale=None, logit_soft_cap: float = 0.0):
    """Single-token decode attention: q (B, Hq, 1, D) against a cache."""
    return mha(q, k, v, causal=False, kv_len=kv_len, scale=scale,
               logit_soft_cap=logit_soft_cap)


def gather_kv_pages(pages, block_tables):
    """Materialize per-slot contiguous KV from pooled pages.

    pages: (P, Hkv, page, D) — the pool buffer, page id on axis 0 —
    or any rank with the page-token axis second-to-last (MLA latent
    pools are (P, page, r)); block_tables: (B, n_pages) int32 page ids.
    Returns (B, Hkv, n_pages * page, D) / (B, n_pages * page, r) —
    slot ``b``'s KV laid out contiguously in token order (garbage
    beyond the slot's kv_len; the caller masks).
    """
    page = pages.shape[-2]
    B, n = block_tables.shape
    g = pages[block_tables]                     # (B, n, *mid, page, last)
    g = jnp.moveaxis(g, 1, -3)                  # (B, *mid, n, page, last)
    return g.reshape(*g.shape[:-3], n * page, g.shape[-1])


# ---------------------------------------------------------------------------
# KV quantization (int8 / fp8 paged pools)
# ---------------------------------------------------------------------------


def kv_qmax(dtype):
    """Max representable magnitude for a quantized-KV storage dtype, or
    ``None`` if ``dtype`` is not a quantized KV format."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.int8):
        return 127.0
    if dt == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0
    return None


def quantize_kv(x, dtype):
    """Symmetric per-vector amax quantization over the last axis.

    x: (..., D) any float dtype. Returns (q, scale): ``q`` is ``x``
    stored in ``dtype`` (int8 or fp8_e4m3), ``scale`` is (...,) float32
    with ``dequantize_kv(q, scale) ~= x``. All-zero vectors get scale 0
    and quantize to exact zeros — dequant reproduces them bit-exactly,
    which keeps untouched pool pages (the trash page included) at 0.
    """
    qmax = kv_qmax(dtype)
    assert qmax is not None, f"not a quantized KV dtype: {dtype}"
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    y = xf / safe[..., None]
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(dtype)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv`: (..., D) values + (...,) scales
    -> float32 (..., D)."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def gather_dequant_kv_pages(pages, scales, block_tables):
    """Gather quantized pool pages into the contiguous per-slot view and
    dequantize with the per-position scale sidecar.

    pages: quantized pool buffer, page-token axis second-to-last;
    scales: float32 sidecar, same shape minus the trailing feature axis
    (one scale per written position per kv-head). Returns float32.
    """
    g = gather_kv_pages(pages, block_tables)
    s = gather_kv_pages(scales[..., None], block_tables)
    return g.astype(jnp.float32) * s.astype(jnp.float32)


def paged_attention(q, k_pages, v_pages, *, block_tables, kv_len, scale=None,
                    logit_soft_cap: float = 0.0, pos_offset=None,
                    k_scales=None, v_scales=None):
    """Paged decode attention, pure-jnp oracle: gather the block-table
    row into a contiguous (B, Hkv, S, D) view, then run the standard
    decode attention. The Pallas kernel performs the same gather inside
    the kernel, a block of live pages at a time, via the scalar-
    prefetched block table.

    q: (B, Hq, 1, D); k_pages, v_pages: (P, Hkv, page, D);
    block_tables: (B, n_pages); kv_len: scalar or (B,).
    pos_offset: optional scalar or (B,) — tokens rolled out of the
    slot's window. The block table holds only the surviving pages, so
    the slot-space KV length is ``kv_len - pos_offset``.
    k_scales, v_scales: optional (P, Hkv, page) float32 sidecars for
    quantized pools — when given, pages dequantize as
    ``page.astype(f32) * scale`` and the math runs in float32, matching
    the in-kernel dequant of the Pallas path.
    """
    kv_len = jnp.asarray(kv_len)
    if pos_offset is not None:
        kv_len = kv_len - jnp.asarray(pos_offset)
    if k_scales is not None:
        k = gather_dequant_kv_pages(k_pages, k_scales, block_tables)
        v = gather_dequant_kv_pages(v_pages, v_scales, block_tables)
    else:
        k = gather_kv_pages(k_pages, block_tables).astype(q.dtype)
        v = gather_kv_pages(v_pages, block_tables).astype(q.dtype)
    return decode_attention(q, k, v, kv_len=kv_len, scale=scale,
                            logit_soft_cap=logit_soft_cap)


def mha_chunked(q, k, v, *, causal: bool = True, scale=None,
                logit_soft_cap: float = 0.0, chunk_q: int = 512):
    """Exact attention computed in query chunks (flash-style memory
    behaviour without the kernel): the (Sq, Skv) score matrix is never
    materialized beyond (chunk_q, Skv). This is the path the dry-run
    lowers for long prefill/training sequences; the Pallas kernel
    replaces it on TPU."""
    B, Hq, Sq, D = q.shape
    if Sq <= chunk_q:
        return mha(q, k, v, causal=causal, scale=scale, logit_soft_cap=logit_soft_cap)
    pad = (-Sq) % chunk_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = q.shape[2] // chunk_q
    qc = q.reshape(B, Hq, nc, chunk_q, D).transpose(2, 0, 1, 3, 4)  # (nc,B,H,cq,D)

    def one(i, qi):
        off = i * chunk_q + (k.shape[2] - Sq) if causal else None
        return mha(qi, k, v, causal=causal, q_offset=off, scale=scale,
                   logit_soft_cap=logit_soft_cap)

    out = jax.lax.map(lambda args: one(args[0], args[1]),
                      (jnp.arange(nc), qc))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, nc * chunk_q, D)
    return out[:, :, :Sq]


# ---------------------------------------------------------------------------
# Mamba-2 SSD (chunked scan)
# ---------------------------------------------------------------------------


def _segsum(logd):
    """Log-space segment sums: out[..., t, s] = sum_{r=s+1..t} logd[..., r].

    logd: (..., L). Returns (..., L, L), -inf above the diagonal.
    """
    L = logd.shape[-1]
    c = jnp.cumsum(logd, axis=-1)
    diff = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), dtype=bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd(x, dt, A, B, C, D, *, chunk: int = 64, h0=None):
    """Mamba-2 state-space duality (chunked) forward.

    x:  (b, T, H, P)   values
    dt: (b, T, H)      positive step sizes (already softplus'd + bias)
    A:  (H,)           negative decay rates
    B:  (b, T, N)      input projection (ngroups=1, shared across heads)
    C:  (b, T, N)      output projection
    D:  (H,)           skip
    h0: optional (b, H, P, N) initial state
    Returns: y (b, T, H, P), h_final (b, H, P, N)
    """
    b, T, H, P = x.shape
    N = B.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    dtf = dt.astype(jnp.float32)
    logd = dtf * A.astype(jnp.float32)[None, None, :]          # (b, T, H) = log decay
    xc = x.astype(jnp.float32).reshape(b, nc, chunk, H, P)
    dtc = dtf.reshape(b, nc, chunk, H)
    ldc = logd.reshape(b, nc, chunk, H)
    Bc = B.astype(jnp.float32).reshape(b, nc, chunk, N)
    Cc = C.astype(jnp.float32).reshape(b, nc, chunk, N)

    # ---- intra-chunk (quadratic, attention-like) ----
    ld_t = jnp.moveaxis(ldc, -1, -2)                            # (b, nc, H, L)
    G = jnp.exp(_segsum(ld_t))                                  # (b, nc, H, L, L)
    CB = jnp.einsum("bctn,bcsn->bcts", Cc, Bc)                  # (b, nc, L, L)
    dts = jnp.moveaxis(dtc, -1, -2)                             # (b, nc, H, L)
    # M[t, s] = CB[t, s] * G[h, t, s] * dt[h, s]
    M = CB[:, :, None] * G * dts[..., None, :]                  # (b, nc, H, L, L)
    y_intra = jnp.einsum("bchts,bcshp->bcthp", M, xc)

    # ---- chunk states ----
    cum = jnp.cumsum(ld_t, axis=-1)                             # (b, nc, H, L)
    decay_to_end = jnp.exp(cum[..., -1:] - cum)                 # (b, nc, H, L)
    S = jnp.einsum("bchs,bcsh,bcsn,bcshp->bchpn",
                   decay_to_end, dtc, Bc, xc)                   # (b, nc, H, P, N)

    # ---- inter-chunk recurrence: H_c = a_c * H_{c-1} + S_c ----
    a = jnp.exp(cum[..., -1])                                   # (b, nc, H) total chunk decay

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, s2 + a2[..., None, None] * s1

    aa, hh = jax.lax.associative_scan(combine, (a, S), axis=1)  # states *after* each chunk
    if h0 is not None:
        h0f = h0.astype(jnp.float32)
        hh = hh + aa[..., None, None] * h0f[:, None]
    # state entering chunk c = hh[c-1] (or h0 for c=0)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(hh[:, :1]) if h0 is None else h0f[:, None], hh[:, :-1]], axis=1)

    # ---- inter-chunk contribution to outputs ----
    decay_from_start = jnp.exp(cum)                             # (b, nc, H, L) includes own step
    y_inter = jnp.einsum("bctn,bcht,bchpn->bcthp", Cc, decay_from_start, h_prev)

    y = y_intra + y_inter + D.astype(jnp.float32)[None, None, None, :, None] * xc
    return y.reshape(b, T, H, P).astype(x.dtype), hh[:, -1].astype(jnp.float32)


def ssd_step(x, dt, A, B, C, D, h):
    """Single-token SSD recurrence (decode). Shapes as ssd() with T==1 squeezed.

    x: (b, H, P), dt: (b, H), B/C: (b, N), h: (b, H, P, N).
    """
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32)[None, :])          # (b, H)
    xB = jnp.einsum("bhp,bn->bhpn", x.astype(jnp.float32) * dtf[..., None], B.astype(jnp.float32))
    h_new = dA[..., None, None] * h + xB
    y = jnp.einsum("bn,bhpn->bhp", C.astype(jnp.float32), h_new)
    y = y + D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), h_new


# ---------------------------------------------------------------------------
# AWQ-style W4A16 grouped-dequant matmul (TPU-native Marlin adaptation)
# ---------------------------------------------------------------------------


def awq_pack(w_int, bits: int = 4):
    """Pack int weights (K, N), values in [0, 2^bits), into int32 (K//pack, N)."""
    pack = 32 // bits
    K, N = w_int.shape
    assert K % pack == 0
    w = w_int.astype(np.uint32).reshape(K // pack, pack, N)
    out = np.zeros((K // pack, N), dtype=np.uint32)
    for i in range(pack):
        out |= w[:, i, :] << (bits * i)
    return jnp.asarray(out.astype(np.int32))


def awq_unpack(qw, bits: int = 4):
    """Unpack int32 (K//pack, N) -> int32 (K, N) in [0, 2^bits)."""
    pack = 32 // bits
    Kp, N = qw.shape
    u = qw.astype(jnp.uint32)
    parts = [(u >> (bits * i)) & ((1 << bits) - 1) for i in range(pack)]
    w = jnp.stack(parts, axis=1).reshape(Kp * pack, N)
    return w.astype(jnp.int32)


def awq_matmul(x, qw, scales, zeros, *, bits: int = 4, group_size: int = 128):
    """x (M, K) @ dequant(qw) -> (M, N).

    qw: packed int32 (K // (32/bits), N)
    scales, zeros: (K // group_size, N) float
    w = (q - z) * s per group.
    """
    K = x.shape[-1]
    w_int = awq_unpack(qw, bits)                                # (K, N)
    g = jnp.arange(K) // group_size
    s = scales.astype(jnp.float32)[g]                           # (K, N)
    z = zeros.astype(jnp.float32)[g]
    w = (w_int.astype(jnp.float32) - z) * s
    return (x.astype(jnp.float32) @ w).astype(x.dtype)
