"""Paged decode attention: Pallas kernel (interpret mode) and jnp
reference against the contiguous decode oracle.

The contract: for any block-table layout, paged attention over pool
pages equals contiguous decode attention over the gathered per-slot
view — including pages holding other sessions' garbage beyond a slot's
kv_len (masked to an exact 0 contribution), trash-page entries (page 0)
in the table's padding, and shared pages appearing in several slots'
tables at once.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention


def randn(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape), dtype)


def _setup(rng, *, B=3, Hq=8, Hkv=2, D=64, page=16, n_pages=6, P=32):
    q = randn(rng, (B, Hq, 1, D))
    k_pages = randn(rng, (P, Hkv, page, D))
    v_pages = randn(rng, (P, Hkv, page, D))
    # distinct non-trash pages per slot, padded with 0 (the trash page)
    bt = np.zeros((B, n_pages), np.int32)
    ids = rng.permutation(np.arange(1, P))[: B * n_pages]
    bt[:] = ids.reshape(B, n_pages)
    return q, k_pages, v_pages, jnp.asarray(bt)


# ------------------------------------------------------------ ref oracle
def test_ref_paged_equals_contiguous_decode():
    """Gathering the block table then running contiguous decode IS the
    definition — check the one-shot ref entry point agrees with the
    manual two-step, per-slot kv_len."""
    rng = np.random.default_rng(0)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([1, 37, 96], jnp.int32)
    out = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len)
    k = ref.gather_kv_pages(kp, bt)
    v = ref.gather_kv_pages(vp, bt)
    exp = ref.decode_attention(q, k, v, kv_len=kv_len)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_gather_kv_pages_rank3_latent():
    """MLA latent pools are (P, page, r) — the gather must handle the
    head-axis-free rank too."""
    rng = np.random.default_rng(1)
    pages = randn(rng, (10, 16, 24))
    bt = jnp.asarray([[3, 1, 4], [1, 5, 9]], jnp.int32)
    g = ref.gather_kv_pages(pages, bt)
    assert g.shape == (2, 48, 24)
    np.testing.assert_array_equal(np.asarray(g[0, 16:32]), np.asarray(pages[1]))
    np.testing.assert_array_equal(np.asarray(g[1, :16]), np.asarray(pages[1]))


def test_garbage_pages_cannot_leak_past_kv_len():
    """Pages past kv_len hold other sessions' KV, not zeros. The mask
    must make their contribution exactly zero: replacing them with
    anything finite must not change a single output bit."""
    rng = np.random.default_rng(2)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([17, 33, 49], jnp.int32)
    out = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len)
    tail = jnp.asarray(np.asarray(bt)[:, 4])               # clobber tail pages
    kp2 = kp.at[tail].set(1e6)
    vp2 = vp.at[tail].set(-1e6)
    out2 = ref.paged_attention(q, kp2, vp2, block_tables=bt, kv_len=kv_len)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ------------------------------------------------------------ Pallas kernel
def test_pallas_paged_matches_ref():
    rng = np.random.default_rng(3)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([1, 37, 96], jnp.int32)
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                          interpret=True)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)


def test_pallas_paged_single_partial_page():
    """One slot, kv_len inside the first page — every other page in the
    table must be skipped entirely."""
    rng = np.random.default_rng(4)
    q, kp, vp, bt = _setup(rng, B=1, n_pages=4, P=8)
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=5, interpret=True)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)


def test_pallas_paged_shared_pages_across_slots():
    """Two slots whose tables share every page but the last (the prefix-
    cache layout after a dedupe hit)."""
    rng = np.random.default_rng(5)
    q = randn(rng, (2, 4, 1, 32))
    kp = randn(rng, (12, 4, 16, 32))
    vp = randn(rng, (12, 4, 16, 32))
    bt = jnp.asarray([[5, 6, 7, 1], [5, 6, 7, 2]], jnp.int32)
    kv_len = jnp.asarray([64, 52], jnp.int32)
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                          interpret=True)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)


def test_pallas_paged_mqa_and_soft_cap():
    """Hkv == Hq (group size 1) with logit soft-capping."""
    rng = np.random.default_rng(6)
    q = randn(rng, (2, 4, 1, 32))
    kp = randn(rng, (9, 4, 16, 32))
    vp = randn(rng, (9, 4, 16, 32))
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    kv_len = jnp.asarray([40, 48], jnp.int32)
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                          logit_soft_cap=30.0, interpret=True)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                              logit_soft_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)


# ------------------------------------------------------- rolling pos_offset
def test_ref_pos_offset_shortens_slot_kv():
    """pos_offset semantics: the slot-space KV length is
    kv_len - pos_offset, so (kv_len=L, pos_offset=p) must equal
    (kv_len=L-p, pos_offset=0) exactly — the block table already maps
    the post-roll layout; the offset only converts absolute length."""
    rng = np.random.default_rng(7)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([20, 70, 96], jnp.int32)
    poff = jnp.asarray([0, 16, 48], jnp.int32)
    out = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                              pos_offset=poff)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt,
                              kv_len=kv_len - poff)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_pallas_pos_offset_matches_ref_and_skips_rolled_pages():
    """The kernel receives pos_offset via scalar prefetch: outputs must
    match the ref oracle, and pages past the slot-space length are
    fully skipped — clobbering them cannot change a bit."""
    rng = np.random.default_rng(8)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([36, 80, 96], jnp.int32)
    poff = jnp.asarray([16, 32, 64], jnp.int32)
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                          pos_offset=poff, interpret=True)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                              pos_offset=poff)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
    # slot 2's slot-space length is 32: pages 3..5 of its table are
    # garbage the mask must zero out entirely
    tail = jnp.asarray(np.asarray(bt)[2, 3:])
    out2 = paged_attention(q, kp.at[tail].set(1e6), vp.at[tail].set(-1e6),
                           block_tables=bt, kv_len=kv_len, pos_offset=poff,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ------------------------------------------------------- quantized pages
def _quantize_pools(kp, vp, dtype):
    kq, ks = ref.quantize_kv(kp, dtype)
    vq, vs = ref.quantize_kv(vp, dtype)
    return kq, ks, vq, vs


def test_quantize_kv_roundtrip_and_invariants():
    """Symmetric amax quantization: per-position scale over the last
    axis, int8 within 0.5/127 of amax relative error, all-zero vectors
    (the trash page) to exact zeros with scale 0, and dequant always
    finite thanks to the scale-0 guard."""
    rng = np.random.default_rng(10)
    x = randn(rng, (5, 16, 64)) * jnp.asarray(
        rng.uniform(0.01, 100.0, (5, 16, 1)), jnp.float32)  # wild ranges
    for dt, qmax in ((jnp.int8, 127.0), (jnp.float8_e4m3fn, 448.0)):
        q, s = ref.quantize_kv(x, dt)
        assert q.dtype == dt and s.dtype == jnp.float32
        assert s.shape == x.shape[:-1]
        back = ref.dequantize_kv(q, s)
        assert back.dtype == jnp.float32
        amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
        tol = (0.51 / qmax) if dt == jnp.int8 else (1.0 / 16)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=float((amax * tol).max()))
        # zeros quantize to zeros with zero scale, and dequant is finite
        zq, zs = ref.quantize_kv(jnp.zeros_like(x), dt)
        assert not np.asarray(zq, np.float32).any()
        assert not np.asarray(zs).any()
        assert np.isfinite(np.asarray(ref.dequantize_kv(zq, zs))).all()


def test_gather_dequant_matches_dequant_then_gather():
    rng = np.random.default_rng(11)
    _, kp, _, bt = _setup(rng)
    kq, ks = ref.quantize_kv(kp, jnp.int8)
    g = ref.gather_dequant_kv_pages(kq, ks, bt)
    exp = ref.gather_kv_pages(ref.dequantize_kv(kq, ks), bt)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(exp))


def test_ref_quantized_paged_close_to_fp32():
    """The jnp oracle with scale operands: output within the attention-
    level quantization error of the fp32 pool (values are O(1) randn,
    so absolute logit error stays small)."""
    rng = np.random.default_rng(12)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([1, 37, 96], jnp.int32)
    base = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len)
    for dt, atol in ((jnp.int8, 0.05), (jnp.float8_e4m3fn, 0.25)):
        kq, ks, vq, vs = _quantize_pools(kp, vp, dt)
        out = ref.paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                                  k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=atol)


def test_pallas_quantized_matches_ref():
    """In-kernel dequant (scale blocks steered by the same scalar-
    prefetch block table) against the jnp oracle, both dtypes, ragged
    kv_len + pos_offset."""
    rng = np.random.default_rng(13)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([17, 80, 96], jnp.int32)
    poff = jnp.asarray([0, 16, 48], jnp.int32)
    for dt in (jnp.int8, jnp.float8_e4m3fn):
        kq, ks, vq, vs = _quantize_pools(kp, vp, dt)
        out = paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                              pos_offset=poff, k_scales=ks, v_scales=vs,
                              interpret=True)
        exp = ref.paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                                  pos_offset=poff, k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-4)


def test_pallas_quantized_garbage_pages_masked():
    """Masking must hold with scale operands too: clobbering pages past
    kv_len (values AND scales) cannot change a bit of the output."""
    rng = np.random.default_rng(14)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([17, 33, 49], jnp.int32)
    kq, ks, vq, vs = _quantize_pools(kp, vp, jnp.int8)
    out = paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                          k_scales=ks, v_scales=vs, interpret=True)
    tail = jnp.asarray(np.asarray(bt)[:, 4])
    out2 = paged_attention(q, kq.at[tail].set(127), vq.at[tail].set(-127),
                           block_tables=bt, kv_len=kv_len,
                           k_scales=ks.at[tail].set(1e6),
                           v_scales=vs.at[tail].set(1e6), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_rope_shift_requant_error_bounded():
    """The rolling-window requant cycle: dequant -> rope_shift -> requant
    must stay within ~2x a single quantization step of rotating the
    exact values (rotation is norm-preserving per 2D pair, so amax —
    and with it the quantization step — cannot blow up)."""
    from repro.models.layers import rope_shift

    rng = np.random.default_rng(15)
    x = randn(rng, (4, 2, 32, 64))                  # (pages, Hkv, page, D)
    for dt, step in ((jnp.int8, 1 / 127.0), (jnp.float8_e4m3fn, 1 / 16.0)):
        q1, s1 = ref.quantize_kv(x, dt)
        rolled = rope_shift(ref.dequantize_kv(q1, s1), -32, 10000.0)
        q2, s2 = ref.quantize_kv(rolled, dt)
        exact = rope_shift(x, -32, 10000.0)
        amax = np.abs(np.asarray(exact)).max()
        err = np.abs(np.asarray(ref.dequantize_kv(q2, s2))
                     - np.asarray(exact)).max()
        assert err < 2.5 * step * float(amax) * np.sqrt(2), (dt, err)


def test_pos_offset_zero_is_bitwise_default():
    """poff=0 must take the exact same arithmetic path as no poff at
    all — the token-identity guarantee for window-fitting sessions."""
    rng = np.random.default_rng(9)
    q, kp, vp, bt = _setup(rng)
    kv_len = jnp.asarray([17, 37, 96], jnp.int32)
    base = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                           interpret=True)
    zero = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                           pos_offset=jnp.zeros((3,), jnp.int32),
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(zero))


# ------------------------------------------- block stepping (live pages)
# Shapes at which the derived pages-per-block is small against a 64-page
# table: one page of all 8 kv heads is 32 KB in bf16 (8 pages a block)
# and 16 KB in int8 (16 pages a block), so every case below spans
# several blocks and ends inside, on or just past a block boundary.
_SB = dict(Hq=4, Hkv=2, D=128, page=64)


def _block_pools(rng, pool, *, B, n_pages):
    """Distinct non-trash pages per slot over a pool of B * n_pages + 1;
    returns q, pools, table, the scale kwargs and pages_per_block."""
    from repro.kernels.paged_attention import pages_per_block

    q, kp, vp, bt = _setup(rng, B=B, n_pages=n_pages, P=B * n_pages + 1,
                           **_SB)
    kw = {}
    if pool == "int8":
        kp, ks, vp, vs = _quantize_pools(kp, vp, jnp.int8)
        kw = dict(k_scales=ks, v_scales=vs)
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    page_bytes = _SB["Hkv"] * _SB["page"] * _SB["D"] * kp.dtype.itemsize
    return q, kp, vp, bt, kw, pages_per_block(page_bytes, n_pages)


def _case_lengths(case, ppb, page):
    """(slot-space kv lengths, table width) of a named case."""
    edge, full = ppb * page, 64 * page
    return {
        "block_boundary": ([edge], 64),
        "block_plus_one": ([edge + 1], 64),
        "one_page": ([page], 64),
        "full_table": ([full], 64),
        "ragged": ([edge, edge + 1, page, full, 3 * edge - 5, 1], 64),
        # a 60-page table is no multiple of 8 or 16 pages a block
        "table_not_block_multiple": ([60 * page, edge + 1, 7], 60),
    }[case]


def _kernel_and_ref(q, kp, vp, bt, kv_len, poff, kw):
    out = paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                          pos_offset=poff, interpret=True, **kw)
    exp = ref.paged_attention(q, kp, vp, block_tables=bt, kv_len=kv_len,
                              pos_offset=poff, **kw)
    return np.asarray(out), np.asarray(exp)


@pytest.mark.parametrize("roll", [0, 128], ids=["no_offset", "pos_offset"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("case", ["block_boundary", "block_plus_one",
                                  "one_page", "full_table", "ragged",
                                  "table_not_block_multiple"])
def test_pallas_paged_block_stepping(case, pool, roll):
    """Block-wise walking of live pages equals the gathered oracle at
    every place a slot can end against the derived block size; the
    rolled offset shifts absolute lengths and nothing else."""
    rng = np.random.default_rng(20)
    ppb = _block_pools(rng, pool, B=1, n_pages=64)[-1]
    lens, n_pages = _case_lengths(case, ppb, _SB["page"])
    assert ppb < 64 and 64 % ppb == 0 and 60 % ppb != 0
    q, kp, vp, bt, kw, _ = _block_pools(rng, pool, B=len(lens),
                                        n_pages=n_pages)
    slot_len = jnp.asarray(lens, jnp.int32)
    poff = jnp.full((len(lens),), roll, jnp.int32)
    out, exp = _kernel_and_ref(q, kp, vp, bt, slot_len + poff, poff, kw)
    np.testing.assert_allclose(out, exp, atol=1e-4)


@pytest.mark.parametrize("roll", [0, 128], ids=["no_offset", "pos_offset"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_pallas_paged_dead_pages_poisoned(pool, roll):
    """Every pool page wholly past each slot's live length — the trash
    page too, which pads the shorter slots' tables — is NaN, values and
    scales alike. Dead pages never enter the math: the output is finite
    and bit-identical to the kernel's over the clean pool, and equals
    the oracle's."""
    rng = np.random.default_rng(21)
    q, kp, vp, bt, kw, ppb = _block_pools(rng, pool, B=4, n_pages=64)
    page = _SB["page"]
    lens = [ppb * page, ppb * page + 1, page, 3 * ppb * page - 5]
    live = [-(-n // page) for n in lens]
    bt = np.asarray(bt).copy()
    bt[2, live[2]:] = 0                  # a short slot's tail is trash
    dead = sorted({0} | {int(p) for b, n in enumerate(live)
                         for p in bt[b, n:]})
    dead = jnp.asarray(dead)
    bt = jnp.asarray(bt)
    poff = jnp.full((4,), roll, jnp.int32)
    kv_len = jnp.asarray(lens, jnp.int32) + poff
    clean, exp = _kernel_and_ref(q, kp, vp, bt, kv_len, poff, kw)
    nan = float("nan")
    if pool == "int8":
        # int8 has no NaN: the scales carry it into every dead position
        kw = dict(k_scales=kw["k_scales"].at[dead].set(nan),
                  v_scales=kw["v_scales"].at[dead].set(nan))
        kp, vp = kp.at[dead].set(127), vp.at[dead].set(-127)
    else:
        kp, vp = kp.at[dead].set(nan), vp.at[dead].set(nan)
    out = np.asarray(paged_attention(q, kp, vp, block_tables=bt,
                                     kv_len=kv_len, pos_offset=poff,
                                     interpret=True, **kw))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)
    np.testing.assert_allclose(out, exp, atol=1e-4)


def test_pallas_paged_true_mqa_soft_cap_fp8():
    """One kv head for all query heads (Hkv = 1, G = Hq), soft-capped,
    over an fp8 pool."""
    rng = np.random.default_rng(22)
    q, kp, vp, bt = _setup(rng, B=2, Hq=8, Hkv=1, D=128, page=16,
                           n_pages=8, P=17)
    kq, ks, vq, vs = _quantize_pools(kp, vp, jnp.float8_e4m3fn)
    kv_len = jnp.asarray([37, 8 * 16], jnp.int32)
    out = paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                          k_scales=ks, v_scales=vs, logit_soft_cap=30.0,
                          interpret=True)
    exp = ref.paged_attention(q, kq, vq, block_tables=bt, kv_len=kv_len,
                              k_scales=ks, v_scales=vs, logit_soft_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
