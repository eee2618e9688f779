"""W4A16 grouped-dequant matmul (AWQ layout) — Pallas TPU.

The paper's HPC tier lives or dies by its AWQ kernels (§2.1: the CUDA
PTX mismatch silently disabled Marlin and cut throughput to 20.1 tok/s).
Marlin's warp-level tricks don't port; the TPU-native version of the
same insight is: keep the int4 weights packed in HBM (4x less traffic
than bf16 — decode is weight-bandwidth-bound), dequantize tile-by-tile
in VMEM, and feed the MXU with bf16 tiles.

Layout: qw int32 (K/8, N) — 8 nibbles per word along K; scales/zeros
(K/group_size, N). Block K == group_size so each K-tile uses exactly
one scale row. Grid (M/bm, N/bn, K/bk), K innermost sequential, fp32
accumulator in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _awq_kernel(x_ref, qw_ref, s_ref, z_ref, o_ref, acc_ref, *, bits):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                 # (bm, bk)
    qw = qw_ref[...]                                   # (bk/pack, bn) int32
    pack = 32 // bits
    mask = (1 << bits) - 1
    # logical shifts on int32: Mosaic has no uint32 -> float32 cast
    parts = [(jax.lax.shift_right_logical(qw, bits * i) & mask).astype(jnp.float32)
             for i in range(pack)]
    w_int = jnp.stack(parts, axis=1).reshape(qw.shape[0] * pack, qw.shape[1])
    s = s_ref[0].astype(jnp.float32)                   # (1, bn)
    z = z_ref[0].astype(jnp.float32)
    w = (w_int - z) * s                                # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def awq_matmul(x, qw, scales, zeros, *, bits=4, group_size=128,
               interpret=False, block_m=128, block_n=128):
    """x (M, K) @ dequant(qw (K/pack, N)) -> (M, N). The K tile is one
    quantization group, so each grid step reads one scale/zero row."""
    M, K = x.shape
    pack = 32 // bits
    N = qw.shape[1]
    bk = group_size
    assert K % bk == 0
    bm = min(block_m, M)
    bn = min(block_n, N)
    padm = (-M) % bm
    if padm:
        x = jnp.pad(x, ((0, padm), (0, 0)))
    assert N % bn == 0, (N, bn)
    # one scale/zero row per K tile: viewed as (K/g, 1, N), that row is a
    # (1, bn) block whose second-minor dim equals the array's, as the
    # TPU tiling rule requires of a block that is not a multiple of 8
    groups = scales.shape[0]
    scales = scales.reshape(groups, 1, N)
    zeros = zeros.reshape(groups, 1, N)

    out = pl.pallas_call(
        functools.partial(_awq_kernel, bits=bits),
        grid=((M + padm) // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk // pack, bn), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((1, 1, bn), lambda im, jn, ik: (ik, 0, jn)),
            pl.BlockSpec((1, 1, bn), lambda im, jn, ik: (ik, 0, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((M + padm, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, qw, scales, zeros)
    return out[:M]
