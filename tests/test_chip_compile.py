"""The served path's Pallas kernels compile for a TPU v5e at Minitron-8B
widths (B=8, Hq=32, Hkv=8, D=128, page 16, bf16 activations).

Nothing runs: the TPU compiler installed with JAX compiles for a v5e
that is described, not attached, and refuses what the chip's compiler
would refuse (block shapes off the tiling, casts Mosaic lacks) — which
interpret-mode tests cannot see. The topology is described inside a
fixture, never at import, so test workers that are not given this file
never load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import awq_matmul, decode_attention, paged_attention, rmsnorm

B, HQ, HKV, D, PAGE, POOL, N_PAGES = 8, 32, 8, 128, 16, 512, 64
D_MODEL, D_FF, GROUP = 4096, 16384, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,pool", [(B, POOL), (32, 2049)],
                         ids=["b8-pool512", "b32-pool2049"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8", "float8_e4m3fn"])
def test_paged_attention_compiles(one_chip, kv_dtype, b, pool):
    """Today's small shape and the hpc-api cell's: 32 slots over a pool
    of 2,048 pages plus the trash page."""
    kv = jnp.dtype(kv_dtype)
    shapes = [((b, HQ, 1, D), jnp.bfloat16), ((pool, HKV, PAGE, D), kv),
              ((pool, HKV, PAGE, D), kv), ((b, N_PAGES), jnp.int32),
              ((b,), jnp.int32), ((b,), jnp.int32)]
    if kv == jnp.bfloat16:
        def fn(q, k, v, bt, kv_len, poff):
            return paged_attention.paged_attention(
                q, k, v, block_tables=bt, kv_len=kv_len, pos_offset=poff)
    else:
        shapes += [((pool, HKV, PAGE), jnp.float32)] * 2

        def fn(q, k, v, bt, kv_len, poff, ks, vs):
            return paged_attention.paged_attention(
                q, k, v, block_tables=bt, kv_len=kv_len, pos_offset=poff,
                k_scales=ks, v_scales=vs)
    _compile(fn, one_chip, *shapes)


def test_decode_attention_compiles(one_chip):
    S = 1024
    _compile(lambda q, k, v, kv_len: decode_attention.decode_attention(
                 q, k, v, kv_len=kv_len),
             one_chip, ((B, HQ, 1, D), jnp.bfloat16),
             ((B, HKV, S, D), jnp.bfloat16), ((B, HKV, S, D), jnp.bfloat16),
             ((B,), jnp.int32))


@pytest.mark.parametrize("rows", [(B, D_MODEL), (1, PAGE, D_MODEL)])
def test_rmsnorm_compiles(one_chip, rows):
    _compile(lambda x, w: rmsnorm.rmsnorm(x, w), one_chip,
             (rows, jnp.bfloat16), ((D_MODEL,), jnp.bfloat16))


def test_awq_matmul_compiles(one_chip):
    K, N = D_MODEL, D_FF
    _compile(lambda x, qw, s, z: awq_matmul.awq_matmul(
                 x, qw, s, z, group_size=GROUP),
             one_chip, ((B, K), jnp.bfloat16), ((K // 8, N), jnp.int32),
             ((K // GROUP, N), jnp.bfloat16), ((K // GROUP, N), jnp.bfloat16))
