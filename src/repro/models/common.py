"""Shared model-config dataclass and the ParamDef mini-framework.

No flax/haiku offline -- parameters are plain nested dicts of arrays.
Models declare a nested dict of ``ParamDef`` (shape + logical sharding
axes + initializer); helpers materialize it (``init_params``), turn it
into abstract ShapeDtypeStructs for the dry-run (``shape_tree``), and
extract the logical-axis tree for pjit shardings (``spec_tree``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0        # 0 -> d_model // n_heads
    d_ff: int = 256
    vocab_size: int = 512
    act: str = "silu"        # silu (swiglu) | gelu (geglu)
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    use_rope: bool = True
    tie_embeddings: bool = False
    gemma_style: bool = False   # (1+w) rmsnorm scale + sqrt(d) embed scaling
    max_seq_len: int = 4096

    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0   # prefill/decode paths (no-drop margin)
    first_dense_layers: int = 0     # leading dense layers (deepseek-v2)
    router_renorm: bool = True      # renormalize top-k gate weights
    moe_dispatch: str = "gspmd"     # gspmd | shard_map (manual local dispatch)

    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    mla_absorbed_decode: bool = False   # fold W_uk/W_uv into q/out at decode
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    # --- SSM / hybrid (mamba2, zamba2) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    attn_every: int = 0        # hybrid: shared attention block every k layers

    # --- xLSTM ---
    xlstm_slstm_every: int = 0  # sLSTM every k-th layer, else mLSTM
    xlstm_proj_factor: float = 2.0

    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_seq_len: int = 1500

    # --- vision (llama-3.2-vision) ---
    cross_attn_every: int = 0   # cross-attn layer every k-th layer
    n_image_tokens: int = 0
    vision_dim: int = 0         # stub frontend embedding dim (pre-projector)

    # --- numerics / execution ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    use_kernels: bool = False    # Pallas path (TPU); False -> jnp reference path
    remat: bool = True
    scan_layers: bool = True
    vocab_pad_to: int = 2048

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))

    # ---- derived ----
    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, self.vocab_pad_to)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:  # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def n_param_estimate(self) -> int:
        """Rough dense-equivalent parameter count (for 6ND roofline math)."""
        shapes = jax.eval_shape(lambda: None)  # placeholder, overridden by count_params
        return 0

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape, logical sharding axes, initializer."""
    shape: tuple
    logical: tuple            # logical axis name (or None) per dim
    init: str = "normal"      # normal | zeros | ones | embed | scaled
    scale: float = 1.0
    dtype: Any = None         # override param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_def(x):
    return isinstance(x, ParamDef)


def _fan_in(shape):
    if len(shape) == 1:
        return shape[0]
    return int(np.prod(shape[:-1])) if len(shape) == 2 else int(np.prod(shape[-2:-1]))


def _init_leaf(d: ParamDef, key, dt):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "normal" or d.init == "embed":
        std = 0.02 * d.scale
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dt)
    if d.init == "scaled":  # 1/sqrt(fan_in)
        fan = d.shape[-2] if len(d.shape) >= 2 else d.shape[0]
        std = d.scale / math.sqrt(max(fan, 1))
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dt)
    if d.init == "ssm_a":   # mamba A_log in [1, 16]
        u = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dt)
    if d.init == "ssm_dt":  # dt bias ~ softplus-inv of U(1e-3, 1e-1)
        u = jax.random.uniform(key, d.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dt)
    raise ValueError(f"unknown init {d.init}")


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaves(leaves: tuple, rng, param_dtype):
    rngs = jax.random.split(rng, len(leaves))
    return [_init_leaf(d, k, d.dtype or param_dtype)
            for d, k in zip(leaves, rngs)]


def init_params(defs, rng, param_dtype=jnp.float32):
    """Materialize a ParamDef tree into actual arrays. Every leaf is
    drawn and cast inside one jitted program, so each float32 draw fuses
    into its cast and no leaf is ever held on the device wider than its
    own dtype (a stacked bf16 MLP leaf at published widths would
    otherwise pass through a float32 copy twice its size)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    return treedef.unflatten(
        _init_leaves(tuple(leaves), rng, jnp.dtype(param_dtype)))


def shape_tree(defs, param_dtype=jnp.float32):
    """ParamDef tree -> ShapeDtypeStruct tree (no allocation; dry-run input)."""
    def one(d: ParamDef):
        return jax.ShapeDtypeStruct(d.shape, d.dtype or param_dtype)
    return jax.tree.map(one, defs, is_leaf=_is_def)


def spec_tree(defs):
    """ParamDef tree -> logical-axes tree (same structure, tuple leaves)."""
    return jax.tree.map(lambda d: d.logical, defs, is_leaf=_is_def)


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=_is_def)
    return int(sum(np.prod(d.shape) for d in leaves))


def cast_tree(params, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


# ---------------------------------------------------------------------------
# cache-layout introspection (the cache_specs() contract)
# ---------------------------------------------------------------------------
#
# Every model exposes cache_specs(): a pytree mirroring init_cache() whose
# leaves are tuples of logical axis names (or None). Two names are load-
# bearing for the serving layer:
#
#   "batch"   — the decode-slot axis. The continuous batcher splices a
#               batch=1 prefilled cache into slot ``s`` along this axis.
#   "kv_seq"  — a growing sequence axis. Splices only need to copy the
#               *used* prefix (rounded up to a page) along it; leaves
#               without it (SSM/xLSTM recurrent states, cross K/V) are
#               copied whole per slot.
#
# The "pos" leaf has spec () and is managed by the caller (scalar for
# plain generation, a (B,) per-slot vector inside the batcher).


def _is_spec(s):
    return isinstance(s, tuple) and all(isinstance(e, (str, type(None))) for e in s)


def cache_axes(specs):
    """cache_specs() tree -> (batch_axes, seq_axes): same-structure trees of
    axis indices, -1 where the leaf lacks the axis (a -1 sentinel rather
    than None so the leaves survive pytree flattening)."""

    def axis(name):
        def one(spec):
            if not _is_spec(spec):
                return -1
            return spec.index(name) if name in spec else -1
        return one

    batch = jax.tree.map(axis("batch"), specs, is_leaf=_is_spec)
    seq = jax.tree.map(axis("kv_seq"), specs, is_leaf=_is_spec)
    return batch, seq


@dataclass(frozen=True)
class LeafLayout:
    """Page-granular layout of one cache leaf, as the KV page pool sees
    it (``repro.serving.pagepool``):

      * ``paged``  — the leaf has a "kv_seq" axis: a page is a fixed-size
        slice of that axis and is a pure function of the token ids it
        covers (position-stable prefill), so pages are shareable across
        sessions/turns at page granularity.
      * ``state``  — the leaf has a batch axis but no "kv_seq" axis
        (SSM h0 / conv windows, xLSTM cells, cross-attention K/V): the
        pool stores a per-page *snapshot* of the whole leaf, valid only
        at the exact token position it was taken (a prefix match must
        end on a snapshot-bearing page to resume from it).
      * neither    — no batch axis (the "pos" scalar): not pooled.
    """
    batch_axis: int        # -1 when absent
    seq_axis: int          # -1 when absent

    @property
    def paged(self) -> bool:
        return self.seq_axis >= 0

    @property
    def state(self) -> bool:
        return self.batch_axis >= 0 and self.seq_axis < 0

    def pool_shape(self, leaf_shape, page: int, n_pages: int) -> tuple:
        """Paged-attention view of this leaf: the pool buffer that backs
        it. The decode-slot batch axis becomes the pool-page axis
        (``n_pages`` entries) and the "kv_seq" axis is clipped to one
        ``page`` — e.g. k ``(L, B, Hkv, S, D)`` pools as
        ``(L, P, Hkv, page, D)``. Keeping every other axis in place is
        what lets the models' scan-over-layers and attention code run
        unchanged against pool buffers: a layer slice of the pool has
        the same rank and axis order as a layer slice of a contiguous
        cache, with (batch -> page id, seq -> in-page offset)."""
        assert self.paged and self.batch_axis < self.seq_axis, self
        s = list(leaf_shape)
        s[self.batch_axis] = n_pages
        s[self.seq_axis] = page
        return tuple(s)


def cache_layout(specs):
    """cache_specs() tree -> same-structure tree of :class:`LeafLayout`.
    ``has_state_leaves(layout)`` tells the serving layer whether prefix
    resume needs state snapshots at all (pure-attention models don't)."""

    def one(spec):
        if not _is_spec(spec):
            return LeafLayout(-1, -1)
        return LeafLayout(
            spec.index("batch") if "batch" in spec else -1,
            spec.index("kv_seq") if "kv_seq" in spec else -1)

    return jax.tree.map(one, specs, is_leaf=_is_spec)


def has_state_leaves(layout) -> bool:
    """True when the model carries per-slot state outside the paged KV
    axis (recurrent states, cross K/V) — prefix matches must then end on
    a page that carries a state snapshot."""
    return any(l.batch_axis >= 0 and l.seq_axis < 0
               for l in jax.tree.leaves(
                   layout, is_leaf=lambda x: isinstance(x, LeafLayout)))
