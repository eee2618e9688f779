"""Top-k MoE with shared experts (DeepSeek-V2 / Grok-1 style).

Dispatch is capacity-bounded scatter/gather (sorted-slot formulation)
rather than the GShard (T, E, C) one-hot einsum: the dense dispatch
tensor is O(T^2 k / E) and does not fit at 1M-token global batches,
while the scatter form is linear in T. Experts are expert-parallel:
stacked weights (E, D, F) shard E over the "model" mesh axis when E is
divisible, otherwise F ("expert_ffn") — the divisibility guard in
repro.distributed.sharding picks automatically.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_as
from repro.models.common import ModelConfig, ParamDef
from repro.models import layers


def moe_def(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    defs = {
        "router": ParamDef((d, e), ("embed", None), init="scaled"),
        "w1": ParamDef((e, d, f), ("experts", "embed", "expert_ffn"), init="scaled"),
        "w3": ParamDef((e, d, f), ("experts", "embed", "expert_ffn"), init="scaled"),
        "w2": ParamDef((e, f, d), ("experts", "expert_ffn", "embed"), init="scaled",
                       scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.n_shared_experts:
        defs["shared"] = layers.mlp_def(cfg, d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts)
    return defs


def _experts_ffn(xe, p, cfg: ModelConfig):
    """xe (E, C, D) -> (E, C, D), per-expert gated MLP."""
    dt = xe.dtype
    h1 = jnp.einsum("ecd,edf->ecf", xe, p["w1"].astype(dt))
    h3 = jnp.einsum("ecd,edf->ecf", xe, p["w3"].astype(dt))
    h1 = shard_as(h1, "experts", "moe_cap", "expert_ffn")
    h3 = shard_as(h3, "experts", "moe_cap", "expert_ffn")
    act = jax.nn.silu if cfg.act == "silu" else (lambda z: jax.nn.gelu(z, approximate=True))
    h = act(h1) * h3
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
    return shard_as(y, "experts", "moe_cap", "embed")


def _route(xt, router, cfg: ModelConfig, K):
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)
    if cfg.router_renorm:
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _slots(idx, E, C, K):
    """Capacity-bounded slot per (token, k) unit; E*C == overflow."""
    T = idx.shape[0]
    flat_e = idx.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    return jnp.where(keep, flat_e * C + pos, E * C), keep


def moe_mlp_shard_map(x, p, cfg: ModelConfig, *, capacity_factor: float):
    """Manual expert dispatch under shard_map (§Perf C5): each data shard
    routes and scatters its LOCAL tokens into local expert buffers (no
    cross-device scatter at all); the expert FFN contracts the
    model-sharded d_ff dim with one psum_scatter+all_gather per layer.
    FSDP weight shards are all-gathered along "data" inside — exactly
    what GSPMD does for dense layers, minus the pathological scatter
    resharding (measured: 72s -> see EXPERIMENTS.md)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import _active_mesh, logical_to_pspec

    mesh = _active_mesh()
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    axes = dict(mesh.shape)
    dp = axes.get("data", 1) * axes.get("pod", 1)
    msize = axes.get("model", 1)
    if B % dp != 0:  # divisibility guard -> GSPMD fallback
        return None
    # two regimes: expert-parallel (E shards over "model": all_to_all
    # exchange of expert blocks, full d_ff local) vs d_ff-parallel
    # (E replicated, F sharded: psum of partial outputs)
    expert_parallel = (E % msize == 0) and msize > 1
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    f_full = cfg.moe_d_ff or cfg.d_ff

    T_local = (B // dp) * S
    C = max(int(math.ceil(T_local * K * capacity_factor / E)), 1)
    C = ((C + 7) // 8) * 8

    def local(xl, router, w1, w3, w2):
        # xl (B/dp, S, D); w* (E, D/dp?, F/tp) — gather FSDP shards first
        if router.shape[0] != D:
            router = jax.lax.all_gather(router, data_axes, axis=0, tiled=True)
        if w1.shape[1] != D:
            w1 = jax.lax.all_gather(w1, data_axes, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, data_axes, axis=1, tiled=True)
        if w2.shape[2] != D:
            w2 = jax.lax.all_gather(w2, data_axes, axis=2, tiled=True)
        f_is_sharded = w1.shape[2] != f_full
        xt = xl.reshape(-1, D)
        gates, idx = _route(xt, router, cfg, K)
        slot, keep = _slots(idx, E, C, K)
        tok = jnp.arange(xt.shape[0] * K, dtype=jnp.int32) // K
        x_units = jnp.take(xt, tok, axis=0)
        buf = jnp.zeros((E * C, D), xt.dtype).at[slot].add(x_units, mode="drop")
        xe = buf.reshape(E, C, D)
        if expert_parallel:
            # every model shard routed the same tokens; exchange expert
            # blocks so shard m gets ALL capacity slices for its experts
            xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                    tiled=True)           # (E/m, C*m, D)
        dt = xe.dtype
        h1 = jnp.einsum("ecd,edf->ecf", xe, w1.astype(dt))
        h3 = jnp.einsum("ecd,edf->ecf", xe, w3.astype(dt))
        act = jax.nn.silu if cfg.act == "silu" else (lambda z: jax.nn.gelu(z, approximate=True))
        ye = jnp.einsum("ecf,efd->ecd", act(h1) * h3, w2.astype(dt))
        if expert_parallel:
            ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                    tiled=True)           # (E, C, D)
        # combine is linear in ye, so run it on the PARTIAL sums and
        # psum the (T, D) result instead of the (E, C, D) buffers —
        # ~2.5x less all-reduce traffic (§Perf C6)
        y_units = jnp.take(ye.reshape(E * C, D), slot, axis=0,
                           mode="fill", fill_value=0)
        gf = (gates.reshape(-1) * keep).astype(y_units.dtype)
        y = (y_units * gf[:, None]).reshape(xt.shape[0], K, D).sum(axis=1)
        if f_is_sharded:
            y = jax.lax.psum(y, "model")        # partial over the f shards
        return y.reshape(xl.shape)

    def spec_of(logical, shape):
        return logical_to_pspec(logical, shape, mesh)

    bspec = spec_of(("batch", None, None), x.shape)
    # replication checking off: ``local`` psums its partial results itself
    out = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(bspec,
                  spec_of(("embed", None), p["router"].shape),
                  spec_of(("experts", "embed", "expert_ffn"), p["w1"].shape),
                  spec_of(("experts", "embed", "expert_ffn"), p["w3"].shape),
                  spec_of(("experts", "expert_ffn", "embed"), p["w2"].shape)),
        out_specs=bspec,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    if cfg.n_shared_experts:
        out = out + layers.mlp(x, p["shared"], cfg)
    return out


def moe_mlp(x, p, cfg: ModelConfig, *, capacity_factor: float | None = None):
    """x (B, S, D) -> (B, S, D). Token-choice top-k with capacity drop."""
    if cfg.moe_dispatch == "shard_map":
        from repro.distributed.sharding import _active_mesh, current_rules
        if current_rules() is not None and _active_mesh() is not None:
            cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
            y = moe_mlp_shard_map(x, p, cfg, capacity_factor=cf)
            if y is not None:
                return y
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    C = max(int(math.ceil(T * K * cf / E)), 1)
    # pad capacity to keep matmul dims friendly
    C = ((C + 7) // 8) * 8

    xt = x.reshape(T, D)
    xt = shard_as(xt, "batch", "embed")

    # ---- routing ----
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)                                    # (T, K)
    if cfg.router_renorm:
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # ---- slot assignment (position within expert, capacity-bounded) ----
    flat_e = idx.reshape(T * K)                                             # expert of each unit
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)                     # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                                    # running count
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]           # (T*K,)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)                         # overflow -> trash row

    # ---- dispatch: inverse-permutation GATHER into expert buffers ----
    # A direct scatter of the (T*K, D) token tensor makes GSPMD replicate
    # the updates (TBs of all-gather at 1M tokens; §Perf C2/C4). Instead,
    # scatter only the int32 inverse index (tiny), then gather the wide
    # rows — gathers partition far better than scatters under GSPMD.
    token_of_unit = jnp.arange(T * K, dtype=jnp.int32) // K
    inv = jnp.full((E * C,), -1, jnp.int32).at[slot].set(token_of_unit, mode="drop")
    filled = inv >= 0
    xe = jnp.take(xt, jnp.maximum(inv, 0), axis=0)                          # (E*C, D)
    xe = jnp.where(filled[:, None], xe, 0)
    xe = shard_as(xe, "moe_cap", "embed")
    xe = xe.reshape(E, C, D)
    xe = shard_as(xe, "experts", "moe_cap", "embed")

    # ---- expert compute ----
    ye = _experts_ffn(xe, p, cfg)                                           # (E, C, D)

    # ---- combine: gather back and weight by (renormalized) gates ----
    y_units = jnp.take(ye.reshape(E * C, D), slot, axis=0,
                       mode="fill", fill_value=0)                           # (T*K, D)
    gates_flat = (gates.reshape(T * K) * keep).astype(y_units.dtype)
    y = (y_units * gates_flat[:, None]).reshape(T, K, D).sum(axis=1)

    # ---- shared experts (always-on residual path) ----
    if cfg.n_shared_experts:
        y = y + layers.mlp(x, p["shared"], cfg).reshape(T, D)

    y = shard_as(y, "batch", "embed")
    return y.reshape(B, S, D)


def load_balance_loss(logits, idx, cfg: ModelConfig):
    """Switch-style auxiliary load-balance loss (exposed for training)."""
    E = cfg.n_experts
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=0)                                 # mean router prob per expert
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / idx.size
    return E * jnp.sum(me * ce)
