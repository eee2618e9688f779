"""Test fixture: what a reference module of a MoE decoder with latent
attention (DeepSeek-V2's layout: a dense first block, then blocks of
multi-head latent attention and routed plus shared experts) declares for
the harness, without a forward pass: the weight layout it would read and
the operations a token costs. A later configuration of this family brings
a module like this one with its forward pass beside it, and no file of
``bench/benchlib`` changes for it."""

from __future__ import annotations

import math


def _attn(m: dict, lead: tuple, out: float) -> dict:
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {"wq": (lead + (d, H * (dn + dr)), "normal", d ** -0.5),
            "wdkv": (lead + (d, r), "normal", d ** -0.5),
            "wkr": (lead + (d, dr), "normal", d ** -0.5),
            "kv_norm": (lead + (r,), "gain", 0.1),
            "wuk": (lead + (r, H * dn), "normal", r ** -0.5),
            "wuv": (lead + (r, H * dv), "normal", r ** -0.5),
            "wo": (lead + (H * dv, d), "normal", (H * dv) ** -0.5 * out)}


def _mlp(d: int, f: int, lead: tuple, out: float) -> dict:
    return {"w1": (lead + (d, f), "normal", d ** -0.5),
            "w3": (lead + (d, f), "normal", d ** -0.5),
            "w2": (lead + (f, d), "normal", f ** -0.5 * out)}


def _block(m: dict, lead: tuple, out: float, mlp: dict) -> dict:
    d = m["d_model"]
    return {"ln1": {"w": (lead + (d,), "gain", 0.1)},
            "ln2": {"w": (lead + (d,), "gain", 0.1)},
            "attn": _attn(m, lead, out), "mlp": mlp}


def weight_spec(m: dict) -> dict:
    """{path: (shape, kind, scale)} nested like the parameter tree: the
    routed blocks stacked under ``blocks``, each leading dense block
    under ``dense<i>``."""
    L, d, E = m["n_layers"], m["d_model"], m["n_experts"]
    Ls = L - m["first_dense_layers"]
    f = m["moe_d_ff"]
    vp = -(-m["vocab_size"] // m["vocab_pad_to"]) * m["vocab_pad_to"]
    out = 1.0 / math.sqrt(2 * L)
    moe = {"router": ((Ls, d, E), "normal", d ** -0.5),
           "w1": ((Ls, E, d, f), "normal", d ** -0.5),
           "w3": ((Ls, E, d, f), "normal", d ** -0.5),
           "w2": ((Ls, E, f, d), "normal", f ** -0.5 * out)}
    if m["n_shared_experts"]:
        moe["shared"] = _mlp(d, f * m["n_shared_experts"], (Ls,), out)
    spec = {"embed": {"w": ((vp, d), "normal", 1.0)},
            "blocks": _block(m, (Ls,), out, moe),
            "ln_f": {"w": ((d,), "gain", 0.1)}}
    for i in range(m["first_dense_layers"]):
        spec[f"dense{i}"] = _block(m, (), out, _mlp(d, m["d_ff"], (), out))
    if not m.get("tie_embeddings"):
        spec["lm_head"] = {"w": ((vp, d), "normal", d ** -0.5)}
    return spec


def _body_flops_per_token(m: dict) -> float:
    """Projections of every block and the MLPs a token runs through: the
    dense blocks' MLP, or the router, ``top_k`` routed experts and the
    shared experts."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attn = d * H * (dn + dr) + d * r + d * dr + r * H * dn + r * H * dv + H * dv * d
    dense = 3 * d * m["d_ff"]
    moe = (d * m["n_experts"] + 3 * d * m["moe_d_ff"]
           * (m["top_k"] + m["n_shared_experts"]))
    n_dense = m["first_dense_layers"]
    return 2.0 * (m["n_layers"] * attn + n_dense * dense
                  + (m["n_layers"] - n_dense) * moe)


def _attention_flops(m: dict, seen: int) -> float:
    """Scores (nope and rope parts) and weighted values over ``seen``
    query-key pairs, all blocks."""
    per_key = m["n_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                              + m["v_head_dim"])
    return 2.0 * m["n_layers"] * per_key * seen


def decode_flops(m: dict, kv_len: int) -> float:
    """One decoded token whose query sees ``kv_len`` positions."""
    return (_body_flops_per_token(m) + 2.0 * m["d_model"] * m["vocab_size"]
            + _attention_flops(m, kv_len))


def prefill_flops(m: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens from position ``start``; the
    logits of its last token only."""
    seen = n * start + n * (n + 1) // 2
    return (n * _body_flops_per_token(m) + 2.0 * m["d_model"] * m["vocab_size"]
            + _attention_flops(m, seen))
