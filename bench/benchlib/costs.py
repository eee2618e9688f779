"""Operations and bytes the algorithm needs, from shapes alone.

Dense GQA decoder with a gated (three-matrix) MLP, as the served model
computes it. ``m`` is a configuration's ``model`` dict (the program's
field names: d_model, n_heads, n_kv_heads, head_dim, d_ff, vocab_size,
n_layers). A multiply-add counts as two operations. Only useful work is
counted: the vocabulary projection once per token whose logits are
read, attention over the positions a query may see.
"""

from __future__ import annotations

import math


def layer_weights(m: dict) -> int:
    """Matrix parameters of one block (attention projections + MLP)."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return d * (q + 2 * kv) + q * d + 3 * d * m["d_ff"]


def body_flops_per_token(m: dict) -> float:
    """Projection and MLP operations of every block for one token."""
    return 2.0 * m["n_layers"] * layer_weights(m)


def head_flops(m: dict) -> float:
    """The vocabulary projection for one token."""
    return 2.0 * m["d_model"] * m["vocab_size"]


def attention_flops(m: dict, kv_len: int) -> float:
    """Scores and weighted values of one query over ``kv_len`` keys, all
    blocks: QK^T and PV are each 2 * n_heads * head_dim per key."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * kv_len


def decode_flops(m: dict, kv_len: int) -> float:
    """One decoded token whose query sees ``kv_len`` positions."""
    return body_flops_per_token(m) + head_flops(m) + attention_flops(m, kv_len)


def prefill_flops(m: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens at positions start..start+n-1
    (causal: token i sees i + 1 positions); the program reads the logits
    of the chunk's last token only."""
    seen = n * start + n * (n + 1) // 2
    return (n * body_flops_per_token(m) + head_flops(m)
            + 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seen)


def paged_attention_cost(m: dict, kv_len: int, page: int, itemsize: int = 2):
    """(operations, bytes) of one decode query in the paged-attention
    kernel, all blocks: the K and V pages that cover ``kv_len`` positions
    are read once per kv head, the query read and the output written
    once per query head."""
    pages = math.ceil(kv_len / page)
    hd, L = m["head_dim"], m["n_layers"]
    kv_bytes = 2 * m["n_kv_heads"] * pages * page * hd * itemsize
    qo_bytes = 2 * m["n_heads"] * hd * itemsize
    return attention_flops(m, kv_len), L * (kv_bytes + qo_bytes)


def roofline_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / pk["bf16_flops_s"], nbytes / pk["hbm_bytes_s"])
