"""Is what the timed path produced correct?

Two comparisons, each a number beside a limit:

* ``stream_mismatch`` (limit 0): for every request of the window, the
  token events the client read through SSE must be, one for one, the
  text of the token ids the served engine produced, and the engine must
  have been given the prompt the client sent, whole.
* ``gap_max``: a sample of finished requests, drawn from the seed
  and always holding the longest, is run once through the plain
  reference, over each prompt followed by its served tokens. At each
  served position the served token's reference logit lies some gap
  below the reference's best; the number is the widest such gap. Valid
  for greedy decoding, which the traffic uses.

``gap_mean`` is the mean of those gaps over every sampled position.
The same readings of the reference itself run in a lower precision
(``reference.common``: fp8) are the control a limit must
catch: at each position, the gap of the token the control puts first.
A control run (``run_cell(control=...)``) judges those readings in the
served tokens' place, through the same checks and limits.
"""

from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from benchlib.system import prompt_ids, token_text
from reference.common import vocab_stats


def stream_mismatches(records, handles) -> int:
    """Token events, prompts and counts that disagree between what the
    client read and what the engine produced, over ``records`` that
    completed."""
    bad = 0
    for r in records:
        if not r.ok:
            continue
        h = handles.get(r.req.prompt)
        if h is None:
            bad += 1
            continue
        res = h.result(timeout=60.0)
        if res.n_prompt != r.req.prompt_tokens:
            bad += 1
        if len(res.tokens) != len(r.token_texts) or \
                r.completion_tokens != len(res.tokens):
            bad += 1
        bad += sum(token_text(t) != s for t, s in zip(res.tokens, r.token_texts))
    return bad


def choose(records, handles, seed: int, min_tokens: int, max_seqs: int):
    """(prompt ids, served ids) of a seeded sample of completed requests,
    the longest first, until ``min_tokens`` served tokens are held."""
    done = []
    for r in records:
        if r.ok and r.req.prompt in handles:
            toks = list(handles[r.req.prompt].result(timeout=60.0).tokens)
            done.append((prompt_ids(r.req.prompt), toks, r.req.index))
    if not done:
        return []
    done.sort(key=lambda x: x[2])
    longest = max(done, key=lambda x: (len(x[1]), len(x[0])))
    rest = [d for d in done if d is not longest]
    random.Random(f"check:{seed}").shuffle(rest)
    out, n = [longest], len(longest[1])
    for d in rest:
        if n >= min_tokens or len(out) >= max_seqs:
            break
        out.append(d)
        n += len(d[1])
    return [(p, s) for p, s, _ in out]


def _batch(samples, t_pad, rows):
    """Token ids (rows, T) and, per row, the (position, served id) pairs:
    served token j is read at position len(prompt) - 1 + j. Rows past
    the samples are padding, so every call has one shape."""
    ids = np.zeros((rows, t_pad), np.int32)
    tgt = np.zeros((rows, t_pad), np.int32)
    valid = np.zeros((rows, t_pad), bool)
    for b, (p, s) in enumerate(samples):
        seq = (p + s[:-1])[:t_pad]
        ids[b, :len(seq)] = seq
        pos = np.arange(len(s)) + len(p) - 1
        keep = pos < t_pad
        tgt[b, pos[keep]] = np.asarray(s)[keep]
        valid[b, pos[keep]] = True
    return ids, tgt, valid


def gaps(ref, params, m: dict, samples, t_pad: int, batch: int,
         controls=()) -> dict:
    """Per served position, how far below the best logit of the plain
    reference module ``ref`` the served token lies: {"served": array}.
    For each precision in ``controls`` ("fp8") the reference is also run
    in that precision, and its entry holds the gap, under the
    reference's logits, of the token that the lower precision puts
    first."""
    w = ref.head_weights(params, m)
    V = m["vocab_size"]
    out = {k: [] for k in ("served", *controls)}
    for i in range(0, len(samples), batch):
        ids, tgt, valid = _batch(samples[i:i + batch], t_pad, batch)
        ids = jnp.asarray(ids)
        want = [jnp.asarray(tgt)[..., None]]
        for q in controls:
            cx = ref.final_hidden(params, m, ids, quant=q)
            _, carg, _ = vocab_stats(cx, w, V, want[0], quant=q)
            del cx
            want.append(carg[..., None])
        x = ref.final_hidden(params, m, ids)
        best, _, at = vocab_stats(x, w, V, jnp.concatenate(want, -1))
        g = np.asarray(best[..., None] - at)
        for j, k in enumerate(("served", *controls)):
            out[k].append(g[..., j][valid])
    return {k: np.concatenate(v) if v else np.zeros(0) for k, v in out.items()}


def summary(g: np.ndarray) -> dict:
    """The numbers a run is judged by: the widest gap, the mean gap over
    every served position, and how many positions differ at all."""
    if not len(g):
        return {"gap_max": float("nan"), "gap_mean": float("nan"),
                "flips": 0, "positions": 0}
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "flips": int((g > 0).sum()), "positions": int(len(g))}
