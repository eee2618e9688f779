"""The one traffic generator. A traffic file holds only parameters:

  alias            gateway model alias the requests name ("stream-hpc", ...)
  loop             "closed": ``clients`` workers, each sends its next request
                   when the previous one finishes; "open": requests are due on
                   a schedule of Poisson arrivals at ``rate_per_s``
  clients / users  closed-loop workers / open-loop principals (one bearer,
                   and so one prefix-cache salt, each)
  prompt_tokens    {"median", "sigma", "min", "max"}: lognormal, clipped
  output_tokens    the same, for ``max_tokens``
  stratum          lengths and gaps are drawn in blocks of this many requests;
                   every block holds the same stratified quantiles of the
                   distribution in a seeded order, so that every seed asks for
                   the same work and only its order and text differ
  requests         how many requests to generate (more than a window uses)
  temperature      0 for greedy

Prompts are ASCII text, so a prompt of n tokens is a BOS token and n - 1
bytes. Each prompt starts with a tag unique to the draw, so no two
requests share a prefix page.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist

from benchlib.text import prompt_text


@dataclass(frozen=True)
class Request:
    index: int
    principal: int        # closed loop: the client; open loop: the user
    prompt: str
    prompt_tokens: int    # BOS + one token per byte
    max_tokens: int
    due_s: float = 0.0    # open loop: offset of the due time from the start


def stratified_lengths(dist: dict, n: int) -> list[int]:
    """The ``n`` quantiles at (j + 0.5) / n of the clipped lognormal."""
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = []
    for j in range(n):
        v = math.exp(mu + dist["sigma"] * nd.inv_cdf((j + 0.5) / n))
        out.append(int(min(max(round(v), dist["min"]), dist["max"])))
    return out


def exponential_gaps(rate: float, n: int) -> list[float]:
    """The ``n`` quantiles at (j + 0.5) / n of an exponential of ``rate``."""
    return [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]


def generate(traffic: dict, seed: int) -> list[Request]:
    """The traffic mix for ``seed``: the same seed gives the same list."""
    rng = random.Random(f"traffic:{seed}")
    S = int(traffic["stratum"])
    n = int(traffic["requests"])
    p_levels = stratified_lengths(traffic["prompt_tokens"], S)
    o_levels = stratified_lengths(traffic["output_tokens"], S)
    open_loop = traffic["loop"] == "open"
    if open_loop:
        g_levels = exponential_gaps(float(traffic["rate_per_s"]), S)
        principals = int(traffic["users"])
    else:
        principals = int(traffic["clients"])
    reqs, t = [], 0.0
    for start in range(0, n, S):
        ps, os_ = list(p_levels), list(o_levels)
        rng.shuffle(ps)
        rng.shuffle(os_)
        if open_loop:
            gs = list(g_levels)
            rng.shuffle(gs)
        for j in range(min(S, n - start)):
            k = start + j
            if open_loop:
                t += gs[j]
            reqs.append(Request(index=k, principal=k % principals,
                                prompt=prompt_text(rng, ps[j] - 1),
                                prompt_tokens=ps[j], max_tokens=os_[j],
                                due_s=t if open_loop else 0.0))
    return reqs


def per_principal(reqs: list[Request], n: int) -> list[list[Request]]:
    """Closed loop: each client's own queue, in order."""
    out = [[] for _ in range(n)]
    for r in reqs:
        out[r.principal].append(r)
    return out
