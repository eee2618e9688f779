"""Helpers for the correctness tests: a whole benchmark run at a test
size on the CPU (the look for a chip skipped), and the faults planted
underneath the timed path."""

from __future__ import annotations

import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib.harness import run_cell  # noqa: E402
from benchlib.registry import find_cell  # noqa: E402

SEED = 2 ** 31 + 77
CONTROL = "fp8"          # the precision below bf16 the limits are set to catch


def tiny_root(tmp_path) -> str:
    """A checkout whose BENCHMARK.json names the test-size cells, with the
    benchmark's own readers and reference modules."""
    root = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(FIXTURES, root)
    for d in ("layer_metrics", "reference"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "bench", d))
    return root


def run(root: str, workload: str, *, seconds: float = 2.5,
        control: str | None = None) -> dict:
    return run_cell(find_cell(workload, root), SEED, seconds, False,
                    t_start=time.perf_counter(), compile_cache=False,
                    control=control)


def keep_state(monkeypatch, model_cls):
    """Fault: the decode step returns the cache it was given (only the
    position moves), so no decoded token's state is ever written."""
    orig = model_cls.decode_step

    def decode_step(self, params, token, cache, extra=None):
        logits, _ = orig(self, params, token, cache, extra)
        kept = dict(cache)
        kept["pos"] = cache["pos"] + 1
        return logits, kept

    monkeypatch.setattr(model_cls, "decode_step", decode_step)


def alter_tokens(monkeypatch):
    """Fault: the fused decode tick hands out a different token id than
    the one it sampled, where the token is produced."""
    import jax.numpy as jnp
    from repro.serving.scheduler import ContinuousBatcher
    orig = ContinuousBatcher._make_fused

    def make_fused(self):
        fused = orig(self)

        def bad(*args):
            nxt, cache, packed = fused(*args)
            shifted = jnp.where(packed[:, 0] > 3, packed[:, 0] - 1, packed[:, 0] + 1)
            return shifted[:, None], cache, packed.at[:, 0].set(shifted)

        return bad

    monkeypatch.setattr(ContinuousBatcher, "_make_fused", make_fused)
