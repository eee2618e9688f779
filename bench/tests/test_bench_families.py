"""A configuration of a new model family joins the benchmark by files
alone: a configuration file and a reference module that declares its
weight layout and op counts, added to a checkout beside the harness,
with no file of ``bench/benchlib``, ``bench/layer_metrics`` or
``bench/reference`` changed. The family here is the program's
``deepseek-v2-lite-smoke`` (MoE with latent attention, a dense first
block, routed and shared experts), on the CPU."""

from __future__ import annotations

import json
import os
import shutil
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchfaults as bf  # noqa: E402
from benchlib import costs, harness, layers, registry, system as sysmod, weights  # noqa: E402
from benchlib.client import Record  # noqa: E402
from benchlib.traffic import Request  # noqa: E402

FAMILY = os.path.join(bf.FIXTURES, "moe_mla")
CONFIG, REFERENCE, CELL = "deepseek-v2-lite-smoke", "moe_mla_lm", "deepseek-smoke-api"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A test-size checkout, then the new family's two files and its
    entries in BENCHMARK.json."""
    root = bf.tiny_root(tmp_path_factory.mktemp("family"))
    shutil.copy(os.path.join(FAMILY, "configs", f"{CONFIG}.json"),
                os.path.join(root, "bench", "configs"))
    shutil.copy(os.path.join(FAMILY, "reference", f"{REFERENCE}.py"),
                os.path.join(root, "bench", "reference"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": CONFIG, "source": "test",
                             "file": f"bench/configs/{CONFIG}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": "tiny-closed", "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _tokens(n_prompt, out, start):
    r = Record(req=Request(index=0, principal=0, prompt="x" * (n_prompt - 1),
                           prompt_tokens=n_prompt, max_tokens=out), start=start)
    r.status = 200
    r.token_times = [start + 0.1 + 0.01 * k for k in range(out)]
    r.token_texts = ["a"] * out
    return r


def test_the_new_family_builds_fits_and_is_counted_by_its_files(root):
    cell = registry.find_cell(CELL, root)
    m = cell.config["model"]
    assert m["family"] == "moe" and m["use_mla"] and m["first_dense_layers"] == 1
    ref = registry.reference_module(cell.config["reference"], root)

    layout_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(jax.eval_shape(
            lambda: weights.make(ref.weight_spec(m), m["param_dtype"], 0))))
    plan = sysmod.plan_bytes(cell.config, ref)
    assert plan > layout_bytes > 0

    system, engine, params = sysmod.build(cell.config, ref, bf.SEED, "hpc")
    try:
        shapes = jax.eval_shape(engine.model.init, jax.random.PRNGKey(0))
        assert weights.same_layout(params, shapes) == []
        assert {"dense0", "blocks", "embed", "ln_f", "lm_head"} <= set(params)
        assert set(params["blocks"]["mlp"]) == {"router", "w1", "w3", "w2", "shared"}
        # the warm-up requests stream through the new family's engine, and
        # the configuration's named counter is read beside the fixed three
        failed = sysmod.warm(system, system.globus.issue_token("warmup@uic.edu"),
                             cell.config, cell.traffic, bf.SEED)
        assert failed == []
        sysmod.wait_idle(engine)
        c = sysmod.batcher_counters(engine, cell.config["counters"])
        assert set(c) == {"transfers", "admissions", "slots", "pages_walked"}
        assert c["slots"] == cell.config["build"]["scheduler_slots"]
        assert c["transfers"] > 0 and c["pages_walked"] > 0
    finally:
        sysmod.shutdown(system)

    recs = [_tokens(20, 6, 0.0), _tokens(33, 4, 0.2)]
    ctx = harness.Context(model=m, records=recs, t0=0.0, seconds=10.0, wall0=0.0,
                          counters0={}, counters1={}, task_records=[], peaks={},
                          reference=ref)
    want = (sum(ref.decode_flops(m, r.req.prompt_tokens + i)
                for r in recs for i in range(1, len(r.token_times)))
            + sum(ref.prefill_flops(m, 0, r.req.prompt_tokens) for r in recs))
    got = layers.model_flops(ctx, 0.0, 10.0)
    assert got == want
    dense = (sum(costs.decode_flops(m, r.req.prompt_tokens + i)
                 for r in recs for i in range(1, len(r.token_times)))
             + sum(costs.prefill_flops(m, 0, r.req.prompt_tokens) for r in recs))
    assert got != dense

