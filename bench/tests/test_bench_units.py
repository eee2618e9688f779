"""CPU tests of the benchmark's own arithmetic and plumbing: traffic,
percentiles, cost functions, trace reduction, the registry that finds a
cell's files by name, and the refusal to measure without a TPU. Nothing
here touches a TPU topology."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import statistics
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib import costs, harness, registry, stats, trace as tracemod  # noqa: E402
from benchlib.client import Record  # noqa: E402
from benchlib.traffic import Request, generate, per_principal  # noqa: E402

BENCHMARK = registry.load_benchmark(ROOT)
TRAFFIC_FILES = sorted({w["traffic"] for w in BENCHMARK["workloads"]})


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ traffic
@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_traffic_reproduces_from_its_seed(name):
    t = _traffic(name)
    seed = 2 ** 31 + 12345          # larger than 32 signed bits hold
    a, b = generate(t, seed), generate(t, seed)
    assert a == b
    c = generate(t, seed + 1)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_traffic_lengths_stay_within_clips(name):
    t = _traffic(name)
    reqs = generate(t, 7)
    assert len(reqs) == t["requests"]
    p, o = t["prompt_tokens"], t["output_tokens"]
    for r in reqs:
        assert p["min"] <= r.prompt_tokens <= p["max"]
        assert o["min"] <= r.max_tokens <= o["max"]
        assert r.prompt.isascii() and len(r.prompt) == r.prompt_tokens - 1
    # the median of the drawn lengths is the distribution's median
    assert abs(statistics.median(r.prompt_tokens for r in reqs) - p["median"]) <= 0.05 * p["median"]
    assert abs(statistics.median(r.max_tokens for r in reqs) - o["median"]) <= 0.05 * o["median"]


@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_traffic_lengths_keep_their_sources_means(name):
    """Each length distribution is fitted so that the mean of what is
    sent (clipped, stratified) is the mean its public source reports."""
    t = _traffic(name)
    assert t["source"]
    reqs = generate(t, 11)
    for key, field in (("prompt_tokens", "prompt_tokens"), ("output_tokens", "max_tokens")):
        mean = statistics.mean(getattr(r, field) for r in reqs)
        assert mean == pytest.approx(t[key]["source_mean"], rel=0.01), key


@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_every_seed_asks_for_the_same_work(name):
    """Each block of ``stratum`` requests holds the same lengths (and,
    open loop, the same gaps) whatever the seed: only order and text
    differ, so seeds do not change the amount of work."""
    t = _traffic(name)
    S = t["stratum"]
    a, b = generate(t, 1), generate(t, 99)
    for k in range(0, 4 * S, S):
        blk_a, blk_b = a[k:k + S], b[k:k + S]
        assert sorted(r.prompt_tokens for r in blk_a) == sorted(r.prompt_tokens for r in blk_b)
        assert sorted(r.max_tokens for r in blk_a) == sorted(r.max_tokens for r in blk_b)
    if t["loop"] == "open":
        assert a[S - 1].due_s == pytest.approx(b[S - 1].due_s)
        due = [r.due_s for r in a]
        assert due == sorted(due)
        # the mean gap is the configured rate's
        assert len(a) / due[-1] == pytest.approx(t["rate_per_s"], rel=0.05)


def test_no_two_prompts_share_a_prefix_page():
    reqs = generate(_traffic("api-closed"), 3)
    heads = {r.prompt[:15] for r in reqs}           # BOS + 15 bytes = a page
    assert len(heads) == len(reqs)


def test_closed_loop_queues_belong_to_one_client_each():
    t = _traffic("api-closed")
    reqs = generate(t, 5)
    qs = per_principal(reqs, t["clients"])
    assert len(qs) == t["clients"]
    assert sum(len(q) for q in qs) == len(reqs)
    assert all(r.principal == i for i, q in enumerate(qs) for r in q)


# ------------------------------------------------------------------ statistics
def test_percentile_matches_numpy_over_all_values():
    rng = np.random.default_rng(0)
    xs = list(rng.lognormal(0.0, 1.0, 1001))
    for q in (50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _record(i, start, ttft, n_tok, gap, ok=True):
    r = Record(req=Request(index=i, principal=0, prompt="x", prompt_tokens=2,
                           max_tokens=n_tok), start=start)
    r.status = 200 if ok else 502
    if ok:
        r.token_times = [start + ttft + k * gap for k in range(n_tok)]
        r.token_texts = ["a"] * n_tok
    else:
        r.error = "upstream"
    return r


def test_end_to_end_takes_tails_over_all_requests():
    """Percentiles over every request of the window, not medians of
    per-client chunks; a failed request misses every limit."""
    recs = [_record(i, 0.0, (i + 1) / 1000.0, 3, 0.010) for i in range(100)]
    e2e = harness.end_to_end(recs, t0=0.0, seconds=10.0)
    ttft = [(i + 1) for i in range(100)]
    assert e2e["ttft_p50_ms"] == pytest.approx(float(np.percentile(ttft, 50)))
    assert e2e["ttft_p90_ms"] == pytest.approx(float(np.percentile(ttft, 90)))
    chunk_medians = [statistics.median(ttft[k:k + 10]) for k in range(0, 100, 10)]
    assert e2e["ttft_p90_ms"] != pytest.approx(float(np.percentile(chunk_medians, 90)))
    assert e2e["itl_p99_ms"] == pytest.approx(10.0)
    assert e2e["output_tok_s"] == pytest.approx(300 / 10.0)

    recs.append(_record(100, 0.0, 0.0, 0, 0.0, ok=False))
    e2e = harness.end_to_end(recs, t0=0.0, seconds=10.0)
    miss = (10.0 + harness.DRAIN_S) * 1e3     # ranks above every success
    assert e2e["ttft_p90_ms"] == pytest.approx(float(np.percentile(ttft + [miss], 90)))
    assert e2e["ttft_p50_ms"] == pytest.approx(float(np.percentile(ttft + [miss], 50)))


def test_output_rate_counts_only_tokens_inside_the_window():
    recs = [_record(0, 9.0, 0.5, 10, 0.1)]     # tokens at 9.5 .. 10.4
    e2e = harness.end_to_end(recs, t0=0.0, seconds=10.0)
    assert e2e["output_tok_s"] == pytest.approx(5 / 10.0)


# ------------------------------------------------------------------ costs
SMALL = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
             d_ff=16, vocab_size=10)


def test_model_flops_match_hand_counts():
    # one block: q 8x8, k and v 8x4 each, o 8x8, gated MLP 3 x 8x16
    assert costs.layer_weights(SMALL) == 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    body = 2 * 2 * 576                     # 2 flops x 2 layers x 576 weights
    head = 2 * 8 * 10
    attn5 = 2 * 2 * 4 * 2 * 2 * 5          # QK and PV, 2 layers, 4 heads, dh 2
    assert costs.decode_flops(SMALL, 5) == body + head + attn5
    # prefill of 3 tokens from 0: keys seen 1 + 2 + 3 = 6; one head read
    assert costs.prefill_flops(SMALL, 0, 3) == 3 * body + head + 2 * 2 * 4 * 2 * 2 * 6
    # a chunk of 2 at positions 4, 5 sees 5 + 6 keys
    assert costs.prefill_flops(SMALL, 4, 2) == 2 * body + head + 2 * 2 * 4 * 2 * 2 * 11


def test_paged_attention_cost_matches_hand_count():
    flops, nbytes = costs.paged_attention_cost(SMALL, kv_len=5, page=4, itemsize=2)
    assert flops == 2 * 2 * 4 * 2 * 2 * 5
    # 2 pages of 4 positions cover 5; K and V, 2 kv heads, dh 2, 2 bytes;
    # plus q read and output written for 4 heads; both layers
    kv = 2 * 2 * 2 * 4 * 2 * 2
    qo = 2 * 4 * 2 * 2
    assert nbytes == 2 * (kv + qo)
    pk = {"bf16_flops_s": 100.0, "hbm_bytes_s": 10.0}
    assert costs.roofline_seconds(flops, nbytes, pk) == pytest.approx(max(flops / 100, nbytes / 10))


# ------------------------------------------------------------------ the mistral cell, pinned
# The dense layout and op counts moved from ``benchlib`` into the
# reference module the configuration names; these pin them to what the
# harness drew and counted before the move.
PINNED_SEED = 2 ** 31 + 77
PINNED_SHA256 = "361cee0ab5fb814a6fdd92e074af9755ca9e16d94c5b197939e7124c2beccb09"


def _config(path):
    with open(path) as f:
        return json.load(f)


def test_test_size_mistral_weights_are_bit_identical_to_the_pinned_draw():
    import hashlib
    import jax
    from benchlib import weights
    m = _config(os.path.join(FIXTURES, "bench", "configs", "tiny-dense.json"))["model"]
    ref = registry.reference_module("dense_lm", ROOT)
    p = weights.make(ref.weight_spec(m), m["param_dtype"], PINNED_SEED)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED_SHA256


def test_mistral_model_flops_are_the_dense_arithmetic():
    from benchlib import layers
    cell = registry.find_cell("mistral7b-hpc-api", ROOT)
    m = cell.config["model"]
    ref = registry.reference_module(cell.config["reference"], ROOT)
    recs = [_record(i, 0.1 * i, 0.05, 3 + i, 0.02) for i in range(5)]
    for i, r in enumerate(recs):
        r.req = Request(index=i, principal=0, prompt="x" * (16 + 40 * i),
                        prompt_tokens=17 + 40 * i, max_tokens=3 + i)
    ctx = harness.Context(model=m, records=recs, t0=0.0, seconds=10.0, wall0=0.0,
                          counters0={}, counters1={}, task_records=[], peaks={},
                          reference=ref)
    want = (sum(costs.decode_flops(m, r.req.prompt_tokens + i)
                for r in recs for i in range(1, len(r.token_times)))
            + sum(costs.prefill_flops(m, 0, r.req.prompt_tokens) for r in recs))
    assert layers.model_flops(ctx, 0.0, 10.0) == want
    # a window that cuts a request counts only the tokens read inside it
    a, b = recs[2].token_times[1], recs[2].token_times[-1]
    part = layers.model_flops(ctx, a, b)
    assert 0 < part < want


# ------------------------------------------------------------------ counters
class _Batcher:
    transfers, admissions, B, kv_pages_walked = 41, 7, 32, 1234


class _Engine:
    def __init__(self, scheduler):
        self.scheduler = scheduler


def test_batcher_counters_add_the_configuration_s_named_ones():
    from types import SimpleNamespace
    from benchlib import system as sysmod
    eng = _Engine(SimpleNamespace(batcher=_Batcher()))
    assert sysmod.batcher_counters(eng) == {"transfers": 41, "admissions": 7, "slots": 32}
    named = {"pages_walked": "scheduler.batcher.kv_pages_walked"}
    assert sysmod.batcher_counters(eng, named) == {
        "transfers": 41, "admissions": 7, "slots": 32, "pages_walked": 1234}
    with pytest.raises(AttributeError):
        sysmod.batcher_counters(eng, {"x": "scheduler.batcher.no_such_counter"})
    assert sysmod.batcher_counters(_Engine(None)) == {"transfers": 0, "admissions": 0,
                                                      "slots": 0}


# ------------------------------------------------------------------ program spans
def _span_ctx():
    """A traced window: 4 decode ticks between the trace's counters; the
    program's spans and sessions (ns on the trace's clock, s on the
    host's); device ops at [0, 10), [30, 40), [100, 110) ns."""
    from types import SimpleNamespace as S
    ms, us = 1_000_000, 1_000
    spans = ([["stream.batcher.admit", 0, 2 * ms], ["stream.batcher.admit", 0, 6 * ms]]
             + [["stream.batcher.decode", 0, 30 * ms]] * 4
             + [["stream.batcher.emit", 15, 10], ["stream.batcher.emit", 50, 100],
                ["stream.batcher.emit", 1000, 40 * ms - 110]]
             + [["stream.batcher.tick", 0, 200 * ms]]
             + [["stream.relay.encrypt", 0, d * us] for d in (1000, 1200, 1400)]
             + [["stream.relay.decrypt", 0, d * us] for d in (400, 500)])
    sessions = [S(submitted_at=10.0, admitted_at=10.1, first_token_at=10.5),
                S(submitted_at=11.0, admitted_at=11.3, first_token_at=11.4),
                S(submitted_at=12.0, admitted_at=12.2, first_token_at=13.0),
                S(submitted_at=9.0, admitted_at=19.0, first_token_at=29.0),   # lead-in
                S(submitted_at=20.0, admitted_at=20.0, first_token_at=20.0)]  # after close
    tr = {"device": {tracemod.OPS_LINE: [["a", 0, 10], ["b", 30, 10], ["c", 100, 10]]},
          "host": []}
    ctx = harness.Context(model=dict(SMALL), records=[], t0=10.0, seconds=10.0,
                          wall0=0.0, counters0={"transfers": 0}, counters1={"transfers": 99},
                          task_records=[], peaks={}, trace=tr,
                          trace_counters0={"transfers": 10, "slots": 4},
                          trace_counters1={"transfers": 14, "slots": 4})
    ctx.extra.update(program_spans=spans, sessions=sessions)
    return ctx


@pytest.mark.parametrize("name,want", [
    ("queue_wait_ms.api", 200.0),     # waits 100, 300, 200 ms in the window
    ("admit_ms.api", 400.0),          # 400, 100, 800 ms
    ("tick_admit_ms.api", 2.0),       # 8 ms of admit spans over 4 traced ticks
    ("tick_decode_ms.api", 30.0),
    ("tick_emit_ms.api", 10.0),       # 40 ms of emit spans over 4 ticks
    ("relay_encrypt_us", 1200.0),
    ("relay_decrypt_us", 450.0),
    ("idle_emit_share.api", 75.0),    # gaps of 20 + 60 ns; emit covers 10 + 50
])
def test_program_span_readers_on_a_synthetic_window(name, want):
    ctx = _span_ctx()
    assert registry.metric_reader(name, ROOT)(ctx) == pytest.approx(want)
    # with no traced counters or no spans there is nothing to read
    bare = _span_ctx()
    bare.trace_counters0 = bare.trace_counters1 = {}
    bare.extra.update(program_spans=[], sessions=[])
    bare.trace = None
    assert registry.metric_reader(name, ROOT)(bare) is None


# ------------------------------------------------------------------ trace
def _fixture_trace():
    with open(os.path.join(FIXTURES, "trace_small.json")) as f:
        return json.load(f)


def test_trace_reduction_on_a_recorded_trace():
    tr = _fixture_trace()
    ops = tr["device"][tracemod.OPS_LINE]
    busy = tracemod.busy_seconds(tr)
    # the union of the op intervals, computed the slow way
    ends = sorted((s, s + d) for _, s, d in ops)
    covered, cur_s, cur_e = 0, None, None
    for s, e in ends:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                covered += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    covered += cur_e - cur_s
    assert busy == pytest.approx(covered / 1e9)
    span = (max(s + d for _, s, d in ops) - min(s for _, s, d in ops)) / 1e9
    assert 0 < busy <= span
    top = tracemod.top_ops(tr, k=3)
    assert len(top) <= 3 and top == sorted(top, key=lambda x: -x[1])
    total = sum(d for _, _, d in ops) / 1e9
    assert sum(t for _, t in tracemod.top_ops(tr, k=10 ** 6)) == pytest.approx(total)
    gaps = tracemod.idle_gaps(tr)
    assert sum(t for _, t in gaps) == pytest.approx(span - busy)


PAGED_KERNEL_OP = (  # the paged-attention kernel as a v5e trace names it
    "%closed_call.40 = bf16[128,4,128]{2,1,0:T(4,128)(2,1)S(1)} custom-call("
    "s32[1024]{0:T(1024)S(1)} %get-tuple-element.1058, s32[16]{0:T(128)S(1)} "
    "%broadcast_add_fusion.5, s32[16]{0:T(128)S(1)} %copy-done.19, bf16[128,4,128]"
    "{2,1,0:T(4,128)(2,1)S(1)} %pad_maximum_fusion.6, bf16[1025,8,16,128]{3,2,1,0:"
    "T(8,128)(2,1)} %copy.93, bf16[1025,8,16,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
    "%copy.96), custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{s32[1024]{0}, s32[16]{0}, s32[16]{0}, bf16[128,4,128]{2,1,0}, "
    "bf16[1025,8,16,128]{3,2,1,0}, bf16[1025,8,16,128]{3,2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_the_paged_kernel_is_found_by_its_operands_and_nothing_else():
    """The recorded trace holds the rmsnorm kernel's custom calls and no
    paged-attention call; the reader's pattern finds only the latter."""
    from benchlib import registry as reg
    pattern = reg.metric_reader("paged_attention_roofline", ROOT).__globals__["KERNEL"]
    tr = _fixture_trace()
    kernels = [n for n, _, _ in tr["device"][tracemod.OPS_LINE]
               if 'custom_call_target="tpu_custom_call"' in n]
    assert kernels
    assert tracemod.totals(tr, tracemod.OPS_LINE, pattern) == (0.0, 0)
    tr["device"][tracemod.OPS_LINE].append([PAGED_KERNEL_OP, 0, 1000])
    assert tracemod.totals(tr, tracemod.OPS_LINE, pattern) == (1e-6, 1)


def test_idle_gaps_go_to_the_innermost_open_host_span():
    tr = {"device": {tracemod.OPS_LINE: [["a", 0, 10], ["b", 30, 10], ["c", 100, 10]]},
          "host": [["bench.relay.encrypt", 12, 15], ["bench.batcher.tick", 35, 100],
                   ["bench.relay.encrypt", 50, 20]]}
    gaps = dict(tracemod.idle_gaps(tr))
    # gap [10, 30): 2 + 3 ns with no span open, 15 ns in encrypt;
    # gap [40, 100): the tick, but 20 ns of it inside a nested encrypt
    assert gaps == pytest.approx({"unattributed": 5 / 1e9,
                                  "bench.relay.encrypt": 35 / 1e9,
                                  "bench.batcher.tick": 40 / 1e9})
    s, n = tracemod.totals(tr, tracemod.OPS_LINE, "^b$")
    assert (s, n) == (10 / 1e9, 1)


def test_op_names_are_read_from_hlo_text():
    name = ("%fusion.139 = bf16[16,16384]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[16,4096]"
            "{1,0} %closed_call.21), kind=kOutput, calls=%fused_computation.152")
    assert tracemod.op_parts(name) == ("%fusion.139", "fusion", "bf16[16,16384]")
    loop = "%while.12 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1}) while((s32[], bf16[16,1,4096]) %tuple)"
    assert tracemod.op_parts(loop)[1] == "while"
    tr = {"device": {tracemod.OPS_LINE: [[loop, 0, 100], [name, 10, 50]]}, "host": []}
    assert [n for n, _ in tracemod.top_ops(tr)] == ["%fusion.139 fusion bf16[16,16384]"]


# ------------------------------------------------------------------ registry
def test_every_cell_finds_its_files_by_name():
    for w in BENCHMARK["workloads"]:
        cell = registry.find_cell(w["name"], ROOT)
        assert cell.config["model"] and cell.config["build"]
        assert cell.traffic["loop"] in ("open", "closed")
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(registry.metric_reader(m["name"], ROOT))
    with pytest.raises(KeyError):
        registry.find_cell("no-such-cell", ROOT)


@pytest.mark.parametrize("name,reader", [("tick_ms.api", "tick_ms.py"),
                                         ("tick_ms.chat", "tick_ms.py"),
                                         ("step_mfu", "step_mfu.py")])
def test_a_metric_split_by_cell_shares_one_reader(name, reader):
    assert os.path.basename(registry.reader_path(name, ROOT)) == reader


def test_a_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files plus new entries in BENCHMARK.json; no file the
    harness already has changes."""
    root = tmp_path / "checkout"
    shutil.copytree(FIXTURES, root)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), root / "bench" / "layer_metrics")
    cfg = json.loads((root / "bench/configs/tiny-dense.json").read_text())
    cfg["name"] = "tiny-dense-b"
    (root / "bench/configs/tiny-dense-b.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/tiny-closed.json").read_text())
    tr["clients"] = 2
    (root / "bench/traffic/tiny-closed-b.json").write_text(json.dumps(tr))
    (root / "bench/layer_metrics/requests_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dense-b", "source": "test",
                             "file": "bench/configs/tiny-dense-b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-b", "config": "tiny-dense-b",
                               "traffic": "tiny-closed-b", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "batcher", "moves": "ttft_p50_ms",
                               "workloads": ["tiny-b"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.find_cell("tiny-b", str(root))
    assert cell.config["name"] == "tiny-dense-b"
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    read = registry.metric_reader("requests_seen", str(root))
    assert read(harness.Context(model={}, records=[1, 2], t0=0, seconds=1, wall0=0,
                                counters0={}, counters1={}, task_records=[],
                                peaks={})) == 2.0


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = harness.Context(model=dict(SMALL), records=[], t0=0.0, seconds=1.0,
                          wall0=0.0, counters0={"transfers": 5, "slots": 4},
                          counters1={"transfers": 5, "slots": 4},
                          task_records=[], peaks={}, trace=None)
    for m in BENCHMARK["per_layer"]:
        assert registry.metric_reader(m["name"], ROOT)(ctx) is None, m["name"]


def test_benchmark_json_is_well_formed():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    cfgs = {c["name"]: c for c in b["configs"]}
    used = {w["config"] for w in b["workloads"]}
    assert used == set(cfgs)
    for c in b["configs"]:
        assert name.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert all(name.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in b["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(registry.reader_path(m["name"], ROOT))
        for w in m.get("workloads", []):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", [w]), (m["name"], w)
    for w in b["workloads"]:
        cell = registry.find_cell(w["name"], ROOT)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


# ------------------------------------------------------------------ refusal
def test_run_refuses_to_measure_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("bench_run_cli",
                                                  os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", BENCHMARK["workloads"][0]["name"],
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err
