"""Benchmark harness — one function per paper table + the roofline
report. Prints a final ``name,value,derived`` CSV summary.

``--ci`` runs the regression subset instead: seven serving-path metrics
written to ``BENCH_ci.json`` for ``benchmarks/compare.py`` to gate
against ``benchmarks/baselines.json`` (>15% regression on any metric
fails the build). The subset is sized for a CPU CI runner, so absolute
numbers are noisy — compare.py checks ratios against a baseline
captured on the same class of machine, not paper targets.
"""

from __future__ import annotations

import json
import sys
import time


def run_ci(out_path: str = "BENCH_ci.json") -> dict:
    """The five regression-gated serving metrics (see compare.py for
    each metric's better-direction):

    * ``bg_decode_retention`` — background decode tok/s retained while
      long-prompt admissions churn (chunked prefill + fused tick);
    * ``agg_speedup_16_sessions`` — 16 concurrent proxy sessions,
      aggregate tok/s over the serial backend;
    * ``warm_over_cold_ttft`` — multi-turn TTFT with the prefix cache
      on vs off at a 512-token shared prefix;
    * ``gateway_ttft_ratio`` — OpenAI-gateway TTFT over direct-engine
      TTFT for the local tier;
    * ``bytes_copied_per_admission`` — device bytes moved by KV
      splice/store plumbing per admitted session; the paged decode
      path's headline number, exactly 0 by construction;
    * ``spec_decode_speedup`` / ``spec_acceptance_rate`` — fused
      speculative verify vs plain decode tok/s at a controlled 80%
      draft-agreement rate, plus the acceptance rate itself
      (benchmarks/speculative.py; identity is asserted in-run);
    * ``longcontext_tok_s_flatness`` / ``longcontext_occupancy_ratio``
      — a 16x-window rolling session's last-quarter over first-quarter
      decode tok/s, and its pool high-water over full-context pages
      (benchmarks/longcontext.py; needle-retrieval parity with the
      full-context oracle is asserted in-run);
    * ``fleet_scaling_efficiency`` — 2-replica EngineFleet aggregate
      tok/s at 64 concurrent sessions over 2x the single-replica
      aggregate (failover stream identity is asserted in-run; the
      efficiency floor in baselines.json assumes a multi-core runner);
    * ``kv_pool_bytes_ratio`` / ``kv_quant_logit_err`` — int8 page-pool
      device bytes over fp32 (f32 amax-scale sidecars included; the
      quantized-KV capacity headline) and the worst teacher-forced
      |logit| error across quantized dtypes vs the fp32 pool
      (benchmarks/kv_quant.py; int8 greedy-token identity on GQA is
      asserted in-run).
    """
    t0 = time.perf_counter()

    from benchmarks import batch_throughput
    r_int = batch_throughput.run_interference(n_admissions=4, repeats=3,
                                              quiet=True)

    from benchmarks import concurrency
    r_cc = concurrency.run(concurrency=(1, 16), tokens=8, repeats=2,
                           quiet=True)

    from benchmarks import prefix_cache
    r_mt = prefix_cache.run_multi_turn(prefix_tokens=512, turns=2,
                                       repeats=2, quiet=True)
    r_bc = prefix_cache.run_bytes_copied(n_sessions=4, quiet=True)

    from benchmarks import gateway
    r_gw = gateway.run(tokens=8, repeats=5, n_routed=9, quiet=True)

    from benchmarks import speculative
    r_sp = speculative.run(tokens=96, repeats=3, quiet=True)

    from benchmarks import longcontext
    r_lc = longcontext.run(total_tokens=1024, quiet=True)

    r_fl = concurrency.run_fleet(replicas=2, sessions=64, tokens=8,
                                 repeats=2, quiet=True)

    from benchmarks import kv_quant
    r_kv = kv_quant.run_pool_and_decode(n_sessions=4, tokens=12, repeats=1,
                                        quiet=True)
    r_le = kv_quant.run_logit_error(seq_tokens=48, quiet=True)
    assert r_kv["int8"]["tokens0"] == r_kv["fp32"]["tokens0"], \
        "int8 pages lost greedy-token identity on the GQA family"

    metrics = {
        "bg_decode_retention": r_int["retention"],
        "agg_speedup_16_sessions": r_cc["summary"]["speedup_at_max"],
        "warm_over_cold_ttft": r_mt["warm_over_cold_best"],
        "gateway_ttft_ratio": r_gw["overhead_ratio"],
        "bytes_copied_per_admission":
            r_bc["paged"]["bytes_per_admission"],
        "spec_decode_speedup": r_sp["speedup"],
        "spec_acceptance_rate": r_sp["acceptance_rate"],
        "longcontext_tok_s_flatness": r_lc["tok_s_flatness"],
        "longcontext_occupancy_ratio": r_lc["occupancy_ratio"],
        "fleet_scaling_efficiency":
            r_fl["summary"]["fleet_scaling_efficiency"],
        "kv_pool_bytes_ratio": r_kv["pool_bytes_ratio"],
        "kv_quant_logit_err": r_le["worst"],
    }
    out = {
        "metrics": metrics,
        "detail": {
            "bg_tok_s_quiet": r_int["bg_tok_s_quiet"],
            "bg_tok_s_under_admissions": r_int["bg_tok_s_under_admissions"],
            "bytes_copied_per_admission_contiguous":
                r_bc["contiguous"]["bytes_per_admission"],
            "prefix_hit_tokens": r_mt["hit_tokens_total"],
            "spec_plain_tok_s": r_sp["plain_tok_s"],
            "spec_tok_s": r_sp["spec_tok_s"],
            "spec_tokens_per_tick": r_sp["tokens_per_tick"],
            "longcontext_rolls": r_lc["rolls"],
            "longcontext_needle_recall": r_lc["needle_recall"],
            "longcontext_high_water_pages": r_lc["high_water_pages"],
            "fleet_agg_tok_s_1rep": r_fl["summary"]["agg_tok_s_1rep"],
            "fleet_agg_tok_s_2rep": r_fl["summary"]["agg_tok_s_2rep"],
            "fleet_cpus": r_fl["summary"]["cpus"],
            "fleet_failover_identical":
                r_fl["summary"]["failover_identical_greedy"]
                and r_fl["summary"]["failover_identical_seeded"],
            "kv_quant_tok_s": {dt: r_kv[dt]["agg_tok_s"]
                               for dt in kv_quant.KV_DTYPES},
            "kv_pool_bytes": {dt: r_kv[dt]["pool_bytes"]
                              for dt in kv_quant.KV_DTYPES},
            "kv_quant_logit_err_per_dtype": r_le["max_logit_err"],
            "kv_quant_int8_token_identical": True,
        },
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\n=== CI metrics (written to {out_path}, "
          f"{out['wall_s']:.0f}s) ===")
    for name, val in metrics.items():
        print(f"{name},{val}")
    return out


def main() -> None:
    small = "--quick" in sys.argv
    csv_rows = []

    t0 = time.perf_counter()
    from benchmarks import routing_accuracy
    r1 = routing_accuracy.run(n_per_class=100 if small else 400)
    for name, m in r1.items():
        csv_rows.append((f"table1.{name}.accuracy", f"{m['accuracy']*100:.1f}%",
                         f"retention={m['retention']*100:.1f}% leaked={m['leaked']}"))
        csv_rows.append((f"table1.{name}.judge_ms_p50", f"{m['judge_ms_p50']:.3f}",
                         f"p95={m['judge_ms_p95']:.3f}ms"))

    from benchmarks import latency
    r2 = latency.run(runs=10 if small else 25)
    for tier in ("local", "hpc_relay", "hpc_batch", "cloud(sim)"):
        csv_rows.append((f"table2.{tier}.ttft_s", f"{r2[tier]['ttft_s']:.3f}",
                         f"tok/s={r2[tier]['tok_per_s']:.1f}"))
    csv_rows.append(("table2.relay_speedup", f"{r2['ratio_batch_over_relay']:.1f}x",
                     "paper: 21.1x"))

    from benchmarks import summarization
    r3 = summarization.run()
    csv_rows.append(("table3.first_upgrade.no_summ",
                     str(r3["first_upgrade"]["no_summ"]), "paper: turn 30"))
    csv_rows.append(("table3.first_upgrade.with_summ",
                     str(r3["first_upgrade"]["with_summ"] or "Never"), "paper: Never"))

    from benchmarks import batch_throughput
    r_bt = batch_throughput.run(n_requests=8 if small else 12)
    best_slots = max(r_bt, key=lambda s: r_bt[s]["agg_tok_s"])
    csv_rows.append(("batching.best_tok_s", f"{r_bt[best_slots]['agg_tok_s']:.0f}",
                     f"slots={best_slots}"))

    r_int = batch_throughput.run_interference(n_admissions=4 if small else 6)
    csv_rows.append(("interference.retention",
                     f"{r_int['retention']*100:.0f}%",
                     f"bg tok/s {r_int['bg_tok_s_quiet']:.1f} -> "
                     f"{r_int['bg_tok_s_under_admissions']:.1f} under admissions"))

    r_tl = latency.run_ttft_under_load(n_admissions=4 if small else 6)
    csv_rows.append(("serving.ttft_under_load_p50",
                     f"{r_tl['ttft_under_load_p50']:.3f}",
                     f"solo={r_tl['ttft_solo_s']:.3f}s"))

    from benchmarks import concurrency
    r_cc = concurrency.run(concurrency=(1, 4) if small else (1, 4, 16),
                           tokens=8 if small else 24)
    cc = r_cc["summary"]
    csv_rows.append(("concurrency.speedup_at_max",
                     f"{cc['speedup_at_max']:.2f}x",
                     f"{cc['max_concurrency']} proxy sessions vs serial backend"))
    csv_rows.append(("concurrency.ttft_c1_ratio",
                     f"{cc['ttft_c1_ratio']:.2f}x",
                     "concurrent/serial TTFT at 1 session"))

    from benchmarks import prefix_cache
    r_pc = prefix_cache.run(prefix_tokens=512, smoke=small, quiet=True)
    csv_rows.append(("prefix_cache.warm_over_cold_ttft",
                     f"{r_pc['multi_turn']['warm_over_cold_best']:.3f}",
                     "512-token shared prefix (target <= 0.5)"))
    csv_rows.append(("prefix_cache.shared_prompt_speedup",
                     f"{r_pc['shared_prompt']['speedup']:.2f}x",
                     f"{r_pc['shared_prompt']['n_sessions']} sessions, "
                     f"{r_pc['shared_prompt']['prefix_tokens']}-tok system prompt"))

    from benchmarks import gateway
    r_gw = gateway.run(tokens=8 if small else 12, repeats=5 if small else 9,
                       n_routed=9 if small else 30, quiet=True)
    csv_rows.append(("gateway.local_ttft_ratio",
                     f"{r_gw['overhead_ratio']:.3f}",
                     "gateway/direct local TTFT (target <= 1.10)"))
    dist = r_gw["tier_distribution"]
    csv_rows.append(("gateway.auto_tier_distribution",
                     "|".join(f"{t}:{n}" for t, n in sorted(dist.items())),
                     "stream-auto routed tiers over mixed queries"))
    for alias, r in r_gw["per_alias"].items():
        csv_rows.append((f"gateway.{alias}.ttft_s",
                         f"{r['ttft_p50']:.3f}", f"max={r['ttft_max']:.3f}s"))

    from benchmarks import speculative
    r_sp = speculative.run(tokens=48 if small else 96,
                           repeats=2 if small else 3, quiet=True)
    csv_rows.append(("speculative.decode_speedup",
                     f"{r_sp['speedup']:.2f}x",
                     f"acceptance={r_sp['acceptance_rate']*100:.0f}% "
                     f"k={r_sp['spec_k']} (target >= 2x)"))

    from benchmarks import roofline
    r4 = roofline.run()
    if r4:
        worst = min(r4.values(), key=lambda r: r["mfu_bound"] if r["shape"] == "train_4k" else 1)
        best = max(r4.values(), key=lambda r: r["mfu_bound"])
        csv_rows.append(("roofline.cells", str(len(r4)), "single-pod 16x16"))
        csv_rows.append(("roofline.best_mfu_bound",
                         f"{best['mfu_bound']:.3f}", f"{best['arch']}/{best['shape']}"))

    print("\n=== summary CSV (name,value,derived) ===")
    for name, val, derived in csv_rows:
        print(f"{name},{val},{derived}")
    print(f"\ntotal benchmark time: {time.perf_counter()-t0:.0f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--ci" in sys.argv:
        run_ci()
    else:
        main()
