"""§Roofline: per (arch x shape x mesh) — compute / memory / collective
terms (seconds/step/device), dominant bottleneck, MODEL_FLOPS/HLO ratio.
Reads the dry-run artifact (results/dryrun.jsonl); run
``python -m repro.launch.dryrun --all --mesh both --out results/dryrun.jsonl``
first (CPU-only container: terms are derived from the compiled HLO, not
wall time — see DESIGN.md)."""

from __future__ import annotations

import json
import os

from repro.launch.analysis import peaks
from repro.launch.mesh import TARGET_DEVICE_KIND

DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.jsonl")


def load(path=DEFAULT_PATH):
    recs = {}
    if not os.path.exists(path):
        return recs
    for line in open(path):
        r = json.loads(line)
        if r.get("ok"):
            recs[(r["arch"], r["shape"], r["mesh"])] = r  # keep latest
    return recs


def run(path=DEFAULT_PATH, mesh="16x16", quiet=False):
    recs = load(path)
    if not recs:
        print(f"no dry-run records at {path}; run repro.launch.dryrun --all first")
        return {}
    rows = []
    for (arch, shape, m), r in sorted(recs.items()):
        if m != mesh:
            continue
        rf = r["roofline"]
        rows.append({
            "arch": arch, "shape": shape,
            "compute_s": rf["compute_s"], "memory_s": rf["memory_s"],
            "collective_s": rf["collective_s"], "bottleneck": rf["bottleneck"],
            "model_flops_ratio": rf["model_flops_ratio"],
            "mfu_bound": rf["mfu_bound"],
            "temp_gb": r["mem_temp_bytes"] / 2**30,
        })
    if not quiet:
        pk = peaks(TARGET_DEVICE_KIND)
        print(f"\n=== Roofline (per device, mesh {mesh}; {TARGET_DEVICE_KIND}: "
              f"{pk['flops'] / 1e12:g} TF/s bf16, {pk['hbm_bw'] / 1e9:g} GB/s HBM, "
              f"{pk['ici_bw'] / 1e9:g} GB/s ICI) ===")
        print(f"{'arch':22s} {'shape':12s} {'compute_s':>10s} {'memory_s':>9s} "
              f"{'coll_s':>9s} {'bottleneck':>12s} {'MF/HLO':>7s} {'MFUbound':>8s} {'tempGB':>7s}")
        for r in rows:
            print(f"{r['arch']:22s} {r['shape']:12s} {r['compute_s']:10.4f} "
                  f"{r['memory_s']:9.4f} {r['collective_s']:9.4f} "
                  f"{r['bottleneck'].replace('_s',''):>12s} "
                  f"{r['model_flops_ratio']:7.2f} {r['mfu_bound']:8.3f} {r['temp_gb']:7.1f}")
    return {f"{r['arch']}/{r['shape']}": r for r in rows}


if __name__ == "__main__":
    run()
