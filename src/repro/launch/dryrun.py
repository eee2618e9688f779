import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this:
  1. builds the FULL-size model config (abstract params via eval_shape —
     nothing is allocated),
  2. resolves logical-axis rules -> NamedShardings on the production
     mesh (single-pod 16x16 or multi-pod 2x16x16),
  3. lowers + compiles train_step / prefill / serve_step as the shape
     cell dictates,
  4. extracts memory_analysis(), cost_analysis() and the collective-op
     byte totals from the partitioned HLO (roofline inputs),
  5. appends a JSON record to --out (benchmarks/roofline reads it).

Usage:
  python -m repro.launch.dryrun --arch minitron-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.jsonl
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, list_archs, shapes_for
from repro.distributed.sharding import (DEFAULT_RULES, axis_rules, logical_to_pspec,
                                        spec_tree_to_shardings)
from repro.launch.mesh import TARGET_DEVICE_KIND, make_production_mesh
from repro.models import build_model
from repro.models.common import count_params, shape_tree, spec_tree
from repro.training.optim import AdamWConfig
from repro.training.train import make_train_step

# ---------------------------------------------------------------------------
# per-cell sharding rules (see DESIGN.md §4)
# ---------------------------------------------------------------------------


def n_dev_of(mesh) -> int:
    n = 1
    for v in dict(mesh.shape).values():
        n *= v
    return n


def rules_for(cfg, cell, mesh):
    rules = dict(DEFAULT_RULES)
    rules["embed"] = ("pod", "data")          # ZeRO-3-style FSDP on params
    msize = dict(mesh.shape)["model"]
    if cell.kind == "decode":
        if cell.global_batch == 1:
            rules["kv_seq"] = ("data",)        # long-context: shard the cache seq
        elif cfg.use_mla or (cfg.n_kv_heads % msize != 0):
            rules["kv_seq"] = ("model",)       # few KV heads: shard cache seq on TP
    return rules


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def input_specs(cfg, cell):
    """ShapeDtypeStruct stand-ins + logical axes for every model input."""
    B, S = cell.global_batch, cell.seq_len
    sd = jax.ShapeDtypeStruct
    if cell.kind == "train":
        # S+1 tokens: the loss shifts by one, so the TRAINED width is
        # exactly S (and stays mesh-divisible for sequence sharding)
        specs = {"tokens": (sd((B, S + 1), jnp.int32), ("batch", None))}
        if cfg.family == "vlm":
            specs["vision"] = (sd((B, cfg.n_image_tokens, cfg.vision_dim), jnp.bfloat16),
                               ("batch", None, None))
        if cfg.is_encoder_decoder:
            specs["frames"] = (sd((B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16),
                               ("batch", None, None))
        return specs
    if cell.kind == "prefill":
        specs = {"tokens": (sd((B, S), jnp.int32), ("batch", None))}
    else:  # decode: one new token against a seq_len cache
        specs = {"token": (sd((B, 1), jnp.int32), ("batch", None))}
    if cfg.family == "vlm":
        specs["vision"] = (sd((B, cfg.n_image_tokens, cfg.vision_dim), jnp.bfloat16),
                           ("batch", None, None))
    if cfg.is_encoder_decoder:
        specs["frames"] = (sd((B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16),
                           ("batch", None, None))
    return specs


def _extra_from_specs(specs):
    extra = {}
    if "vision" in specs:
        extra["vision"] = specs["vision"][0]
    if "frames" in specs:
        extra["frames"] = specs["frames"][0]
    return extra or None


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, verbose: bool = True,
             config_overrides: dict | None = None, rules_override=None,
             accum_steps: int | None = None, train_overrides: dict | None = None):
    cell = SHAPES[shape_name]
    cfg = get_config(arch, compute_dtype="bfloat16", use_kernels=False,
                     **(config_overrides or {}))
    if cell.kind != "train":
        cfg = cfg.replace(param_dtype="bfloat16")  # serving runs bf16 weights
    elif count_params(build_model(cfg).param_defs()) > 1e11:
        # >100B: bf16 params + fp32 moments (HBM ceiling; see DESIGN.md §4)
        cfg = cfg.replace(param_dtype="bfloat16")
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_override or rules_for(cfg, cell, mesh)
    model = build_model(cfg)
    defs = model.param_defs()
    p_shapes = shape_tree(defs, cfg.pdtype())
    p_specs = spec_tree(defs)
    n_params = count_params(defs)
    specs = input_specs(cfg, cell)
    extra = _extra_from_specs(specs)

    t0 = time.time()
    with jax.set_mesh(mesh), axis_rules(rules):
        p_shard = spec_tree_to_shardings(p_specs, p_shapes, mesh, rules)

        if cell.kind == "train":
            oc = AdamWConfig()
            # microbatch so per-device activations fit HBM: target <=2 seqs
            # per device per microbatch (see EXPERIMENTS.md §Dry-run).
            dp = n_dev_of(mesh) // dict(mesh.shape)["model"]
            per_dev = max(cell.global_batch // dp, 1)
            accum = accum_steps if accum_steps is not None else max(per_dev // 2, 1)
            tov = dict(train_overrides or {})
            if tov.get("grad_shardings") == "auto":
                tov["grad_shardings"] = p_shard
            use_master = tov.pop("fp32_master", False)
            step_fn = make_train_step(model, oc, accum_steps=accum, **tov)
            fp32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
            opt_shapes = {
                "mu": jax.tree.map(fp32, p_shapes),
                "nu": jax.tree.map(fp32, p_shapes),
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            opt_shard = {
                "mu": spec_tree_to_shardings(p_specs, opt_shapes["mu"], mesh, rules),
                "nu": spec_tree_to_shardings(p_specs, opt_shapes["nu"], mesh, rules),
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            }
            if use_master:  # bf16 live params + fp32 masters in opt state
                opt_shapes["master"] = jax.tree.map(fp32, p_shapes)
                opt_shard["master"] = spec_tree_to_shardings(
                    p_specs, opt_shapes["master"], mesh, rules)
            batch_shapes = {"tokens": specs["tokens"][0]}
            batch_shard = {"tokens": jax.sharding.NamedSharding(
                mesh, logical_to_pspec(specs["tokens"][1], specs["tokens"][0].shape, mesh, rules))}
            if extra:
                batch_shapes["extra"] = extra
                batch_shard["extra"] = {
                    k: jax.sharding.NamedSharding(
                        mesh, logical_to_pspec(specs[k][1], specs[k][0].shape, mesh, rules))
                    for k in extra}
            fn = jax.jit(step_fn,
                         in_shardings=(p_shard, opt_shard, batch_shard),
                         out_shardings=(p_shard, opt_shard, None))
            lowered = fn.lower(p_shapes, opt_shapes, batch_shapes)
        else:
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(cell.global_batch, cell.seq_len))
            c_specs = model.cache_specs()
            cache_shard = spec_tree_to_shardings(c_specs, cache_shapes, mesh, rules)
            tok_key = "tokens" if cell.kind == "prefill" else "token"
            tok_shape, tok_logical = specs[tok_key]
            tok_shard = jax.sharding.NamedSharding(
                mesh, logical_to_pspec(tok_logical, tok_shape.shape, mesh, rules))
            extra_shard = None
            if extra:
                extra_shard = {
                    k: jax.sharding.NamedSharding(
                        mesh, logical_to_pspec(specs[k][1], specs[k][0].shape, mesh, rules))
                    for k in extra}

            if cell.kind == "prefill":
                def step(params, tokens, cache, extra):
                    return model.prefill(params, tokens, cache, extra)
            else:
                def step(params, token, cache, extra):
                    return model.decode_step(params, token, cache, extra)

            fn = jax.jit(step,
                         in_shardings=(p_shard, tok_shard, cache_shard, extra_shard),
                         out_shardings=(None, cache_shard))
            lowered = fn.lower(p_shapes, tok_shape, cache_shapes, extra)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    from repro.launch.analysis import analyze_hlo, analytic_costs, roofline_terms

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = analyze_hlo(compiled.as_text())
    ana = analytic_costs(cfg, cell)
    n_dev = int(np.prod(list(dict(mesh.shape).values())))

    def _m(attr):
        try:
            return int(getattr(mem, attr))
        except Exception:
            return None

    rec = {
        "arch": arch,
        "shape": shape_name,
        "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "n_params": int(n_params),
        "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "accum_steps": accum if cell.kind == "train" else None,
        # raw XLA numbers (NOTE: while bodies counted once — see analysis.py)
        "xla_flops_per_device_scan_once": cost.get("flops") if isinstance(cost, dict) else None,
        # trip-count-corrected per-device numbers
        "hlo_flops_per_device": hlo["flops"],
        "collective_bytes_total_per_device": hlo["collective_bytes_total"],
        "collective_bytes_by_kind": hlo["collective_bytes"],
        "collective_op_counts": hlo["collective_op_counts"],
        "n_while_loops": hlo["n_while"],
        # analytic model costs (global)
        **ana,
        # memory analysis (per device)
        "mem_argument_bytes": _m("argument_size_in_bytes"),
        "mem_output_bytes": _m("output_size_in_bytes"),
        "mem_temp_bytes": _m("temp_size_in_bytes"),
        "ok": True,
    }
    rec["roofline"] = roofline_terms(rec, n_dev, TARGET_DEVICE_KIND)
    if verbose:
        print(json.dumps(rec))
        sys.stdout.flush()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for cell in shapes_for(arch):
                cells.append((arch, cell.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_f = open(args.out, "a") if args.out else None
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp)
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "ok", "error")}))
                n_fail += 1
            if out_f:
                out_f.write(json.dumps(rec) + "\n")
                out_f.flush()
    if out_f:
        out_f.close()
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
