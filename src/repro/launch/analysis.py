"""Compiled-HLO analyzer for the roofline pass.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE — a
scanned 95-layer model reports ~1 layer of FLOPs (verified empirically,
see EXPERIMENTS.md §Methodology). Since every big model here scans its
layer stack (compile-time necessity on one host core), we re-derive
trip-count-correct totals from the partitioned HLO text itself:

  1. split the module into computations; map value name -> shape;
  2. collect per-computation costs: dot FLOPs (2 * prod(out_dims) *
     contraction), collective output bytes by kind;
  3. recover each while loop's trip count from the integer constant in
     its condition computation;
  4. propagate multipliers through the call graph (body= gets
     caller_mult * trip; calls= / condition= / to_apply= get
     caller_mult);
  5. total = sum over computations of cost * multiplier.

The memory (HBM traffic) term is computed analytically per cell —
params read once per step + KV-cache traffic + activation rw — since
reimplementing XLA's full bytes-accessed model per-op would add noise,
not signal. Formulas live in analytic_costs().
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\((.*?)\)\s*->", re.S)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclass
class Computation:
    name: str
    lines: list = field(default_factory=list)
    shapes: dict = field(default_factory=dict)       # value name -> (dtype, dims)
    dot_flops: float = 0.0
    coll_bytes: dict = field(default_factory=lambda: {k: 0 for k in COLL_KINDS})
    coll_counts: dict = field(default_factory=lambda: {k: 0 for k in COLL_KINDS})
    calls: list = field(default_factory=list)        # (kind, callee, trip_or_None)


def _split_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("->" in line) and line.endswith("{"):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = Computation(name=m.group(1))
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry = cur.name
                # parameter shapes from the signature
                for pm in re.finditer(r"%?([\w\.\-]+):\s*([a-z0-9]+)\[([0-9,]*)\]",
                                      m.group(2)):
                    cur.shapes[pm.group(1)] = (pm.group(2), pm.group(3))
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            cur.lines.append(line)
    if entry:
        comps["__entry__"] = comps[entry]
    return comps


def _parse_computation(comp: Computation):
    converts = set()
    for line in comp.lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        sm = _SHAPE_RE.match(rest)
        if sm:
            comp.shapes[name] = (sm.group(1), sm.group(2))
        if name.startswith("convert"):
            # value exists only as an upcast: XLA's CPU backend promotes
            # bf16 collectives to f32 through a convert; a TPU build moves
            # these at their original width. Track so collective bytes
            # reflect the TARGET hardware, not the CPU-sim artifact.
            converts.add(name)
        # ---- dot flops ----
        if re.search(r"\bdot\(", rest):
            out = _SHAPE_RE.match(rest)
            ops = re.search(r"dot\(([^)]*)\)", rest)
            lhs_c = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
            if out and ops:
                out_elems = _shape_elems(out.group(2))
                contraction = 1
                # operands carry inline typed shapes in newer XLA text
                # ("dot(f32[64,128]{1,0} %a, ...)"), bare (possibly
                # %-less) names in older; prefer the inline shape, fall
                # back to the shape map.
                operands = re.findall(
                    r"(?:([a-z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?\s+)?%?([\w\.\-]+)",
                    ops.group(1))
                if lhs_c is not None and operands:
                    _, dims_s, opname = operands[0]
                    if not dims_s:
                        lhs_shape = comp.shapes.get(opname)
                        dims_s = lhs_shape[1] if lhs_shape else ""
                    dims = dims_s.split(",") if dims_s else []
                    for ci in lhs_c.group(1).split(","):
                        if ci != "" and int(ci) < len(dims):
                            contraction *= int(dims[int(ci)])
                comp.dot_flops += 2.0 * out_elems * contraction
        # ---- collectives ----
        for kind in COLL_KINDS:
            if re.search(rf"\b{kind}\(", rest) or re.search(rf"\b{kind}-start\(", rest):
                sm2 = _SHAPE_RE.match(rest)
                if sm2:
                    b = _shape_bytes(sm2.group(1), sm2.group(2))
                else:
                    b = 0
                om = re.search(rf"{kind}(?:-start)?\(%?([\w\.\-]+)", rest)
                if om and om.group(1) in converts and sm2 and sm2.group(1) == "f32":
                    b //= 2  # promotion artifact: true width is bf16
                mult = 2 if kind == "all-reduce" else 1
                comp.coll_bytes[kind] += b * mult
                comp.coll_counts[kind] += 1
        # ---- calls ----
        if " while(" in rest or rest.startswith("while("):
            bm = re.search(r"body=%?([\w\.\-]+)", rest)
            cm = re.search(r"condition=%?([\w\.\-]+)", rest)
            # XLA's simplifier records the resolved trip count on the
            # while op itself; prefer it over the condition-constant scan
            tm = re.search(r'"known_trip_count":\s*\{"n":\s*"(\d+)"\}', rest)
            if bm:
                trip = int(tm.group(1)) if tm else (cm.group(1) if cm else None)
                comp.calls.append(("body", bm.group(1), trip))
            if cm:
                comp.calls.append(("condition", cm.group(1), None))
        for cm in re.finditer(r"(?:calls|to_apply)=%?([\w\.\-]+)", rest):
            comp.calls.append(("call", cm.group(1), None))


def _trip_count(comps: dict, cond_name: str) -> int:
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    best = 1
    for line in cond.lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    # a condition may delegate the compare to a fused computation; the
    # constant still lives in the condition region itself in practice.
    return best


def analyze_hlo(hlo: str) -> dict:
    """Trip-count-corrected totals: dot FLOPs + collective bytes (per device)."""
    comps = _split_computations(hlo)
    entry = comps.get("__entry__")
    for key, c in comps.items():
        if key != "__entry__":       # alias of the entry object; parse once
            _parse_computation(c)
    if entry is None:
        return {"flops": 0.0, "collectives": {}, "warning": "no entry computation"}

    # propagate multipliers through the call graph
    mult: dict[str, float] = {}

    def visit(name: str, m: float):
        comp = comps.get(name)
        if comp is None:
            return
        mult[name] = mult.get(name, 0.0) + m
        for kind, callee, cond in comp.calls:
            if kind == "body":
                if isinstance(cond, int):
                    trips = cond
                else:
                    trips = _trip_count(comps, cond) if cond else 1
                visit(callee, m * trips)
            else:
                visit(callee, m)

    visit(entry.name, 1.0)

    flops = 0.0
    coll = {k: 0.0 for k in COLL_KINDS}
    counts = {k: 0 for k in COLL_KINDS}
    for name, m in mult.items():
        comp = comps[name]
        flops += comp.dot_flops * m
        for k in COLL_KINDS:
            coll[k] += comp.coll_bytes[k] * m
            counts[k] += int(comp.coll_counts[k] * m)
    return {
        "flops": flops,
        "collective_bytes": {k: int(v) for k, v in coll.items()},
        "collective_bytes_total": int(sum(coll.values())),
        "collective_op_counts": counts,
        "n_computations": len(comps) - 1,
        "n_while": sum(1 for c in comps.values() for k, _, _ in c.calls if k == "body"),
    }


# ---------------------------------------------------------------------------
# analytic model costs (MODEL_FLOPS + HBM-traffic term)
# ---------------------------------------------------------------------------


def analytic_costs(cfg, cell) -> dict:
    """Closed-form per-step totals (GLOBAL, all devices):

      * model_flops — 6*N*D for train (dense N; MoE uses active params),
        2*N_active per generated/prefilled token for inference, plus the
        attention term 2*S*kv per token where applicable;
      * hbm_bytes — params read once + KV cache traffic + activation rw
        estimate (the classic inference/training byte model).
    """
    B, S = cell.global_batch, cell.seq_len
    d, L = cfg.d_model, cfg.n_layers
    V = cfg.padded_vocab

    # ---- parameter counts ----
    if cfg.use_mla:
        attn_p = d * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        attn_p += d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        attn_p += cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        attn_p += cfg.n_heads * cfg.v_head_dim * d
    else:
        attn_p = d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d
    if cfg.n_experts:
        expert_p = 3 * d * (cfg.moe_d_ff or cfg.d_ff)
        mlp_total = cfg.n_experts * expert_p + cfg.n_shared_experts * expert_p
        mlp_active = (cfg.top_k + cfg.n_shared_experts) * expert_p
    elif cfg.family == "ssm":
        u = int(cfg.xlstm_proj_factor * d)
        mlp_total = mlp_active = 2 * d * u + 3 * u * u + u * d   # mLSTM approx
    elif cfg.family == "hybrid":
        di = cfg.d_inner
        mlp_total = mlp_active = d * (2 * di + 2 * cfg.ssm_state + cfg.n_ssm_heads) + di * d
    else:
        mlp_total = mlp_active = 3 * d * cfg.d_ff

    if cfg.family == "hybrid":
        n_attn_blocks = cfg.n_layers // cfg.attn_every
        shared = d * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * d + 3 * d * cfg.d_ff
        layer_total = mlp_total            # per mamba layer
        body_total = L * layer_total + shared
        body_active = body_total           # all active; shared reused n_attn_blocks times
        flops_layers = L * mlp_total + n_attn_blocks * shared  # weight reuse counts each use
    elif cfg.family == "ssm":
        body_total = body_active = L * mlp_total
        flops_layers = L * mlp_total
    else:
        enc = 0
        if cfg.is_encoder_decoder:
            enc = cfg.n_encoder_layers * (attn_p + mlp_total) + cfg.n_encoder_layers * attn_p
        body_total = L * (attn_p + mlp_total) + enc
        body_active = L * (attn_p + mlp_active) + enc
        flops_layers = body_active

    embed_p = V * d * (1 if cfg.tie_embeddings else 2)
    n_total = body_total + embed_p
    n_active = body_active + V * d     # unembed always active

    # ---- flops ----
    n_attn_layers = (cfg.n_layers // cfg.attn_every if cfg.family == "hybrid"
                     else (cfg.n_layers + cfg.n_encoder_layers if cfg.is_encoder_decoder
                           else cfg.n_layers))
    if cell.kind == "train":
        tokens = B * S
        model_flops = 6.0 * (flops_layers + V * d) * tokens
        if not cfg.use_mla and cfg.family != "ssm":
            # causal attention: QK^T + AV = 2 matmuls * 2 flops * q_dim * S/2
            # per token per attention layer; x3 for fwd+bwd
            model_flops += 3.0 * 2.0 * 2.0 * cfg.q_dim * (S / 2) * tokens * n_attn_layers
    elif cell.kind == "prefill":
        tokens = B * S
        model_flops = 2.0 * (flops_layers + V * d) * tokens
        if not cfg.use_mla and cfg.family != "ssm":
            model_flops += 2.0 * 2.0 * cfg.q_dim * (S / 2) * tokens * n_attn_layers
    else:  # decode: one token per sequence
        tokens = B
        model_flops = 2.0 * (flops_layers + V * d) * tokens
        if cfg.family not in ("ssm", "hybrid"):
            kv_read = S * (cfg.kv_lora_rank + cfg.qk_rope_head_dim if cfg.use_mla
                           else 2 * cfg.kv_dim)
            model_flops += 2.0 * cfg.q_dim * S * 2 * tokens * (L if not cfg.is_encoder_decoder else L)

    # ---- hbm bytes (per step, global) ----
    pb = 2  # bf16 serving; fp32 training handled below
    if cell.kind == "train":
        # fp32 params + grads + 2 moments touched once each + bf16 activations
        param_traffic = n_total * (4 + 4 + 8 + 8)
        act = tokens * d * L * 2 * 6      # rough rw of activations w/ remat
        hbm = param_traffic + act
    elif cell.kind == "prefill":
        kv_write = (B * S * L * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * pb
                    if cfg.use_mla else B * S * L * 2 * cfg.kv_dim * pb)
        hbm = n_total * pb + tokens * d * L * 2 * 4 + kv_write
    else:
        if cfg.family == "ssm":
            state = B * L * (cfg.n_heads * (d // max(cfg.n_heads, 1)) ** 2) * 4
            kv_traffic = 2 * state
        elif cfg.family == "hybrid":
            ssm_state = B * L * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            n_inv = L // cfg.attn_every
            attn_kv = B * S * n_inv * 2 * cfg.kv_dim * pb
            kv_traffic = 2 * ssm_state + attn_kv
        elif cfg.use_mla:
            kv_traffic = B * S * L * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * pb
        else:
            kv_traffic = B * S * L * 2 * cfg.kv_dim * pb
            if cfg.is_encoder_decoder:
                kv_traffic += B * cfg.encoder_seq_len * L * 2 * cfg.kv_dim * pb
        hbm = n_total * pb + kv_traffic + tokens * d * L * 2 * 4

    return {
        "n_params_total": float(n_total),
        "n_params_active": float(n_active),
        "model_flops_global": float(model_flops),
        "hbm_bytes_global": float(hbm),
        "tokens_per_step": float(tokens),
    }


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM bandwidth, 1,600 Gbit/s of inter-chip interconnect
# (taken here as four links of 50 GB/s). A kind missing from the table
# is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_terms(rec: dict, n_devices: int, device_kind: str) -> dict:
    """Per-device seconds for each roofline term on ``device_kind`` chips
    + the bottleneck."""
    pk = peaks(device_kind)
    flops_dev = rec["hlo_flops_per_device"]
    hbm_dev = rec["hbm_bytes_global"] / n_devices
    coll_dev = rec["collective_bytes_total_per_device"]
    t_compute = flops_dev / pk["flops"]
    t_memory = hbm_dev / pk["hbm_bw"]
    t_coll = coll_dev / pk["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)
    step_s = max(terms.values())
    return {**terms, "bottleneck": bottleneck, "step_time_bound_s": step_s,
            "model_flops_ratio": (rec["model_flops_global"] / n_devices) / max(flops_dev, 1.0),
            "mfu_bound": (rec["model_flops_global"] / n_devices / pk["flops"]) / max(step_s, 1e-12)}
