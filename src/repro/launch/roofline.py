import os

# host-device dry run: the 512 virtual devices are CPU devices, never a TPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Roofline analysis per (arch x shape) on the single-pod production mesh.

Terms (per assignment, TPU v5e):
    compute term    = HLO_FLOPs / (chips * 197 TFLOP/s)
    memory term     = HLO_bytes / (chips * 819 GB/s)
    collective term = collective_bytes / (chips * 50 GB/s/link)
      (all-reduce counted 2x: reduce-scatter + all-gather phases)

METHODOLOGY NOTE (scan calibration): XLA's cost_analysis counts a
``lax.scan`` body ONCE, not trip-count times, and the HLO text likewise
shows in-body collectives once.  The deliverable compile (scan-over-layers,
full depth) proves the cell compiles and fits memory; the *costs* are
derived from two small UNROLLED compiles (1-group and 2-group deep) on the
same mesh: per-group cost = diff, outside cost = intercept, and
    corrected_total = outside + n_groups * per_group.
Group = the layer-pattern period (1 dense layer; 6 for gemma3's 5:1
local:global; 3 for recurrentgemma's rec/rec/attn; enc+dec pair for
whisper).  Remainder layers (gemma3: 62 = 10*6+2) are charged at the group
average (<2% error, noted per-cell).

MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (prefill, decode), with
N_active = analytic active matmul params (MoE counts shared + top-k only).
"""

import argparse
import dataclasses
import json
from typing import Optional

import jax

from repro.configs.base import ModelConfig, ShapeConfig, param_count, shapes_for
from repro.configs.registry import ASSIGNED, get_config
from repro.launch.dryrun import analyze_cell
from repro.launch.mesh import make_production_mesh

HW = {
    "peak_flops": 197e12,  # bf16 per chip (int8 is 2x — noted, not assumed)
    "hbm_bw": 819e9,
    "link_bw": 50e9,
}


# ---------------------------------------------------------------------------
# Analytic active-parameter count (for MODEL_FLOPS)
# ---------------------------------------------------------------------------


def active_matmul_params(cfg: ModelConfig) -> float:
    """Matmul-visible parameters touched per token (MoE: top-k + shared)."""
    pc = param_count(cfg)
    total = pc["total"]
    # embedding lookup is not a matmul; the LM head is
    embed = cfg.vocab_size * cfg.d_model
    total -= embed if cfg.tie_embeddings else 2 * embed
    total += cfg.vocab_size * cfg.d_model  # head matmul
    if cfg.moe:
        mats = 3 if cfg.glu else 2
        per_expert = mats * cfg.d_model * cfg.d_ff_expert
        n_moe_layers = cfg.n_layers - cfg.first_k_dense
        inactive = (cfg.n_routed_experts - cfg.moe_top_k) * per_expert * n_moe_layers
        total -= inactive
    q = cfg.quant
    if q.mode == "pquant" and q.num_experts > 1:
        mats = 3 if cfg.glu else 2
        per_branch = mats * cfg.d_model * q.r
        n_ffn_layers = cfg.n_layers + cfg.n_enc_layers
        total -= (q.num_experts - 1) * per_branch * n_ffn_layers
    return float(total)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n = active_matmul_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


# ---------------------------------------------------------------------------
# Calibration configs
# ---------------------------------------------------------------------------


def group_size(cfg: ModelConfig) -> int:
    if cfg.global_every > 0:
        return cfg.global_every
    if cfg.block_pattern:
        return len(cfg.block_pattern)
    return 1


def calib_config(cfg: ModelConfig, groups: int) -> ModelConfig:
    g = group_size(cfg)
    repl = {
        "n_layers": cfg.first_k_dense * int(cfg.moe) + groups * g,
        "scan_layers": False,
    }
    if cfg.family == "encdec":
        repl["n_enc_layers"] = groups
        repl["n_layers"] = groups
    return dataclasses.replace(cfg, **repl)


def n_groups_full(cfg: ModelConfig) -> float:
    g = group_size(cfg)
    layers = cfg.n_layers - (cfg.first_k_dense if cfg.moe else 0)
    return layers / g  # fractional remainder charged at group average


# ---------------------------------------------------------------------------
# Roofline per cell
# ---------------------------------------------------------------------------


def _coll_total(coll: dict) -> float:
    """Collective seconds numerator: AR counts 2x (RS + AG phases)."""
    t = 0.0
    for kind, b in coll.items():
        t += 2.0 * b if kind == "all-reduce" else float(b)
    return t


def roofline_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    rule_overrides: Optional[dict] = None,
    full_result: Optional[dict] = None,
    serve_quant=None,
):
    """Returns the full roofline record for one cell."""
    # 1. deliverable compile (scan, full depth): memory + compiles-at-all
    if full_result is None:
        full_result, _, _ = analyze_cell(cfg, shape, mesh, rule_overrides,
                                         serve_quant)

    # 2. calibration pair (unrolled, small)
    c1, _, _ = analyze_cell(calib_config(cfg, 1), shape, mesh, rule_overrides,
                            serve_quant)
    c2, _, _ = analyze_cell(calib_config(cfg, 2), shape, mesh, rule_overrides,
                            serve_quant)

    def corrected(key, sub=None):
        v1 = c1[key] if sub is None else c1[key].get(sub, 0)
        v2 = c2[key] if sub is None else c2[key].get(sub, 0)
        per_group = v2 - v1
        outside = v1 - per_group
        # clamp: when a term is near zero, layout noise between the two
        # calibration compiles can extrapolate slightly negative
        return max(0.0, outside + n_groups_full(cfg) * per_group)

    flops_dev = corrected("flops_total")
    bytes_dev = corrected("bytes_accessed_total")
    coll_kinds = set(c1["collective_bytes_per_device"]) | set(
        c2["collective_bytes_per_device"]
    )
    coll_dev = {k: corrected("collective_bytes_per_device", k) for k in coll_kinds}

    # DTYPE CORRECTION: the CPU backend upcasts every bf16 tensor to f32
    # during lowering (CPU dots don't support bf16), so raw HLO byte counts
    # are ~2x what the TPU artifact moves.  Principal tensors (activations,
    # forward weights, collective payloads) are bf16 on TPU; fp32 survives
    # only in scalar stats + optimizer slots (<10% of traffic).  We report
    # the /2-corrected terms and keep raw values alongside.
    BF16_CORR = 0.5
    compute_s = flops_dev / HW["peak_flops"]
    memory_s = bytes_dev * BF16_CORR / HW["hbm_bw"]
    collective_s = _coll_total(coll_dev) * BF16_CORR / HW["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    chips = full_result["chips"]
    hlo_flops_global = flops_dev * chips

    # roofline fraction: how close the cell is to its compute roofline —
    # the fraction of the bound step time spent at peak FLOPs.  1.0 means
    # compute-bound at peak; lower means memory/collective overhang.
    return {
        **full_result,
        "flops_per_device_corrected": flops_dev,
        "bytes_per_device_raw": bytes_dev,
        "collective_bytes_corrected": coll_dev,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_flops_ratio": mf / hlo_flops_global if hlo_flops_global else 0.0,
        "roofline_fraction": terms["compute"] / max(terms.values()),
        "model_roofline_fraction": (mf / full_result["chips"] / HW["peak_flops"])
        / max(terms.values()),
        "step_time_lower_bound_s": max(terms.values()),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--quant-mode", default="pquant")
    ap.add_argument("--n-experts", type=int, default=1)
    ap.add_argument("--out", default="roofline.json")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=False)
    archs = [args.arch] if args.arch else ASSIGNED
    results = []
    for arch in archs:
        cfg = get_config(arch, quant_mode=args.quant_mode, n_experts=args.n_experts)
        for shape in shapes_for(cfg):
            if args.shape and shape.name != args.shape:
                continue
            tag = f"{arch} x {shape.name}"
            try:
                rec = roofline_cell(cfg, shape, mesh)
                print(
                    f"[OK] {tag}: compute {rec['compute_s']*1e3:.1f}ms "
                    f"memory {rec['memory_s']*1e3:.1f}ms "
                    f"coll {rec['collective_s']*1e3:.1f}ms "
                    f"-> {rec['bottleneck']}-bound, "
                    f"useful-FLOPs {rec['useful_flops_ratio']:.2f}"
                )
                results.append(rec)
            except Exception as e:  # noqa: BLE001
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                results.append({"arch": arch, "shape": shape.name,
                                "error": f"{type(e).__name__}: {e}"})
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
