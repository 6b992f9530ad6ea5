import os

# host-device dry run: the 512 virtual devices are CPU devices, never a TPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape) cell and each production mesh
(16x16 single-pod, 2x16x16 multi-pod), lower + compile the real train_step
(train shapes) or serve_step (decode shapes) against ShapeDtypeStruct
inputs, then record:
  * memory_analysis()      — per-device bytes (does it fit HBM)
  * cost_analysis()        — HLO FLOPs / bytes accessed (roofline §compute/§memory)
  * collective bytes       — parsed from the optimized HLO (roofline §collective)

Usage:
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import json
import re
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig, shapes_for
from repro.configs.registry import ASSIGNED, get_config
from repro.distributed.sharding import (
    param_sharding_for,
    sharding_rules,
)
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.models import api
from repro.train.serve import make_serve_step
from repro.train.trainer import make_train_step, train_state_shape_and_axes

# ---------------------------------------------------------------------------
# Collective-byte accounting from optimized HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9untpd\[\]{},\- ]+?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes per collective op kind (per-device view:
    optimized HLO after SPMD partitioning has per-shard shapes)."""
    out: dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    rule_overrides: Optional[dict] = None,
    serve_quant: Optional[str] = None,  # None | "int8" | "packed"
):
    """Lower + compile one (arch x shape x mesh) cell.

    serve_quant: for prefill/decode cells, lower against the integer
    serving weight layout (train/quantized_serving) instead of FP latents.

    Returns (lowered, compiled, seconds).
    """
    overrides = dict(rule_overrides or {})
    if shape.name == "long_500k":
        # batch=1: shard the KV-cache sequence dim over `data` instead
        overrides.setdefault("cache_seq", "data")

    def get_params_shapes():
        if serve_quant:
            from repro.train.quantized_serving import serving_params_shape_and_axes

            return serving_params_shape_and_axes(cfg, packed=serve_quant == "packed")
        return api.params_shape_and_axes(cfg)

    specs, spec_axes = api.input_specs(cfg, shape)
    t0 = time.time()
    with sharding_rules(mesh, overrides):
        if shape.kind == "train":
            state_shapes, state_axes = train_state_shape_and_axes(cfg)
            state_sh = param_sharding_for(state_shapes, state_axes, mesh)
            batch_sh = param_sharding_for(specs, spec_axes, mesh)
            step = make_train_step(cfg, total_steps=10000)
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_shapes, specs)
        elif shape.kind == "prefill":
            state_shapes, state_axes = None, None
            p_shapes, p_axes = get_params_shapes()
            p_sh = param_sharding_for(p_shapes, p_axes, mesh)
            batch_sh = param_sharding_for(specs, spec_axes, mesh)
            from repro.train.serve import make_prefill_step

            step = make_prefill_step(cfg, cache_len=shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(p_shapes, specs)
        else:  # decode
            p_shapes, p_axes = get_params_shapes()
            p_sh = param_sharding_for(p_shapes, p_axes, mesh)
            tok_sh = param_sharding_for(
                {"tokens": specs["tokens"]}, {"tokens": spec_axes["tokens"]}, mesh
            )["tokens"]
            cache_sh = param_sharding_for(
                specs["caches"], spec_axes["caches"], mesh
            )
            step = make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, tok_sh, cache_sh, None),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                p_shapes, specs["tokens"], specs["caches"], specs["pos"]
            )
        compiled = lowered.compile()
    return lowered, compiled, time.time() - t0


def analyze_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, rule_overrides=None,
                 serve_quant=None):
    lowered, compiled, secs = lower_cell(cfg, shape, mesh, rule_overrides, serve_quant)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    chips = mesh_chip_count(mesh)
    result = {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "chips": chips,
        "compile_s": round(secs, 1),
        "flops_total": float(cost.get("flops", 0.0)),
        "bytes_accessed_total": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
    }
    return result, lowered, compiled


def run_cells(
    archs: list[str],
    shape_names: Optional[list[str]],
    multi_pod: bool,
    quant_mode: str,
    n_experts: int,
    out_path: Optional[str],
    rule_overrides: Optional[dict] = None,
):
    mesh = make_production_mesh(multi_pod=multi_pod)
    results = []
    for arch in archs:
        cfg = get_config(arch, quant_mode=quant_mode, n_experts=n_experts)
        for shape in shapes_for(cfg):
            if shape_names and shape.name not in shape_names:
                continue
            tag = f"{arch} x {shape.name} x {'2x16x16' if multi_pod else '16x16'}"
            try:
                res, lowered, compiled = analyze_cell(cfg, shape, mesh, rule_overrides)
                coll_total = sum(res["collective_bytes_per_device"].values())
                print(
                    f"[OK]   {tag}: compile {res['compile_s']}s, "
                    f"{res['flops_total']:.3e} FLOPs, "
                    f"peak {res['memory']['peak_bytes']/2**30:.2f} GiB/dev, "
                    f"coll {coll_total/2**20:.1f} MiB/dev"
                )
                results.append(res)
                del lowered, compiled
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                results.append(
                    {"arch": arch, "shape": shape.name, "error": f"{type(e).__name__}: {e}"}
                )
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {out_path}")
    n_fail = sum(1 for r in results if "error" in r)
    print(f"== {len(results) - n_fail}/{len(results)} cells OK ==")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape name (default: all)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--quant-mode", default="pquant",
                    choices=["pquant", "bitnet", "bitnet158", "none"])
    ap.add_argument("--n-experts", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else None
    run_cells(archs, shapes, args.multi_pod, args.quant_mode,
              args.n_experts, args.out)


if __name__ == "__main__":
    main()
