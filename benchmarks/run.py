"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Mapping (DESIGN.md §6):
  Table 2   -> bench_quality       main quality, 4 modes trained from scratch
  Fig 4/T5  -> bench_scaling       loss vs number of 8-bit branches N
  Table 3   -> bench_matched       matched-total-params comparison
  Fig 2/5a  -> bench_sensitivity   parameter-democratization scores
  Figure 6  -> bench_memory        weight bytes moved per forward
  Figure 8  -> bench_kernels       linear-op time across precisions
  Table 8   -> bench_step_time     QAT step-time overhead
  Figure 10 -> bench_stability     divergence/spike counts at hot LR
  §Roofline -> bench_roofline      dry-run roofline terms per cell
  §Decode   -> bench_decode        python loop vs compiled engine tok/s
  §Serving  -> bench_serving       lockstep vs continuous batching latency
"""

import argparse
import json
import pathlib
import platform
import sys
import time

# allow `python benchmarks/run.py` from the repo root (the benchmarks
# package is importable either way)
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single benchmark id")
    ap.add_argument("--steps", type=int, default=120,
                    help="training steps for the learning benchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI subset: the serving-path suites "
                         "(decode incl. packed weights, continuous "
                         "batching) plus the allocation-free memory rows")
    ap.add_argument("--out", default=None,
                    help="write a JSON results artifact to this path "
                         "(default: BENCH_serving.json under --smoke, so "
                         "CI tracks the serving perf trajectory per run)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_decode,
        bench_kernels,
        bench_matched,
        bench_memory,
        bench_quality,
        bench_roofline,
        bench_scaling,
        bench_sensitivity,
        bench_serving,
        bench_stability,
        bench_step_time,
    )

    suites = {
        "memory": lambda: bench_memory.run(),
        "kernels": lambda: bench_kernels.run(),
        "roofline": lambda: bench_roofline.run(),
        "step_time": lambda: bench_step_time.run(),
        "decode": lambda: bench_decode.run(),
        "serving": lambda: bench_serving.run(),
        "quality": lambda: bench_quality.run(steps=args.steps),
        "scaling": lambda: bench_scaling.run(steps=args.steps),
        "matched": lambda: bench_matched.run(steps=args.steps),
        "sensitivity": lambda: bench_sensitivity.run(steps=max(60, args.steps // 2)),
        "stability": lambda: bench_stability.run(steps=max(80, args.steps // 2)),
    }
    if args.smoke:
        # the smoke serving run also emits the engine's metrics snapshot
        # and JSONL request trace next to BENCH_serving.json, so CI can
        # schema-validate and archive the telemetry alongside the numbers
        suites = {
            "memory": lambda: bench_memory.run(),
            "decode": lambda: bench_decode.run(smoke=True),
            "serving": lambda: bench_serving.run(
                smoke=True,
                metrics_out="BENCH_serving_metrics.json",
                trace_out="BENCH_serving_trace.jsonl",
            ),
            # a short probe-instrumented pQuant train run; its metrics
            # snapshot + lifecycle trace are the training-side telemetry
            # artifacts CI validates and archives
            "stability": lambda: bench_stability.run(
                smoke=True,
                metrics_out="BENCH_train_metrics.json",
                trace_out="BENCH_train_trace.jsonl",
            ),
        }
    def jsonable(x):
        """Suites return CSV-row lists OR nested result dicts (e.g.
        bench_memory) — keep whichever structure intact in the artifact,
        stringifying only leaves json can't encode."""
        try:
            json.dumps(x)
            return x
        except TypeError:
            if isinstance(x, dict):
                return {str(k): jsonable(v) for k, v in x.items()}
            if isinstance(x, (list, tuple)):
                return [jsonable(v) for v in x]
            return str(x)

    print("name,us_per_call,derived")
    results: dict[str, dict] = {}
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — later suites still run; exit 1 below
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            rows = None
            results[name] = {"error": f"{type(e).__name__}:{e}"}
        dt = time.time() - t0
        if rows is not None:
            results[name] = {"rows": jsonable(rows), "seconds": round(dt, 2)}
        print(f"# suite {name} done in {dt:.1f}s", file=sys.stderr)

    out = args.out or ("BENCH_serving.json" if args.smoke else None)
    if out:
        payload = {
            "smoke": bool(args.smoke),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "suites": results,
        }
        pathlib.Path(out).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"# wrote {out}", file=sys.stderr)
    failed = [n for n, r in results.items() if "error" in r]
    if failed:
        print(f"# suites failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
