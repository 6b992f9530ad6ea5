"""Production training launcher.

Single-process on local devices by default; on a real cluster each host
runs this same entrypoint with ``--coordinator`` set and jax.distributed
wires the pods together (the mesh spans all hosts; per-host data sharding
comes from the deterministic pipeline, DESIGN.md §4).

  python -m repro.launch.train --arch pquant-300m --steps 200 \
      --seq-len 512 --global-batch 8 --ckpt-dir /tmp/ckpt

Fault tolerance: checkpoints are atomic + async; on restart the Trainer
resumes from the latest manifest automatically (same flag set).  The
orchestrator (launch/orchestrator.py) adds supervised restarts.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
from typing import Optional

import jax

from repro.configs.registry import get_config, reduced
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticSource, TextFileSource
from repro.train.trainer import Trainer, TrainerConfig


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--quant-mode", default="pquant",
                    choices=["pquant", "bitnet", "bitnet158", "none"])
    ap.add_argument("--n-experts", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU-scale) variant of the arch")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--peak-lr", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default=None, help="text file path (default: synthetic)")
    ap.add_argument("--dtype", default=None, choices=["float32", "bfloat16"],
                    help="model compute dtype override (default: the "
                         "config's bfloat16; fp32 is faster on CPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--history-out", default=None)
    # telemetry (name registry + trace format: repro.telemetry docs)
    ap.add_argument("--probes", action="store_true",
                    help="on-device QAT health probes in the step metrics")
    ap.add_argument("--sensitivity-every", type=int, default=0,
                    help="democratization snapshot cadence in steps (0=off)")
    ap.add_argument("--trace-jsonl", default=None,
                    help="stream the run lifecycle trace (JSONL) here")
    ap.add_argument("--history-jsonl", default=None,
                    help="stream history records as JSONL instead of "
                         "holding them in host memory")
    ap.add_argument("--metrics-out", default=None,
                    help="write the trainer's metrics snapshot "
                         "(validate_snapshot schema) as JSON on exit")
    # multi-host
    ap.add_argument("--coordinator", default=None,
                    help="host:port of jax.distributed coordinator")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    return ap


def main(argv: Optional[list[str]] = None):
    args = build_argparser().parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    cfg = get_config(args.arch, quant_mode=args.quant_mode, n_experts=args.n_experts)
    if args.reduced:
        cfg = reduced(cfg)
    if args.dtype:
        import dataclasses

        cfg = dataclasses.replace(cfg, dtype=args.dtype)

    host_count = jax.process_count()
    dcfg = DataConfig(
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        host_index=jax.process_index(),
        host_count=host_count,
        seed=args.seed,
    )
    if args.data:
        source = TextFileSource([args.data])
        assert source.vocab <= cfg.vocab_size, "tokenizer vocab exceeds model"
    else:
        source = SyntheticSource(cfg.vocab_size, seed=args.seed)
    data = PrefetchIterator(source, dcfg)

    tcfg = TrainerConfig(
        total_steps=args.steps,
        log_every=args.log_every,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        accum=args.accum,
        seed=args.seed,
        peak_lr=args.peak_lr,
        probes=args.probes,
        sensitivity_every=args.sensitivity_every,
        trace_path=args.trace_jsonl,
        history_path=args.history_jsonl,
    )
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
        )
    trainer = Trainer(cfg, tcfg, data)
    history = trainer.run()
    data.close()
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(trainer.snapshot(), f, indent=2)
    final = [h for h in history if "loss" in h and "event" not in h]
    if final:
        logging.getLogger(__name__).info(
            "final loss: %.4f (recoveries: %d)",
            final[-1]["loss"], trainer.recoveries,
        )
    return history


if __name__ == "__main__":
    main()
