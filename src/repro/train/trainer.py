"""Training loop: QAT train_step (pjit-ready), gradient accumulation,
checkpoint/restart, and the single-host Trainer used by examples/.

train_step semantics (paper §3.1 / Appendix B): latent master weights are
FP32; the forward pass casts to the model dtype (bf16) and fake-quantizes
(weights 1-bit / INT8, activations INT8) with STE gradients; AdamW with the
two-phase LR/WD schedule updates the FP32 latents.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import tempfile
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig
from repro.models import api
from repro.optim.adamw import (
    AdamWConfig,
    AdamWState,
    adamw_state_axes,
    adamw_update,
    init_adamw,
)
from repro.optim.schedule import schedule_for_mode
from repro.telemetry import probes as qprobes
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.tracing import JsonlSink, TrainTracer, annotate

Array = jax.Array

_log = logging.getLogger(__name__)


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def init_train_state(key: Array, cfg: ModelConfig) -> tuple[TrainState, Any]:
    """Returns (state, state_axes) — axes mirror the state for sharding."""
    params, axes = api.init_model(key, cfg)
    state = TrainState(params=params, opt=init_adamw(params))
    state_axes = TrainState(params=axes, opt=adamw_state_axes(axes))
    return state, state_axes


def train_state_shape_and_axes(cfg: ModelConfig):
    """ShapeDtypeStructs + axes without allocation (dry-run path)."""
    axes_box = {}

    def f(key):
        state, state_axes = init_train_state(key, cfg)
        axes_box["axes"] = state_axes
        return state

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, axes_box["axes"]


def cast_for_forward(params, dtype):
    """Latent FP32 master -> model dtype for the quantized forward pass."""
    if dtype == jnp.float32:
        return params

    def cast(p):
        return p.astype(dtype) if p.dtype == jnp.float32 else p

    return jax.tree.map(cast, params)


def make_train_step(
    cfg: ModelConfig,
    total_steps: int,
    accum: int = 1,
    adamw_cfg: AdamWConfig = AdamWConfig(),
    peak_lr: Optional[float] = None,
    probes: bool = False,
) -> Callable:
    """Build the (jit-able) train_step(state, batch) -> (state, metrics).

    ``accum`` > 1 splits the batch into microbatches scanned sequentially
    with FP32 gradient accumulation (memory relief at fixed global batch).

    ``probes=True`` adds the on-device QAT health probes (sign-flip /
    clip / scale-drift / branch-share / grad-split / router-entropy —
    name registry in ``repro.telemetry``) to the metrics dict.  The flag
    is a static Python gate: with ``probes=False`` no probe op is ever
    staged, so the lowered program is byte-identical to a probe-unaware
    build (pinned by ``tests/test_train_telemetry.py``).  The profiler
    annotations below are metadata-only and applied unconditionally,
    exactly like the serving stack's (PR 7 invariant).
    """
    sched = schedule_for_mode(cfg.quant.mode, total_steps, peak_lr)
    model_dtype = jnp.dtype(cfg.dtype)
    # the encdec family runs its own layer scan without probe drain
    # points, so forward taps would leak scan tracers there — force off
    probes_on = bool(probes) and cfg.family != "encdec"

    def loss_fn(params, batch):
        fwd_params = cast_for_forward(params, model_dtype)
        if probes_on:
            with qprobes.collect():
                return api.loss_fn(fwd_params, batch, cfg)
        return api.loss_fn(fwd_params, batch, cfg)

    def grads_one(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return loss, metrics, grads

    def compute_grads(params, batch):
        if accum == 1:
            return grads_one(params, batch)
        # microbatch scan: leading batch dim must divide by accum
        def split(x):
            b = x.shape[0]
            assert b % accum == 0, (b, accum)
            return x.reshape(accum, b // accum, *x.shape[1:])

        micro = jax.tree.map(split, batch)
        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, metrics, g = grads_one(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / accum, g_acc, g
            )
            return (loss_acc + loss / accum, g_acc), metrics

        with annotate("train/accum"):
            (loss, grads), metrics = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_g), micro
            )
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss, metrics, grads

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        with annotate("train/grads"):
            loss, metrics, grads = compute_grads(state.params, batch)
        step = state.opt.step
        lr = sched.lr(step)
        wd = sched.wd(step)
        with annotate("train/update"):
            new_params, new_opt, opt_metrics = adamw_update(
                grads, state.opt, state.params, lr, wd, adamw_cfg
            )
        out_metrics = {
            "loss": loss.astype(jnp.float32),
            "nll": metrics["nll"].astype(jnp.float32),
            **opt_metrics,
        }
        if probes_on:
            # forward-tap probes folded into metrics by api.loss_fn ...
            out_metrics.update(
                {
                    k: v.astype(jnp.float32)
                    for k, v in metrics.items()
                    if k.startswith("qat_")
                }
            )
            # ... plus the param/grad-side probes, all on device: they
            # ride the existing metrics transfer (no extra host syncs)
            with annotate("train/probes"):
                out_metrics.update(
                    qprobes.train_step_probes(state.params, new_params, grads)
                )
        return TrainState(params=new_params, opt=new_opt), out_metrics

    return train_step


# ---------------------------------------------------------------------------
# Single-host Trainer (examples / paper-claim benchmarks)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 200
    log_every: int = 10
    ckpt_every: int = 100
    ckpt_dir: Optional[str] = None
    accum: int = 1
    seed: int = 0
    peak_lr: Optional[float] = None
    # fault tolerance: reload last checkpoint if loss goes non-finite
    # (paper Fig. 10: BitNet needs this; pQuant shouldn't)
    auto_recover: bool = True
    # heartbeat file for the orchestrator's straggler/hang detection
    heartbeat_path: Optional[str] = os.environ.get("REPRO_HEARTBEAT")
    # --- telemetry (name registry + trace format: repro.telemetry docs) ---
    # on-device QAT health probes in the per-step metrics dict
    probes: bool = False
    # cadence (steps) of the host-side democratization snapshot; 0 = off
    sensitivity_every: int = 0
    # JSONL run-lifecycle trace (TrainTracer); None = no trace
    trace_path: Optional[str] = None
    # stream history records to this JSONL path instead of growing an
    # unbounded host list (run() then returns an empty list)
    history_path: Optional[str] = None


def _write_atomic(path: str, text: str) -> None:
    """Crash-atomic small-file write: tmp in the same directory, fsync,
    ``os.replace`` (the ``tile_cache.store`` pattern) — a reader or a
    crash sees the old or the new content, never a torn write.  The
    heartbeat rides this: a torn heartbeat looks like a hang to the
    orchestrator's straggler detection."""
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    ok = False
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        ok = True
    finally:
        if not ok:
            try:
                os.unlink(tmp)
            except OSError:
                pass


class Trainer:
    """Single-host training loop with the shared observability tier:

    * ``metrics`` — a :class:`~repro.telemetry.metrics.MetricsRegistry`
      (own one by default, injectable for tests/aggregation) updated every
      step; :meth:`snapshot` exports the CI-validated schema and
      ``metrics.prometheus_text()`` the scrape format.
    * ``tracer`` — a :class:`~repro.telemetry.tracing.TrainTracer` wired
      to ``tcfg.trace_path`` (or injected) streaming the run lifecycle as
      JSONL: step records, checkpoint/restore/recovery events, heartbeats.
    * console output goes through ``logging`` (logger ``repro.train``):
      the human one-liner at ``log_every`` on INFO, a structured JSON
      record per step on DEBUG.
    * ``train/grads`` / ``train/accum`` / ``train/update`` profiler
      annotations: capture them with ``jax.profiler.trace(dir)`` around
      :meth:`run`.

    All of it detaches cleanly: no registry/tracer and ``probes=False``
    reproduce the bare loop, with ``train_step`` lowering byte-identical.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainerConfig,
        data_iter,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[TrainTracer] = None,
    ):
        self.cfg, self.tcfg = cfg, tcfg
        self.data = data_iter
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._owns_tracer = tracer is None and tcfg.trace_path is not None
        if tracer is not None:
            self.tracer = tracer
        elif tcfg.trace_path:
            self.tracer = TrainTracer(JsonlSink(tcfg.trace_path))
        else:
            self.tracer = None
        self.state, self.state_axes = init_train_state(
            jax.random.PRNGKey(tcfg.seed), cfg
        )
        self.step_fn = jax.jit(
            make_train_step(
                cfg,
                tcfg.total_steps,
                tcfg.accum,
                peak_lr=tcfg.peak_lr,
                probes=tcfg.probes,
            ),
            donate_argnums=(0,),
        )
        self.ckpt = Checkpointer(tcfg.ckpt_dir) if tcfg.ckpt_dir else None
        self.history: list[dict] = []
        self.recoveries = 0
        self.start_step = 0
        if self.ckpt and self.ckpt.latest_step() is not None:
            self._restore()

    def _restore(self, step: Optional[int] = None):
        restored = self.ckpt.restore(self.state._asdict(), step=step)
        self.state = TrainState(**restored)
        self.start_step = int(self.state.opt.step)
        self.metrics.counter("train_restores_total").inc()
        if self.tracer:
            self.tracer.emit("restore", step=self.start_step,
                             from_step=self.start_step)

    def snapshot(self) -> dict:
        """The run's metrics in the CI-validated snapshot schema
        (:func:`repro.telemetry.metrics.validate_snapshot`)."""
        return self.metrics.snapshot()

    def _record(self, rec: dict, hist_f) -> None:
        """History record: streamed as JSONL (``history_path``) or
        appended to the in-memory list; mirrored to the tracer and to
        the per-step DEBUG log."""
        if hist_f is not None:
            hist_f.write(json.dumps(rec, sort_keys=True) + "\n")
            hist_f.flush()
        else:
            self.history.append(rec)
        if self.tracer:
            event = rec.get("event", "step")
            fields = {k: v for k, v in rec.items()
                      if k not in ("step", "event")}
            self.tracer.emit(event, step=rec["step"], **fields)
        if _log.isEnabledFor(logging.DEBUG):
            _log.debug("%s", json.dumps(rec, sort_keys=True))

    def _gauges(self, rec: dict) -> None:
        g = self.metrics.gauge
        for k, v in rec.items():
            if k == "step":
                g("train_step").set(v)
            elif k in ("loss", "nll", "lr", "wd", "grad_norm"):
                g("train_" + k).set(v)
            elif k.startswith(("qat_", "demo_")):
                g(k).set(v)

    def run(self) -> list[dict]:
        tcfg = self.tcfg
        hist_f = open(tcfg.history_path, "a") if tcfg.history_path else None
        steps_total = self.metrics.counter("train_steps_total")
        step_seconds = self.metrics.histogram("train_step_seconds")
        if self.tracer:
            self.tracer.emit(
                "run_start", step=self.start_step, arch=self.cfg.name,
                quant=self.cfg.quant.mode, total_steps=tcfg.total_steps,
            )
        t_last = time.time()
        try:
            for step, batch in self.data:
                if step < self.start_step:
                    continue
                if step >= tcfg.total_steps:
                    break
                jb = {k: jnp.asarray(v) for k, v in batch.items()}
                t0 = time.time()
                self.state, metrics = self.step_fn(self.state, jb)
                loss = float(metrics["loss"])  # the one host sync
                dt_step = time.time() - t0
                if not np.isfinite(loss) and tcfg.auto_recover and self.ckpt:
                    # fault path: reload last good ckpt (paper Fig. 10)
                    # — recorded, not silent: the history/trace carry
                    # (step, restored-from step, running count)
                    self.recoveries += 1
                    self._restore()
                    self.metrics.counter("train_recoveries_total").inc()
                    rec = {
                        "step": step, "event": "recovery", "loss": loss,
                        "from_step": self.start_step,
                        "recoveries": self.recoveries,
                    }
                    self._record(rec, hist_f)
                    _log.warning(
                        "step %d: non-finite loss, restored from step %d "
                        "(recovery #%d)",
                        step, self.start_step, self.recoveries,
                    )
                    continue
                rec = {k: float(v) for k, v in metrics.items()}
                rec["step"] = step
                rec["step_time_s"] = dt_step
                if (
                    tcfg.sensitivity_every > 0
                    and step % tcfg.sensitivity_every == 0
                ):
                    # cadenced democratization snapshot — host-side,
                    # off the jit path (repro.telemetry.probes)
                    rec.update(
                        qprobes.sensitivity_snapshot(self.state.params)
                    )
                self._record(rec, hist_f)
                steps_total.inc()
                step_seconds.observe(dt_step)
                self._gauges(rec)
                if tcfg.heartbeat_path:
                    _write_atomic(tcfg.heartbeat_path, str(step))
                if step % tcfg.log_every == 0:
                    dt = time.time() - t_last
                    t_last = time.time()
                    _log.info(
                        "step %5d loss %.4f nll %.4f lr %.2e gnorm %.2f "
                        "(%.1fs)", step, rec["loss"], rec["nll"],
                        rec["lr"], rec["grad_norm"], dt,
                    )
                    if self.tracer:
                        self.tracer.emit("heartbeat", step=step)
                if self.ckpt and step > 0 and step % tcfg.ckpt_every == 0:
                    self.ckpt.save(step, self.state._asdict())
                    self.metrics.counter("train_checkpoints_total").inc()
                    if self.tracer:
                        self.tracer.emit("checkpoint", step=step)
            if self.ckpt:
                final = int(self.state.opt.step)
                self.ckpt.save(final, self.state._asdict())
                self.ckpt.wait()
                self.metrics.counter("train_checkpoints_total").inc()
                if self.tracer:
                    self.tracer.emit("checkpoint", step=final)
            if self.tracer:
                self.tracer.emit(
                    "run_end", step=int(self.state.opt.step),
                    recoveries=self.recoveries,
                )
        finally:
            if hist_f is not None:
                hist_f.close()
            if self._owns_tracer and self.tracer:
                self.tracer.close()
        return self.history
