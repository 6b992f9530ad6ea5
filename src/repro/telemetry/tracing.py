"""Tracing and profiling hooks shared by the serving stack and the Trainer.

(Originally ``repro.serve.tracing``, PR 7; promoted here so training and
serving trace through one core.  The serving module re-exports.)

Three layers, all zero-overhead when disabled:

1. **Request lifecycle tracing** — :class:`RequestTracer` turns every
   request's life into an ordered span record::

       submitted -> admitted -> prefill_chunk* -> first_token ->
       decode_chunk* -> finished(reason)

   plus block-alloc/free events, preemptions, fired faults, the
   prefix-cache lifecycle (``prefix_hit`` when an admission walk reuses
   cached blocks — with ``n_blocks``/``n_tokens`` — and ``block_cow``
   when a fully-cached prompt copies its final shared page before
   diverging) and one ``step`` event per engine step, each a
   JSON-serialisable dict ``{"t": ...,
   "event": ..., "uid": ..., **fields}`` pushed through a pluggable sink (:class:`JsonlSink` for
   structured JSONL on disk, :class:`ListSink` for in-memory assertions).
   Timestamps come from the ENGINE's clock — the same ``now()`` that
   drives deadline math and the latency histograms — so a chaos failure
   or a ``SchedulerStall`` ships a replayable timeline on one timebase
   instead of a bare exception.  ``tracer=None`` (the default) skips
   every emit site behind one ``is not None`` check.

2. **Profiler annotations** — :func:`annotate` is a context manager
   combining ``jax.profiler.TraceAnnotation`` (host-timeline span) with
   ``jax.named_scope`` (HLO metadata, so device kernel time is
   attributable by name in a TensorBoard trace).  It is safe both around
   host-side dispatch (the scheduler's chunk boundaries) and inside
   traced code (the chunk fns, the kernel dispatch wrappers in
   ``repro.kernels.ops``) — it never changes numerics or lowered
   programs, only metadata, and it is applied unconditionally so
   enabling/disabling metrics cannot perturb compiled programs.

   To capture, bracket any region with ``jax.profiler.trace(dir)``: the
   spans land in the same ``.xplane.pb`` as the device ops, on one clock.
   The serving engine's step spans are listed in
   ``repro.serve.scheduler``'s module docstring.

3. **Training lifecycle tracing** — :class:`TrainTracer` is the Trainer's
   counterpart to :class:`RequestTracer`: per-step records plus
   checkpoint / restore / recovery / heartbeat events through the same
   sinks, self-clocked (run-relative seconds) because a training run has
   no engine clock.  Event vocabulary and a reader example live in
   ``repro.telemetry.__init__``'s "reading a train trace" section.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import IO, Callable, Optional, Union

import jax


@contextlib.contextmanager
def annotate(name: str):
    """Profiler span ``name`` for the enclosed region: a host-timeline
    ``TraceAnnotation`` plus a ``named_scope`` so any ops traced inside
    carry the name into HLO metadata (kernel attribution in the device
    timeline).  Metadata only — numerics and lowering semantics are
    untouched."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


# ---------------------------------------------------------------------------
# Request tracing
# ---------------------------------------------------------------------------


class ListSink:
    """In-memory sink: ``records`` is the list of emitted event dicts (the
    test suite's sink)."""

    def __init__(self):
        self.records: list[dict] = []

    def write(self, record: dict) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass


class JsonlSink:
    """Structured JSONL sink: one compact JSON object per line, flushed
    per event so a crash mid-run still leaves a replayable prefix (the
    whole point of shipping a timeline with a failure)."""

    def __init__(self, path_or_file: Union[str, os.PathLike, IO[str]]):
        if hasattr(path_or_file, "write"):
            self._f: IO[str] = path_or_file
            self._owns = False
        else:
            self._f = open(path_or_file, "w", encoding="utf-8")
            self._owns = True

    def write(self, record: dict) -> None:
        self._f.write(json.dumps(record, sort_keys=True, default=str) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._owns:
            self._f.close()


class RequestTracer:
    """Emit lifecycle events through a sink.

    The tracer is deliberately thin: it holds no per-request state (the
    sink's output IS the record — no unbounded in-memory lists riding
    along with the bounded histograms), stamps nothing itself (callers
    pass ``t`` from the one engine clock), and counts events so tests can
    assert emission without parsing."""

    def __init__(self, sink):
        self.sink = sink
        self.events = 0

    def emit(
        self, event: str, *, t: float, uid: Optional[int] = None, **fields
    ) -> None:
        record = {"t": float(t), "event": str(event)}
        if uid is not None:
            record["uid"] = int(uid)
        for k, v in fields.items():
            if v is not None:
                record[k] = v
        self.events += 1
        self.sink.write(record)

    def close(self) -> None:
        self.sink.close()


class TrainTracer:
    """Training-run lifecycle tracer: the Trainer's twin of
    :class:`RequestTracer`, writing through the same pluggable sinks.

    Differences from the request tracer, both deliberate:

    * **self-clocked** — a training run has no engine clock, so the tracer
      stamps events itself with run-relative seconds (injectable ``clock``
      with ``now()`` for tests — a :class:`~repro.telemetry.metrics.ManualClock`
      gives deterministic timestamps);
    * **step-keyed, not uid-keyed** — every event carries the training
      ``step`` instead of a request uid.

    Like the request tracer it holds no state beyond an event count: the
    sink's output IS the record, flushed per event so a crashed run still
    leaves a replayable prefix up to the failing step.
    """

    def __init__(self, sink, clock=None):
        from repro.telemetry.metrics import MonotonicClock

        self.sink = sink
        self.clock = clock if clock is not None else MonotonicClock()
        self.events = 0

    def emit(self, event: str, *, step: Optional[int] = None, **fields) -> None:
        record = {"t": float(self.clock.now()), "event": str(event)}
        if step is not None:
            record["step"] = int(step)
        for k, v in fields.items():
            if v is not None:
                record[k] = v
        self.events += 1
        self.sink.write(record)

    def close(self) -> None:
        self.sink.close()


def fault_hook(
    tracer: RequestTracer, now: Callable[[], float]
) -> Callable[[str, dict], None]:
    """Adapter: a :class:`repro.serve.faults.FaultInjector` ``on_fire``
    callback that lands every fired fault on the request timeline (event
    ``fault_<kind>``), timestamped by the engine clock."""

    def on_fire(kind: str, info: dict) -> None:
        tracer.emit(f"fault_{kind}", t=now(), **info)

    return on_fire
