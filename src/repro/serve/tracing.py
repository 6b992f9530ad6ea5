"""Compatibility re-export: tracing/profiling moved to
``repro.telemetry.tracing`` so the training loop shares one tracer core
with the serving stack.  Serving-side imports keep working unchanged."""

from repro.telemetry.tracing import (  # noqa: F401
    JsonlSink,
    ListSink,
    RequestTracer,
    TrainTracer,
    annotate,
    fault_hook,
)
