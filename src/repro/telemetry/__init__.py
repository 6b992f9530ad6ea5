"""Shared observability core: one metrics/tracing tier for serving AND
training.

PR 7 built the serving observability stack (``serve/metrics.py`` /
``serve/tracing.py``); this package is that code promoted to a shared home
so the Trainer rides the same registry, the same snapshot schema
(:func:`~repro.telemetry.metrics.validate_snapshot`, checked in CI against
both serving and training artifacts), the same Prometheus exporter and the
same JSONL sinks.  ``repro.serve.metrics`` / ``repro.serve.tracing`` remain
as re-export shims, so nothing serving-side changed.

Layout
------
``metrics``   Counter / Gauge / fixed-bucket Histogram, MetricsRegistry
              (snapshot + Prometheus text), validate_snapshot, clocks.
``tracing``   annotate (profiler spans), JsonlSink/ListSink, RequestTracer
              (serving lifecycle), TrainTracer (training lifecycle).
              Capture a profile with ``jax.profiler.trace(dir)``.
``probes``    On-device QAT health probes: an ambient collector that
              forward-pass tap sites record into, scan-boundary helpers,
              the param-side probe computations and the cadenced
              democratization snapshot.

Metric name registry
--------------------
One namespace across the codebase — names are stable, CI artifacts and
dashboards key on them.  Prometheus-safe (``[a-zA-Z_][a-zA-Z0-9_]*``).

Serving (wired by the engines / scheduler / kv_pool — see PR 7/9):
  ``requests_submitted_total`` / ``requests_finished_total{reason=...}``
  ``tokens_generated_total``, ``prefill_chunks_total``, ``decode_chunks_total``
  ``queue_depth``, ``batch_occupancy``, ``pool_blocks_used``
  ``ttft_seconds``, ``itl_seconds``, ``request_latency_seconds``
  ``prefix_cache_hits_total`` / ``prefix_cache_misses_total`` /
  ``prefix_cache_hit_tokens_total`` / ``prefix_cache_cow_total`` /
  ``prefix_cache_evictions_total``
  ``preemptions_total``
  ``program_traces_total{program=...}``  traces of each compiled engine
                            program (``chunk``, ``prefill_chunk``,
                            ``prefill``, ``install``, ``set_tables``, ...),
                            counted in the program's Python body, so only
                            at trace time: a retrace names its program

Training (wired by ``repro.train.trainer.Trainer``):
  counters   ``train_steps_total``, ``train_recoveries_total``,
             ``train_restores_total``, ``train_checkpoints_total``
  gauges     ``train_loss``, ``train_nll``, ``train_lr``, ``train_wd``,
             ``train_grad_norm``, ``train_step`` (latest step id)
  histogram  ``train_step_seconds``

QAT health probes (join the per-step ``metrics`` dict when
``TrainerConfig.probes`` is on; all computed ON DEVICE inside
``train_step`` — no extra host syncs):
  ``qat_flip_attn`` / ``qat_flip_ffn1`` / ``qat_flip_ffn8`` /
  ``qat_flip_embed``        latent-weight sign-flip rate vs the previous
                            step, per layer family (centered sign,
                            matching the AbsMean binarizer)
  ``qat_clip_w8``           INT8-branch weight saturation rate (|q|=127)
  ``qat_clip_act``          INT8 activation saturation rate across every
                            act-quant site in the forward
  ``qat_scale_drift_absmean`` / ``qat_scale_drift_absmax``
                            relative per-step drift of the 1-bit AbsMean
                            scales (lambda) / 8-bit AbsMax scales
  ``qat_branch_share8``     fraction of decoupled-layer output norm
                            carried by the 8-bit branch (alpha*y8) vs the
                            1-bit trunk (beta*y1) — the paper's
                            allocation claim, live
  ``qat_gnorm_ffn8`` / ``qat_gnorm_ffn1`` / ``qat_gnorm_share8``
                            per-branch gradient-norm split
  ``qat_router_entropy``    routed-expert load entropy (1.0 = perfectly
                            balanced top-1 routing, 0.0 = collapsed)

Cadenced democratization snapshot (host-side, every
``TrainerConfig.sensitivity_every`` steps, off the jit path; reuses
``core/sensitivity``): ``demo_score_<fam>``, ``demo_kurtosis_<fam>``,
``demo_top1pct_<fam>`` for ``fam`` in attn / ffn1 / ffn8.

Reserved (wired by upcoming PRs — see ROADMAP):
  ``spec_tokens_proposed_total`` / ``spec_tokens_accepted_total``
  (self-speculative decoding acceptance accounting).

Reading a train trace
---------------------
``TrainerConfig.trace_path`` streams the run lifecycle as JSONL (one
compact object per line, flushed per event — a crash leaves a replayable
prefix).  Events, all carrying ``{"t": run-relative seconds,
"event": ..., "step": ...}``:

  ``run_start``    config digest: arch name, quant mode, total steps
  ``step``         per-step record: loss/nll/lr/grad_norm + every qat_*
                   probe — the JSONL twin of the history record
  ``sensitivity``  cadenced democratization snapshot (demo_* keys)
  ``checkpoint``   async checkpoint save issued at ``step``
  ``restore``      state restored from ``from_step`` (startup resume)
  ``recovery``     auto-recovery: non-finite loss at ``step``, rolled
                   back to ``from_step``; ``recoveries`` = running count
  ``heartbeat``    liveness mark at ``log_every`` cadence
  ``run_end``      final step + total recoveries

A minimal reader::

    import json
    events = [json.loads(l) for l in open("train_trace.jsonl")]
    steps = [e for e in events if e["event"] == "step"]
    flips = [e.get("qat_flip_ffn1") for e in steps]

Healthy pQuant runs show ``qat_flip_*`` decaying toward 0 as latents
settle, ``qat_branch_share8`` well above 0 (the 8-bit branch is carrying
signal — democratization is being broken), and ``qat_clip_act`` low;
spikes in ``qat_scale_drift_*`` precede the loss spikes that trigger
``recovery`` events (paper Fig. 10).

Reading a serving trace
-----------------------
With a ``RequestTracer`` attached the engine emits, besides the request
lifecycle (``submitted``, ``block_alloc``, ``admitted``, ``prefill_chunk``,
``first_token``, ``decode_chunk``, ``finished``, ``block_free``,
``preempted``, ``prefix_hit``, ``block_cow``, ``stall``, ``fault_*``),
one ``step`` event per engine step on the engine clock:

  ``step``, ``prefill_rows`` (prompt tokens written), ``decode_tokens``,
  ``n_decoding``, ``n_live``, ``queue_depth``, ``blocks_used`` (paged
  only), ``preempted`` and ``traces`` (during the step), ``wall_s`` and
  ``fetch_s`` (``time.perf_counter`` seconds, real even under the virtual
  clock), ``phase_s`` ({span name: inclusive seconds}).

``decode_chunk`` events carry ``n_decoding`` and ``blocks_used`` (pool
blocks held while the chunk ran).  Profiler spans of a step (host
timeline, the device trace's clock): ``serve/step`` > ``serve/admit``,
``serve/chunked_prefill``, ``serve/wait_arrival``,
``serve/ensure_blocks``, ``serve/decode_chunk`` (> ``serve/decode_dispatch``,
``serve/decode_fetch``), ``serve/process_chunk``; ``serve/prefill_fetch``
under admission or the prompt's last slice.  Fake-quant ops carry the
named scopes ``quant/weights`` and ``quant/acts`` in HLO metadata
(``repro.core.quantization``).

The invariant that makes all of this free: with telemetry disabled
(``probes=False``, no tracer/registry attached), ``train_step`` lowers to
a byte-identical program — pinned by ``tests/test_train_telemetry.py``,
exactly like the serving-side pin in ``tests/test_metrics.py``.
"""

from repro.telemetry.metrics import (  # noqa: F401
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    ManualClock,
    MetricsRegistry,
    MonotonicClock,
    resolve_clock,
    validate_snapshot,
)
from repro.telemetry.tracing import (  # noqa: F401
    JsonlSink,
    ListSink,
    RequestTracer,
    TrainTracer,
    annotate,
    fault_hook,
)
