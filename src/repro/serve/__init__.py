"""Serving subsystem — three engine tiers over ONE model forward.

Every tier runs the same cache-resident multi-token forward,
``repro.models.api.forward_chunk``: T tokens per slot at per-slot position
offsets, K/V written into an *existing* cache (dense ring or paged) under
a causal mask against the already-resident prefix.  Prefill is
forward_chunk from an empty cache; a decode step is forward_chunk with
T=1; chunked admission prefill is a sequence of forward_chunk slices.
One read path to optimise — the prerequisite the paged-attention kernel
work builds on.

1. **Python loop** (``repro.train.serve.BatchedServer.generate_python_loop``)
   — one jitted decode + one host sync per token.  Kept as the benchmark
   baseline and the scan-equivalence oracle.
2. **Compiled lockstep** (:class:`~repro.serve.engine.DecodeEngine`) —
   prefill (one forward_chunk) + ``lax.scan`` decode + on-device sampling
   fused into one XLA program; a fixed batch decodes in lockstep, one
   device->host transfer per ``generate`` (per chunk when streaming, with
   the stop-token done mask riding the same transfer for early exit).
3. **Continuous batching**
   (:class:`~repro.serve.scheduler.ContinuousBatchingEngine`) — the same
   compiled chunked decode, plus a request lifecycle around it: queued
   requests are admitted into slots at chunk boundaries, tracked with
   per-slot positions / PRNG keys / stop masks on device, and evicted the
   chunk they finish, freeing their KV blocks for the next request.  With
   ``prefill_chunk`` set, admission runs token-budget **chunked prefill**
   (Sarathi-style): each engine step spends a bounded slice of at most
   one admitting prompt alongside the decode chunk, writing straight into
   the shared caches (``kv_pool.write_span``), so a long prompt no longer
   freezes every live decode stream — the head-of-line latency the tier
   exists to remove.

Cache-adapter protocol: decode caches are per-layer dicts in one of two
interchangeable layouts — dense ``{"k", "v"}`` ring buffers, or paged
``{"kpool", "vpool", "table"}`` backed by the shared block pool in
:mod:`repro.serve.kv_pool` (a ``(num_blocks, n_kv_heads, block, head_dim)``
pool per global-attention layer plus per-slot block tables; sliding-window
layers keep their dense ring caches, whose length *is* the window).  The
model stack dispatches on the ``"table"`` key, so every engine tier runs
either layout and produces identical tokens.

Paged attention kernel: scoring over the paged layout dispatches to the
Pallas block-table kernel (``repro.kernels.paged_attention``) whenever
``kernels.ops.paged_attention_enabled()`` — ``REPRO_PAGED_ATTN=1`` forces
it on (interpret mode off-TPU), ``=0`` forces the fallback, default
enables it on TPU only — and the static shapes qualify
(``ops.paged_attention_supported``: GQA grouping divides, block_size and
head_dim 8-aligned).  The kernel walks each slot's block table in place
with a flash-decoding online softmax (per-slot work bounded by the
resident length, never the table capacity) and serves all three tiers
through the one read path: decode steps (T=1), chunked-prefill slices and
one-shot prefill (T>1).  The ``kv_pool.read`` gather + SDPA path remains
the fallback and parity oracle — it is bitwise the dense computation,
while the kernel is float-rounding-close (online softmax re-associates
the reduction), which is exactly why the default keeps the fallback on
CPU where the bit-for-bit cross-layout suites run.  Pages-per-step is
autotuned per (T, heads, head_dim, block, table-width) signature via
``ops.sweep_paged_tiles`` and persisted per backend alongside the GEMV
tile tables (``REPRO_TILE_CACHE`` / ``REPRO_TILE_CACHE_DIR`` env vars).

All three tiers serve either weight layout: latent fake-quant params (float
matmuls on the quantization grid) or the packed integer export from
``repro.train.quantized_serving.quantize_params_for_serving(packed=True)``,
where every backbone linear runs the Pallas W1A8 kernel tier and decode
steps hit the fused-act-quant GEMV kernels (``repro.kernels``).  The packed
engines are bit-for-bit self-consistent across tiers and stay within float
rounding of the fake-quant oracle (``tests/test_packed_serving.py``).

Sharded serving — every tier accepts ``mesh=`` (a ``(data, model)`` device
mesh from ``repro.launch.mesh.make_host_mesh`` / ``mesh_from_env``, or the
``--mesh DxM`` flag on ``examples/serve_lm.py``) and runs the same
compiled programs tensor-parallel:

* **What shards** — weights column-parallel only (N-major, the *output*
  dim: packed sign-bit planes, INT8-branch matrices and their latent
  float counterparts for Q/K/V and the FFN up/gate projections) over the
  ``model`` axis, with the per-tensor AbsMean / AbsMax scales replicated
  — a shard dequantizes with the same scalar as the whole weight, so
  every per-shard output is a bitwise slice of the unsharded result (no
  K reduction is ever split).  Paged K/V pools shard over KV heads
  (``cache_heads``); packed-weight kernels run inside per-shard
  ``shard_map`` islands (``kernels.ops.*_nshard``) so each shard
  autotunes its own GEMV tile for its local N.
* **What replicates** — the host-side scheduler, admission queue,
  fault/metrics/tracing layers, per-slot positions / masks / PRNG keys,
  block tables, and dense ring caches (serving overrides map ``batch``
  to no mesh axis; indivisible head counts relax to replicated).
* **Where the collective sits** — one all-gather per sublayer, at the
  boundary where the N-sharded activation meets the replicated
  down/output projection; XLA inserts it from the shardings, so the
  1-device mesh lowers to exactly the meshless program.

``tests/test_sharded_serving.py`` pins the contract: mesh ``(1,1)`` is
bit-for-bit the meshless engine (both layouts, one-shot and chunked
prefill, greedy and sampled), and a forced 2-device CPU mesh reproduces
the token streams with weights and pools genuinely sharded.  The mesh
shape is exported as ``mesh_data_parallelism`` / ``mesh_model_parallelism``
gauges in the metrics snapshot.

Request lifecycle (tier 3) — every submitted request traverses the state
machine exactly once and finishes exactly once::

    submit() ──────────────▶ queued ──admit──▶ prefilling ──first token──▶
        │                      │                  │
        │ dead on arrival      │ shed / deadline  │ deadline / NaN logits
        ▼                      ▼                  ▼
    finished(rejected)    finished(shed |    finished(deadline | error)
                          deadline)
                                                ┌──────────────────────┐
    decoding ──stop token──▶ finished(stop)     │ preemption loops back│
        │        budget ────▶ finished(length)  │ to queued; restart is│
        │        deadline ──▶ finished(deadline)│ deterministic, so the│
        └──non-finite logits▶ finished(error)   │ stream is unchanged  │
                                                └──────────────────────┘

``FinishedRequest.finish_reason`` is one of ``FINISH_REASONS``
(``stop | length | deadline | shed | rejected | error``).  Robustness
knobs on :class:`~repro.serve.scheduler.ContinuousBatchingEngine`:
``max_queue`` + ``overload_policy`` bound the admission queue (load
shedding), per-request ``deadline`` / ``ttft_budget`` are enforced at
chunk boundaries, non-finite logits quarantine only the poisoned stream
(reason ``"error"``; everyone else is bit-for-bit untouched), and a
watchdog raises :class:`~repro.serve.scheduler.SchedulerStall` instead of
spinning when no progress is possible.

Prefix caching (``prefix_cache=True``, paged layout only) — KV blocks
gain content identity and a second lifecycle that overlays the request
state machine.  Every full prompt block is named by the chain hash
``hash((parent_hash, block_tokens))`` over the HOST token stream (mesh-
and layout-independent), and each block walks::

                 alloc (miss)                register
    blank ────────────────────▶ private ──────────────▶ cached+referenced
      ▲                            │                      │           ▲
      │ LRU eviction               │ unref                │ unref     │ ref
      │ (hash entry dies)          ▼                      ▼           │ (hit)
      └───────────────────── blank pool            cached+unreferenced
                                                     (parked on LRU,
                                                      still hittable)

* **hit** — admission walks the prompt's block-hash chain through the
  allocator's index; every *leading* hit is taken by ``ref`` (refcount++,
  off the LRU) before the tail is allocated, so an admission can never
  evict its own hits.  Only the unshared suffix is prefilled — bitwise
  the full prefill, which is why streams stay bit-for-bit identical to a
  cold engine (``tests/test_prefix_cache.py``).
* **miss** — the tail blocks come from the blank pool first, then by
  evicting the least-recently-released refcount-0 cached block (its hash
  entry dies with it: ``prefix_cache_evictions_total``).  A block
  registers into the index only once its pages are fully written and
  will receive no more writes; on release the chain extends over
  *generated* tokens, so multi-turn follow-ups hit the whole previous
  conversation.
* **CoW** — a block-aligned fully-cached prompt still recomputes its
  final position (the sampler needs those logits), which would write
  inside the last shared block: admission copies that page to a private
  block first (``prefix_cache_cow_total``; trace event ``block_cow``),
  so no slot ever mutates a page another slot references.
* **unref** — "free" is refcount decrement: a released shared block
  stays resident for its other owners, and a refcount-0 *cached* block
  parks on the LRU — still hittable, still counted free
  (``free_count = blank + parked``), so a drained engine reconciles to
  ``pool_blocks_used == 0`` with a warm cache.

Configs whose recurrent state lives outside the paged pool (sliding-
window rings, SSM/rec state, MLA latents) or whose routing couples
tokens (MoE, VLM prefixes) decline the cache with one warning and run
cold.  Hits/misses/reused tokens are exported as
``prefix_cache_{hits,misses,hit_tokens}_total`` and admission hits land
on the request trace as ``prefix_hit`` events.

Fault injection (:mod:`repro.serve.faults`) drives all of this
deterministically for tests and chaos runs::

    from repro.serve import ContinuousBatchingEngine
    from repro.serve.faults import (
        AllocFailure, FaultInjector, PoisonLogits,
    )

    inj = FaultInjector([
        AllocFailure(index=3),          # 4th alloc call fails
        PoisonLogits(uid=1, gen_index=5),  # NaN logits at token 5
    ])  # or FaultInjector.random(seed, uids) for a seeded schedule
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=128,
                                   faults=inj)
    eng.submit(prompt, max_new_tokens=16, deadline=40.0)
    done = eng.run()   # uid 1 finishes with reason "error"; all other
                       # streams are bit-for-bit the fault-free run

With ``faults=None`` (default) the hooks are skipped entirely and the
compiled programs are byte-identical to the fault-free build — the chaos
suite (``tests/test_chaos.py``) asserts the graceful-degradation
contract under random schedules in both cache layouts.

Observability tier (:mod:`repro.serve.metrics` +
:mod:`repro.serve.tracing`) — zero-overhead-when-disabled telemetry
threaded through the whole stack:

* **Metrics registry** — every engine owns a
  :class:`~repro.serve.metrics.MetricsRegistry` of typed counters,
  gauges and fixed-bucket histograms (log-spaced edges, bounded memory —
  no per-request lists).  ``engine.snapshot()`` returns one plain dict
  (``validate_snapshot`` pins the schema,
  ``MetricsRegistry.prometheus_text`` renders the exposition format)
  covering submissions, per-``finish_reason`` totals, shed / rejection /
  deadline / quarantine counts, preemptions and restarts, admission-queue
  depth and batch occupancy, paged-pool block utilization, and
  engine-computed TTFT / inter-token-latency / request-latency
  histograms on the engine's own clock — the benchmark reports what the
  engine measures, not a host-side recount.  Legacy counter attributes
  (``engine.shed_requests`` etc.) remain as aliases over the registry.
  Process-wide autotune-cache stats (``kernels.tile_cache``: dispatch
  hits/misses, sweeps, sweep milliseconds) ride the same snapshot via a
  registered collector.
* **Request tracing** — pass a
  :class:`~repro.serve.tracing.RequestTracer` (``tracer=``) wrapping a
  :class:`~repro.serve.tracing.JsonlSink` or
  :class:`~repro.serve.tracing.ListSink` to stream one structured event
  per lifecycle edge: submitted → block_alloc → admitted →
  prefill_chunk → first_token → decode_chunk → finished(reason), plus
  block_free, preempted, stall and fault_* events, all timestamped on
  the engine clock.  ``tracer=None`` (default) skips every emission.
* **Profiling hooks** — :func:`~repro.serve.tracing.annotate` brackets
  the admission-prefill / chunked-prefill / decode-chunk / sample
  regions (and the kernel dispatch sites in ``kernels.ops``) with
  ``jax.profiler.TraceAnnotation`` + ``named_scope``; the annotations
  are applied unconditionally, so enabling or disabling metrics/tracing
  changes NO compiled program — byte-identical lowering is asserted in
  ``tests/test_metrics.py``.  Setting ``REPRO_PROFILE_DIR=/path`` wraps
  engine runs in ``jax.profiler.start_trace``/``stop_trace`` for a
  loadable device profile.

Clocks: ``clock=None`` keeps the deterministic virtual clock (one tick
per decode chunk); any ``now()`` callable or a
:class:`~repro.serve.metrics.ManualClock` /
:class:`~repro.serve.metrics.MonotonicClock` object supplies real (or
test-controlled) time, including the drive-loop sleep — tests fake time
without sleeping.
"""

from repro.serve.engine import (  # noqa: F401
    DecodeEngine,
    SamplerConfig,
    decode_logits,
    sample_token,
)
from repro.serve.faults import (  # noqa: F401
    AllocFailure,
    DelayArrival,
    FaultInjector,
    ForcePreempt,
    PoisonLogits,
)
from repro.serve.metrics import (  # noqa: F401
    ManualClock,
    MetricsRegistry,
    MonotonicClock,
    validate_snapshot,
)
from repro.serve.scheduler import (  # noqa: F401
    FINISH_REASONS,
    ContinuousBatchingEngine,
    FinishedRequest,
    InadmissibleRequest,
    Request,
    RequestState,
    SchedulerStall,
)
from repro.serve.tracing import (  # noqa: F401
    JsonlSink,
    ListSink,
    RequestTracer,
    annotate,
    maybe_profile,
)
