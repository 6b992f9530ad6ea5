"""Observability tier: metrics registry semantics (bucketing, quantile
bounds, snapshot schema, Prometheus export), request-trace span ordering,
clock injection (ManualClock drives the engine with zero real sleeps),
compatibility aliases over the registry, the tile-cache stats collector,
the engine's per-step events, trace counters and profiler spans — and the
load-bearing contract:
attaching metrics/tracing changes NO compiled program (byte-identical
lowering, asserted below)."""

import json
import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.quantization import QuantConfig
from repro.kernels import tile_cache
from repro.models import api
from repro.serve.engine import SamplerConfig
from repro.serve.metrics import (
    Counter,
    Gauge,
    Histogram,
    ManualClock,
    MetricsRegistry,
    MonotonicClock,
    resolve_clock,
    validate_snapshot,
)
from repro.serve.scheduler import FINISH_REASONS, ContinuousBatchingEngine
from repro.serve.tracing import (
    JsonlSink,
    ListSink,
    RequestTracer,
)

QC = QuantConfig(mode="pquant", r=16, num_experts=1)
CFG = ModelConfig(name="t", family="decoder", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=48, vocab_size=64, quant=QC)
MAX_LEN = 32
SCFG = SamplerConfig(temperature=0.7, top_k=10, max_new_tokens=5)


@pytest.fixture(scope="module")
def params():
    return api.init_model(jax.random.PRNGKey(1), CFG)[0]


def _prompt(seed, n=6):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 64), np.int32
    )


def _engine(params, **kw):
    kw.setdefault("layout", "paged")
    kw.setdefault("block_size", 8)
    kw.setdefault("chunk", 4)
    return ContinuousBatchingEngine(
        params, CFG, num_slots=2, max_len=MAX_LEN, scfg=SCFG, **kw
    )


# ---------------------------------------------------------------------------
# histogram semantics
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_bucketing_edges_inclusive_upper(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0))
        for x in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 5.0, 100.0):
            h.observe(x)
        # bucket i covers (edge[i-1], edge[i]]; the last is overflow
        assert h.counts == [2, 2, 2, 2]
        assert h.count == 8
        assert h.sum == pytest.approx(sum((0.5, 1.0, 1.5, 2.0, 3.0, 4.0,
                                           5.0, 100.0)))

    def test_quantile_bounds_bracket_exact_percentile(self):
        h = Histogram("h")
        rng = np.random.default_rng(0)
        xs = rng.exponential(0.05, size=500)
        for x in xs:
            h.observe(float(x))
        for q in (0.5, 0.95, 0.99):
            lo, hi = h.quantile_bounds(q)
            exact = float(np.quantile(xs, q, method="inverted_cdf"))
            assert lo < exact <= hi
            # the interpolated quantile stays inside the same bucket
            assert lo <= h.quantile(q) <= hi

    def test_overflow_bucket_reports_inf(self):
        h = Histogram("h", buckets=(1.0,))
        h.observe(10.0)
        assert h.quantile_bounds(0.5) == (1.0, math.inf)
        assert h.quantile(0.5) == 1.0  # clamped to the last finite edge

    def test_empty_histogram(self):
        h = Histogram("h")
        with pytest.raises(ValueError, match="empty"):
            h.quantile(0.5)
        assert h.to_dict()["p50"] is None

    def test_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=())
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))

    def test_memory_is_bounded(self):
        h = Histogram("h")
        n_counts = len(h.counts)
        for i in range(10_000):
            h.observe(i * 1e-3)
        assert len(h.counts) == n_counts  # no per-observation state


# ---------------------------------------------------------------------------
# registry: get-or-create, snapshot schema, Prometheus export
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_and_kind_conflict(self):
        m = MetricsRegistry()
        c = m.counter("a_total")
        assert m.counter("a_total") is c
        assert m.counter("a_total", reason="x") is not c  # distinct labels
        with pytest.raises(TypeError, match="already registered"):
            m.gauge("a_total")

    def test_family_by_label(self):
        m = MetricsRegistry()
        m.counter("fin_total", reason="stop").inc(2)
        m.counter("fin_total", reason="shed").inc()
        fam = m.family("fin_total")
        assert {dict(k)["reason"] for k in fam} == {"stop", "shed"}

    def test_snapshot_json_round_trip_validates(self):
        m = MetricsRegistry()
        m.counter("c_total").inc(3)
        m.gauge("g").set(7)
        m.histogram("h_seconds").observe(0.01)
        m.counter("fin_total", reason="stop").inc()
        m.register_collector(lambda: {"extra_stat": 1.5})
        snap = json.loads(json.dumps(m.snapshot()))
        validate_snapshot(snap)
        assert snap["counters"]["c_total"] == 3
        assert snap["counters"]['fin_total{reason="stop"}'] == 1
        assert snap["gauges"]["g"] == 7
        assert snap["histograms"]["h_seconds"]["count"] == 1
        assert snap["collected"]["extra_stat"] == 1.5

    def test_validate_snapshot_rejects_drift(self):
        m = MetricsRegistry()
        snap = m.snapshot()
        bad = dict(snap)
        del bad["gauges"]
        with pytest.raises(AssertionError, match="gauges"):
            validate_snapshot(bad)
        bad = json.loads(json.dumps(snap))
        bad["counters"]["x"] = "nope"
        with pytest.raises(AssertionError, match="number"):
            validate_snapshot(bad)

    def test_prometheus_text(self):
        m = MetricsRegistry()
        m.counter("req_total", reason="stop").inc(2)
        h = m.histogram("lat_seconds", buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(1.5)
        h.observe(9.0)
        text = m.prometheus_text()
        assert "# TYPE req_total counter" in text
        assert 'req_total{reason="stop"} 2' in text
        assert "# TYPE lat_seconds histogram" in text
        # cumulative bucket counts, then the +Inf total
        assert 'lat_seconds_bucket{le="1.0"} 1' in text
        assert 'lat_seconds_bucket{le="2.0"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text

    def test_reset_zeroes_everything(self):
        m = MetricsRegistry()
        m.counter("c_total").inc(5)
        m.gauge("g").set(2)
        m.histogram("h_seconds").observe(1.0)
        m.reset()
        snap = m.snapshot()
        assert snap["counters"]["c_total"] == 0
        assert snap["gauges"]["g"] == 0
        assert snap["histograms"]["h_seconds"]["count"] == 0


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------


class TestClocks:
    def test_resolve_none_is_virtual(self):
        now, sleep = resolve_clock(None)
        assert now is None
        sleep(5.0)  # no-op, returns instantly

    def test_resolve_bare_callable(self):
        now, sleep = resolve_clock(lambda: 3.5)
        assert now() == 3.5
        assert sleep is time.sleep

    def test_resolve_clock_object(self):
        c = ManualClock(start=2.0)
        now, sleep = resolve_clock(c)
        assert now() == 2.0
        sleep(1.5)  # routed to the clock's own sleep: virtual, recorded
        assert now() == 3.5 and c.sleeps == [1.5]
        with pytest.raises(TypeError):
            resolve_clock(object())

    def test_manual_clock_sleeps_virtually(self):
        c = ManualClock(start=1.0)
        c.sleep(2.5)
        c.advance(0.5)
        assert c.now() == 4.0
        assert c.sleeps == [2.5]

    def test_monotonic_clock_runs_forward(self):
        c = MonotonicClock()
        a = c.now()
        b = c.now()
        assert 0.0 <= a <= b


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


class TestEngineMetrics:
    def test_manual_clock_drives_waits_without_real_sleep(self, params):
        clock = ManualClock()
        eng = _engine(params, clock=clock)
        eng.submit(_prompt(0), max_new_tokens=4, seed=0, uid=0, arrival=0.0)
        eng.submit(_prompt(1), max_new_tokens=4, seed=1, uid=1, arrival=50.0)
        fins = eng.run()
        assert sorted(f.uid for f in fins) == [0, 1]
        # the drive loop waited for uid 1's arrival on the FAKE clock
        assert clock.sleeps, "drive loop never consulted the injected clock"
        assert clock.now() >= 50.0
        by_uid = {f.uid: f for f in fins}
        assert by_uid[1].first_token_at >= 50.0
        # engine-computed latency histograms live on the same timeline
        snap = eng.snapshot()
        assert snap["histograms"]["ttft_seconds"]["count"] == 2
        assert snap["histograms"]["request_latency_seconds"]["count"] == 2
        assert snap["counters"]["requests_submitted_total"] == 2
        assert eng.finished_by_reason["stop"] + \
            eng.finished_by_reason["length"] == 2

    def test_trace_span_ordering(self, params):
        sink = ListSink()
        eng = _engine(params, prefill_chunk=2,
                      tracer=RequestTracer(sink))
        eng.submit(_prompt(2), max_new_tokens=4, seed=2, uid=7)
        fins = eng.run()
        assert len(fins) == 1
        evs = sink.records
        assert evs, "tracer attached but nothing emitted"
        # timestamps are nondecreasing on the one engine clock
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts)
        kinds = [e["event"] for e in evs]
        for k in ("submitted", "block_alloc", "admitted", "prefill_chunk",
                  "first_token", "finished", "block_free"):
            assert k in kinds, f"missing lifecycle event {k!r}"
        order = [
            kinds.index("submitted"), kinds.index("admitted"),
            kinds.index("first_token"), kinds.index("finished"),
        ]
        assert order == sorted(order)
        assert kinds.index("block_alloc") < kinds.index("admitted")
        assert kinds.index("prefill_chunk") < kinds.index("first_token")
        fin = next(e for e in evs if e["event"] == "finished")
        assert fin["uid"] == 7 and fin["reason"] in FINISH_REASONS
        assert eng.tracer.events == len(evs)

    def test_jsonl_sink_round_trip(self, params, tmp_path):
        path = tmp_path / "trace.jsonl"
        eng = _engine(params, tracer=RequestTracer(JsonlSink(path)))
        eng.submit(_prompt(3), max_new_tokens=3, seed=3, uid=1)
        eng.run()
        eng.tracer.close()
        evs = [json.loads(line) for line in path.read_text().splitlines()]
        assert evs and all("t" in e and "event" in e for e in evs)
        assert any(e["event"] == "finished" for e in evs)

    def test_compat_aliases_are_registry_backed(self, params):
        eng = _engine(params)
        assert eng.shed_requests == 0
        eng.metrics.counter("shed_requests_total").inc(2)
        assert eng.shed_requests == 2
        eng.shed_requests = 0  # legacy bench reset form
        assert eng.metrics.counter("shed_requests_total").value == 0
        eng.host_transfers = 9
        assert eng.metrics.counter("host_transfers_total").value == 9

    def test_tile_cache_stats_ride_the_snapshot(self, params):
        tile_cache.reset_stats()
        tile_cache.record_hit()
        tile_cache.record_miss()
        tile_cache.record_sweep_ms(4.0)
        eng = _engine(params)
        col = eng.snapshot()["collected"]
        assert col["tile_cache_hits"] == 1
        assert col["tile_cache_misses"] == 1
        assert col["tile_cache_sweeps"] == 1
        assert col["tile_cache_sweep_ms"] == pytest.approx(4.0)
        tile_cache.reset_stats()

    def test_paged_path_stats_ride_the_snapshot(self, params):
        from repro.kernels import ops

        ops.reset_paged_path_stats()
        ops.record_paged_path("decode")
        ops.record_paged_path("chunk")
        ops.record_paged_path("chunk")
        col = _engine(params).snapshot()["collected"]
        assert col["paged_attn_path_decode"] == 1
        assert col["paged_attn_path_chunk"] == 2
        assert col["paged_attn_path_gather"] == 0
        ops.reset_paged_path_stats()

    def test_disabled_observability_lowers_byte_identical(self, params):
        """The hard contract: metrics + tracer attached vs absent must
        produce the SAME compiled decode-chunk program — all
        instrumentation is host-side at chunk boundaries, and the
        profiler annotations are applied unconditionally."""
        bare = _engine(params)
        instrumented = _engine(
            params, metrics=MetricsRegistry(),
            tracer=RequestTracer(ListSink()), clock=ManualClock(),
        )
        low = [
            e._chunk_fn.lower(e.params, e._caches, e._state).as_text()
            for e in (bare, instrumented)
        ]
        assert low[0] == low[1]


# ---------------------------------------------------------------------------
# engine steps: step events, program trace counts, profiler spans
# ---------------------------------------------------------------------------

STEP_FIELDS = {"step", "prefill_rows", "decode_tokens", "n_decoding",
               "n_live", "queue_depth", "blocks_used", "preempted",
               "traces", "wall_s", "fetch_s", "phase_s"}


def _steps(sink):
    return [e for e in sink.records if e["event"] == "step"]


def _traces(eng, program):
    return int(eng.metrics.counter("program_traces_total",
                                   program=program).value)


class TestEngineSteps:
    def test_one_step_event_per_step(self, params):
        """Each step() emits one ``step`` event whose pool reading is the
        allocator's own and whose work adds up to the engine's counters."""
        sink = ListSink()
        eng = _engine(params, prefill_chunk=4, tracer=RequestTracer(sink))
        for uid in range(3):
            eng.submit(_prompt(uid, n=6 + 3 * uid), max_new_tokens=5,
                       seed=uid, uid=uid)
        n = 0
        while eng._queue or eng._live():
            eng.step()
            ev = _steps(sink)[-1]
            assert ev["step"] == n and set(ev) >= STEP_FIELDS
            assert ev["blocks_used"] == eng.allocator.used_count
            assert ev["n_live"] == len(eng._live())
            assert ev["queue_depth"] == len(eng._queue)
            assert 0.0 <= ev["fetch_s"] <= ev["wall_s"]
            n += 1
        evs = _steps(sink)
        assert len(evs) == n == eng.snapshot()["counters"][
            "engine_steps_total"]
        assert sum(e["prefill_rows"] for e in evs) == eng.prefill_tokens
        # first tokens come from the prefill slices, the rest from chunks
        assert sum(e["decode_tokens"] for e in evs) == \
            eng.tokens_generated - 3
        chunks = [e for e in sink.records if e["event"] == "decode_chunk"]
        assert [e["n_decoding"] for e in evs if e["n_decoding"]] == \
            [e["n_decoding"] for e in chunks]
        assert all(0 < e["blocks_used"] <= eng.num_blocks for e in chunks)

    def test_step_event_names_its_phases(self, params):
        sink = ListSink()
        eng = _engine(params, prefill_chunk=4, tracer=RequestTracer(sink))
        eng.submit(_prompt(1), max_new_tokens=6, seed=1, uid=1)
        eng.run()
        phases = set().union(*(e["phase_s"] for e in _steps(sink)))
        assert {"serve/admit", "serve/chunked_prefill",
                "serve/prefill_fetch", "serve/ensure_blocks",
                "serve/decode_chunk", "serve/decode_dispatch",
                "serve/decode_fetch", "serve/process_chunk"} <= phases
        for e in _steps(sink):
            p = e["phase_s"]
            if "serve/decode_chunk" in p:
                assert p["serve/decode_chunk"] >= p["serve/decode_fetch"]

    def test_waiting_for_an_arrival_is_its_own_phase(self, params):
        clock = ManualClock()
        sink = ListSink()
        eng = _engine(params, clock=clock, tracer=RequestTracer(sink))
        eng.submit(_prompt(1), max_new_tokens=2, seed=1, uid=1,
                   arrival=0.2)
        eng.step()
        (ev,) = _steps(sink)
        assert "serve/wait_arrival" in ev["phase_s"]
        assert ev["n_live"] == 0 and ev["queue_depth"] == 1

    def test_a_steady_window_traces_nothing(self, params):
        """Warm engine, same shapes: no step traces a program; each
        program was traced once."""
        sink = ListSink()
        eng = _engine(params, prefill_chunk=4, tracer=RequestTracer(sink))
        eng.submit(_prompt(1), max_new_tokens=6, seed=1, uid=1)
        eng.run()
        assert _traces(eng, "chunk") == 1
        assert _traces(eng, "prefill_chunk") == 1
        sink.records.clear()
        eng.submit(_prompt(2, n=9), max_new_tokens=6, seed=2, uid=2)
        eng.run()
        assert [e["traces"] for e in _steps(sink)] == \
            [0] * len(_steps(sink))
        assert _traces(eng, "chunk") == 1

    def test_a_retrace_names_its_program_and_step(self, params):
        """One-shot admission pads prompts to power-of-two buckets: a
        prompt in a new bucket retraces the prefill program, and the
        count lands on the step that admitted it."""
        sink = ListSink()
        eng = _engine(params, tracer=RequestTracer(sink))
        eng.submit(_prompt(1, n=6), max_new_tokens=3, seed=1, uid=1)
        eng.run()
        program = ("prefill_bucketed" if eng._prefill_bucketed is not None
                   else "prefill")
        before = _traces(eng, program)
        sink.records.clear()
        eng.submit(_prompt(2, n=12), max_new_tokens=3, seed=2, uid=2)
        eng.run()
        evs = _steps(sink)
        assert _traces(eng, program) == before + 1
        assert evs[0]["traces"] >= 1 and "serve/admit" in evs[0]["phase_s"]
        assert all(e["traces"] == 0 for e in evs[1:])

    def test_profile_holds_the_step_spans(self, params, tmp_path):
        """``jax.profiler.trace`` around a run captures the engine's span
        tree on the host timeline."""
        from jax.profiler import ProfileData

        eng = _engine(params, prefill_chunk=4)
        eng.submit(_prompt(1), max_new_tokens=6, seed=1, uid=1)
        with jax.profiler.trace(str(tmp_path)):
            eng.run()
        (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
        names = {e.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for e in line.events}
        assert {"serve/step", "serve/admit", "serve/chunked_prefill",
                "serve/decode_chunk", "serve/decode_fetch",
                "serve/process_chunk"} <= names
