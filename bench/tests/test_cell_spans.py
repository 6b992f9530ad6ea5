"""The engine's step spans and step events as the benchmark reads them: the
span tree of a profiled tiny engine run, self times, the step events
against the loop's own reconstruction of each step, and the readers of
``host_share.serve`` and ``kv_pool_used_share.serve``."""

import time

import numpy as np
import pytest

from bench import run_cell
from bench.harness import program, spans, trace
from bench.loops import serve

from . import tiny

MS = 1_000_000  # ns
PHASES = ("serve/admit", "serve/chunked_prefill", "serve/ensure_blocks",
          "serve/decode_chunk", "serve/process_chunk")


def _engine(sink, num_blocks=None):
    from repro.serve.engine import SamplerConfig
    from repro.serve.scheduler import ContinuousBatchingEngine
    from repro.telemetry.tracing import RequestTracer

    cfg, mix = tiny.config("pquant-1.3b"), tiny.serve_mix()
    e = mix["engine"]
    params, qaxes = program.serving_weights(tiny.SEED, cfg)
    return cfg, mix, ContinuousBatchingEngine(
        params, program.model_config(cfg), e["num_slots"], e["max_len"],
        SamplerConfig(temperature=0.0, top_k=0, max_new_tokens=24,
                      stop_tokens=()), layout="paged",
        block_size=e["block_size"], num_blocks=num_blocks or e["num_blocks"],
        chunk=e["chunk"], prefill_chunk=e["prefill_chunk"],
        clock=time.perf_counter, tracer=RequestTracer(sink),
        param_axes=qaxes)


def _submit(eng, vocab, shapes):
    rng = np.random.default_rng(0)
    budgets = {}
    for uid, (plen, new) in enumerate(shapes):
        eng.submit(rng.integers(0, vocab, plen), max_new_tokens=new,
                   seed=uid, uid=uid, arrival=0.0)
        budgets[uid] = new
    return budgets


SHAPES = [(70, 5), (9, 20), (33, 12), (40, 1), (12, 9), (64, 17)]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A tiny engine run under the profiler, inside a window span: the
    extracted trace and the engine's events."""
    import jax

    from repro.telemetry.tracing import ListSink

    sink = ListSink()
    cfg, mix, eng = _engine(sink)
    _submit(eng, cfg["vocab_size"], SHAPES)
    eng.step()  # compiles the programs outside the profile
    logdir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(logdir)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            while eng._queue or eng._live():
                eng.step()
    return trace.extract(trace.xplane_file(str(logdir))), sink.records


def test_every_step_holds_its_phases(profiled):
    ext, events = profiled
    tree = spans.nest(ext)
    steps = [i for i, sp in enumerate(tree) if sp[0] == "serve/step"]
    assert len(steps) == sum(1 for e in events if e["event"] == "step") - 1
    kids = {i: set() for i in steps}
    for name, _, _, parent in tree:
        if parent in kids:
            kids[parent].add(name)
    assert all("serve/admit" in k for k in kids.values())
    assert set().union(*kids.values()) >= set(PHASES)
    for name, _, _, parent in tree:
        if name in ("serve/decode_fetch", "serve/decode_dispatch"):
            assert tree[parent][0] == "serve/decode_chunk"
        elif name == "serve/prefill_fetch":
            assert tree[parent][0] == "serve/chunked_prefill"
        elif name in PHASES:
            assert tree[parent][0] == "serve/step"


def test_step_self_time_is_what_the_phases_leave(profiled):
    ext, _ = profiled
    got = spans.summarize(ext)
    step = got["serve/step"]
    phases = sum(got[n]["total_s"] for n in PHASES)
    assert step["self_s"] == pytest.approx(step["total_s"] - phases,
                                           abs=1e-6)
    chunk = got["serve/decode_chunk"]
    assert chunk["self_s"] == pytest.approx(
        chunk["total_s"] - got["serve/decode_dispatch"]["total_s"]
        - got["serve/decode_fetch"]["total_s"], abs=1e-6)
    assert all(0 <= v["self_s"] <= v["total_s"] for v in got.values())


def test_host_share_reads_the_engine_phases(profiled):
    """With the device busy exactly while the host waits in a fetch, every
    idle gap falls in host work: the engine's phases name the gaps and
    host_share.serve reads in (0, 100)."""
    ext, _ = profiled
    fetches = [[n, s, d] for n, s, d in ext["host"]
               if n.endswith("_fetch")]
    dev = {"ops": [["fusion.1", "jit_chunk(1)", s, d, ""]
                   for _, s, d in fetches],
           "programs": [["jit_chunk(1)", s, d] for _, s, d in fetches]}
    red = trace.reduce(dict(ext, device={"/device:TPU:0": dev}),
                       {"decode": r"^jit_chunk\b"})
    value = run_cell.load_reader("host_share.serve")({"trace": red})
    assert value is not None and 0 < value < 100
    gaps = dict(red["idle_gaps"])
    host = run_cell.load_reader("host_share.serve").__globals__[
        "HOST_PHASES"]
    assert gaps.keys() & set(host)
    assert gaps.keys() <= set(host) | {"other", trace.SHORT_GAP}


def test_spans_self_times_synthetic():
    ext = {"device": {}, "host": [
        [trace.WINDOW_SPAN, 0, 100 * MS],
        ["serve/step", 10 * MS, 50 * MS],
        ["serve/decode_chunk", 20 * MS, 30 * MS],
        ["serve/decode_dispatch", 20 * MS, 5 * MS],
        ["serve/decode_fetch", 30 * MS, 15 * MS],
        ["serve/step", 70 * MS, 20 * MS],
        ["serve/step", 95 * MS, 10 * MS],  # runs past the window's end
    ]}
    got = spans.summarize(ext)
    assert got["serve/step"]["count"] == 2
    assert got["serve/step"]["total_s"] == pytest.approx(0.070)
    assert got["serve/step"]["self_s"] == pytest.approx(0.040)
    assert got["serve/decode_chunk"]["self_s"] == pytest.approx(0.010)
    assert got["serve/decode_fetch"]["self_s"] == pytest.approx(0.015)
    assert [p for *_, p in spans.nest(ext)] == [-1, 0, 1, 1, -1]


@pytest.fixture(scope="module")
def stepped():
    """The tiny serve cell's engine stepped to the end with the loop's
    slot tracker; per step: the engine's step event, the loop's
    ``step_work`` and the allocator's used blocks."""
    from repro.telemetry.tracing import ListSink

    sink = ListSink()
    cfg, mix, eng = _engine(sink)
    budgets = _submit(eng, cfg["vocab_size"], SHAPES)
    slots = serve.SlotTracker(mix["engine"]["chunk"], budgets)
    slots.feed(sink.records)
    sink.records.clear()
    rows = []
    while eng._queue or eng._live():
        before = slots.view()
        fin = eng.step()
        slots.feed(sink.records)
        (ev,) = [e for e in sink.records if e["event"] == "step"]
        rows.append((ev, serve.step_work(cfg, before, slots.view(), fin),
                     eng.allocator.used_count))
        sink.records.clear()
    return rows


def test_step_events_are_the_loops_reconstruction(stepped):
    assert len(stepped) > 4
    for ev, work, used in stepped:
        assert ev["prefill_rows"] == work["prefill_rows"]
        assert ev["n_decoding"] == work["n_decoding"]
        assert ev["blocks_used"] == used
        assert ev["preempted"] == 0


def test_kv_pool_used_share_reads_the_decode_chunks():
    from repro.telemetry.tracing import ListSink

    sink = ListSink()
    cfg, mix, eng = _engine(sink)
    _submit(eng, cfg["vocab_size"], SHAPES)
    eng.run()
    chunks = [e for e in sink.records if e["event"] == "decode_chunk"]
    rec = {"decode_chunk_events": chunks, "mix": mix}
    value = run_cell.load_reader("kv_pool_used_share.serve")(rec)
    blocks = mix["engine"]["num_blocks"]
    want = 100.0 * np.mean([e["blocks_used"] for e in chunks]) / blocks
    assert value == pytest.approx(want) and 0 < value <= 100


@pytest.mark.parametrize("name", ["host_share.serve",
                                  "kv_pool_used_share.serve",
                                  "fake_quant_share.train"])
def test_new_readers_are_silent_without_a_trace_or_events(name):
    read = run_cell.load_reader(name)
    assert read({}) is None
    # what an engine without step spans or pool counts leaves
    old = {"kind": "serve", "decode_chunk_events": [
               {"event": "decode_chunk", "n_decoding": 2}],
           "mix": tiny.serve_mix(),
           "trace": {"window_s": 1.0, "busy_s": 0.9, "ops_by_kind": {},
                     "idle_gaps": [["bench/engine_step", 0.05],
                                   ["serve/decode_chunk", 0.03],
                                   ["other", 0.02]]}}
    assert read(old) is None
