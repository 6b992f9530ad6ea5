"""Training-side observability (PR 10): the on-device QAT health probes,
the Trainer's metrics/trace/heartbeat wiring, and the load-bearing
contract inherited from the serving stack — telemetry disabled must
lower the SAME compiled train_step, byte for byte."""

import json
import os
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.quantization import (
    EPS,
    INT8_QMAX,
    QuantConfig,
    quantize_activations_int8,
)
from repro.data.pipeline import DataConfig, SyntheticSource, host_batch
from repro.telemetry import probes
from repro.telemetry.metrics import ManualClock, MetricsRegistry, validate_snapshot
from repro.telemetry.tracing import JsonlSink, ListSink, TrainTracer
from repro.train.trainer import (
    Trainer,
    TrainerConfig,
    _write_atomic,
    init_train_state,
    make_train_step,
)

QC = QuantConfig(mode="pquant", r=16, num_experts=1)
CFG = ModelConfig(name="t", family="decoder", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=48, vocab_size=64, quant=QC)


def _data_iter(cfg, steps, seq=16, batch=4, seed=0):
    src = SyntheticSource(cfg.vocab_size, seed=seed)
    dcfg = DataConfig(seq_len=seq, global_batch=batch, seed=seed)
    for s in range(steps + 1):
        yield s, host_batch(src, dcfg, s)


def _batch(cfg, seq=16, batch=4):
    src = SyntheticSource(cfg.vocab_size, seed=0)
    raw = host_batch(src, DataConfig(seq_len=seq, global_batch=batch), 0)
    return {k: jnp.asarray(v) for k, v in raw.items()}


# ---------------------------------------------------------------------------
# the invariant: telemetry off => byte-identical lowering
# ---------------------------------------------------------------------------


class TestByteIdenticalLowering:
    def test_trainer_with_telemetry_lowers_identically(self, tmp_path):
        """Registry + tracer + history streaming attached, probes=False:
        the compiled train_step must equal a bare build's, byte for byte
        (all of that instrumentation is host-side)."""
        state, _ = init_train_state(jax.random.PRNGKey(0), CFG)
        batch = _batch(CFG)
        bare = jax.jit(make_train_step(CFG, 10), donate_argnums=(0,))
        tcfg = TrainerConfig(
            total_steps=10, probes=False,
            trace_path=str(tmp_path / "t.jsonl"),
            history_path=str(tmp_path / "h.jsonl"),
        )
        tr = Trainer(CFG, tcfg, _data_iter(CFG, 0),
                     metrics=MetricsRegistry(),
                     tracer=TrainTracer(ListSink()))
        low_bare = bare.lower(state, batch).as_text()
        low_tr = tr.step_fn.lower(state, batch).as_text()
        assert low_bare == low_tr

    def test_probe_flag_defaults_off(self):
        step_default = jax.jit(make_train_step(CFG, 10), donate_argnums=(0,))
        step_off = jax.jit(make_train_step(CFG, 10, probes=False),
                           donate_argnums=(0,))
        state, _ = init_train_state(jax.random.PRNGKey(0), CFG)
        batch = _batch(CFG)
        assert (step_default.lower(state, batch).as_text()
                == step_off.lower(state, batch).as_text())

    def test_quant_scopes_change_metadata_only(self, monkeypatch):
        """The fake-quant named scopes (``quant/weights``, ``quant/acts``)
        reach the compiled step's metadata and nothing else: without them
        the compiled program is the same, metadata aside."""
        import contextlib
        import re

        state, _ = init_train_state(jax.random.PRNGKey(0), CFG)
        batch = _batch(CFG)

        def compiled():
            step = jax.jit(make_train_step(CFG, 10))
            return step.lower(state, batch).compile().as_text()

        def strip(text):
            text = text.split("\nFileNames")[0]
            return re.sub(r", metadata=\{[^}]*\}", "", text)

        scoped = compiled()
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = compiled()
        assert 'quant/weights' in scoped and 'quant/acts' in scoped
        assert "quant/" not in bare
        assert strip(scoped) == strip(bare)


# ---------------------------------------------------------------------------
# probe correctness on hand-built weights
# ---------------------------------------------------------------------------


class TestParamProbes:
    def test_sign_flip_rate_known_counts(self):
        # mixer leaf (family attn): per-slice mean is 0; flipping the sign
        # of every element flips every centered sign -> rate 1.0
        w_old = jnp.asarray([[1.0, -1.0], [1.0, -1.0]])
        tree_old = {"mixer": {"w": w_old}}
        tree_new = {"mixer": {"w": -w_old}}
        grads = {"mixer": {"w": jnp.zeros_like(w_old)}}
        out = probes.train_step_probes(tree_old, tree_new, grads)
        assert float(out["qat_flip_attn"]) == 1.0
        # |w| unchanged -> AbsMean scale drift exactly 0
        assert float(out["qat_scale_drift_absmean"]) == 0.0

    def test_partial_flip_and_branch_split(self):
        w1_old = jnp.asarray([[1.0, -1.0], [1.0, -1.0]])
        w1_new = jnp.asarray([[1.0, -1.0], [-1.0, 1.0]])  # 2 of 4 flip
        # 8-bit branch halves uniformly: signs keep, amax 2 -> 1
        w8_old = jnp.asarray([[2.0, 1.0], [0.5, 2.0]])
        w8_new = w8_old / 2.0
        g1 = jnp.asarray([[3.0, 4.0], [0.0, 0.0]])  # ||g1|| = 5
        g8 = jnp.asarray([[2.0, 2.0], [2.0, 2.0]])  # ||g8|| = 4
        old = {"ffn": {"w1_up": w1_old, "w8_up": w8_old}}
        new = {"ffn": {"w1_up": w1_new, "w8_up": w8_new}}
        grads = {"ffn": {"w1_up": g1, "w8_up": g8}}
        out = probes.train_step_probes(old, new, grads)
        assert float(out["qat_flip_ffn1"]) == 0.5
        assert float(out["qat_flip_ffn8"]) == 0.0
        np.testing.assert_allclose(
            float(out["qat_scale_drift_absmax"]), 1.0 / (2.0 + EPS), rtol=1e-6
        )
        np.testing.assert_allclose(float(out["qat_gnorm_ffn1"]), 5.0)
        np.testing.assert_allclose(float(out["qat_gnorm_ffn8"]), 4.0)
        np.testing.assert_allclose(
            float(out["qat_gnorm_share8"]), 16.0 / (16.0 + 25.0), rtol=1e-6
        )

    def test_int8_weight_clip_fraction(self):
        # amax = 1.0 -> scale = 127/(1+EPS); the two 1.0 entries round to
        # 127 (clip), 0.5 -> 63 and 0.25 -> 32 stay inside the grid
        w8 = jnp.asarray([[1.0, 0.5], [0.25, 1.0]])
        tree = {"ffn": {"w8_up": w8}}
        zeros = {"ffn": {"w8_up": jnp.zeros_like(w8)}}
        out = probes.train_step_probes(tree, tree, zeros)
        assert float(out["qat_clip_w8"]) == 0.5

    def test_norm_and_router_leaves_are_skipped(self):
        tree = {
            "ffn_norm": {"scale": jnp.ones((4, 4))},
            "ffn": {"subln": {"scale": jnp.ones((4, 4))},
                    "router": {"w": jnp.ones((4, 4))}},
        }
        out = probes.train_step_probes(tree, tree, tree)
        assert out == {}

    def test_family_classification(self):
        cases = {
            "segments/0/b0/mixer/wq/w": "attn",
            "segments/0/b0/ffn/w1_up": "ffn1",
            "segments/0/b0/ffn/w8_down": "ffn8",
            "embed/table": "embed",
            "segments/0/b0/ffn/router/w": None,
            "segments/0/b0/ffn/subln/scale": None,
            "final_norm/scale": None,
        }
        for key, fam in cases.items():
            assert probes.family_of(key) == fam, key


class TestForwardTaps:
    def test_activation_clip_tap(self):
        # per-token AbsMax: amax = 4 -> the three 4.0s hit the 127 rail
        x = jnp.asarray([[4.0, 4.0, 4.0, 1.0]])
        with probes.collect():
            quantize_activations_int8(x)
            out = probes.summaries()
        np.testing.assert_allclose(float(out["qat_clip_act"]), 0.75, rtol=1e-6)

    def test_taps_are_silent_outside_collect(self):
        x = jnp.asarray([[4.0, 4.0]])
        quantize_activations_int8(x)  # no ambient collector: no recording
        assert not probes.active()
        assert probes.summaries() == {}

    def test_branch_share_ratio(self):
        with probes.collect():
            probes.add("branch1_sq", 3.0)
            probes.add("branch8_sq", 1.0)
            out = probes.summaries()
        np.testing.assert_allclose(float(out["qat_branch_share8"]), 0.25)

    def test_weighted_mean_across_tap_sites(self):
        with probes.collect():
            probes.add_mean("clip_act", 1.0, 1.0)
            probes.add_mean("clip_act", 0.0, 3.0)
            out = probes.summaries()
        np.testing.assert_allclose(float(out["qat_clip_act"]), 0.25)

    def test_scan_discipline_round_trip(self):
        """Records inside a scan body leave as ys and re-merge summed;
        pre-scan records are held aside, not broadcast per iteration."""
        with probes.collect():
            probes.add("pre", 1.0)
            with probes.scan_scope():
                def body(c, x):
                    probes.add("inner", x)
                    return c, probes.scan_drain()
                _, ys = jax.lax.scan(body, 0.0, jnp.asarray([1.0, 2.0, 3.0]))
                probes.scan_merge(ys)
            probes.add_mean("clip_act", 0.5, 2.0)
            c = probes._COLLECTOR
            assert float(c.sums["pre"]) == 1.0
            assert float(c.sums["inner"]) == 6.0


# ---------------------------------------------------------------------------
# TrainTracer / atomic heartbeat
# ---------------------------------------------------------------------------


class TestTrainTracer:
    def test_jsonl_round_trip_on_manual_clock(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        clock = ManualClock(start=5.0)
        tracer = TrainTracer(JsonlSink(path), clock=clock)
        tracer.emit("run_start", step=0, arch="t", total_steps=3)
        clock.advance(1.0)
        tracer.emit("step", step=1, loss=2.5, skipme=None)
        tracer.emit("run_end", step=3, recoveries=0)
        tracer.close()
        evs = [json.loads(l) for l in path.read_text().splitlines()]
        assert [e["event"] for e in evs] == ["run_start", "step", "run_end"]
        assert [e["t"] for e in evs] == [5.0, 6.0, 6.0]
        assert evs[0]["arch"] == "t" and evs[0]["total_steps"] == 3
        assert evs[1]["step"] == 1 and "skipme" not in evs[1]  # None dropped
        assert tracer.events == 3


class TestAtomicWrite:
    def test_heartbeat_replaces_atomically(self, tmp_path):
        path = str(tmp_path / "hb")
        _write_atomic(path, "7")
        _write_atomic(path, "8")
        assert open(path).read() == "8"
        leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
        assert leftovers == []


# ---------------------------------------------------------------------------
# end-to-end: instrumented Trainer run
# ---------------------------------------------------------------------------


class TestInstrumentedRun:
    def test_probes_trace_history_heartbeat(self, tmp_path, monkeypatch):
        trace = tmp_path / "trace.jsonl"
        hist_path = tmp_path / "history.jsonl"
        hb = tmp_path / "heartbeat"
        tcfg = TrainerConfig(
            total_steps=3, log_every=10, ckpt_every=10**9,
            probes=True, sensitivity_every=2,
            trace_path=str(trace), history_path=str(hist_path),
            heartbeat_path=str(hb),
        )
        tr = Trainer(CFG, tcfg, _data_iter(CFG, 3))
        returned = tr.run()
        # history streamed to JSONL, not held on the host
        assert returned == [] and tr.history == []
        hist = [json.loads(l) for l in hist_path.read_text().splitlines()]
        assert [h["step"] for h in hist] == [0, 1, 2]
        for h in hist:
            for k in ("qat_clip_act", "qat_branch_share8", "qat_flip_attn",
                      "qat_flip_ffn1", "qat_clip_w8", "qat_gnorm_share8",
                      "qat_scale_drift_absmean", "qat_scale_drift_absmax"):
                assert k in h, k
                assert np.isfinite(h[k]), k
            assert 0.0 <= h["qat_clip_act"] <= 1.0
            assert 0.0 <= h["qat_branch_share8"] <= 1.0
        # democratization snapshot at the sensitivity_every cadence only
        assert "demo_score_ffn1" in hist[0] and "demo_score_ffn1" in hist[2]
        assert "demo_score_ffn1" not in hist[1]
        # lifecycle trace: run bracket + one record per step + heartbeat
        evs = [json.loads(l) for l in trace.read_text().splitlines()]
        kinds = [e["event"] for e in evs]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        assert kinds.count("step") == 3
        assert "heartbeat" in kinds  # step 0 hits log_every
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts)
        # crash-atomic heartbeat file holds the last completed step
        assert hb.read_text() == "2"
        assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
        # metrics snapshot: CI schema + the run's counters/gauges
        snap = json.loads(json.dumps(tr.snapshot()))
        validate_snapshot(snap)
        assert snap["counters"]["train_steps_total"] == 3
        assert snap["histograms"]["train_step_seconds"]["count"] == 3
        assert snap["gauges"]["train_step"] == 2
        assert np.isfinite(snap["gauges"]["train_loss"])
        assert "qat_clip_act" in snap["gauges"]
        assert "demo_score_ffn1" in snap["gauges"]
        text = tr.metrics.prometheus_text()
        assert "train_steps_total 3" in text

    def test_recovery_recorded_in_history_trace_metrics(self, tmp_path):
        trace = tmp_path / "trace.jsonl"
        with tempfile.TemporaryDirectory() as d:
            tcfg = TrainerConfig(total_steps=12, ckpt_every=5, ckpt_dir=d,
                                 log_every=1000, trace_path=str(trace))
            tr = Trainer(CFG, tcfg, _data_iter(CFG, 20))
            orig = tr.step_fn
            hits = {"n": 0}

            def poisoned(state, batch):
                state, m = orig(state, batch)
                hits["n"] += 1
                if hits["n"] == 8:  # past the (async) step-5 checkpoint
                    m = dict(m)
                    m["loss"] = jnp.asarray(float("nan"))
                return state, m

            tr.step_fn = poisoned
            hist = tr.run()
            assert tr.recoveries == 1
            recs = [h for h in hist if h.get("event") == "recovery"]
            assert len(recs) == 1
            assert recs[0]["from_step"] == 6 and recs[0]["recoveries"] == 1
            evs = [json.loads(l) for l in trace.read_text().splitlines()]
            kinds = [e["event"] for e in evs]
            assert "restore" in kinds and "recovery" in kinds
            rec_ev = next(e for e in evs if e["event"] == "recovery")
            assert rec_ev["from_step"] == 6 and rec_ev["recoveries"] == 1
            snap = tr.snapshot()
            assert snap["counters"]["train_recoveries_total"] == 1
            assert snap["counters"]["train_restores_total"] == 1
            assert snap["counters"]["train_checkpoints_total"] >= 2

    def test_probe_metrics_finite_for_baselines(self):
        """bitnet (no 8-bit branch) and fp (no quantizers) emit their
        reduced probe sets without error."""
        for mode, expect, absent in (
            ("bitnet", ("qat_flip_ffn1", "qat_clip_act"), ("qat_clip_w8",)),
            ("none", ("qat_flip_ffn1",), ("qat_clip_act", "qat_clip_w8")),
        ):
            qc = QuantConfig(mode=mode, r=0, num_experts=1)
            cfg = ModelConfig(name=f"t-{mode}", family="decoder", n_layers=1,
                              d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
                              vocab_size=64, quant=qc)
            state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
            step = jax.jit(make_train_step(cfg, 10, probes=True))
            _, metrics = step(state, _batch(cfg))
            for k in expect:
                assert k in metrics and np.isfinite(float(metrics[k])), (mode, k)
            for k in absent:
                assert k not in metrics, (mode, k)


# ---------------------------------------------------------------------------
# smoke artifacts (the pair CI validates and archives)
# ---------------------------------------------------------------------------


class TestBenchArtifacts:
    def test_stability_smoke_emits_validated_artifacts(self, tmp_path):
        from benchmarks import bench_stability

        metrics_out = tmp_path / "BENCH_train_metrics.json"
        trace_out = tmp_path / "BENCH_train_trace.jsonl"
        out = bench_stability.run(steps=4, smoke=True,
                                  metrics_out=str(metrics_out),
                                  trace_out=str(trace_out))
        assert set(out) == {"bitnet", "pquant"}
        snap = json.load(open(metrics_out))
        validate_snapshot(snap)
        assert snap["counters"]["train_steps_total"] > 0
        assert any(k.startswith("qat_") for k in snap["gauges"])
        evs = [json.loads(l) for l in trace_out.read_text().splitlines()]
        kinds = {e["event"] for e in evs}
        assert {"run_start", "step", "run_end"} <= kinds
