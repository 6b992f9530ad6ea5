"""Tensor-parallel serving: the 1-device mesh must be bit-for-bit the
meshless engine (both cache layouts, one-shot and chunked admission
prefill, greedy decode), N-major shards of packed weights must round-trip
through pack/unpack with the replicated per-tensor scales, and a forced
2-device CPU mesh must reproduce the single-device token streams (the
column-parallel design never splits a K reduction, so even multi-device
decode is token-exact on these sizes)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import packing
from repro.core.quantization import QuantConfig
from repro.distributed import sharding as sh
from repro.launch.mesh import make_host_mesh, mesh_from_env
from repro.models import api
from repro.serve.engine import DecodeEngine, SamplerConfig, serving_overrides
from repro.serve.scheduler import ContinuousBatchingEngine
from repro.train.quantized_serving import quantize_params_for_serving

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

QC = QuantConfig(mode="pquant", r=16, num_experts=1)
CFG = ModelConfig(name="t", family="decoder", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=48, vocab_size=64, quant=QC)
MAX_LEN = 32
GREEDY = SamplerConfig(temperature=0.0, max_new_tokens=6)


@pytest.fixture(scope="module")
def params():
    return api.init_model(jax.random.PRNGKey(1), CFG)[0]


@pytest.fixture(scope="module")
def qparams(params):
    _, axes = api.params_shape_and_axes(CFG)
    return quantize_params_for_serving(params, axes, CFG, packed=True)[0]


def _prompt(seed, n=5):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 64), np.int32
    )


class TestNMajorRoundTrip:
    """Sharding a packed weight N-major (last axis) with the replicated
    per-tensor scale must reconstruct the unsharded dequantization — this
    is the invariant that makes column-parallel serving exact."""

    def test_bit_packed_shards_roundtrip(self):
        w = np.asarray(
            jax.random.normal(jax.random.PRNGKey(0), (32, 48), jnp.float32)
        )
        exp = packing.export_bit_weight(jnp.asarray(w))
        full = np.asarray(exp.dequantize())
        for ws in (2, 4):  # every shard dequantizes with the SAME lam
            shards = np.split(np.asarray(exp.packed), ws, axis=-1)
            got = np.concatenate(
                [
                    np.asarray(packing.unpack_signs(jnp.asarray(s)))
                    * float(exp.lam)
                    for s in shards
                ],
                axis=-1,
            )
            np.testing.assert_array_equal(got, full)

    def test_int8_shards_roundtrip(self):
        w = np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (32, 48), jnp.float32)
        )
        exp = packing.export_int8_weight(jnp.asarray(w))
        full = np.asarray(exp.dequantize())
        shards = np.split(np.asarray(exp.q), 4, axis=-1)
        got = np.concatenate(
            [s.astype(np.float32) / float(exp.scale) for s in shards], axis=-1
        )
        np.testing.assert_array_equal(got, full)

    def test_nmajor_axis_gates_on_divisibility(self):
        mesh = make_host_mesh(1, 1)
        with sh.sharding_rules(mesh, None):
            # size-1 axis -> no island, 1-device lowering stays identical
            assert sh.nmajor_axis(48, "ffn") is None
        assert sh.nmajor_axis(48, "ffn") is None  # no active mesh


class TestServingOverrides:
    def test_indivisible_heads_replicate(self):
        mesh = make_host_mesh(1, 1)
        odd = ModelConfig(name="o", family="decoder", n_layers=1, d_model=30,
                          n_heads=3, n_kv_heads=3, d_ff=48, vocab_size=64,
                          quant=QC)
        ov = serving_overrides(odd, mesh)
        # model axis is size 1 here, so no relaxation is needed
        assert "kv_heads" not in ov and ov["batch"] is None

    def test_mesh_env_parsing(self, monkeypatch):
        monkeypatch.setenv("REPRO_MESH", "1,1")
        assert dict(mesh_from_env().shape) == {"data": 1, "model": 1}
        monkeypatch.setenv("REPRO_MESH", "bogus")
        with pytest.raises(ValueError):
            mesh_from_env()
        monkeypatch.delenv("REPRO_MESH")
        assert mesh_from_env() is None

    def test_oversubscribed_mesh_raises(self):
        with pytest.raises(ValueError):
            make_host_mesh(data=jax.device_count() + 1, model=2)


class TestOneDeviceMeshParity:
    """Acceptance: mesh=(1,1) is bit-for-bit the meshless engine."""

    @pytest.mark.parametrize("packed", [False, True])
    def test_decode_engine_bitwise(self, params, qparams, packed):
        p = qparams if packed else params
        mesh = make_host_mesh(1, 1)
        ref = DecodeEngine(p, CFG, MAX_LEN)
        got = DecodeEngine(p, CFG, MAX_LEN, mesh=mesh)
        prompt = jnp.asarray(_prompt(7)[None])
        np.testing.assert_array_equal(
            got.generate(prompt, GREEDY, seed=0),
            ref.generate(prompt, GREEDY, seed=0),
        )
        # sampled decode shares the PRNG stream (replicated), so it must
        # match too
        scfg = SamplerConfig(temperature=0.7, top_k=10, max_new_tokens=6)
        np.testing.assert_array_equal(
            got.generate(prompt, scfg, seed=3),
            ref.generate(prompt, scfg, seed=3),
        )

    def test_decode_engine_stream_bitwise(self, qparams):
        mesh = make_host_mesh(1, 1)
        ref = DecodeEngine(qparams, CFG, MAX_LEN)
        got = DecodeEngine(qparams, CFG, MAX_LEN, mesh=mesh)
        prompt = jnp.asarray(_prompt(9)[None])
        a = np.concatenate(
            list(ref.generate_stream(prompt, GREEDY, chunk=3, seed=0)), axis=1
        )
        b = np.concatenate(
            list(got.generate_stream(prompt, GREEDY, chunk=3, seed=0)), axis=1
        )
        np.testing.assert_array_equal(b, a)

    @pytest.mark.parametrize("prefill_chunk", [None, 3])
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_continuous_bitwise(self, qparams, layout, prefill_chunk):
        mesh = make_host_mesh(1, 1)
        kw = dict(num_slots=2, max_len=MAX_LEN, scfg=GREEDY, layout=layout,
                  block_size=8, chunk=4, prefill_chunk=prefill_chunk)
        ref = ContinuousBatchingEngine(qparams, CFG, **kw)
        got = ContinuousBatchingEngine(qparams, CFG, mesh=mesh, **kw)
        for eng in (ref, got):
            for uid, n in ((0, 5), (1, 7)):
                eng.submit(_prompt(uid + 10, n), max_new_tokens=6,
                           seed=uid, uid=uid)
        want = {f.uid: f.tokens for f in ref.run()}
        have = {f.uid: f.tokens for f in got.run()}
        assert want.keys() == have.keys()
        for uid in want:
            np.testing.assert_array_equal(have[uid], want[uid])

    def test_mesh_gauges_exported(self, qparams):
        eng = ContinuousBatchingEngine(
            qparams, CFG, num_slots=2, max_len=MAX_LEN, scfg=GREEDY,
            layout="paged", block_size=8, chunk=4, mesh=make_host_mesh(1, 1),
        )
        snap = eng.metrics.snapshot()
        assert snap["gauges"]["mesh_data_parallelism"] == 1.0
        assert snap["gauges"]["mesh_model_parallelism"] == 1.0


@pytest.mark.slow
class TestMultiDevice:
    """Forced 2-device CPU mesh: weights genuinely shard, kernel islands
    agree with the unsharded kernels, and token streams match the
    single-device engines."""

    def _run(self, code: str, **extra_env) -> dict:
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = REPO_SRC
        env["JAX_PLATFORMS"] = "cpu"  # the forced host devices; never a TPU
        env.update(extra_env)
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(code)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_two_device_parity(self):
        res = self._run("""
            import json
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ModelConfig
            from repro.core.quantization import QuantConfig
            from repro.distributed import sharding as sh
            from repro.kernels import ops
            from repro.launch.mesh import make_host_mesh
            from repro.models import api
            from repro.serve.engine import DecodeEngine, SamplerConfig
            from repro.serve.scheduler import ContinuousBatchingEngine
            from repro.train.quantized_serving import (
                quantize_params_for_serving,
            )

            assert jax.device_count() == 2
            qc = QuantConfig(mode="pquant", r=16, num_experts=1)
            cfg = ModelConfig(name="t", family="decoder", n_layers=2,
                              d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
                              vocab_size=64, quant=qc)
            params, axes = api.init_model(jax.random.PRNGKey(1), cfg)
            qp, _ = quantize_params_for_serving(params, axes, cfg,
                                                packed=True)
            mesh = make_host_mesh(1, 2)
            scfg = SamplerConfig(temperature=0.0, max_new_tokens=6)
            prompt = np.asarray(jax.random.randint(
                jax.random.PRNGKey(7), (5,), 0, 64), np.int32)

            # kernel islands vs unsharded kernels
            x = jax.random.normal(jax.random.PRNGKey(2), (4, 32),
                                  jnp.float32)
            from repro.core.packing import export_bit_weight
            exp = export_bit_weight(
                jax.random.normal(jax.random.PRNGKey(3), (32, 48),
                                  jnp.float32))
            lam = exp.lam.reshape(1, 1)
            with sh.sharding_rules(mesh, None):
                a = ops.bit_linear_infer(x, exp.packed, lam)
                b = ops.bit_linear_infer_nshard(x, exp.packed, lam, "model")
            island_ok = bool(np.allclose(np.asarray(a), np.asarray(b),
                                         atol=1e-5))

            ref = DecodeEngine(qp, cfg, 32)
            eng = DecodeEngine(qp, cfg, 32, mesh=mesh)
            n_sharded = sum(
                1 for leaf in jax.tree_util.tree_leaves(eng.params)
                if any(s is not None
                       for s in getattr(leaf.sharding, "spec", ()))
            )
            a = ref.generate(jnp.asarray(prompt[None]), scfg, seed=0)
            b = eng.generate(jnp.asarray(prompt[None]), scfg, seed=0)
            decode_ok = bool(np.array_equal(a, b))

            kw = dict(num_slots=2, max_len=32, scfg=scfg, layout="paged",
                      block_size=8, chunk=4, prefill_chunk=3)
            e0 = ContinuousBatchingEngine(qp, cfg, **kw)
            e1 = ContinuousBatchingEngine(qp, cfg, mesh=mesh, **kw)
            for e in (e0, e1):
                e.submit(prompt, max_new_tokens=6, seed=0, uid=0)
            f0 = {f.uid: f.tokens for f in e0.run()}
            f1 = {f.uid: f.tokens for f in e1.run()}
            cb_ok = all(np.array_equal(f0[u], f1[u]) for u in f0)
            pool_sharded = sum(
                1 for leaf in jax.tree_util.tree_leaves(e1._caches)
                if any(s is not None
                       for s in getattr(leaf.sharding, "spec", ()))
            )
            print(json.dumps({
                "island_ok": island_ok, "decode_ok": decode_ok,
                "cb_ok": bool(cb_ok), "n_sharded": n_sharded,
                "pool_sharded": pool_sharded,
            }))
        """)
        assert res["island_ok"], "nshard kernel island diverged"
        assert res["decode_ok"], "2-device DecodeEngine tokens diverged"
        assert res["cb_ok"], "2-device continuous engine tokens diverged"
        assert res["n_sharded"] > 0, "no weight leaf actually sharded"
        assert res["pool_sharded"] > 0, "no KV pool leaf actually sharded"

    def test_two_device_paged_attention_island(self):
        """The paged-attention kernel (interpret mode) run per KV-head
        shard must be bitwise the unsharded kernel, in decode (T=1) and
        chunk (T>1) form, and the continuous engine on the kernel path
        must keep the single-device token streams."""
        res = self._run("""
            import json
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs.base import ModelConfig
            from repro.core.quantization import QuantConfig
            from repro.distributed import sharding as sh
            from repro.kernels import ops
            from repro.launch.mesh import make_host_mesh
            from repro.models import api
            from repro.serve.engine import SamplerConfig, serving_overrides
            from repro.serve.scheduler import ContinuousBatchingEngine
            from repro.train.quantized_serving import (
                quantize_params_for_serving,
            )

            assert jax.device_count() == 2 and ops.paged_attention_enabled()
            qc = QuantConfig(mode="pquant", r=16, num_experts=1)
            cfg = ModelConfig(name="t", family="decoder", n_layers=2,
                              d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
                              vocab_size=64, quant=qc)
            mesh = make_host_mesh(1, 2)
            ov = serving_overrides(cfg, mesh)

            # kernel island vs the unsharded kernel: 2 slots, 8 pages of 8
            b, nb, bs, hkv, hq, d = 2, 8, 8, 2, 4, 16
            ks = jax.random.split(jax.random.PRNGKey(4), 3)
            kpool = jax.random.normal(ks[0], (nb, hkv, bs, d))
            vpool = jax.random.normal(ks[1], (nb, hkv, bs, d))
            table = jnp.asarray([[3, 0, 6, 1], [5, 2, 7, 4]], jnp.int32)
            pool_sh = NamedSharding(mesh, P(None, "model", None, None))
            island_ok = {}
            for t in (1, 5):
                q = jax.random.normal(ks[2], (b, t, hq, d))
                start = jnp.asarray([20, 9], jnp.int32)
                kv_lens = start + t
                want = ops.paged_attention(q, kpool, vpool, table, start,
                                           kv_lens)
                with sh.sharding_rules(mesh, ov):
                    got = jax.jit(ops.paged_attention)(
                        q, jax.device_put(kpool, pool_sh),
                        jax.device_put(vpool, pool_sh), table, start,
                        kv_lens)
                island_ok[t] = bool(np.array_equal(np.asarray(got),
                                                   np.asarray(want)))

            params, axes = api.init_model(jax.random.PRNGKey(1), cfg)
            qp, _ = quantize_params_for_serving(params, axes, cfg,
                                                packed=True)
            scfg = SamplerConfig(temperature=0.0, max_new_tokens=6)
            kw = dict(num_slots=2, max_len=32, scfg=scfg, layout="paged",
                      block_size=8, chunk=4, prefill_chunk=3)
            e0 = ContinuousBatchingEngine(qp, cfg, **kw)
            e1 = ContinuousBatchingEngine(qp, cfg, mesh=mesh, **kw)
            for e in (e0, e1):
                for uid in range(2):
                    prompt = np.asarray(jax.random.randint(
                        jax.random.PRNGKey(7 + uid), (5 + 4 * uid,), 0, 64),
                        np.int32)
                    e.submit(prompt, max_new_tokens=6, seed=uid, uid=uid)
            f0 = {f.uid: f.tokens for f in e0.run()}
            f1 = {f.uid: f.tokens for f in e1.run()}
            cb_ok = all(np.array_equal(f0[u], f1[u]) for u in f0)
            print(json.dumps({"island_ok": island_ok, "cb_ok": bool(cb_ok)}))
        """, REPRO_PAGED_ATTN="1")
        assert res["island_ok"] == {"1": True, "5": True}, res
        assert res["cb_ok"], "2-device kernel-path engine tokens diverged"
