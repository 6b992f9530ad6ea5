"""Distribution tests: sharding rules in-process, plus multi-device tests
(quantized gather, gradient compression, sharded train step) in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the
main test process keeps its single CPU device."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    env["JAX_PLATFORMS"] = "cpu"  # the forced host devices; never a TPU
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestRules:
    def test_logical_to_spec(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        with sh.sharding_rules(mesh, None):
            spec = sh.logical_to_spec(("batch", None, "ffn"))
            assert spec == P("data", None, "model")

    def test_pod_axis_dropped_on_single_pod(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        with sh.sharding_rules(mesh, None):
            # batch -> (pod, data); pod missing on this mesh
            assert sh.logical_to_spec(("batch",)) == P("data")

    def test_overrides(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        with sh.sharding_rules(mesh, {"cache_seq": "data"}):
            assert sh.logical_to_spec(("cache_seq",)) == P("data")

    def test_no_mesh_noop(self):
        import jax.numpy as jnp

        x = jnp.ones((4, 4))
        assert sh.shard_hint(x, "batch", "ffn") is x

    def test_param_sharding_relaxes_indivisible(self):
        import jax.numpy as jnp

        mesh = make_mesh((1, 1), ("data", "model"))
        with sh.sharding_rules(mesh, None):
            tree = {"w": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
            axes = {"w": ("embed", "ffn")}
            out = sh.param_sharding_for(tree, axes, mesh)
            # dims divisible by 1 -> kept
            assert out["w"].spec == P("data", "model")


MULTIDEV_QGATHER = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import sharding_rules
from repro.distributed.qgather import binarize_gather
from repro.core.quantization import binarize_weights
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
w = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
ws = jax.device_put(w, NamedSharding(mesh, P("data", "model")))

with sharding_rules(mesh, None):
    f = jax.jit(lambda w: binarize_gather(w, ("embed", "ffn")))
    out = f(ws)
    # value check: equals plain binarization
    ref, _ = binarize_weights(w)
    ok_val = bool(np.allclose(np.asarray(out), np.asarray(ref), rtol=1e-5))
    # gradient check: STE passthrough, resharded back
    g = jax.jit(jax.grad(lambda w: jnp.sum(binarize_gather(w, ("embed", "ffn")) * 3.0)))(ws)
    ok_grad = bool(np.isfinite(np.asarray(g)).all())
    # int8 payload in the HLO
    hlo = f.lower(ws).compile().as_text()
    ok_int8 = ("all-gather" in hlo and "s8[" in hlo)
print(json.dumps({"ok_val": ok_val, "ok_grad": ok_grad, "ok_int8": ok_int8}))
"""


MULTIDEV_COMPRESSION = """
import jax, jax.numpy as jnp, numpy as np, json, functools
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.compression import compress_psum
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.1
res = jnp.zeros((8, 64))

@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P(), P("data")), check_vma=False)
def reduce_fn(g, r):
    mean, new_r = compress_psum(g[0], r[0], "data", chunk=16)
    return mean, new_r[None]

mean, new_res = reduce_fn(g, res)
true_mean = np.asarray(jnp.mean(g, axis=0))
err = np.abs(np.asarray(mean) - true_mean).max()
scale = np.abs(true_mean).max()
# error feedback: residual equals what was not transmitted
ok_res = bool(np.isfinite(np.asarray(new_res)).all())
print(json.dumps({"rel_err": float(err / (scale + 1e-9)), "ok_res": ok_res}))
"""


MULTIDEV_TRAIN = """
import jax, jax.numpy as jnp, numpy as np, json, dataclasses
from repro.configs.registry import get_config, reduced
from repro.distributed.sharding import sharding_rules, param_sharding_for
from repro.train.trainer import make_train_step, init_train_state
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=4, model=2)
cfg = reduced(get_config("pquant-300m"))
with sharding_rules(mesh, None):
    state, axes = init_train_state(jax.random.PRNGKey(0), cfg)
    st_sh = param_sharding_for(state, axes, mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size)}
    b_sh = param_sharding_for(batch, {"tokens": ("batch", None), "labels": ("batch", None)}, mesh)
    batch = jax.device_put(batch, b_sh)
    state = jax.device_put(state, st_sh)
    step = jax.jit(make_train_step(cfg, 10), in_shardings=(st_sh, b_sh))
    new_state, metrics = step(state, batch)
    # compare against single-device result
loss_sharded = float(metrics["loss"])
state1, _ = init_train_state(jax.random.PRNGKey(0), cfg)
batch1 = jax.tree.map(lambda x: jax.device_put(np.asarray(x)), batch)
step1 = jax.jit(make_train_step(cfg, 10))
_, m1 = step1(state1, batch1)
print(json.dumps({"sharded": loss_sharded, "single": float(m1["loss"])}))
"""


@pytest.mark.slow
class TestMultiDevice:
    def test_quantized_gather(self):
        out = run_subprocess(MULTIDEV_QGATHER)
        assert out["ok_val"] and out["ok_grad"] and out["ok_int8"]

    def test_gradient_compression_psum(self):
        out = run_subprocess(MULTIDEV_COMPRESSION)
        assert out["rel_err"] < 0.05 and out["ok_res"]

    def test_sharded_train_step_matches_single_device(self):
        out = run_subprocess(MULTIDEV_TRAIN)
        assert abs(out["sharded"] - out["single"]) / abs(out["single"]) < 1e-3
