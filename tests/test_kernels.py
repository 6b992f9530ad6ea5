"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import export_bit_weight, pack_signs
from repro.kernels import ops, ref
from repro.kernels.decoupled_matmul import decoupled_matmul
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.rmsnorm_quant import rmsnorm_quant
from repro.kernels.w1a8_matmul import w1a8_matmul

RNG = np.random.default_rng(0)


def _inputs(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(-127, 128, (m, k)).astype(np.int8)
    signs = np.where(rng.random((k, n)) > 0.5, 1, -1).astype(np.int8)
    wp = np.asarray(pack_signs(jnp.asarray(signs)))
    gamma = (rng.random(m) + 0.5).astype(np.float32)
    lam = np.float32(0.042)
    return jnp.asarray(x), jnp.asarray(wp), jnp.asarray(gamma), jnp.asarray(lam)


W1A8_CASES = [
    # (m, k, n, bm, bk, bn)
    (8, 16, 8, 8, 16, 8),
    (8, 256, 128, 8, 128, 128),
    (128, 256, 256, 128, 256, 256),
    (64, 512, 128, 32, 256, 128),
    (256, 1024, 512, 128, 512, 256),
    (16, 128, 384, 8, 64, 128),
]


@pytest.mark.parametrize("m,k,n,bm,bk,bn", W1A8_CASES)
def test_w1a8_vs_ref(m, k, n, bm, bk, bn):
    x, wp, gamma, lam = _inputs(m, k, n, seed=m + k + n)
    got = w1a8_matmul(x, wp, gamma, lam, bm=bm, bk=bk, bn=bn, interpret=True)
    want = ref.w1a8_matmul_ref(x, wp, gamma, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_w1a8_out_dtypes(out_dtype):
    x, wp, gamma, lam = _inputs(16, 64, 32)
    got = w1a8_matmul(x, wp, gamma, lam, bm=8, bk=64, bn=32,
                      out_dtype=out_dtype, interpret=True)
    want = ref.w1a8_matmul_ref(x, wp, gamma, lam, out_dtype=out_dtype)
    assert got.dtype == out_dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-2
    )


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (128, 256, 128), (32, 512, 256)])
def test_int8_vs_ref(m, k, n):
    rng = np.random.default_rng(m + n)
    x = jnp.asarray(rng.integers(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (k, n)).astype(np.int8))
    gamma = jnp.asarray((rng.random(m) + 0.5).astype(np.float32))
    ws = jnp.asarray(np.float32(3.7))
    got = int8_matmul(x, w, gamma, ws, bm=min(128, m), bk=min(256, k),
                      bn=min(256, n), interpret=True)
    want = ref.int8_matmul_ref(x, w, gamma, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("m,d", [(8, 64), (256, 128), (32, 512), (64, 96)])
def test_rmsnorm_quant_vs_ref(m, d):
    rng = np.random.default_rng(m + d)
    x = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    sc = jnp.asarray((rng.random(d) + 0.5).astype(np.float32))
    q, g = rmsnorm_quant(x, sc, bm=min(256, m), interpret=True)
    qr, gr = ref.rmsnorm_quant_ref(x, sc)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5)
    # rounding at exactly .5 may differ by 1 ulp between paths
    assert (np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32)) <= 1).all()


@pytest.mark.parametrize("m,k,n,r", [(8, 16, 16, 8), (64, 256, 512, 128), (16, 512, 256, 64)])
def test_decoupled_vs_ref(m, k, n, r):
    x, wp, gamma, lam = _inputs(m, k, n, seed=r)
    rng = np.random.default_rng(r)
    w8 = jnp.asarray(rng.integers(-127, 128, (k, r)).astype(np.int8))
    w8s, alpha, beta = (jnp.asarray(np.float32(v)) for v in (2.1, 2.0, 0.2))
    y1, y8 = decoupled_matmul(
        x, wp, w8, gamma, lam, w8s, alpha, beta,
        bm=min(128, m), bk=min(256, k), bn=max(min(256, n), r), interpret=True,
    )
    r1, r8 = ref.decoupled_matmul_ref(x, wp, w8, gamma, lam, w8s, alpha, beta)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(r1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(r8), rtol=1e-5)


class TestOpsEndToEnd:
    def test_bit_linear_infer_matches_fake_quant(self):
        """The true-integer serving path equals the dequantized matmul."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((5, 256)).astype(np.float32) * 0.4)
        w = jnp.asarray(rng.standard_normal((256, 128)).astype(np.float32) * 0.03)
        pw = export_bit_weight(w)
        y = ops.bit_linear_infer(x, pw.packed, pw.lam, out_dtype=jnp.float32)
        yref = jnp.asarray(x) @ pw.dequantize()
        rel = np.abs(np.asarray(y) - np.asarray(yref)).max() / (
            np.abs(np.asarray(yref)).max() + 1e-9
        )
        assert rel < 2e-2  # activation-quant noise only

    def test_ragged_rows_padded(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((3, 64)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32) * 0.1)
        pw = export_bit_weight(w)
        y = ops.bit_linear_infer(x, pw.packed, pw.lam)
        assert y.shape == (3, 32)
        assert np.isfinite(np.asarray(y, np.float32)).all()

    def test_fused_rmsnorm_quant_3d(self):
        x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 7, 64)), jnp.float32)
        sc = jnp.ones((64,), jnp.float32)
        q, g = ops.fused_rmsnorm_quant(x, sc)
        assert q.shape == x.shape and g.shape == (2, 7)
