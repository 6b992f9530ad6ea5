"""Decode GEMV tier vs references: fused-act-quant kernels in interpret
mode against the quantize_act + matmul oracle path, ragged-M dispatch
through ops, and the tile dispatch/autotune table."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import export_bit_weight, pack_signs, padded_k
from repro.kernels import ops, ref
from repro.kernels.w1a8_gemv import decoupled_gemv, w1a8_gemv

TOL = 1e-4  # acceptance: max abs error vs the reference path


def _inputs(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    signs = np.where(rng.random((k, n)) > 0.5, 1, -1).astype(np.int8)
    wp = pack_signs(jnp.asarray(signs))
    lam = jnp.asarray(np.float32(0.042))
    return x, wp, lam


GEMV_CASES = [
    # (m, k, n, bk, bn)
    (8, 64, 64, 64, 32),
    (8, 256, 512, 128, 256),
    (16, 512, 256, 512, 128),
    (32, 128, 384, 64, 128),
    (8, 256, 512, 256, 512),  # single-tile N and K
]


@pytest.mark.parametrize("m,k,n,bk,bn", GEMV_CASES)
def test_w1a8_gemv_vs_ref(m, k, n, bk, bn):
    x, wp, lam = _inputs(m, k, n, seed=m + k + n)
    got = w1a8_gemv(x, wp, lam, bk=bk, bn=bn, interpret=True)
    want = ref.w1a8_gemv_ref(x, wp, lam)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= TOL


def test_w1a8_gemv_fused_quant_matches_xla_pass():
    """The in-kernel prologue quantization equals the separate XLA pass +
    prefill kernel route on the same inputs."""
    x, wp, lam = _inputs(8, 256, 256, seed=7)
    got = w1a8_gemv(x, wp, lam, bk=128, bn=128, interpret=True)
    xq, gamma = ops.quantize_act_int8(x)
    want = ref.w1a8_matmul_ref(xq, wp, gamma, lam)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= TOL


@pytest.mark.parametrize("m", [1, 3, 17])
def test_decode_dispatch_ragged_m(m):
    """ops.bit_linear_infer routes M <= 32 to the GEMV tier; ragged rows are
    padded to the 8-row sublane minimum, never to 128."""
    x, wp, lam = _inputs(m, 64, 96, seed=m)
    y = ops.bit_linear_infer(x, wp, lam, out_dtype=jnp.float32)
    want = ref.w1a8_gemv_ref(x, wp, lam)
    assert y.shape == (m, 96)
    assert np.isfinite(np.asarray(y)).all()
    assert np.abs(np.asarray(y) - np.asarray(want)).max() <= TOL


def test_decode_and_prefill_tiers_agree():
    """Both ops tiers compute the same linear for a decode shape."""
    x, wp, lam = _inputs(4, 128, 64, seed=11)
    y_dec = ops._bit_linear_decode(x, wp, lam, jnp.float32)
    y_pre = ops._bit_linear_prefill(x, wp, lam, jnp.float32)
    assert np.abs(np.asarray(y_dec) - np.asarray(y_pre)).max() <= TOL


@pytest.mark.parametrize("m,k,n,r", [(8, 256, 512, 64), (16, 128, 256, 32)])
def test_decoupled_gemv_vs_ref(m, k, n, r):
    x, wp, lam = _inputs(m, k, n, seed=r)
    rng = np.random.default_rng(r)
    w8 = jnp.asarray(rng.integers(-127, 128, (k, r)).astype(np.int8))
    w8s, alpha, beta = (jnp.asarray(np.float32(v)) for v in (2.1, 0.05, 0.2))
    y1, y8 = decoupled_gemv(
        x, wp, w8, lam, w8s, alpha, beta, bk=128, bn=128, interpret=True
    )
    r1, r8 = ref.decoupled_gemv_ref(x, wp, w8, lam, w8s, alpha, beta)
    assert np.abs(np.asarray(y1) - np.asarray(r1)).max() <= TOL
    assert np.abs(np.asarray(y8) - np.asarray(r8)).max() <= TOL


@pytest.mark.parametrize("m", [1, 3, 17])
def test_decoupled_dispatch_ragged_m(m):
    k, n, r = 64, 128, 16
    x, wp, lam = _inputs(m, k, n, seed=m + 1)
    rng = np.random.default_rng(m)
    w8 = jnp.asarray(rng.integers(-127, 128, (k, r)).astype(np.int8))
    w8s, alpha, beta = (jnp.asarray(np.float32(v)) for v in (1.7, 0.1, 0.3))
    y1, y8 = ops.decoupled_first_gemm(
        x, wp, w8, lam, w8s, alpha, beta, out_dtype=jnp.float32
    )
    r1, r8 = ref.decoupled_gemv_ref(x, wp, w8, lam, w8s, alpha, beta)
    assert y1.shape == (m, n) and y8.shape == (m, r)
    assert np.abs(np.asarray(y1) - np.asarray(r1)).max() <= TOL
    assert np.abs(np.asarray(y8) - np.asarray(r8)).max() <= TOL


@pytest.mark.parametrize("m", [4, 40])
def test_decoupled_dispatch_padded_k(m):
    """A K the serving export pads (320 -> 512), decode and prefill tier:
    the padded sign rows and the dispatcher's zero 8-bit rows meet zero
    activation columns, so both branches equal the unpadded computation."""
    from repro.train.quantized_serving import _binarize_export

    k, n, r = 320, 128, 16
    rng = np.random.default_rng(m)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    e = _binarize_export(jnp.asarray(rng.standard_normal((k, n)), jnp.float32),
                         packed=True)
    assert e["packed"].shape[0] * 8 == padded_k(k) > k
    lam = e["scale"].reshape(())
    w8 = jnp.asarray(rng.integers(-127, 128, (k, r)).astype(np.int8))
    w8s, alpha, beta = (jnp.asarray(np.float32(v)) for v in (1.7, 0.1, 0.3))
    y1, y8 = ops.decoupled_first_gemm(
        x, e["packed"], w8, lam, w8s, alpha, beta, out_dtype=jnp.float32
    )
    unpadded = pack_signs(ref.unpack_ref(e["packed"])[:k])
    r1, r8 = ref.decoupled_gemv_ref(x, unpadded, w8, lam, w8s, alpha, beta)
    assert np.abs(np.asarray(y1) - np.asarray(r1)).max() <= TOL
    assert np.abs(np.asarray(y8) - np.asarray(r8)).max() <= TOL


def test_bit_linear_infer_3d_decode_shape():
    """(B, 1, K) decode activations flatten to M = B rows for dispatch."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 1, 64)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32) * 0.1)
    pw = export_bit_weight(w)
    y = ops.bit_linear_infer(x, pw.packed, pw.lam, out_dtype=jnp.float32)
    assert y.shape == (4, 1, 32)
    yref = ref.w1a8_gemv_ref(x.reshape(4, 64), pw.packed, pw.lam)
    assert np.abs(np.asarray(y).reshape(4, 32) - np.asarray(yref)).max() <= TOL


class TestTileDispatch:
    @pytest.fixture(autouse=True)
    def _isolate_tile_cache(self):
        """Sweeps mutate the process-global cache; keep tests order-free."""
        saved = dict(ops._DECODE_TILE_CACHE)
        yield
        ops._DECODE_TILE_CACHE.clear()
        ops._DECODE_TILE_CACHE.update(saved)

    def test_heuristic_divides(self):
        for (m, k, n) in [(8, 64, 96), (8, 4096, 11008), (32, 48, 56)]:
            bk, bn = ops.decode_tiles(m, k, n)
            assert k % bk == 0 and n % bn == 0 and bk % 8 == 0

    def test_sweep_caches_and_wins_are_used(self):
        best = ops.sweep_decode_tiles(8, 64, 64, warmup=0, iters=1)
        assert ops._DECODE_TILE_CACHE[("w1a8_gemv", 8, 64, 64)] == best
        assert ops.decode_tiles(8, 64, 64) == best
        k, n = 64, 64
        bk, bn = best
        assert k % bk == 0 and n % bn == 0
        # the swept signature still computes correctly through the dispatcher
        x, wp, lam = _inputs(8, 64, 64, seed=2)
        y = ops.bit_linear_infer(x, wp, lam, out_dtype=jnp.float32)
        want = ref.w1a8_gemv_ref(x, wp, lam)
        assert np.abs(np.asarray(y) - np.asarray(want)).max() <= TOL

    def test_sweep_pads_m_to_dispatch_shape(self):
        """A sweep for an unpadded batch (e.g. 4) must land on the 8-padded
        signature _bit_linear_decode actually looks up."""
        best = ops.sweep_decode_tiles(4, 64, 32, warmup=0, iters=1)
        assert ("w1a8_gemv", 8, 64, 32) in ops._DECODE_TILE_CACHE
        assert ops.decode_tiles(8, 64, 32) == best

    def test_sweep_decoupled_op(self):
        best = ops.sweep_decode_tiles(
            8, 64, 64, op="decoupled_gemv", r=16, warmup=0, iters=1
        )
        assert ops._DECODE_TILE_CACHE[("decoupled_gemv", 8, 64, 64, 16)] == best
        assert best[1] >= 16  # bn fits the 8-bit branch
        assert ops.decode_tiles(8, 64, 64, op="decoupled_gemv", r=16) == best
        # a different branch width is a different signature, not a hit
        assert ("decoupled_gemv", 8, 64, 64, 32) not in ops._DECODE_TILE_CACHE
