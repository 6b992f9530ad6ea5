"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e
(no chip attached): Mosaic refuses shapes the interpreter accepts, so
each kernel is lowered at the paper's widths with the tiles ``ops``
dispatches and must come out as a ``tpu_custom_call``.

The topology is described inside a module fixture (never at import): only
the test worker given this file loads the TPU compiler, and where it
cannot be described every test here skips."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import padded_k
from repro.kernels import ops
from repro.kernels.decoupled_matmul import decoupled_matmul
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm_quant import rmsnorm_quant
from repro.kernels.w1a8_gemv import decoupled_gemv, w1a8_gemv
from repro.kernels.w1a8_matmul import w1a8_matmul

F32, BF16, I8, U8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint8, jnp.int32

# pquant-1.3b: d_model 2048, d_ff 5024, r 384, 32 heads of 64;
# pquant-2.6b: d_model 2880, 36 heads of 80
D13, F13, R13 = 2048, 5024, 384
D26, H26, HD26 = 2880, 36, 80
WIDTHS = [(D13, D13), (D13, F13), (F13, D13), (F13, F13)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return ops.pallas_kernels(text)


def _tiles(k, n, r=None):
    # the heuristic tiles, not ``decode_tiles``: a swept winner cached on
    # this host's backend says nothing about what Mosaic accepts
    return ops._k_tile(k), ops._n_tile(n, r)


@pytest.mark.parametrize("m", [8, 32])
@pytest.mark.parametrize("k,n", WIDTHS)
def test_gemv_pair_compiles(one_chip, k, n, m):
    kp = padded_k(k)
    bk, bn = _tiles(kp, n)
    gemv = functools.partial(w1a8_gemv, bk=bk, bn=bn, out_dtype=BF16)
    assert _compile(one_chip, gemv, ((m, kp), BF16), ((kp // 8, n), U8),
                    ((), F32)) == {"w1a8_gemv"}
    bk, bn = _tiles(kp, n, r=R13)
    dec = functools.partial(decoupled_gemv, bk=bk, bn=bn, out_dtype=BF16)
    assert _compile(
        one_chip, dec, ((m, kp), BF16), ((kp // 8, n), U8), ((kp, R13), I8),
        ((), F32), ((), F32), ((), F32), ((), F32),
    ) == {"decoupled_gemv"}


@pytest.mark.parametrize("k,n", WIDTHS)
def test_prefill_pair_compiles(one_chip, k, n):
    m, kp = 512, padded_k(k)
    bk, bn = _tiles(kp, n)
    mm = functools.partial(w1a8_matmul, bm=128, bk=bk, bn=bn, out_dtype=BF16)
    assert _compile(one_chip, mm, ((m, kp), I8), ((kp // 8, n), U8),
                    ((m,), F32), ((), F32)) == {"w1a8_matmul"}
    bk, bn = _tiles(kp, n, r=R13)
    dec = functools.partial(decoupled_matmul, bm=128, bk=bk, bn=bn,
                            out_dtype=BF16)
    assert _compile(
        one_chip, dec, ((m, kp), I8), ((kp // 8, n), U8), ((kp, R13), I8),
        ((m,), F32), ((), F32), ((), F32), ((), F32), ((), F32),
    ) == {"decoupled_matmul"}


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("k,n", [(D13, R13), (R13, D13)])
def test_int8_matmul_compiles(one_chip, k, n, m):
    bk, bn = ops._prefill_tiles(k, n, k_candidates=ops._BK_INT8_CANDIDATES)
    mm = functools.partial(int8_matmul, bm=min(m, 128), bk=bk, bn=bn,
                           out_dtype=BF16)
    assert _compile(one_chip, mm, ((m, k), I8), ((k, n), I8), ((m,), F32),
                    ((), F32)) == {"int8_matmul"}


@pytest.mark.parametrize("m", [8, 256])
def test_rmsnorm_quant_compiles(one_chip, m):
    fn = functools.partial(rmsnorm_quant, bm=min(m, 256))
    assert _compile(one_chip, fn, ((m, D13), BF16), ((D13,), F32)) == {
        "rmsnorm_quant"}


def _paged(one_chip, t, hq, hkv, d, bs=16, b=8, mb=32, nb=512):
    pool = ((nb, hkv, bs, d), BF16)
    fn = functools.partial(
        paged_attention, pages=ops.paged_tiles(t, hq, hkv, d, bs, mb))
    return _compile(one_chip, fn, ((b, t, hq, d), BF16), pool, pool,
                    ((b, mb), I32), ((b,), I32), ((b,), I32))


@pytest.mark.parametrize("t", [1, 16])
def test_paged_attention_compiles(one_chip, t):
    assert _paged(one_chip, t, 32, 32, 64) == {"paged_attention"}


def test_gemv_compiles_at_2_6b_width(one_chip):
    kp = padded_k(D26)
    bk, bn = _tiles(kp, D26)
    gemv = functools.partial(w1a8_gemv, bk=bk, bn=bn, out_dtype=BF16)
    assert _compile(one_chip, gemv, ((8, kp), BF16), ((kp // 8, D26), U8),
                    ((), F32)) == {"w1a8_gemv"}


@pytest.mark.parametrize("t", [1, 16])
def test_paged_attention_compiles_at_head_dim_80(one_chip, t):
    assert _paged(one_chip, t, H26, H26, HD26) == {"paged_attention"}
