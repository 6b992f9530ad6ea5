"""W8A8 matmul Pallas kernel — the decoupled layer's high-precision branch.

Straight int8 x int8 -> int32 MXU matmul with the per-token activation
scale (gamma) and per-tensor weight scale folded into the epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.w1a8_matmul import SMEM_SPEC, int8_dot, smem_scalars

Array = jax.Array

DEFAULT_BM, DEFAULT_BK, DEFAULT_BN = 128, 512, 512


def _int8_kernel(x_ref, w_ref, gamma_ref, wscale_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        inv = 1.0 / (gamma_ref[...] * wscale_ref[0, 0])  # (bm, 1)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * inv).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "out_dtype", "interpret")
)
def int8_matmul(
    x_i8: Array,
    w_i8: Array,
    gamma: Array,
    wscale: Array,
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> Array:
    """M and K tile evenly; a ragged last N tile's extra columns are dropped."""
    m, k = x_i8.shape
    k2, n = w_i8.shape
    assert k == k2
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm_ == 0 and k % bk_ == 0, (m, k, bm_, bk_)

    return pl.pallas_call(
        _int8_kernel,
        grid=(m // bm_, pl.cdiv(n, bn_), k // bk_),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm_, 1), lambda i, j, kk: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
    )(
        x_i8,
        w_i8,
        gamma.reshape(m, 1).astype(jnp.float32),
        smem_scalars(wscale),
    )
