"""Fused RMSNorm + per-token AbsMax INT8 quantize Pallas kernel.

Paper §A: "the RMSNorm operation can be merged with activation
quantization, as both are element-wise transformations."  Fusing them means
the normalized fp tensor never round-trips HBM between the norm and the
quantized GEMM — on a bandwidth-bound decode step this halves activation
traffic for the norm+quant stage.

Row-tiled: each grid step owns (bm, D) rows, computes rsqrt(mean(x^2)),
scales by the norm weight, takes the row AbsMax, and writes INT8 + gamma.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BM = 256


def _rmsnorm_quant_kernel(x_ref, scale_ref, q_ref, gamma_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(normed), axis=-1, keepdims=True)
    gamma = 127.0 / (amax + 1e-5)  # (bm, 1)
    q = jnp.clip(jnp.round(normed * gamma), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    gamma_ref[...] = gamma


@functools.partial(jax.jit, static_argnames=("bm", "eps", "interpret"))
def rmsnorm_quant(
    x: Array,
    scale: Array,
    *,
    bm: int = DEFAULT_BM,
    eps: float = 1e-6,
    interpret: bool = False,
):
    """x (M, D), scale (D,) -> (q (M, D) int8, gamma (M,) f32)."""
    m, d = x.shape
    bm_ = min(bm, m)
    assert m % bm_ == 0

    q, gamma = pl.pallas_call(
        functools.partial(_rmsnorm_quant_kernel, eps=eps),
        grid=(m // bm_,),
        in_specs=[
            pl.BlockSpec((bm_, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm_, d), lambda i: (i, 0)),
            pl.BlockSpec((bm_, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, d), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, scale.reshape(1, d))
    return q, gamma[:, 0]
