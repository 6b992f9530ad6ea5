"""Fused decoupled-FFN first GEMM — both branches in one activation pass.

Paper §A (third optimization): "the same input must be multiplied with both
the 8-bit and 1-bit branches of the up projection ... distributed across
multiple thread groups, enabling parallel execution without redundant data
reads."  TPU adaptation: one Pallas kernel whose grid walks the 1-bit
branch's N tiles; the (much narrower, r << d_ff) 8-bit branch weight tile
rides along pinned in VMEM, and both accumulators advance per K step — the
INT8 activation tile is read from HBM exactly once for the two GEMMs.

Outputs are pre-scaled by the feature-scaling factors beta (1-bit) and
alpha (8-bit), folding paper Eq. 11 into the epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import GROUP_K

from repro.kernels.w1a8_matmul import (
    SMEM_SPEC,
    _unpack_tile,
    int8_dot,
    smem_scalars,
)

Array = jax.Array

DEFAULT_BM, DEFAULT_BK, DEFAULT_BN = 128, 1024, 512


def _decoupled_kernel(
    x_ref, wp_ref, w8_ref, gamma_ref, s_ref, o1_ref, o8_ref, acc1_ref, acc8_ref
):
    j = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc8_ref[...] = jnp.zeros_like(acc8_ref)

    x = x_ref[...]
    w1 = _unpack_tile(wp_ref[...])
    acc1_ref[...] += int8_dot(x, w1)
    # 8-bit branch: only the j == 0 pass accumulates (r fits in one N tile;
    # other j tiles would redundantly recompute it)
    @pl.when(j == 0)
    def _acc8():
        acc8_ref[...] += int8_dot(x, w8_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        lam, w8s, alpha, beta = (s_ref[0, i] for i in range(4))
        gamma = gamma_ref[...]  # (bm, 1)
        y1 = acc1_ref[...].astype(jnp.float32) * (beta * lam / gamma)
        o1_ref[...] = y1.astype(o1_ref.dtype)

        @pl.when(j == 0)
        def _write8():
            y8 = acc8_ref[...].astype(jnp.float32) * (alpha / (gamma * w8s))
            o8_ref[...] = y8.astype(o8_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "out_dtype", "interpret")
)
def decoupled_matmul(
    x_i8: Array,
    w1_packed: Array,
    w8_i8: Array,
    gamma: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """Returns (y1 (M, N), y8 (M, R)): both branch outputs, scale-folded.

    R (the 8-bit width) must fit a single N tile (r <= bn) — true for the
    paper's r in [128, 768] with bn = 512+ (pad in ops.py otherwise).
    M and K tile evenly; a ragged last N tile's extra columns are dropped.
    """
    m, k = x_i8.shape
    kb, n = w1_packed.shape
    _, r = w8_i8.shape
    assert kb * 8 == k
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm_ == 0 and k % bk_ == 0, (m, k, bm_, bk_)
    assert bk_ == k or bk_ % GROUP_K == 0, (
        f"bk={bk_} must hold whole {GROUP_K}-row bit groups")
    assert r <= bn_, f"8-bit width {r} must fit one tile (bn={bn_})"

    nk = k // bk_
    # w8 is only consumed on j == 0 passes; pin its block index at the last
    # K tile for j > 0 so the pipeline re-streams it per i, not per (i, j).
    w8_index = lambda i, j, kk: (jnp.where(j == 0, kk, nk - 1), 0)
    return pl.pallas_call(
        _decoupled_kernel,
        grid=(m // bm_, pl.cdiv(n, bn_), nk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_ // 8, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk_, r), w8_index),
            pl.BlockSpec((bm_, 1), lambda i, j, kk: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm_, r), lambda i, j, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((m, r), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm_, bn_), jnp.int32),
            pltpu.VMEM((bm_, r), jnp.int32),
        ],
        interpret=interpret,
    )(
        x_i8,
        w1_packed,
        w8_i8,
        gamma.reshape(m, 1).astype(jnp.float32),
        smem_scalars(lam, w8scale, alpha, beta),
    )
