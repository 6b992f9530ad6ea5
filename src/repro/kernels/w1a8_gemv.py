"""Decode-shaped W1A8 GEMV Pallas kernels with fused activation quantization.

Autoregressive decode multiplies a handful of token rows (M <= ~32) against
the full packed weight matrix — the op is bandwidth-bound on the 1-bit
weight stream, so the prefill-shaped ``w1a8_matmul`` tiling (M padded to
128-row tiles, a separate XLA activation-quantize pass that round-trips the
activations through HBM) leaves throughput on the table.  This tier is
specialized for that regime:

* **Fused act-quant prologue.**  The float activations (all M rows x full K)
  fit in VMEM at decode shapes, so the kernel's first grid step computes the
  per-token AbsMax INT8 quantization in-kernel (gamma + int8 rows land in
  VMEM scratch) and every later step reads the quantized rows from scratch.
  No ``quantize_act_int8`` XLA pass, no extra HBM round-trip.
* **No 128-row padding.**  M is a single block (padded only to the 8-row
  f32 sublane minimum in ops.py), not a grid dimension.
* **(N, K)-major grid with wide bn tiles.**  The grid walks output tiles
  j over N with K innermost, streaming wide packed-weight tiles HBM->VMEM —
  the weight stream, the bandwidth term that matters, is maximized while
  the tiny activation block stays resident.

``decoupled_gemv`` is the dual-branch variant (paper §A third point): the
8-bit branch tile rides along and both accumulators advance per K step, so
the quantized activations are read once for the two GEMVs.
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

# Wider-than-prefill defaults: weight streaming dominates, so bn leans wide;
# bk is a multiple of 256 (planar bit groups, uint8 sublane tiles).
DEFAULT_BK, DEFAULT_BN = 1024, 512


def _quant_prologue(x_ref, xq_ref, gamma_ref):
    """Per-token AbsMax INT8 quantize of the full (bm, K) activation block
    into VMEM scratch.  gamma = 127 / (amax + 1e-5) is never zero, so pad
    rows (all-zero activations) stay finite through the epilogue.

    This is the in-kernel mirror of ``core.quantization.act_scale_int8``
    (f32 amax, 127 / (amax + 1e-5)) — the single act-quant formula shared
    with the fake-quant path; keep them in lockstep or packed-vs-fake-quant
    parity drifts."""
    xf = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    gamma = 127.0 / (amax + 1e-5)  # (bm, 1)
    xq_ref[...] = jnp.clip(jnp.round(xf * gamma), -127, 127).astype(jnp.int8)
    gamma_ref[...] = gamma


def _x_tile(xq_ref, kk, bk: int):
    return xq_ref[:, pl.ds(pl.multiple_of(kk * bk, bk), bk)]


def _w1a8_gemv_kernel(
    x_ref, wp_ref, s_ref, o_ref, xq_ref, gamma_ref, acc_ref, *, bk: int
):
    """One (j, kk) grid step: j walks N tiles, kk walks K tiles (innermost)."""
    j, kk = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (kk == 0))
    def _prologue():
        _quant_prologue(x_ref, xq_ref, gamma_ref)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = _unpack_tile(wp_ref[...])
    acc_ref[...] += int8_dot(_x_tile(xq_ref, kk, bk), w_tile)

    @pl.when(kk == pl.num_programs(1) - 1)
    def _epilogue():
        scale = s_ref[0, 0] / gamma_ref[...]  # (bm, 1)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bk", "bn", "out_dtype", "interpret")
)
def w1a8_gemv(
    x: Array,
    w_packed: Array,
    lam: Array,
    *,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> Array:
    """Y (M, N) = dequant(quantize(X) @ unpack(W_packed)), act-quant fused.

    x: (M, K) float activations, M small (decode rows; pad to 8 in ops.py);
    w_packed: (K//8, N) uint8 sign bits; lam: scalar AbsMean weight scale.
    K % bk == 0 (pick tiles via ops.decode_tiles); a ragged last N tile's
    extra columns are dropped.
    """
    m, k = x.shape
    kb, n = w_packed.shape
    assert kb * 8 == k, f"packed K mismatch: {kb}*8 != {k}"
    bk_, bn_ = min(bk, k), min(bn, n)
    assert bk_ % 8 == 0, f"bk={bk_} must be a multiple of 8 (packing)"
    assert k % bk_ == 0, (k, bk_)
    assert bk_ == k or bk_ % GROUP_K == 0, (
        f"bk={bk_} must hold whole {GROUP_K}-row bit groups")

    return pl.pallas_call(
        functools.partial(_w1a8_gemv_kernel, bk=bk_),
        grid=(pl.cdiv(n, bn_), k // bk_),
        in_specs=[
            pl.BlockSpec((m, k), lambda j, kk: (0, 0)),  # resident in VMEM
            pl.BlockSpec((bk_ // 8, bn_), lambda j, kk: (kk, j)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((m, bn_), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((m, k), jnp.int8),  # quantized rows
            pltpu.VMEM((m, 1), jnp.float32),  # gamma
            pltpu.VMEM((m, bn_), jnp.int32),  # accumulator
        ],
        interpret=interpret,
    )(x, w_packed, smem_scalars(lam))


def _decoupled_gemv_kernel(
    x_ref, wp_ref, w8_ref, s_ref,
    o1_ref, o8_ref, xq_ref, gamma_ref, acc1_ref, acc8_ref, *, bk: int
):
    j, kk = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (kk == 0))
    def _prologue():
        _quant_prologue(x_ref, xq_ref, gamma_ref)
        acc8_ref[...] = jnp.zeros_like(acc8_ref)

    @pl.when(kk == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)

    x_tile = _x_tile(xq_ref, kk, bk)
    w1 = _unpack_tile(wp_ref[...])
    acc1_ref[...] += int8_dot(x_tile, w1)

    # 8-bit branch: only the j == 0 pass accumulates (r fits one N tile)
    @pl.when(j == 0)
    def _acc8():
        acc8_ref[...] += int8_dot(x_tile, w8_ref[...])

    @pl.when(kk == pl.num_programs(1) - 1)
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
    jax.jit, static_argnames=("bk", "bn", "out_dtype", "interpret")
)
def decoupled_gemv(
    x: Array,
    w1_packed: Array,
    w8_i8: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    *,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """Dual-branch decode GEMV: (y1 (M, N), y8 (M, R)), act-quant fused.

    Same semantics as ``decoupled_matmul`` (outputs pre-scaled by beta /
    alpha) with the activation quantization done in the kernel prologue.
    R must fit one N tile (r <= bn).
    """
    m, k = x.shape
    kb, n = w1_packed.shape
    _, r = w8_i8.shape
    assert kb * 8 == k, f"packed K mismatch: {kb}*8 != {k}"
    bk_, bn_ = min(bk, k), min(bn, n)
    assert bk_ % 8 == 0 and k % bk_ == 0, (k, bk_)
    assert bk_ == k or bk_ % GROUP_K == 0, (
        f"bk={bk_} must hold whole {GROUP_K}-row bit groups")
    assert r <= bn_, f"8-bit width {r} must fit one tile (bn={bn_})"

    nk = k // bk_
    # w8 is only consumed on the j == 0 pass; pinning its block index at the
    # last K tile for j > 0 means the mapped block never changes after that
    # pass, so the pipeline's revisiting logic streams w8 exactly once
    # instead of n/bn times.
    w8_index = lambda j, kk: (jnp.where(j == 0, kk, nk - 1), 0)
    return pl.pallas_call(
        functools.partial(_decoupled_gemv_kernel, bk=bk_),
        grid=(pl.cdiv(n, bn_), nk),
        in_specs=[
            pl.BlockSpec((m, k), lambda j, kk: (0, 0)),
            pl.BlockSpec((bk_ // 8, bn_), lambda j, kk: (kk, j)),
            pl.BlockSpec((bk_, r), w8_index),
            SMEM_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((m, bn_), lambda j, kk: (0, j)),
            pl.BlockSpec((m, r), lambda j, kk: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((m, r), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((m, k), jnp.int8),
            pltpu.VMEM((m, 1), jnp.float32),
            pltpu.VMEM((m, bn_), jnp.int32),
            pltpu.VMEM((m, r), jnp.int32),
        ],
        interpret=interpret,
    )(x, w1_packed, w8_i8, smem_scalars(lam, w8scale, alpha, beta))
