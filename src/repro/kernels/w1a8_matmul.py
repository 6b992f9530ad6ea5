"""W1A8 matmul Pallas kernel — the paper's inference hot spot, TPU-native.

GPU/CPU papers (T-MAC, LUT-GEMM) turn 1-bit GEMV into table lookups; TPUs
have no scalar LUT unit, so we adapt the *insight* (1-bit weights make the
op bandwidth-bound -> shrink bytes moved): weights live in HBM bit-packed
8-per-uint8 (16x smaller than bf16), each grid step streams a packed tile
HBM->VMEM, unpacks to +-1 INT8 on the VPU (shift/mask), and feeds the MXU's
int8 x int8 -> int32 path (2x the bf16 MACs/cycle on v5e).

Epilogue folds the dequant scales lam (weight AbsMean) and gamma (per-token
activation AbsMax) into the final tile write — no separate dequant pass
touches HBM (paper §A scale folding).

Grid: (M/bm, N/bn, K/bk) with a VMEM int32 accumulator; K is innermost so
the accumulator stays resident until the (i, j) tile finishes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import GROUP_K

Array = jax.Array

# Default tile sizes: bm x bk int8 acts (128 KiB), bk//8 x bn packed
# weights (32 KiB), bm x bn int32 accumulator (256 KiB) -> comfortably in
# VMEM with double buffering.  bk is a multiple of 256 (planar bit groups,
# uint8 sublane tiles) and bn of 128 (lanes).
DEFAULT_BM, DEFAULT_BK, DEFAULT_BN = 128, 1024, 512


def _unpack_tile(packed: Array) -> Array:
    """(rows, bn) uint8 -> (8 * rows, bn) int8 {-1, +1}.

    Planar groups (``core.packing``): each group of 8 packed rows holds 64
    K rows as 8 bit planes, so a group unpacks to its 8 shifted planes
    stacked along K.  The shifts run on int32 (8-row sublane tiles); a
    shorter last group occurs only when the tile is a small whole K."""
    p = packed.astype(jnp.int32)
    rows, bn = p.shape
    full = rows - rows % 8
    parts = []
    if full:
        g = p[:full].reshape(full // 8, 1, 8, bn)
        parts.append(jnp.concatenate(
            [(g >> b) & 1 for b in range(8)], axis=1).reshape(full * 8, bn))
    if rows > full:
        t = p[full:]
        parts.append(jnp.concatenate([(t >> b) & 1 for b in range(8)], axis=0))
    bits = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return (bits * 2 - 1).astype(jnp.int8)


def int8_dot(x: Array, w: Array) -> Array:
    """(M, K) int8 @ (K, N) int8 -> int32 on the MXU.  Precision is pinned:
    an ambient ``jax.default_matmul_precision`` would otherwise ask Mosaic
    for an fp32 contraction of int8 operands, which it refuses."""
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )


def _w1a8_kernel(x_ref, wp_ref, gamma_ref, lam_ref, o_ref, acc_ref):
    """One (i, j, k) grid step."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = _unpack_tile(wp_ref[...])  # VPU unpack in VMEM
    acc_ref[...] += int8_dot(x_ref[...], w_tile)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        scale = lam_ref[0, 0] / gamma_ref[...]  # (bm, 1)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


def smem_scalars(*vals: Array) -> Array:
    """Scalars as one (1, n) f32 operand for an SMEM block."""
    return jnp.stack([jnp.asarray(v, jnp.float32).reshape(()) for v in vals])[None]


SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "out_dtype", "interpret"),
)
def w1a8_matmul(
    x_i8: Array,
    w_packed: Array,
    gamma: Array,
    lam: Array,
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> Array:
    """Y (M, N) = dequant(X_int8 (M, K) @ unpack(W_packed (K//8, N))).

    M and K must tile evenly (pad in ops.py for ragged cases); a ragged
    last N tile reads past the weight and its extra columns are dropped.
    """
    m, k = x_i8.shape
    kb, n = w_packed.shape
    assert kb * 8 == k, f"packed K mismatch: {kb}*8 != {k}"
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm_ == 0 and k % bk_ == 0, (m, k, bm_, bk_)
    assert bk_ == k or bk_ % GROUP_K == 0, (
        f"bk={bk_} must hold whole {GROUP_K}-row bit groups")

    return pl.pallas_call(
        _w1a8_kernel,
        grid=(m // bm_, pl.cdiv(n, bn_), k // bk_),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_ // 8, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm_, 1), lambda i, j, kk: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
    )(x_i8, w_packed, gamma.reshape(m, 1).astype(jnp.float32), smem_scalars(lam))
