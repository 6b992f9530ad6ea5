"""Inference-time bit-packing for 1-bit weights (paper Appendix A).

Signs {-1, +1} are stored 8-per-uint8 along the input-feature (K) axis:
16x smaller than FP16, 8x smaller than the INT8 sign view.  The Pallas
W1A8 kernel streams packed tiles HBM->VMEM and unpacks in-register; this
module provides the host-side pack/unpack and the pure-jnp oracle used by
kernel tests.

Bit convention (planar groups): K is cut into groups of 64 rows (8 packed
rows); a last, shorter group takes the remaining ``K % 64`` rows.  In a
group of ``g`` packed rows starting at K row ``base``, bit b of packed row
i encodes the sign of weight ``base + b * g + i`` (bit=1 -> +1, bit=0 ->
-1).  Unpacking a group is then 8 shifted planes stacked along K, with no
interleave of the bit axis into the row axis: the form Mosaic lowers on
TPU (``kernels.w1a8_matmul._unpack_tile``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# K rows per planar bit group (8 packed uint8 rows).
GROUP_K = 64
# The packed kernels stream K in tiles that are multiples of this, or the
# whole K when it is no larger; the serving export pads K up to it.
KERNEL_K_ALIGN = 256


def padded_k(k: int) -> int:
    """K of the packed serving layout: a K that one kernel tile cannot hold
    is padded to a multiple of ``KERNEL_K_ALIGN``.  Padded rows meet
    zero-padded activations (``kernels.ops``), so they add nothing."""
    if k <= KERNEL_K_ALIGN:
        return k
    return -(-k // KERNEL_K_ALIGN) * KERNEL_K_ALIGN


def _groups(k: int) -> list[tuple[int, int]]:
    """(first K row, packed rows) of each planar group of a K-row axis."""
    out = [(s, GROUP_K // 8) for s in range(0, k - k % GROUP_K, GROUP_K)]
    if k % GROUP_K:
        out.append((k - k % GROUP_K, (k % GROUP_K) // 8))
    return out


def pack_signs(signs: Array) -> Array:
    """Pack +-1 (or bool) signs along the K (second-to-last) axis into uint8.

    signs: (..., K, N) with values in {-1, +1}; leading axes (layer stacks,
    expert stacks) pack per slice.  K must be a multiple of 8.
    Returns (..., K//8, N) uint8 in the planar group layout.
    """
    *lead, k, n = signs.shape
    assert k % 8 == 0, f"K={k} must be a multiple of 8"
    bits = (signs > 0).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None, None]
    parts = [
        jnp.sum(
            bits[..., s : s + 8 * g, :].reshape(*lead, 8, g, n) * weights,
            axis=-3, dtype=jnp.uint8,
        )
        for s, g in _groups(k)
    ]
    return jnp.concatenate(parts, axis=-2)


def unpack_signs(packed: Array, dtype=jnp.int8) -> Array:
    """Inverse of :func:`pack_signs`: (..., K//8, N) uint8 -> (..., K, N) +-1."""
    *lead, kb, n = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[:, None, None]
    parts = []
    for s, g in _groups(kb * 8):
        rows = packed[..., None, s // 8 : s // 8 + g, :]
        bits = (rows >> shifts) & jnp.uint8(1)  # (..., 8, g, N)
        parts.append(bits.reshape(*lead, 8 * g, n))
    signs = jnp.concatenate(parts, axis=-2).astype(jnp.int8) * 2 - 1
    return signs.astype(dtype)


@dataclasses.dataclass
class PackedBitWeight:
    """Inference export of one 1-bit linear layer.

    packed: (K//8, N) uint8 sign bits.
    lam:    per-tensor AbsMean dequant scale (float32 scalar array).
    shape:  original (K, N).
    """

    packed: Array
    lam: Array
    shape: tuple[int, int]

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.packed.shape)) + 4

    def dequantize(self, dtype=jnp.float32) -> Array:
        return unpack_signs(self.packed, jnp.int8).astype(dtype) * self.lam.astype(
            dtype
        )


def export_bit_weight(w: Array) -> PackedBitWeight:
    """Offline-quantize a latent FP weight to its packed inference form
    (paper: 'parameters in the 1-bit branch are offline quantized and
    stored in 1-bit precision during inference')."""
    mu = jnp.mean(w)
    lam = jnp.mean(jnp.abs(w))
    signs = jnp.where(w - mu >= 0, 1, -1).astype(jnp.int8)
    return PackedBitWeight(
        packed=pack_signs(signs), lam=lam.astype(jnp.float32), shape=tuple(w.shape)
    )


@dataclasses.dataclass
class PackedInt8Weight:
    """Inference export of one INT8 (high-precision branch) weight."""

    q: Array  # int8, same shape as the latent weight
    scale: Array  # float32 scalar (per-tensor AbsMax)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.q.shape)) + 4

    def dequantize(self, dtype=jnp.float32) -> Array:
        return self.q.astype(dtype) / self.scale.astype(dtype)


def export_int8_weight(w: Array) -> PackedInt8Weight:
    amax = jnp.max(jnp.abs(w))
    scale = 127.0 / (amax + 1e-5)
    q = jnp.clip(jnp.round(w * scale), -127, 127).astype(jnp.int8)
    return PackedInt8Weight(q=q, scale=scale.astype(jnp.float32))


def model_weight_bytes(
    n_1bit: int, n_8bit_total: int, n_fp16: int, seq_active_8bit: int | None = None
) -> dict[str, float]:
    """Bytes moved per forward for weight streaming (paper Figure 6).

    With top-1 routing only one 8-bit branch is *read* per token regardless
    of N (``seq_active_8bit``), while all N are *stored*.
    """
    read_8bit = seq_active_8bit if seq_active_8bit is not None else n_8bit_total
    return {
        "stored_bytes": n_1bit / 8 + n_8bit_total + n_fp16 * 2,
        "read_bytes": n_1bit / 8 + read_8bit + n_fp16 * 2,
    }
