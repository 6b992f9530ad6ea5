"""Pure-jnp oracles for every Pallas kernel.  Tests assert_allclose the
kernels (interpret=True on CPU) against these across shape/dtype sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def unpack_ref(packed: Array) -> Array:
    """(K//8, N) uint8 -> (K, N) int8 in {-1, +1}, per weight row: row k
    of a planar group of g packed rows starting at K row ``base`` is bit
    ``(k - base) // g`` of packed row ``base // 8 + (k - base) % g``
    (``core.packing``)."""
    kb, n = packed.shape
    k = jnp.arange(kb * 8)
    base = k // 64 * 64
    g = jnp.minimum(8, (kb * 8 - base) // 8)
    row = base // 8 + (k - base) % g
    bit = ((k - base) // g).astype(jnp.uint8)
    bits = (packed[row] >> bit[:, None]) & jnp.uint8(1)
    return bits.astype(jnp.int8) * 2 - 1


def w1a8_matmul_ref(
    x_i8: Array, w_packed: Array, gamma: Array, lam: Array, out_dtype=jnp.float32
) -> Array:
    """Y = (X_int8 @ unpack(W)) * lam / gamma   (paper Eq. 10).

    x_i8: (M, K) int8 quantized activations; gamma: (M,) per-token scales;
    w_packed: (K//8, N) uint8 sign bits; lam: scalar AbsMean.
    """
    w = unpack_ref(w_packed)
    acc = jax.lax.dot_general(
        x_i8, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    y = acc.astype(jnp.float32) * lam.astype(jnp.float32) / gamma[:, None].astype(
        jnp.float32
    )
    return y.astype(out_dtype)


def int8_matmul_ref(
    x_i8: Array, w_i8: Array, gamma: Array, wscale: Array, out_dtype=jnp.float32
) -> Array:
    """Y = (X_int8 @ W_int8) / (gamma * wscale)   (W8A8 branch).

    wscale: scalar AbsMax weight scale (q = w * wscale).
    """
    acc = jax.lax.dot_general(
        x_i8, w_i8, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    y = acc.astype(jnp.float32) / (
        gamma[:, None].astype(jnp.float32) * wscale.astype(jnp.float32)
    )
    return y.astype(out_dtype)


def rmsnorm_quant_ref(x: Array, scale: Array, eps: float = 1e-6):
    """Fused RMSNorm + per-token AbsMax INT8 quantize (paper §A: 'RMSNorm
    merged with activation quantization').

    Returns (q (M, D) int8, gamma (M,) f32) with
    q = RoundClip(rmsnorm(x) * gamma), gamma = 127 / max|rmsnorm(x)|.
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)[None, :]
    amax = jnp.max(jnp.abs(normed), axis=-1)
    gamma = 127.0 / (amax + 1e-5)
    q = jnp.clip(jnp.round(normed * gamma[:, None]), -127, 127).astype(jnp.int8)
    return q, gamma


def quantize_act_ref(x: Array):
    """Per-token AbsMax INT8 quantize (the XLA pass the GEMV tier fuses).

    x: (M, K) float -> (q (M, K) int8, gamma (M,) f32)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    gamma = 127.0 / (amax + 1e-5)
    q = jnp.clip(jnp.round(xf * gamma[:, None]), -127, 127).astype(jnp.int8)
    return q, gamma


def w1a8_gemv_ref(
    x: Array, w_packed: Array, lam: Array, out_dtype=jnp.float32
) -> Array:
    """Decode GEMV with fused act-quant: quantize_act_ref + w1a8_matmul_ref."""
    xq, gamma = quantize_act_ref(x)
    return w1a8_matmul_ref(xq, w_packed, gamma, lam, out_dtype=out_dtype)


def decoupled_gemv_ref(
    x: Array,
    w1_packed: Array,
    w8_i8: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    out_dtype=jnp.float32,
):
    """Dual-branch decode GEMV reference (act-quant + decoupled_matmul_ref)."""
    xq, gamma = quantize_act_ref(x)
    return decoupled_matmul_ref(
        xq, w1_packed, w8_i8, gamma, lam, w8scale, alpha, beta,
        out_dtype=out_dtype,
    )


def paged_attention_ref(
    q: Array,  # (B, T, Hq, D)
    kpool: Array,  # (NB, Hkv, BS, D)
    vpool: Array,  # (NB, Hkv, BS, D)
    table: Array,  # (B, MB) int32
    start: Array,  # (B,) int32
    kv_lens: Array,  # (B,) int32 (unused: the causal mask already bounds
    # every valid row's columns — kept so ref and kernel share a signature)
    scale=None,
    out_dtype=None,
):
    """Gather + prefix-masked SDPA at f32 — the dense read path the paged
    kernel replaces (``kv_pool.read`` followed by
    ``models.attention._sdpa`` under ``_span_mask``), with query token t
    of slot b attending absolute columns ``j <= start[b] + t``.
    """
    del kv_lens
    b, t, hq, d = q.shape
    hkv, bs = kpool.shape[1], kpool.shape[2]
    g = hq // hkv
    scale = d**-0.5 if scale is None else scale
    keys = jnp.take(kpool, table, axis=0).swapaxes(2, 3).reshape(b, -1, hkv, d)
    vals = jnp.take(vpool, table, axis=0).swapaxes(2, 3).reshape(b, -1, hkv, d)
    skv = keys.shape[1]
    qg = q.reshape(b, t, hkv, g, d).astype(jnp.float32)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys.astype(jnp.float32)) * scale
    )
    rowpos = start[:, None] + jnp.arange(t, dtype=start.dtype)[None]
    mask = jnp.arange(skv)[None, None, :] <= rowpos[:, :, None]  # (B,T,Skv)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, vals.astype(jnp.float32))
    out = out.reshape(b, t, hq, d)
    return out.astype(out_dtype if out_dtype is not None else q.dtype)


def decoupled_matmul_ref(
    x_i8: Array,
    w1_packed: Array,
    w8_i8: Array,
    gamma: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    out_dtype=jnp.float32,
):
    """Fused first GEMM of the decoupled FFN (paper §A third point): the
    same INT8 activations multiply both branches in one pass.

    Returns (y1 (M, N) = beta * W1A8 result, y8 (M, R) = alpha * W8A8 result).
    """
    y1 = w1a8_matmul_ref(x_i8, w1_packed, gamma, lam) * beta
    y8 = int8_matmul_ref(x_i8, w8_i8, gamma, w8scale) * alpha
    return y1.astype(out_dtype), y8.astype(out_dtype)
