"""Quantized weight gather — a beyond-paper distributed optimization.

Under FSDP, latent weights are sharded over the `data` axis and all-gathered
per layer.  Because pQuant's backbone weights are sign(+-1) x one scalar,
the gather can move **INT8 signs** instead of bf16/fp32 latents: the
collective payload that exists only because of the paper's quantization
shrinks 2-4x (and 16x in the packed variant, tracked in §Perf).

Mechanics: a custom_vjp wraps (binarize -> int8 cast -> sharding constraint
that drops the fsdp axis -> dequantize).  The constraint on the *int8*
tensor forces the SPMD partitioner to all-gather 1-byte data; the backward
pass constrains the gradient back to the sharded spec, which transposes to
a reduce-scatter.  STE semantics are preserved (gradient passes straight
through the quantizer to the latent shard).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.quantization import WEIGHT_SCOPE, scoped
from repro.distributed.sharding import shard_hint

Array = jax.Array

EPS = 1e-5

# logical axes that map to the fsdp (`data`) mesh axis in DEFAULT_RULES;
# the post-gather spec replaces them with None (replicated)
FSDP_LOGICAL = ("embed",)


def _gathered_axes(axes: Sequence[Optional[str]]):
    return tuple(None if a in FSDP_LOGICAL else a for a in axes)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def binarize_gather(w: Array, axes: tuple) -> Array:
    """1-bit quantize + gather-as-int8 + dequantize.  Returns +-lambda values
    replicated over the fsdp axis, sharded as before elsewhere."""
    y, _ = _fwd(w, axes)
    return y


@scoped(WEIGHT_SCOPE)
def _fwd(w: Array, axes: tuple):
    mu = jnp.mean(w)
    lam = jnp.mean(jnp.abs(w)) + EPS
    signs = jnp.where(w - mu >= 0, jnp.int8(1), jnp.int8(-1))
    # the all-gather happens HERE, on int8 payload
    signs = shard_hint(signs, *_gathered_axes(axes))
    y = signs.astype(w.dtype) * lam.astype(w.dtype)
    return y, axes


def _bwd(axes, res, g):
    # STE: gradient passes straight through to the latent shard; the
    # constraint transposes the gather into a reduce-scatter.
    del res
    return (shard_hint(g, *axes),)


def _fwd_vjp(w, axes):
    y, _ = _fwd(w, axes)
    return y, None


binarize_gather.defvjp(_fwd_vjp, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def binarize_gather_stacked(w: Array, axes: tuple) -> Array:
    """Per-slice (stacked expert) 1-bit quantize + int8 gather: stats are
    computed over the trailing two axes so each expert keeps its own
    mu/lambda (matches core.quantization.binarize_weights_stacked)."""
    y, _ = _fwd_stacked(w, axes)
    return y


@scoped(WEIGHT_SCOPE)
def _fwd_stacked(w: Array, axes: tuple):
    red = tuple(range(max(0, w.ndim - 2), w.ndim))
    mu = jnp.mean(w, axis=red, keepdims=True)
    lam = jnp.mean(jnp.abs(w), axis=red, keepdims=True) + EPS
    signs = jnp.where(w - mu >= 0, jnp.int8(1), jnp.int8(-1))
    signs = shard_hint(signs, *_gathered_axes(axes))
    return signs.astype(w.dtype) * lam.astype(w.dtype), axes


def _bwd_stacked(axes, res, g):
    del res
    return (shard_hint(g, *axes),)


def _fwd_stacked_vjp(w, axes):
    y, _ = _fwd_stacked(w, axes)
    return y, None


binarize_gather_stacked.defvjp(_fwd_stacked_vjp, _bwd_stacked)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def int8_gather(w: Array, axes: tuple) -> Array:
    """AbsMax-INT8 quantize + gather-as-int8 + dequantize (for the 8-bit
    branch weights under FSDP)."""
    y, _ = _fwd8(w, axes)
    return y


@scoped(WEIGHT_SCOPE)
def _fwd8(w: Array, axes: tuple):
    amax = jnp.max(jnp.abs(w)) + EPS
    scale = 127.0 / amax
    q = jnp.clip(jnp.round(w * scale), -127, 127).astype(jnp.int8)
    q = shard_hint(q, *_gathered_axes(axes))
    return q.astype(w.dtype) / scale.astype(w.dtype), axes


def _bwd8(axes, res, g):
    del res
    return (shard_hint(g, *axes),)


def _fwd8_vjp(w, axes):
    y, _ = _fwd8(w, axes)
    return y, None


int8_gather.defvjp(_fwd8_vjp, _bwd8)
