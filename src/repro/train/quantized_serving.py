"""Serving-time weight quantization: export latent FP weights to the
integer layout that actually lives in HBM (paper Appendix A).

Training keeps FP latents (fake-quant + STE).  For deployment, the 1-bit
backbone becomes INT8 signs (optionally bit-PACKED uint8, 8/byte = 16x
smaller than bf16) with one AbsMean scale; the 8-bit branch becomes INT8
with an AbsMax scale.  The model apply functions accept this layout
transparently (core.quantization._dequant_stored), so the dry-run's
compiled serve_step shows integer parameters in HBM and the memory-roofline
term drops accordingly (§Perf iteration A).

Weight classification is by parameter path name:
  1-bit backbone: attention projections, FFN trunk, MoE experts, SSM/RG-LRU
  projections.  8-bit branch: w8_*.  Everything else (embeddings, norms,
  scales, routers, RG-LRU gates, conv, SSD params) stays FP.

Shardability contract (tensor-parallel serving): every exported weight is
N-major-shardable — the layout keeps N as the LAST axis (``packed`` is
``(..., K//8, N)`` uint8, ``q`` is ``(..., K, N)`` int8) so slicing the
last axis yields a valid shard of the same layout, and the AbsMean /
AbsMax ``scale`` is a per-tensor keepdims scalar (per slice for stacked
weights) that REPLICATES: a shard dequantizes with the same scalar as the
whole weight, making each per-shard kernel output a bitwise slice of the
unsharded result.  ``distributed.sharding.nmajor_param_sharding`` places
this export on a mesh (only the trailing logical axis shards) and the
``kernels.ops.*_nshard`` shard_map islands consume it with per-shard GEMV
tile keys — see ``tests/test_sharded_serving.py`` for the round-trip and
parity pins.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packing import pack_signs, padded_k

Array = jax.Array

# parent-key names of 1-bit backbone linears ({"w": array} wrappers)
INT1_WRAPPED = {
    "wq", "wk", "wv", "wo", "wq_down", "wq_up", "wkv_down", "wkv_up",
    "wx", "wy", "wout",
}
# direct-array leaf names
INT1_DIRECT = {"w1_gate", "w1_up", "w1_down", "we_up", "we_gate", "we_down", "w1"}
INT8_DIRECT = {"w8_gate", "w8_up", "w8_down", "w8_a", "w8_b"}


def _path_keys(path) -> list[str]:
    return [str(getattr(e, "key", getattr(e, "idx", ""))) for e in path]


def _binarize_export(w: Array, packed: bool, name: str = ""):
    """Latent -> {"q" | "packed", "scale"}; per-slice for stacked (layer- or
    expert-stacked) weights: ``pack_signs`` packs along the K (second-to-
    last) axis of every slice, so scanned layer stacks and MoE expert stacks
    bit-pack exactly like plain 2-D linears.  A K that isn't byte-aligned
    cannot pack (the kernels stream whole uint8 K-bytes); that case falls
    back to unpacked INT8 signs with an explicit warning instead of
    silently losing the 16x weight-traffic story.  A packed K longer than
    one kernel tile is padded to ``padded_k`` (extra rows hold -1 signs
    and meet zero-padded activations, so they add nothing)."""
    red = tuple(range(max(0, w.ndim - 2), w.ndim))
    mu = jnp.mean(w, axis=red, keepdims=True)
    lam = (jnp.mean(jnp.abs(w), axis=red, keepdims=True) + 1e-5).astype(jnp.float32)
    signs = jnp.where(w - mu >= 0, jnp.int8(1), jnp.int8(-1))
    if packed:
        k = w.shape[-2]
        if k % 8 == 0:
            pad = [(0, 0)] * (w.ndim - 2) + [(0, padded_k(k) - k), (0, 0)]
            signs = jnp.pad(signs, pad, constant_values=-1)
            return {"packed": pack_signs(signs), "scale": lam}
        warnings.warn(
            f"packed export of {name or 'a 1-bit weight'} "
            f"{tuple(w.shape)}: K={w.shape[-2]} is not a multiple of 8; "
            "storing unpacked INT8 signs (8x larger, no packed-kernel "
            "dispatch for this layer)",
            stacklevel=2,
        )
    return {"q": signs, "scale": lam}


def _int8_export(w: Array):
    red = tuple(range(max(0, w.ndim - 2), w.ndim))
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True) + 1e-5
    scale = (amax / 127.0).astype(jnp.float32)  # dequant multiplier
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def quantize_params_for_serving(
    params, axes, cfg: ModelConfig, packed: bool = False
):
    """Transform (params, axes) into the integer serving layout.

    packed=True additionally bit-packs 1-bit weights 8/byte along the K
    axis — per slice for layer-scanned and expert-stacked weights, so the
    whole 1-bit backbone is kernel-consumable.  Weights whose K isn't a
    multiple of 8 fall back to unpacked INT8 signs with a warning.
    Returns (qparams, qaxes): axes mirror the new structure (the integer
    tensor keeps the latent's logical axes; scales are replicated).
    """
    if cfg.quant.mode == "none":
        return params, axes
    import jax.tree_util as jtu

    paths_and_leaves, treedef = jtu.tree_flatten_with_path(params)
    flat_axes = []
    new_leaves = []
    from repro.distributed.sharding import _lookup_path

    for path, leaf in paths_and_leaves:
        keys = _path_keys(path)
        leaf_axes = _lookup_path(axes, path)
        name = keys[-1]
        parent = keys[-2] if len(keys) >= 2 else ""
        is_int1 = name in INT1_DIRECT or (name == "w" and parent in INT1_WRAPPED)
        is_int8 = name in INT8_DIRECT
        if is_int1 and leaf.ndim >= 2:
            q = _binarize_export(leaf, packed, name="/".join(keys))
            if "packed" in q:
                # packed dim0 = K//8: same logical axis, 1/8 length
                qa = {"packed": tuple(leaf_axes), "scale": ((None,) * leaf.ndim)}
            else:
                qa = {"q": tuple(leaf_axes), "scale": ((None,) * leaf.ndim)}
            new_leaves.append(q)
            flat_axes.append(qa)
        elif is_int8 and leaf.ndim >= 2:
            new_leaves.append(_int8_export(leaf))
            flat_axes.append(
                {"q": tuple(leaf_axes), "scale": ((None,) * leaf.ndim)}
            )
        else:
            new_leaves.append(leaf)
            flat_axes.append(tuple(leaf_axes))
    qparams = jtu.tree_unflatten(treedef, new_leaves)
    qaxes = jtu.tree_unflatten(treedef, flat_axes)
    return qparams, qaxes


def cast_serving_params(qparams, dtype):
    """Float leaves of a serving tree in the model's compute dtype, so
    activations and KV caches follow ``cfg.dtype``; ``scale`` leaves (export
    dequant scales, norm weights) stay float32."""
    import jax.tree_util as jtu

    dtype = jnp.dtype(dtype)

    def cast(path, leaf):
        if leaf.dtype != jnp.float32 or _path_keys(path)[-1] == "scale":
            return leaf
        return leaf.astype(dtype)

    return jtu.tree_map_with_path(cast, qparams)


def init_serving_params(cfg: ModelConfig, seed: int = 0, packed: bool = True):
    """Randomly initialised serving weights in ``cfg.dtype``, as (qparams,
    qaxes): the model init and the export run as one compiled program, so
    the latent float tree exists only inside it and never sits in device
    memory beside the exported integers."""
    from repro.models import api

    _, axes = api.params_shape_and_axes(cfg)

    def build(key):
        params, _ = api.init_model(key, cfg)
        qparams = quantize_params_for_serving(params, axes, cfg, packed)[0]
        return cast_serving_params(qparams, cfg.dtype)

    qparams = jax.jit(build)(jax.random.PRNGKey(seed))
    _, qaxes = serving_params_shape_and_axes(cfg, packed)
    return qparams, qaxes


def serving_params_shape_and_axes(cfg: ModelConfig, packed: bool = False):
    """ShapeDtypeStructs + axes of the quantized serving layout, without
    allocating (dry-run path)."""
    from repro.models import api

    axes_box = {}

    def f(key):
        p, a = api.init_model(key, cfg)
        qp, qa = quantize_params_for_serving(p, a, cfg, packed)
        axes_box["axes"] = qa
        return qp

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, axes_box["axes"]


def serving_bytes(params_shapes) -> int:
    """Total parameter bytes in the serving layout."""
    return sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(params_shapes)
    )
