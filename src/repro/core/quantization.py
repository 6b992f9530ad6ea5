"""Quantization primitives for pQuant (paper §3.1, Eq. 3-10).

All training-time quantizers are *fake-quant*: they return values in the
original float dtype but restricted to the quantization grid, and carry a
straight-through estimator (STE) so gradients flow to the latent weights.

The inference-time (packed, integer) path lives in ``repro.core.packing``
and ``repro.kernels``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.telemetry import probes

Array = jax.Array

# Small epsilon used throughout to avoid division by zero in scale
# computation (paper's `eps` in Eq. 7 guards the clip range instead; we fold
# it into the scale denominator, which is equivalent and cheaper).
EPS = 1e-5

INT8_QMAX = 127.0  # paper uses [-2^7, 2^7]; we clip to the representable 127


# ---------------------------------------------------------------------------
# Straight-through estimator
# ---------------------------------------------------------------------------


@jax.custom_vjp
def ste(x: Array, x_quant: Array) -> Array:
    """Return ``x_quant`` in the forward pass, d/dx = identity in backward.

    Canonical STE: the quantizer is treated as the identity for gradient
    purposes (paper Appendix B.1).
    """
    return x_quant


def _ste_fwd(x, x_quant):
    return x_quant, None


def _ste_bwd(_, g):
    return g, None


ste.defvjp(_ste_fwd, _ste_bwd)


def ste_round(x: Array) -> Array:
    """round() with identity gradient."""
    return ste(x, jnp.round(x))


def ste_sign(x: Array) -> Array:
    """sign() mapped to {-1, +1} with identity gradient.

    ``jnp.sign(0) == 0`` would create a third level; the paper's Eq. 4 only
    defines +-1, so we map 0 -> +1 (measure-zero under continuous latents).
    """
    s = jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)
    return ste(x, s)


# ---------------------------------------------------------------------------
# Weight quantizers
# ---------------------------------------------------------------------------


def binarize_weights(w: Array) -> tuple[Array, Array]:
    """1-bit weight fake-quant (paper Eq. 3-6).

    W_int1 = Sign(W - mu),   mu = mean(W),   lambda = mean(|W|)

    Returns ``(w_q, lam)`` where ``w_q`` contains +-lambda values (the
    dequantized 1-bit weights, still in float dtype) and ``lam`` is the
    per-tensor AbsMean scale.  The +-1 integer view is ``w_q / lam``.
    """
    mu = jnp.mean(w)
    lam = jnp.mean(jnp.abs(w)) + EPS
    signs = ste_sign(w - mu)
    return signs * lam, lam


def binarize_weights_grouped(w: Array, group_size: int) -> tuple[Array, Array]:
    """Group-wise 1-bit quantization (paper §4.6 ablation, groups of 64).

    Groups run along the last (input-feature) axis.  One fp scale per group:
    better accuracy, 16-bit metadata per ``group_size`` weights (the paper
    notes this is hardware-unfriendly; we keep it as an ablation).
    """
    *lead, k = w.shape
    assert k % group_size == 0, f"{k=} not divisible by {group_size=}"
    wg = w.reshape(*lead, k // group_size, group_size)
    mu = jnp.mean(wg, axis=-1, keepdims=True)
    lam = jnp.mean(jnp.abs(wg), axis=-1, keepdims=True) + EPS
    signs = ste_sign(wg - mu)
    return (signs * lam).reshape(w.shape), lam.squeeze(-1)


def binarize_weights_channelwise(w: Array) -> tuple[Array, Array]:
    """Channel-wise (per output column) 1-bit quantization (paper §4.6)."""
    mu = jnp.mean(w, axis=0, keepdims=True)
    lam = jnp.mean(jnp.abs(w), axis=0, keepdims=True) + EPS
    signs = ste_sign(w - mu)
    return signs * lam, lam.squeeze(0)


def binarize_weights_stacked(w: Array, n_batch_axes: int = 1) -> tuple[Array, Array]:
    """Per-slice 1-bit quantization for stacked (e.g. per-expert) weights.

    w: (N..., d_in, d_out) with ``n_batch_axes`` leading stack axes; mu and
    lambda are computed per slice so each expert keeps its own scale.
    """
    red = tuple(range(n_batch_axes, w.ndim))
    mu = jnp.mean(w, axis=red, keepdims=True)
    lam = jnp.mean(jnp.abs(w), axis=red, keepdims=True) + EPS
    signs = ste_sign(w - mu)
    return signs * lam, lam


def ternarize_weights_stacked(w: Array, n_batch_axes: int = 1) -> tuple[Array, Array]:
    """Per-slice ternary quantization for stacked weights."""
    red = tuple(range(n_batch_axes, w.ndim))
    lam = jnp.mean(jnp.abs(w), axis=red, keepdims=True) + EPS
    q = jnp.clip(ste_round(w / lam), -1.0, 1.0)
    return q * lam, lam


def quantize_weights_int8_stacked(w, n_batch_axes: int = 1) -> tuple[Array, Array]:
    """Per-slice INT8 AbsMax for stacked weights.  Accepts the serving dict
    layout ({"q": int8, "scale"}), in which case it dequantizes directly."""
    if isinstance(w, dict):
        return _dequant_stored(w), w["scale"]
    red = tuple(range(n_batch_axes, w.ndim))
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    scale = INT8_QMAX / (amax + EPS)
    q = jnp.clip(ste_round(w * scale), -INT8_QMAX, INT8_QMAX)
    return q / scale, scale


def fake_quant_stacked(w, cfg: "QuantConfig", n_batch_axes: int = 1) -> Array:
    """Backbone quantizer for stacked (per-expert) weights."""
    if isinstance(w, dict):
        return _dequant_stored(w)
    if cfg.mode == "none":
        return w
    if cfg.mode == "bitnet158":
        return ternarize_weights_stacked(w, n_batch_axes)[0]
    return binarize_weights_stacked(w, n_batch_axes)[0]


def ternarize_weights(w: Array) -> tuple[Array, Array]:
    """BitNet-1.58 ternary {-1, 0, +1} AbsMean quantization (baseline).

    W_q = RoundClip(W / mean(|W|), -1, 1) * mean(|W|)
    """
    lam = jnp.mean(jnp.abs(w)) + EPS
    q = jnp.clip(ste_round(w / lam), -1.0, 1.0)
    return q * lam, lam


def quantize_weights_int8(w: Array, axis: Optional[int] = None) -> tuple[Array, Array]:
    """INT8 AbsMax weight fake-quant for the high-precision branch.

    The paper quantizes the 8-bit branch "identically to 8-bit activations"
    (AbsMax, Eq. 7-9).  ``axis=None`` gives a per-tensor scale; pass an axis
    for per-channel.
    """
    if axis is None:
        amax = jnp.max(jnp.abs(w))
    else:
        amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = INT8_QMAX / (amax + EPS)
    q = jnp.clip(ste_round(w * scale), -INT8_QMAX, INT8_QMAX)
    return q / scale, scale


# ---------------------------------------------------------------------------
# Activation quantizer
# ---------------------------------------------------------------------------


def act_scale_int8(x: Array) -> Array:
    """Per-token AbsMax INT8 scale: gamma = 127 / (max|x| + eps) along the
    feature (last) axis, computed in float32.

    The SINGLE source of truth for activation quantization scales: the
    fake-quant trainer path (:func:`quantize_activations_int8`), the
    runtime integer path (:func:`quantize_act_int8`, re-exported by
    ``repro.kernels.ops``) and the fused kernel prologues
    (``w1a8_gemv._quant_prologue``, ``rmsnorm_quant``) all compute exactly
    this — float32 amax, ``INT8_QMAX / (amax + EPS)`` — so packed-vs-
    fake-quant parity cannot drift in bf16 (bf16 amax used to round
    differently from the kernels' f32 amax).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return INT8_QMAX / (amax + EPS)


def quantize_activations_int8(x: Array) -> tuple[Array, Array]:
    """Per-token AbsMax INT8 activation fake-quant (paper Eq. 7-9).

    gamma = 127 / max|x| along the feature (last) axis, per token
    (:func:`act_scale_int8`).  Returns ``(x_q, gamma)`` with
    ``x_q = RoundClip(x * gamma) / gamma`` in the input dtype.
    """
    gamma = act_scale_int8(x)
    q = jnp.clip(ste_round(x.astype(jnp.float32) * gamma), -INT8_QMAX, INT8_QMAX)
    if probes.active():
        # saturation fraction at the INT8 rails, weighted by element count
        # so summaries() yields the global rate across all tap sites
        probes.add_mean(
            "clip_act", jnp.mean(jnp.abs(q) >= INT8_QMAX), float(x.size)
        )
    return (q / gamma).astype(x.dtype), gamma


def quantize_act_int8(x: Array) -> tuple[Array, Array]:
    """Per-token AbsMax INT8 (runtime, true-integer path).

    Same grid as :func:`quantize_activations_int8` (one
    :func:`act_scale_int8` source of truth), but returns the int8 tensor
    and a flat per-row gamma for the kernel epilogues.
    """
    gamma = act_scale_int8(x)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) * gamma), -INT8_QMAX, INT8_QMAX
    )
    return q.astype(jnp.int8), gamma[..., 0]


# ---------------------------------------------------------------------------
# Quantization mode config
# ---------------------------------------------------------------------------

QuantMode = Literal["none", "bitnet", "bitnet158", "pquant"]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Selects the quantization scheme for a whole model.

    mode:
      none       FP16/BF16 baseline (LLaMA-class).
      bitnet     all linear layers 1-bit W1A8 (BitNet baseline).
      bitnet158  all linear layers ternary W1.58A8 (BitNet-1.58 baseline).
      pquant     MHA 1-bit; FFN decoupled 1-bit + r-wide INT8 branch(es).
    r:           width of the 8-bit branch (per paper Table 1; multiples of 128).
    num_experts: N routable 8-bit branches (paper §3.3); 1 = single branch.
    alpha_init / beta_init: feature-scaling init (paper §3.2: alpha >> beta).
    act_bits:    activation precision (8 everywhere in the paper).
    weight_scheme: per-tensor | channelwise | groupwise (paper §4.6 ablations).
    group_size:  group width for groupwise.
    native_mix_frac: if > 0, run the "Native Mix" ablation (paper Fig. 7):
                 keep this fraction of *1-bit* weights in high precision
                 in-place instead of the decoupled branch.
    """

    mode: QuantMode = "pquant"
    r: int = 128
    num_experts: int = 1
    alpha_init: float = 2.0
    beta_init: float = 0.2
    act_bits: int = 8
    weight_scheme: Literal["tensor", "channel", "group"] = "tensor"
    group_size: int = 64
    native_mix_frac: float = 0.0
    # beyond-paper: all-gather FSDP weight shards as INT8 signs instead of
    # fp latents (repro.distributed.qgather); measured in EXPERIMENTS §Perf
    qgather: bool = False

    @property
    def quantize_acts(self) -> bool:
        return self.mode != "none"

    def binarize(self, w: Array) -> tuple[Array, Array]:
        if self.weight_scheme == "channel":
            return binarize_weights_channelwise(w)
        if self.weight_scheme == "group":
            return binarize_weights_grouped(w, self.group_size)
        return binarize_weights(w)


def _dequant_stored(w: dict) -> Array:
    """Dequantize a serving-format weight: {"q": int8, "scale": f32} or
    {"packed": uint8 (..., K//8, N), "scale": f32} (see
    train/quantized_serving; leading axes are layer/expert stacks).
    The integer tensor is what lives in HBM — this is the paper's deployment
    layout (§A) expressed in the compiled artifact.

    This float fallback is only for paths without a packed kernel (training
    utilities, routed 8-bit experts); the model forward dispatches packed
    layouts to ``repro.kernels.ops`` (``bit_linear_infer`` /
    ``decoupled_first_gemm`` / ``int8_linear_infer``) instead.  A packed
    K may carry the export's padding rows (``core.packing.padded_k``)."""
    if "packed" in w:
        from repro.core.packing import unpack_signs

        signs = unpack_signs(w["packed"], jnp.int8)
        return signs.astype(w["scale"].dtype) * w["scale"]
    return w["q"].astype(w["scale"].dtype) * w["scale"]


def is_packed_1bit(w) -> bool:
    """True for the bit-packed 1-bit serving layout {"packed", "scale"}
    consumable by ``ops.bit_linear_infer`` / ``ops.decoupled_first_gemm``."""
    return isinstance(w, dict) and "packed" in w


def is_stored_int8(w) -> bool:
    """True for the INT8 serving layout {"q", "scale"} (8-bit branch, or the
    1-bit sign fallback when K isn't byte-aligned)."""
    return isinstance(w, dict) and "q" in w


def fake_quant_linear_weights(w, cfg: QuantConfig) -> Array:
    """Apply the configured *backbone* weight quantizer (1-bit or ternary).
    Accepts either a latent float tensor (training fake-quant) or the
    pre-quantized serving dict layout."""
    if isinstance(w, dict):
        return _dequant_stored(w)
    if cfg.mode == "none":
        return w
    if cfg.mode == "bitnet158":
        return ternarize_weights(w)[0]
    return cfg.binarize(w)[0]


def maybe_quant_acts(x: Array, cfg: QuantConfig) -> Array:
    if not cfg.quantize_acts:
        return x
    return quantize_activations_int8(x)[0]


# ---------------------------------------------------------------------------
# Effective bits-per-weight accounting (paper reports 1.28 / 1.35 bit)
# ---------------------------------------------------------------------------


def effective_bits(n_1bit: int, n_8bit: int, n_fp16: int = 0) -> float:
    """Weighted average bits/weight across parameter populations."""
    total = n_1bit + n_8bit + n_fp16
    if total == 0:
        return 0.0
    return (n_1bit * 1.0 + n_8bit * 8.0 + n_fp16 * 16.0) / total
