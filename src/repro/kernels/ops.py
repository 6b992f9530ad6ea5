"""Jit'd public wrappers around the Pallas kernels.

Handles: CPU fallback (interpret=True so the kernel *body* is executed and
validated on CPU), ragged-shape padding to tile multiples, the
quantize -> kernel -> output plumbing used by the serving path
(``repro.train.serve`` W1A8 inference), and shape-keyed dispatch between
the prefill-tiled kernels and the decode GEMV tier:

* M <= DECODE_M_MAX (decode/GEMV regime): route to ``w1a8_gemv`` /
  ``decoupled_gemv`` — activation quantization fused into the kernel
  prologue, M padded only to the 8-row sublane minimum, wide-bn (N, K)
  grid for maximum packed-weight streaming.
* M > DECODE_M_MAX (prefill/train regime): the existing M-tiled kernels
  behind a separate ``quantize_act_int8`` pass.

Mosaic tiling: K tiles are multiples of 256 (packed planar bit groups and
uint8 sublane tiles) or the whole K; the serving export pads a longer
packed K to a multiple of 256 (``core.packing.padded_k``) and the
dispatchers zero-pad activations to match, so padded rows add nothing.  N
tiles are multiples of 128 or the whole N; a ragged N runs a partial last
tile whose extra columns the kernel never stores.

Tile sizes for the decode tier come from a per-(M, K, N) dispatch table:
``decode_tiles`` answers from divisor heuristics, and ``sweep_decode_tiles``
runs a timed sweep on the current backend and caches the winner under the
same signature so later calls (and jit retraces) pick it up.  Swept
winners are also mirrored to a per-backend JSON file
(``repro.kernels.tile_cache``) loaded on the first lookup, so autotuning
survives process restarts.

The paged-attention family (``paged_attention`` + ``paged_tiles`` /
``sweep_paged_tiles`` + the ``paged_attention_enabled`` /
``paged_attention_supported`` dispatch gates) lives at the bottom of this
module: ``models.attention._paged_scores`` routes the serving stack's
paged-KV branches here, keeping the ``kv_pool.read`` gather + SDPA path
as fallback and parity oracle.
"""

from __future__ import annotations

import functools
import logging
import os
import re
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantization import quantize_act_int8  # noqa: F401  (re-export:
# the single act-quant source of truth lives in core.quantization)
from repro.distributed import sharding as _sharding
from repro.kernels import ref, tile_cache
from repro.kernels.decoupled_matmul import decoupled_matmul
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import paged_attention as _paged_attention
from repro.kernels.rmsnorm_quant import rmsnorm_quant
from repro.kernels.w1a8_gemv import decoupled_gemv, w1a8_gemv
from repro.kernels.w1a8_matmul import w1a8_matmul

Array = jax.Array

# Largest flattened row count routed to the decode GEMV tier.  Decode serves
# one token per request, so M = batch; 32 covers the batched-decode regime
# while anything larger amortizes like prefill.
DECODE_M_MAX = 32

# (op, m, k, n) -> (bk, bn): filled by sweep_decode_tiles (and, lazily, by
# the on-disk per-backend cache); consulted before the divisor heuristic so
# an autotuned signature sticks for the process.
_DECODE_TILE_CACHE: dict[tuple, tuple[int, int]] = {}
_TILE_CACHE_LOADED = False


def _ensure_tile_cache_loaded() -> None:
    """Merge persisted winners on first use (in-process entries win).
    Lazy so importing ops never forces jax backend initialisation."""
    global _TILE_CACHE_LOADED
    if _TILE_CACHE_LOADED:
        return
    _TILE_CACHE_LOADED = True
    for key, tiles in tile_cache.load(jax.default_backend()).items():
        _DECODE_TILE_CACHE.setdefault(key, tiles)

log = logging.getLogger(__name__)

# K tiles of the packed kernels; int8 weights may also take 128.
_BK_CANDIDATES = (1024, 512, 256)
_BK_INT8_CANDIDATES = (1024, 512, 256, 128)
_BN_CANDIDATES = (512, 256, 128)


def _annotate(name: str):
    """Profiler span (``repro.serve.tracing.annotate``) around a kernel
    dispatch site — host-timeline TraceAnnotation + named_scope so kernel
    time is attributable by name in a profiler trace.  Imported lazily:
    the kernel tier stays importable without the serving layer, and the
    context manager runs at trace time, never per decode step."""
    from repro.serve.tracing import annotate

    return annotate(name)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_KERNEL_RE = re.compile(
    r"%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = [^\n]*"
    r'custom_call_target="tpu_custom_call"')


def pallas_kernels(compiled_text: str) -> set[str]:
    """Names of the Pallas kernels in a compiled TPU program's text
    (``jit(f).lower(...).compile().as_text()``): each kernel is a
    ``tpu_custom_call`` named after its jitted wrapper (``w1a8_gemv``,
    ``paged_attention``, ...).  Empty for an interpret-mode program."""
    return set(_KERNEL_RE.findall(compiled_text))


def _pad_rows(x: Array, mult: int):
    m = x.shape[0]
    pad = (-m) % mult
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, m


def _pad_gamma(gamma: Array, mult: int) -> Array:
    """Pad per-token scales with ONES, not zeros: kernel epilogues divide by
    gamma, and a zero-padded row would compute 1/0 * 0 = NaN before the
    [:m] slice drops it."""
    pad = (-gamma.shape[0]) % mult
    if pad:
        gamma = jnp.pad(gamma, ((0, pad),), constant_values=1.0)
    return gamma


# ---------------------------------------------------------------------------
# Decode-tier tile dispatch / autotune
# ---------------------------------------------------------------------------


def _k_tile(k: int, candidates=_BK_CANDIDATES) -> int:
    """Widest candidate that divides K, else the whole K."""
    for c in candidates:
        if c <= k and k % c == 0:
            return c
    return k


def _n_tile(n: int, r: int | None = None, candidates=_BN_CANDIDATES) -> int:
    """Widest candidate (>= r when given) that divides N; else the whole N
    when it fits the widest candidate; else the widest candidate, run as a
    partial last tile."""
    lo = r or 1
    for c in candidates:
        if lo <= c <= n and n % c == 0:
            return c
    if n <= max(candidates[0], lo):
        return n
    return max(candidates[0], -(-lo // 128) * 128)


def _pad_k(x: Array, k: int) -> Array:
    """Zero-pad activations (M, K') to the weight's (padded) K."""
    pad = k - x.shape[-1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _tile_key(op: str, m: int, k: int, n: int, r: int | None):
    # r is part of the decoupled signature: the same (m, k, n) with a
    # different 8-bit branch width is a different kernel launch.
    return (op, m, k, n) if r is None else (op, m, k, n, r)


def decode_tiles(m: int, k: int, n: int, op: str = "w1a8_gemv",
                 r: int | None = None):
    """(bk, bn) for a decode-shaped call: autotuned entry if one was swept
    (this process or a persisted earlier one), otherwise the Mosaic-legal
    heuristic tiles (``_k_tile`` / ``_n_tile``).  For the decoupled op, bn
    always fits the 8-bit branch (bn >= r)."""
    _ensure_tile_cache_loaded()
    cached = _DECODE_TILE_CACHE.get(_tile_key(op, m, k, n, r))
    if cached is not None:
        tile_cache.record_hit()
        return cached
    tile_cache.record_miss()
    return _k_tile(k), _n_tile(n, r)


def sweep_decode_tiles(
    m: int,
    k: int,
    n: int,
    *,
    op: str = "w1a8_gemv",
    r: int | None = None,
    bk_candidates=None,
    bn_candidates=None,
    warmup: int = 1,
    iters: int = 3,
    seed: int = 0,
):
    """Time the decode kernel over candidate (bk, bn) tiles on the current
    backend, cache the winner per (m, k, n[, r]) signature, and return it.

    M is normalized to the 8-row padded shape the dispatcher actually
    launches, so a sweep for batch 4 is found by the batch-4 inference call.
    op selects the kernel: "w1a8_gemv" or "decoupled_gemv" (r = the 8-bit
    branch width to sweep with).  The sweep runs whatever backend is active
    (interpret on CPU, compiled on TPU) — call it once per decode signature
    at server start-up; subsequent calls with that signature use the cache.
    Winners are mirrored to the per-backend on-disk cache
    (``repro.kernels.tile_cache``), so later processes skip the sweep.
    On TPU a candidate the compiler refuses is counted and logged, and a
    sweep in which no candidate compiles raises.
    """
    import numpy as np

    if op == "decoupled_gemv" and r is None:
        raise ValueError("decoupled_gemv sweeps need r (8-bit branch width)")
    sweep_t0 = time.perf_counter()
    m_p = m + (-m) % 8  # the shape _bit_linear_decode pads to and looks up
    key = _tile_key(op, m_p, k, n, r if op == "decoupled_gemv" else None)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m_p, k)).astype(np.float32))
    wp = jnp.asarray(rng.integers(0, 256, (k // 8, n)).astype(np.uint8))
    lam = jnp.asarray(np.float32(0.05))
    interp = not on_tpu()
    if op == "decoupled_gemv":
        w8 = jnp.asarray(rng.integers(-127, 128, (k, r)).astype(np.int8))
        scales = [jnp.asarray(np.float32(v)) for v in (2.0, 1.0, 1.0)]

        def call(bk, bn):
            return decoupled_gemv(
                x, wp, w8, lam, *scales, bk=bk, bn=bn, interpret=interp
            )[0]
    else:
        def call(bk, bn):
            return w1a8_gemv(x, wp, lam, bk=bk, bn=bn, interpret=interp)

    rr = r if op == "decoupled_gemv" else None
    bks = sorted({c for c in (bk_candidates or _BK_CANDIDATES)
                  if c % 8 == 0 and c <= k and k % c == 0} | {_k_tile(k)},
                 reverse=True)
    bns = sorted({c for c in (bn_candidates or _BN_CANDIDATES)
                  if c <= n and n % c == 0 and c >= (rr or 1)}
                 | {_n_tile(n, rr)}, reverse=True)
    best = _timed_best(
        [(bk, bn) for bk in bks for bn in bns],
        lambda c: call(*c), warmup, iters, key,
    )
    _DECODE_TILE_CACHE[key] = best
    tile_cache.store(jax.default_backend(), {key: best})
    tile_cache.record_sweep_ms((time.perf_counter() - sweep_t0) * 1e3)
    return best


def _timed_best(candidates, call, warmup: int, iters: int, key):
    """The fastest candidate of a sweep.  A candidate that raises loses;
    on TPU the refusals are counted and logged.  A sweep where none runs
    raises (the heuristic tile is always a candidate)."""
    best, best_t, refused = None, float("inf"), []
    for c in candidates:
        try:
            for _ in range(warmup):
                jax.block_until_ready(call(c))
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(call(c))
                ts.append(time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 — refused candidates are counted
            refused.append((c, e))
            continue
        if min(ts) < best_t:
            best, best_t = c, min(ts)
    if refused and on_tpu():
        log.warning("tile sweep %s: %d of %d candidates refused: %s", key,
                    len(refused), len(candidates),
                    [c for c, _ in refused])
    if best is None:
        raise RuntimeError(f"tile sweep {key}: no candidate compiled") from (
            refused[-1][1] if refused else None)
    return best


# ---------------------------------------------------------------------------
# Inference linears (shape-dispatched)
# ---------------------------------------------------------------------------


def _prefill_tiles(k: int, n: int, r: int | None = None,
                   k_candidates=_BK_CANDIDATES):
    """(bk, bn) for the M-tiled prefill kernels: Mosaic-legal tiles
    (``_k_tile`` / ``_n_tile``); with ``r`` set, bn also fits the 8-bit
    branch (bn >= r)."""
    return _k_tile(k, k_candidates), _n_tile(n, r)


def _bit_linear_prefill(xf: Array, w_packed: Array, lam: Array, out_dtype):
    """Prefill-tiled path: XLA act-quant pass + M-tiled w1a8_matmul."""
    xq, gamma = quantize_act_int8(xf)
    xq = _pad_k(xq, w_packed.shape[0] * 8)
    bm = 8 if xq.shape[0] <= 128 else 128
    xq, m = _pad_rows(xq, bm)
    gamma_p = _pad_gamma(gamma, bm)
    bk, bn = _prefill_tiles(xq.shape[1], w_packed.shape[1])
    with _annotate("kernels/w1a8_matmul"):
        y = w1a8_matmul(
            xq, w_packed, gamma_p, lam,
            bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=not on_tpu(),
        )
    return y[:m]


def _bit_linear_decode(xf: Array, w_packed: Array, lam: Array, out_dtype):
    """Decode GEMV path: act-quant fused into the kernel prologue."""
    xp, m = _pad_rows(_pad_k(xf, w_packed.shape[0] * 8), 8)
    bk, bn = decode_tiles(xp.shape[0], xp.shape[1], w_packed.shape[1])
    with _annotate("kernels/w1a8_gemv"):
        y = w1a8_gemv(
            xp, w_packed, lam,
            bk=bk, bn=bn, out_dtype=out_dtype, interpret=not on_tpu(),
        )
    return y[:m]


def bit_linear_infer(
    x: Array, w_packed: Array, lam: Array, out_dtype=jnp.bfloat16
) -> Array:
    """Full W1A8 inference linear: quantize acts -> packed 1-bit matmul.

    x: (..., K) float; w_packed: (Kp//8, N) uint8 with Kp >= K (the
    export's padded K; activations are zero-padded to it); lam: scalar.
    Decode shapes (M <= DECODE_M_MAX flattened rows) take the fused GEMV
    tier; larger M takes the prefill-tiled kernel.
    """
    fn = functools.partial(_bit_linear_local, out_dtype=out_dtype)
    return _island(fn, (x, w_packed, lam))


def _bit_linear_local(x: Array, w_packed: Array, lam: Array, out_dtype):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if xf.shape[0] <= DECODE_M_MAX:
        y = _bit_linear_decode(xf, w_packed, lam, out_dtype)
    else:
        y = _bit_linear_prefill(xf, w_packed, lam, out_dtype)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# Tensor-parallel (N-major) kernel islands
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition a Mosaic kernel: on TPU a pallas_call in a program
# over several devices must sit inside a ``shard_map``.  Every public
# dispatcher therefore runs its kernel in an island over the active mesh
# (``_island``; a no-op without a mesh or on one device).  The plain ones
# take every operand replicated.  The ``*_nshard`` wrappers take x / scales
# replicated and the weight N-major-sharded,
# and each device runs the SAME kernel on its local (K, N/ws) shard — no
# collective inside the island, the dot-product reduction is never split,
# so per-shard outputs are bitwise slices of the unsharded result.  Because
# the kernel body sees the LOCAL shapes, the tile-dispatch keys
# (``_tile_key(op, m, k, n_local)``) become per-shard automatically — a
# swept winner on one shard width never collides with the full-width entry.


def _rep(ndim: int) -> P:
    return P(*([None] * ndim))


def _island(fn, args, in_specs=None, out_specs=None):
    """``fn(*args)``, inside a ``shard_map`` over the active mesh when it
    spans several devices.  Specs default to replicated."""
    mesh = _sharding.active_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    if in_specs is None:
        in_specs = tuple(_rep(jnp.ndim(a)) for a in args)
    if out_specs is None:
        out_specs = jax.tree.map(lambda o: _rep(o.ndim),
                                 jax.eval_shape(fn, *args))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _nshard(ndim: int, axis: str) -> P:
    return P(*([None] * (ndim - 1) + [axis]))


def bit_linear_infer_nshard(
    x: Array, w_packed: Array, lam: Array, axis: str, out_dtype=jnp.bfloat16
) -> Array:
    """:func:`bit_linear_infer` with ``w_packed`` sharded N-major over mesh
    axis ``axis`` (callers decide via ``sharding.nmajor_axis``).  ``lam`` is
    the per-weight AbsMean scalar — replicated, so every shard dequantizes
    with the same scale (per-shard scales == the full scale)."""
    fn = functools.partial(_bit_linear_local, out_dtype=out_dtype)
    return _island(
        fn, (x, w_packed, lam),
        (_rep(x.ndim), _nshard(2, axis), _rep(lam.ndim)),
        _nshard(x.ndim, axis),
    )


def int8_linear_infer_nshard(
    x: Array, w_q: Array, wscale: Array, axis: str, out_dtype=jnp.bfloat16
) -> Array:
    """:func:`int8_linear_infer` with ``w_q`` sharded N-major; the AbsMax
    weight scale is a replicated scalar, shared by every shard."""
    fn = functools.partial(_int8_linear_local, out_dtype=out_dtype)
    return _island(
        fn, (x, w_q, wscale),
        (_rep(x.ndim), _nshard(2, axis), _rep(wscale.ndim)),
        _nshard(x.ndim, axis),
    )


def decoupled_first_gemm_nshard(
    x: Array,
    w1_packed: Array,
    w8_q: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    axis: str,
    out_dtype=jnp.bfloat16,
):
    """:func:`decoupled_first_gemm` with the 1-bit trunk sharded N-major.
    The r-narrow 8-bit branch stays replicated (``ffn8`` maps to no mesh
    axis under the serving rules), so y1 comes out sharded and y8 comes out
    replicated."""
    fn = functools.partial(_decoupled_first_gemm_local, out_dtype=out_dtype)
    return _island(
        fn, (x, w1_packed, w8_q, lam, w8scale, alpha, beta),
        (
            _rep(x.ndim), _nshard(2, axis), _rep(2), _rep(lam.ndim),
            _rep(w8scale.ndim), _rep(alpha.ndim), _rep(beta.ndim),
        ),
        (_nshard(x.ndim, axis), _rep(x.ndim)),
    )


def int8_linear_infer(
    x: Array, w_q: Array, wscale: Array, out_dtype=jnp.bfloat16
) -> Array:
    """Full W8A8 inference linear (8-bit branch)."""
    fn = functools.partial(_int8_linear_local, out_dtype=out_dtype)
    return _island(fn, (x, w_q, wscale))


def _int8_linear_local(x: Array, w_q: Array, wscale: Array, out_dtype):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    xq, gamma = quantize_act_int8(xf)
    bm = 8 if xq.shape[0] <= 128 else 128
    xq, m = _pad_rows(xq, bm)
    gamma_p = _pad_gamma(gamma, bm)
    bk, bn = _prefill_tiles(xf.shape[1], w_q.shape[1],
                            k_candidates=_BK_INT8_CANDIDATES)
    with _annotate("kernels/int8_matmul"):
        y = int8_matmul(
            xq, w_q, gamma_p, wscale, bm=bm, bk=bk, bn=bn,
            out_dtype=out_dtype, interpret=not on_tpu(),
        )
    return y[:m].reshape(*lead, -1)


def fused_rmsnorm_quant(x: Array, scale: Array):
    """(..., D) -> (int8 (..., D), gamma (...,))."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    bm = 8 if xf.shape[0] <= 256 else 256
    xp, m = _pad_rows(xf, bm)
    q, gamma = rmsnorm_quant(xp, scale, bm=bm, interpret=not on_tpu())
    return q[:m].reshape(*lead, -1), gamma[:m].reshape(lead)


def _decoupled_prefill(
    xf, w1_packed, w8_q, lam, w8scale, alpha, beta, out_dtype
):
    xq, gamma = quantize_act_int8(xf)
    xq = _pad_k(xq, w1_packed.shape[0] * 8)
    bm = 8 if xq.shape[0] <= 128 else 128
    xq, m = _pad_rows(xq, bm)
    gamma_p = _pad_gamma(gamma, bm)
    w8_q, _ = _pad_rows(w8_q, xq.shape[1])  # zero rows meet zero acts
    r = w8_q.shape[1]
    bk, bn = _prefill_tiles(xq.shape[1], w1_packed.shape[1], r=r)
    with _annotate("kernels/decoupled_matmul"):
        y1, y8 = decoupled_matmul(
            xq, w1_packed, w8_q, gamma_p, lam, w8scale, alpha, beta,
            bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=not on_tpu(),
        )
    return y1[:m], y8[:m]


def _decoupled_decode(
    xf, w1_packed, w8_q, lam, w8scale, alpha, beta, out_dtype
):
    xp, m = _pad_rows(_pad_k(xf, w1_packed.shape[0] * 8), 8)
    w8_q, _ = _pad_rows(w8_q, xp.shape[1])  # zero rows meet zero acts
    k, n, r = xp.shape[1], w1_packed.shape[1], w8_q.shape[1]
    bk, bn = decode_tiles(xp.shape[0], k, n, op="decoupled_gemv", r=r)
    with _annotate("kernels/decoupled_gemv"):
        y1, y8 = decoupled_gemv(
            xp, w1_packed, w8_q, lam, w8scale, alpha, beta,
            bk=bk, bn=bn, out_dtype=out_dtype, interpret=not on_tpu(),
        )
    return y1[:m], y8[:m]


def decoupled_first_gemm(
    x: Array,
    w1_packed: Array,
    w8_q: Array,
    lam: Array,
    w8scale: Array,
    alpha: Array,
    beta: Array,
    out_dtype=jnp.bfloat16,
):
    """Fused dual-branch up-projection for serving: reads activations once.

    Returns (y1 (..., N), y8 (..., R)), each pre-scaled by beta / alpha.
    Decode shapes route to the fused-act-quant ``decoupled_gemv``.
    """
    fn = functools.partial(_decoupled_first_gemm_local, out_dtype=out_dtype)
    return _island(fn, (x, w1_packed, w8_q, lam, w8scale, alpha, beta))


def _decoupled_first_gemm_local(
    x, w1_packed, w8_q, lam, w8scale, alpha, beta, out_dtype
):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if xf.shape[0] <= DECODE_M_MAX:
        y1, y8 = _decoupled_decode(
            xf, w1_packed, w8_q, lam, w8scale, alpha, beta, out_dtype
        )
    else:
        y1, y8 = _decoupled_prefill(
            xf, w1_packed, w8_q, lam, w8scale, alpha, beta, out_dtype
        )
    return y1.reshape(*lead, -1), y8.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# Paged attention (block-table attention over the serving KV pool)
# ---------------------------------------------------------------------------

# pages-per-step candidates for the paged-attention autotune: how many pool
# pages one grid step scores (the per-step KV tile is pages * block_size
# columns wide)
_PAGES_CANDIDATES = (8, 4, 2, 1)


def paged_attention_enabled() -> bool:
    """Whether the model stack's paged branches dispatch the Pallas kernel.

    ``REPRO_PAGED_ATTN=1`` forces it on (interpret mode off-TPU — the
    parity/bench configuration), ``=0`` forces the gather+SDPA fallback,
    and the default (``auto``) enables it on TPU only: off-TPU the
    interpreted kernel is a correctness tool, not a fast path, and the
    serving parity suites rely on the fallback's bitwise-dense numerics.
    """
    v = os.environ.get("REPRO_PAGED_ATTN", "auto")
    if v == "0":
        _log_once(("paged_attn_off",),
                  "REPRO_PAGED_ATTN=0: paged attention runs the gather path")
        return False
    if v == "1":
        return True
    return on_tpu()


_LOGGED: set = set()


def _log_once(key, msg: str, *args) -> None:
    """Warn once per key on TPU, where a fallback costs real time."""
    if on_tpu() and key not in _LOGGED:
        _LOGGED.add(key)
        log.warning(msg, *args)


def paged_attention_supported(
    block_size: int, head_dim: int, n_q_heads: int, n_kv_heads: int,
    dtype=jnp.float32,
) -> bool:
    """Static shape gate for the kernel (callers fall back on False):
    GQA grouping must divide evenly, a page (block_size rows of one head)
    must fill whole sublane tiles of the pool dtype (8 rows for f32, 16 for
    bf16), and head_dim must be 8-aligned.  On TPU a refused shape is
    logged once, so a gather fallback is visible."""
    rows = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    ok = (
        n_q_heads % n_kv_heads == 0
        and block_size % rows == 0
        and head_dim % 8 == 0
    )
    if not ok:
        shape = (block_size, head_dim, n_q_heads, n_kv_heads,
                 jnp.dtype(dtype).name)
        _log_once(("paged_attn", shape),
                  "paged attention kernel refuses (block_size, head_dim, "
                  "n_q_heads, n_kv_heads, dtype)=%s; using the gather path",
                  shape)
    return ok


def paged_tiles(
    t: int, hq: int, hkv: int, d: int, bs: int, mb: int
) -> int:
    """pages-per-step for a paged-attention call: the autotuned winner if
    one was swept (this process or a persisted earlier one), otherwise the
    widest candidate that divides the table width (no wasted tail step)."""
    _ensure_tile_cache_loaded()
    cached = _DECODE_TILE_CACHE.get(("paged_attn", t, hq, hkv, d, bs, mb))
    if cached is not None:
        tile_cache.record_hit()
        return int(cached[0])
    tile_cache.record_miss()
    for c in _PAGES_CANDIDATES:
        if c <= mb and mb % c == 0:
            return c
    return 1


def sweep_paged_tiles(
    t: int,
    hq: int,
    hkv: int,
    d: int,
    bs: int,
    mb: int,
    *,
    candidates=None,
    warmup: int = 1,
    iters: int = 3,
    seed: int = 0,
) -> int:
    """Time the paged-attention kernel over pages-per-step candidates on
    the current backend, persist the winner under the
    ``(paged_attn, T, Hq, Hkv, D, block, max_blocks)`` signature (same
    per-backend JSON the GEMV tables use), and return it."""
    import numpy as np

    sweep_t0 = time.perf_counter()
    key = ("paged_attn", t, hq, hkv, d, bs, mb)
    rng = np.random.default_rng(seed)
    nb = 2 * mb
    q = jnp.asarray(rng.standard_normal((2, t, hq, d)).astype(np.float32))
    kp = jnp.asarray(
        rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
    )
    vp = jnp.asarray(
        rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
    )
    table = jnp.asarray(
        rng.permutation(nb)[: 2 * mb].reshape(2, mb).astype(np.int32)
    )
    # one full-context slot and one short one (both within capacity)
    s0 = max(mb * bs - t, 0)
    start = jnp.asarray([s0, min(bs, s0)], np.int32)
    lens = start + t
    interp = not on_tpu()
    best = _timed_best(
        [p for p in (candidates or _PAGES_CANDIDATES) if p <= mb],
        lambda p: _paged_attention(q, kp, vp, table, start, lens,
                                   pages=p, interpret=interp),
        warmup, iters, key,
    )
    _DECODE_TILE_CACHE[key] = (best,)
    tile_cache.store(jax.default_backend(), {key: (best,)})
    tile_cache.record_sweep_ms((time.perf_counter() - sweep_t0) * 1e3)
    return best


def paged_attention(
    q: Array,  # (B, T, Hq, D)
    kpool: Array,  # (NB, Hkv, BS, D)
    vpool: Array,  # (NB, Hkv, BS, D)
    table: Array,  # (B, MB) int32
    start: Array,  # (B,) int32 — absolute position of q[:, 0]
    kv_lens: Array,  # (B,) int32 — resident tokens per slot
    scale: float | None = None,
) -> Array:
    """Block-table attention over the paged KV pool (flash-decoding-style
    online softmax, GQA/MQA grouping; T=1 decode, T>1 chunk/prefill).

    The jit'd public wrapper: picks pages-per-step from the autotuned
    table (``paged_tiles`` / ``sweep_paged_tiles``) and runs interpreted
    off-TPU.  Callers gate on :func:`paged_attention_enabled` /
    :func:`paged_attention_supported` and keep the ``kv_pool.read``
    gather + SDPA path as fallback and parity oracle
    (``ref.paged_attention_ref``).  Under a multi-device mesh it runs per
    shard of KV heads, the pools' serving placement (``cache_heads``).
    """
    fn = functools.partial(_paged_attention_local, scale=scale)
    args = (q, kpool, vpool, table, start, kv_lens)
    mesh = _sharding.active_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    pool = _sharding.relaxed_spec(
        kpool.shape, (None, "cache_heads", None, None), mesh)
    heads = P(None, None, pool[1], None)
    return _island(fn, args, (heads, pool, pool, _rep(2), _rep(1), _rep(1)),
                   heads)


def _paged_attention_local(q, kpool, vpool, table, start, kv_lens, scale):
    t, hq, d = q.shape[1:]
    hkv, bs = kpool.shape[1], kpool.shape[2]
    mb = table.shape[1]
    pages = paged_tiles(t, hq, hkv, d, bs, mb)
    with _annotate("kernels/paged_attention"):
        return _paged_attention(
            q, kpool, vpool, table, start, kv_lens,
            pages=pages, scale=scale, interpret=not on_tpu(),
        )
