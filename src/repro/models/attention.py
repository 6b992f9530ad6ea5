"""Attention variants: GQA/MQA with optional sliding window, DeepSeek-V2 MLA,
and cross-attention (Whisper).  All projections are BitLinear (pure 1-bit,
paper §3.1) in quantized modes; on packed serving weights
(``quantize_params_for_serving(packed=True)``) every projection runs the
true-integer W1A8 kernel tier — decode-shaped calls hit the fused-act-quant
``w1a8_gemv`` (see ``core.bitlinear`` / ``kernels.ops``).

Cache-adapter protocol (serving): each layer owns a dict of cache arrays
and ``*_chunk`` extends it by T tokens at per-slot position offsets — the
single cache-resident forward the serving stack runs.  Prefill is a chunk
into an empty cache, decode is a chunk with T=1 (``*_decode`` is the
preserved one-token fast path the chunk entry points dispatch to).  Two
interchangeable layouts ride the same call sites:

* dense — ``{"k", "v"}`` ring buffers ``(B, L, H, D)`` (L < max_len on
  sliding-window layers; slot(p) = p % L *is* the window).
* paged — ``{"kpool", "vpool", "table"}`` from ``repro.serve.kv_pool``: a
  shared block pool plus per-slot block tables.  The ``"table"`` key is
  the layout discriminator.  Paged scoring dispatches to the Pallas
  block-table kernel (``kernels.paged_attention``, walks each slot's
  pages in place) when ``kernels.ops.paged_attention_enabled()``; the
  ``kv_pool.read`` gather + SDPA path remains the fallback and parity
  oracle (see :func:`_paged_scores`).

``pos`` may be the model-level scalar (lockstep decode: every slot at the
same position) or a ``(B,)`` vector (continuous batching: ragged slots).
``active`` is an optional ``(B,)`` bool mask — inactive (finished /
unoccupied) slots produce **no cache writes**, which is what makes block
reclamation safe while a chunk is still in flight.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.bitlinear import bitlinear, init_linear, init_rmsnorm, rmsnorm
from repro.distributed.sharding import shard_hint
from repro.models.layers import apply_rope, rope_table

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Standard GQA attention
# ---------------------------------------------------------------------------


def init_attention(key: Array, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 5)
    params, axes = {}, {}
    for name, k, di, do, ax in (
        ("wq", ks[0], d, nq * hd, ("embed", "heads")),
        ("wk", ks[1], d, nkv * hd, ("embed", "kv_heads")),
        ("wv", ks[2], d, nkv * hd, ("embed", "kv_heads")),
        ("wo", ks[3], nq * hd, d, ("heads", "embed")),
    ):
        p, a = init_linear(k, di, do, ax)
        params[name], axes[name] = p, a
    if cfg.quant.mode != "none":
        # SubLN ahead of the output projection (BitNet placement)
        p, a = init_rmsnorm(nq * hd, axis="heads")
        params["subln"], axes["subln"] = p, a
    return params, axes


def _project_qkv(params, x: Array, cfg: ModelConfig):
    b, s, d = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = bitlinear(params["wq"], x, cfg.quant, waxes=("embed", "heads")).reshape(b, s, nq, hd)
    k = bitlinear(params["wk"], x, cfg.quant, waxes=("embed", "kv_heads")).reshape(b, s, nkv, hd)
    v = bitlinear(params["wv"], x, cfg.quant, waxes=("embed", "kv_heads")).reshape(b, s, nkv, hd)
    q = shard_hint(q, "batch", "seq", "act_heads", None)
    k = shard_hint(k, "batch", "seq", "cache_heads", None)
    v = shard_hint(v, "batch", "seq", "cache_heads", None)
    return q, k, v


def _out_proj(params, attn_out: Array, cfg: ModelConfig) -> Array:
    b, s = attn_out.shape[:2]
    flat = attn_out.reshape(b, s, -1)
    # keep heads*head_dim model-sharded through SubLN + act-quant (see the
    # sharding note in core/decoupled._branch1_apply)
    flat = shard_hint(flat, "batch", "seq", "act_heads")
    subln = params.get("subln")
    return bitlinear(
        params["wo"], flat, cfg.quant, sublayer_norm=subln, waxes=("heads", "embed")
    )


def _sdpa(
    q: Array,
    k: Array,
    v: Array,
    mask: Optional[Array],
    scale: Optional[float] = None,
) -> Array:
    """Grouped scaled-dot-product attention.

    q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) with Hq % Hkv == 0.
    mask: broadcastable to (B, Hq, Sq, Skv); True = attend.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]  # may differ from d (MLA)
    g = hq // hkv
    scale = scale if scale is not None else d**-0.5
    qg = q.reshape(b, sq, hkv, g, d)
    # logits: (B, Hkv, G, Sq, Skv)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        # mask arrives as (B|1, 1, Sq, Skv); add the group axis
        logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(b, sq, hq, dv)


def causal_mask(sq: int, skv: int, window) -> Array:
    """(1, 1, Sq, Skv) boolean mask; ``window`` may be a traced scalar
    (<= 0 means unlimited / global)."""
    i = jnp.arange(sq)[:, None] + (skv - sq)  # absolute query positions
    j = jnp.arange(skv)[None, :]
    m = j <= i
    w = jnp.asarray(window)
    m = m & jnp.where(w > 0, (i - j) < w, True)
    return m[None, None]


def attention(
    params,
    x: Array,
    cfg: ModelConfig,
    sin: Array,
    cos: Array,
    window=0,
    causal: bool = True,
    cache_len: Optional[int] = None,
):
    """Full-sequence attention (train / prefill).

    With ``cache_len`` set, also returns a KV cache buffer of that length
    with positions [0:S] filled (prefill).
    """
    q, k, v = _project_qkv(params, x, cfg)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    s = x.shape[1]
    mask = causal_mask(s, s, window) if causal else None
    out = _sdpa(q, k, v, mask)
    y = _out_proj(params, out, cfg)
    if cache_len is None:
        return y
    if cache_len >= s:
        pad = [(0, 0), (0, cache_len - s), (0, 0), (0, 0)]
        cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    else:
        # RING cache (sliding-window layer): keep the last cache_len
        # positions, placed so that slot(p) == p % cache_len — decode then
        # overwrites the oldest entry in place.
        shift = s % cache_len
        cache = {
            "k": jnp.roll(k[:, s - cache_len :], shift, axis=1),
            "v": jnp.roll(v[:, s - cache_len :], shift, axis=1),
        }
    cache["k"] = shard_hint(cache["k"], "batch", "cache_seq", "cache_heads", None)
    cache["v"] = shard_hint(cache["v"], "batch", "cache_seq", "cache_heads", None)
    return y, cache


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    shape = (batch, max_len, nkv, hd)
    zeros = jnp.zeros(shape, dtype)
    cache = {"k": zeros, "v": zeros}
    axes = {
        "k": ("batch", "cache_seq", "cache_heads", None),
        "v": ("batch", "cache_seq", "cache_heads", None),
    }
    return cache, axes


def _rope_decode(x: Array, pos: Array, head_dim: int, theta) -> Array:
    """Rotate one decode token per slot.  x: (B, 1, H, D).

    Scalar ``pos`` reproduces the original shared-position path bit-for-bit;
    a ``(B,)`` vector applies each slot's own angle (continuous batching).
    """
    if pos.ndim == 0:
        sin, cos = rope_table(pos[None], head_dim, theta)
        return apply_rope(x, sin, cos)
    sin, cos = rope_table(pos, head_dim, theta)  # (B, D/2)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[:, None, None, :].astype(x.dtype)
    cos = cos[:, None, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _slot_write(cache: Array, new: Array, slot: Array, active: Array | None):
    """Dense-adapter write: one token per slot at per-slot ring positions.

    cache: (B, L, ...); new: (B, 1, ...); slot: (B,) int32.  One-hot
    ``where`` rather than dynamic_update_slice because each batch row
    writes a *different* position, and inactive rows write nothing.
    """
    l = cache.shape[1]
    hit = jnp.arange(l)[None, :] == slot[:, None]  # (B, L)
    if active is not None:
        hit = hit & active[:, None]
    hit = hit.reshape(hit.shape + (1,) * (cache.ndim - 2))
    return jnp.where(hit, new.astype(cache.dtype), cache)


def _decode_mask(pos: Array, skv: int, ring: bool) -> Array:
    """Validity mask for a decode read, broadcastable to (B, 1, 1, Skv).

    ring=True caps at the buffer length (after wrap, every slot is live);
    ring=False is the plain prefix mask used by full-length/paged caches.
    """
    j = jnp.arange(skv)
    lim = jnp.minimum(pos, skv - 1) if ring else pos
    if pos.ndim == 0:
        return jnp.broadcast_to((j <= lim)[None, None, None], (1, 1, 1, skv))
    return (j[None, :] <= lim[:, None])[:, None, None, :]


def _rope_at(x: Array, posmat: Array, head_dim: int, theta) -> Array:
    """Rotate a chunk of tokens at absolute positions ``posmat`` (B|1, T).
    x: (B, T, H, D).

    Elementwise rotate-half with per-(slot, token) angle tables — for a
    single token this computes exactly what :func:`_rope_decode` computes,
    and for a shared scalar offset it matches :func:`apply_rope` over a
    ``(T,)`` table (the angles are elementwise equal, so the products
    are bitwise equal).
    """
    sin, cos = rope_table(posmat.reshape(-1), head_dim, theta)
    sin = sin.reshape(posmat.shape + (-1,))[:, :, None, :].astype(x.dtype)
    cos = cos.reshape(posmat.shape + (-1,))[:, :, None, :].astype(x.dtype)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _pos_matrix(pos: Array, t: int) -> Array:
    """Absolute positions of a T-token chunk: (B|1, T) from the per-slot
    (or shared scalar) position of the chunk's first token."""
    offs = jnp.arange(t, dtype=jnp.int32)
    if pos.ndim == 0:
        return (pos + offs)[None]
    return pos[:, None] + offs[None]


def _chunk_valid(
    b: int, t: int, active: Array | None, lengths: Array | None
) -> Array | None:
    """(B, T) bool — which chunk entries really carry a token (``lengths``
    right-pads a ragged final slice; ``active`` gates whole slots)."""
    if active is None and lengths is None:
        return None
    ok = jnp.ones((b, t), bool)
    if lengths is not None:
        ok = ok & (jnp.arange(t)[None, :] < lengths[:, None])
    if active is not None:
        ok = ok & active[:, None]
    return ok


def _span_write(cache: Array, new: Array, rows: Array, valid: Array | None):
    """Dense-adapter span write: T tokens per slot at per-(slot, token)
    rows.  cache: (B, L, ...); new: (B, T, ...); rows: (B, T) int32.
    Invalid entries are routed out of bounds and dropped (no arithmetic on
    resident values — writes are pure placements)."""
    b, t = rows.shape
    if valid is not None:
        rows = jnp.where(valid, rows, cache.shape[1])  # OOB -> mode="drop"
    bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
    return cache.at[bi, rows].set(new.astype(cache.dtype), mode="drop")


def _span_mask(posmat: Array, skv: int) -> Array:
    """Causal validity of a chunk read, (B|1, 1, T, Skv): query at absolute
    position q attends cache columns j <= q.  Columns written by *later*
    chunk tokens sit at j > q, so one prefix rule masks both the resident
    garbage and the in-chunk future (the T=1 case is exactly
    :func:`_decode_mask` with ring=False)."""
    j = jnp.arange(skv)
    return (j[None, None, :] <= posmat[..., None])[:, None]


def _ring_chunk(q, k, v, cache: dict, posmat: Array, valid: Array | None):
    """Sequential per-token chunk over a RING cache (sliding-window layer).

    A parallel span write is wrong here: writing token ``p`` evicts the
    resident key at ``p - W``, which earlier queries in the same chunk
    still attend.  Scanning write->read per token reproduces the decode
    semantics exactly, token for token, so chunked prefill over a ring is
    bitwise the decode stream — while the projections around it stay
    chunk-parallel.  q/k/v: (B, T, H, D); posmat: (B|1, T).
    """
    b, t = q.shape[:2]
    l = cache["k"].shape[1]
    posmat = jnp.broadcast_to(posmat, (b, t))
    ok = jnp.broadcast_to(valid, (b, t)) if valid is not None else None

    def step(carry, inp):
        kc, vc = carry
        qt, kt, vt, pt, okt = inp  # (B, H, D) x3, (B,), (B,) | None
        kc = _slot_write(kc, kt[:, None], pt % l, okt)
        vc = _slot_write(vc, vt[:, None], pt % l, okt)
        mask = _decode_mask(pt, l, ring=True)
        out = _sdpa(qt[:, None], kc.astype(qt.dtype), vc.astype(qt.dtype), mask)
        return (kc, vc), out[:, 0]

    xs = (
        jnp.moveaxis(q, 1, 0),
        jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0),
        posmat.T,
        ok.T if ok is not None else jnp.ones((t, b), bool),
    )
    (kc, vc), outs = jax.lax.scan(step, (cache["k"], cache["v"]), xs)
    return jnp.moveaxis(outs, 0, 1), {"k": kc, "v": vc}


def _paged_scores(
    q: Array,  # (B, T, Hq, D) — rotated queries
    kpool: Array,
    vpool: Array,
    table: Array,
    posv: Array,  # (B,) — absolute position of q[:, 0]
    posmat: Array,  # (B|1, T) — per-(slot, token) absolute positions
    n_valid,  # (B,) lengths of a ragged slice, or the static T
    read_to: int | None,
) -> Array:
    """Score queries against the paged pool: Pallas block-table kernel
    when enabled (``kernels.paged_attention`` — walks each slot's pages
    in place, no dense gather), else the ``kv_pool.read`` gather +
    prefix-masked SDPA, which stays the parity oracle.  The fallback
    clamps its gather to the used-block prefix when the caller provides a
    static ``read_to`` bound; the kernel bounds its page walk per slot
    with the resident length ``posv + n_valid`` instead (no static bound
    needed).  Decode is the T=1 case: ``posmat = posv[:, None]`` makes
    ``_span_mask`` exactly the decode prefix mask."""
    from repro.kernels import ops  # deferred: kernels tier is optional here

    b, t = q.shape[:2]
    bs = kpool.shape[2]
    if ops.paged_attention_enabled() and ops.paged_attention_supported(
        bs, q.shape[-1], q.shape[2], kpool.shape[1], kpool.dtype
    ):
        kv_lens = jnp.clip(posv + n_valid, 1, table.shape[1] * bs)
        return ops.paged_attention(
            q, kpool, vpool, table, posv, kv_lens
        ).astype(q.dtype)
    from repro.serve import kv_pool  # deferred: serve imports models

    mb = table.shape[1]
    nb = mb if read_to is None else max(1, min(mb, -(-read_to // bs)))
    keys = kv_pool.read(kpool, table, blocks=nb)
    vals = kv_pool.read(vpool, table, blocks=nb)
    mask = _span_mask(jnp.broadcast_to(posmat, (b, t)), keys.shape[1])
    return _sdpa(q, keys.astype(q.dtype), vals.astype(q.dtype), mask)


def attention_chunk(
    params,
    x: Array,
    cache: dict,
    pos: Array,
    cfg: ModelConfig,
    theta: float,
    window=0,
    active: Array | None = None,
    lengths: Array | None = None,
    ring: bool = False,
    read_to: int | None = None,
):
    """Cache-resident multi-token attention: process T tokens per slot.

    x: (B, T, D); pos: scalar or (B,) int32 — absolute position of
    x[:, 0].  K/V for tokens ``t < lengths[b]`` (default: all T) of active
    slots are written into the *existing* cache — dense ring, dense full,
    or paged — and each query attends the already-resident prefix plus the
    in-chunk causal keys.  Prefill is this from an empty cache; decode is
    T=1 (dispatched to :func:`attention_decode`, the preserved one-token
    fast path, so decode streams are bit-for-bit unchanged).

    ``ring`` (static) marks a sliding-window layer whose dense cache is
    shorter than the position range — those take the sequential in-chunk
    path (:func:`_ring_chunk`); everything else reads the updated cache in
    parallel under one prefix mask.  ``read_to`` (static) bounds that read
    when the caller knows no position >= read_to can be attended — prefill
    from an empty cache passes its prompt length, keeping scoring
    O(S*S) instead of O(S*cache_len); the masked-out columns it drops
    contribute exact zeros to the softmax either way.  The paged fallback
    gather honors the same bound (``kv_pool.read(blocks=ceil(read_to /
    block_size))``); the paged *kernel* path needs no static bound — it
    clamps each slot's page walk to its resident length.

    Returns (y (B, T, D), new_cache).
    """
    b, t = x.shape[:2]
    if t == 1 and lengths is None:
        return attention_decode(
            params, x, cache, pos, cfg, theta, window=window, active=active
        )
    del window  # window semantics are carried by the cache length (ring)
    q, k, v = _project_qkv(params, x, cfg)
    pos = jnp.asarray(pos, jnp.int32)
    posmat = _pos_matrix(pos, t)
    if cfg.pos_embedding == "rope":
        q = _rope_at(q, posmat, cfg.head_dim, theta)
        k = _rope_at(k, posmat, cfg.head_dim, theta)

    if "table" in cache:  # paged adapter: span-scatter straight into pages
        from repro.serve import kv_pool  # deferred: serve imports models

        posv = jnp.broadcast_to(pos, (b,))
        kp = kv_pool.write_span(
            cache["kpool"], cache["table"], posv, k, active, lengths
        )
        vp = kv_pool.write_span(
            cache["vpool"], cache["table"], posv, v, active, lengths
        )
        # pool layout (NB, BS, Hkv, D): shards over KV heads on `model`;
        # the per-slot table replicates with the rest of the slot state
        kp = shard_hint(kp, None, None, "cache_heads", None)
        vp = shard_hint(vp, None, None, "cache_heads", None)
        out = _paged_scores(
            q, kp, vp, cache["table"], posv, posmat,
            lengths if lengths is not None else t, read_to,
        )
        new_cache = {"kpool": kp, "vpool": vp, "table": cache["table"]}
        return _out_proj(params, out, cfg), new_cache

    valid = _chunk_valid(b, t, active, lengths)
    if ring:
        out, new_cache = _ring_chunk(q, k, v, cache, posmat, valid)
    else:
        skv = cache["k"].shape[1]
        lim = skv if read_to is None else min(read_to, skv)
        rows = jnp.broadcast_to(posmat, (b, t))
        new_k = _span_write(cache["k"], k, rows, valid)
        new_v = _span_write(cache["v"], v, rows, valid)
        new_k = shard_hint(new_k, "batch", "cache_seq", "cache_heads", None)
        new_v = shard_hint(new_v, "batch", "cache_seq", "cache_heads", None)
        mask = _span_mask(posmat, lim)
        out = _sdpa(
            q, new_k[:, :lim].astype(q.dtype), new_v[:, :lim].astype(q.dtype),
            mask,
        )
        new_cache = {"k": new_k, "v": new_v}
    return _out_proj(params, out, cfg), new_cache


def attention_decode(
    params,
    x: Array,
    cache: dict,
    pos: Array,
    cfg: ModelConfig,
    theta: float,
    window=0,
    active: Array | None = None,
):
    """One-token decode step. x: (B, 1, D); pos: scalar or (B,) int32.

    Dense caches may be shorter than the sequence (RING cache for
    sliding-window layers): the write slot is ``pos % cache_len`` and the
    validity mask covers min(pos+1, cache_len) slots — a cache of length W
    IS the W-token sliding window, so no extra window masking is needed.
    Paged caches (``"table"`` key) scatter into the shared block pool and
    score via :func:`_paged_scores` — the Pallas block-table kernel when
    enabled, else the dense-view gather (see ``repro.serve.kv_pool``).

    Returns (y, new_cache).
    """
    b = x.shape[0]
    del window  # window semantics are carried by the cache length (ring)
    q, k, v = _project_qkv(params, x, cfg)
    pos = jnp.asarray(pos, jnp.int32)
    if cfg.pos_embedding == "rope":
        q = _rope_decode(q, pos, cfg.head_dim, theta)
        k = _rope_decode(k, pos, cfg.head_dim, theta)

    if "table" in cache:  # paged adapter
        from repro.serve import kv_pool  # deferred: serve imports models

        posv = jnp.broadcast_to(pos, (b,))
        kp = kv_pool.write(cache["kpool"], cache["table"], posv, k[:, 0], active)
        vp = kv_pool.write(cache["vpool"], cache["table"], posv, v[:, 0], active)
        kp = shard_hint(kp, None, None, "cache_heads", None)
        vp = shard_hint(vp, None, None, "cache_heads", None)
        out = _paged_scores(
            q, kp, vp, cache["table"], posv, posv[:, None], 1, None
        )
        new_cache = {"kpool": kp, "vpool": vp, "table": cache["table"]}
        return _out_proj(params, out, cfg), new_cache

    skv = cache["k"].shape[1]
    if pos.ndim == 0 and active is None:
        # lockstep fast path: every slot writes the same ring position
        slot = pos % skv
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1
        )
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1
        )
    else:
        posv = jnp.broadcast_to(pos, (b,))
        new_k = _slot_write(cache["k"], k, posv % skv, active)
        new_v = _slot_write(cache["v"], v, posv % skv, active)
    new_k = shard_hint(new_k, "batch", "cache_seq", "cache_heads", None)
    new_v = shard_hint(new_v, "batch", "cache_seq", "cache_heads", None)
    mask = _decode_mask(pos, skv, ring=True)
    out = _sdpa(q, new_k.astype(q.dtype), new_v.astype(q.dtype), mask)
    return _out_proj(params, out, cfg), {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# Cross-attention (Whisper decoder)
# ---------------------------------------------------------------------------


def cross_attention(params, x: Array, k: Array, v: Array, cfg: ModelConfig) -> Array:
    """x: (B, Sq, D) queries; k/v precomputed from encoder memory."""
    b, sq, _ = x.shape
    hd, nq = cfg.head_dim, cfg.n_heads
    q = bitlinear(params["wq"], x, cfg.quant).reshape(b, sq, nq, hd)
    out = _sdpa(q, k, v, None)
    return _out_proj(params, out, cfg)


def cross_kv(params, memory: Array, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    b, sm, _ = memory.shape
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    k = bitlinear(params["wk"], memory, cfg.quant).reshape(b, sm, nkv, hd)
    v = bitlinear(params["wv"], memory, cfg.quant).reshape(b, sm, nkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key: Array, cfg: ModelConfig):
    d = cfg.d_model
    nh = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 8)
    params, axes = {}, {}

    def add(name, k, di, do, ax):
        p, a = init_linear(k, di, do, ax)
        params[name], axes[name] = p, a

    if cfg.q_lora_rank > 0:
        add("wq_down", ks[0], d, cfg.q_lora_rank, ("embed", "lora"))
        add("wq_up", ks[1], cfg.q_lora_rank, nh * qk, ("lora", "heads"))
        p, a = init_rmsnorm(cfg.q_lora_rank, axis="lora")
        params["q_norm"], axes["q_norm"] = p, a
    else:
        add("wq", ks[0], d, nh * qk, ("embed", "heads"))
    # joint KV down-projection: [c_kv ; k_rope]
    add("wkv_down", ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim, ("embed", "lora"))
    add(
        "wkv_up",
        ks[3],
        cfg.kv_lora_rank,
        nh * (cfg.qk_nope_dim + cfg.v_head_dim),
        ("lora", "heads"),
    )
    p, a = init_rmsnorm(cfg.kv_lora_rank, axis="lora")
    params["kv_norm"], axes["kv_norm"] = p, a
    add("wo", ks[4], nh * cfg.v_head_dim, d, ("heads", "embed"))
    if cfg.quant.mode != "none":
        p, a = init_rmsnorm(nh * cfg.v_head_dim, axis="heads")
        params["subln"], axes["subln"] = p, a
    return params, axes


def _mla_q(params, x: Array, cfg: ModelConfig):
    b, s, _ = x.shape
    nh, qk = cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank > 0:
        cq = bitlinear(params["wq_down"], x, cfg.quant)
        cq = rmsnorm(params["q_norm"], cq)
        q = bitlinear(params["wq_up"], cq, cfg.quant)
    else:
        q = bitlinear(params["wq"], x, cfg.quant)
    q = q.reshape(b, s, nh, qk)
    return q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]


def _mla_expand_kv(params, ckv: Array, cfg: ModelConfig):
    """Expand compressed latent into per-head K_nope and V."""
    b, s, _ = ckv.shape
    nh = cfg.n_heads
    kv = bitlinear(params["wkv_up"], ckv, cfg.quant)
    kv = kv.reshape(b, s, nh, cfg.qk_nope_dim + cfg.v_head_dim)
    return kv[..., : cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim :]


def mla_attention(
    params,
    x: Array,
    cfg: ModelConfig,
    positions: Array,
):
    """Full-sequence MLA (train / eval; serving goes through
    :func:`mla_chunk`)."""
    b, s, _ = x.shape
    nh = cfg.n_heads
    q_nope, q_rope = _mla_q(params, x, cfg)

    down = bitlinear(params["wkv_down"], x, cfg.quant)
    ckv, k_rope = down[..., : cfg.kv_lora_rank], down[..., cfg.kv_lora_rank :]
    ckv = rmsnorm(params["kv_norm"], ckv)
    k_nope, v = _mla_expand_kv(params, ckv, cfg)

    sin, cos = rope_table(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)  # single shared head

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, nh, cfg.qk_rope_dim))], axis=-1
    )
    mask = causal_mask(s, s, 0)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    out = _sdpa(q, k, v, mask, scale=scale)
    subln = params.get("subln")
    return bitlinear(
        params["wo"], out.reshape(b, s, -1), cfg.quant, sublayer_norm=subln
    )


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """MLA caches only the compressed latent + shared rope key — this is the
    architecture's memory win and must be preserved (not expanded K/V)."""
    cache = {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }
    axes = {
        "ckv": ("batch", "cache_seq", None),
        "krope": ("batch", "cache_seq", None),
    }
    return cache, axes


def mla_chunk(
    params,
    x: Array,
    cache: dict,
    pos: Array,
    cfg: ModelConfig,
    active: Array | None = None,
    lengths: Array | None = None,
    read_to: int | None = None,
):
    """Cache-resident multi-token MLA: span-write T compressed latents,
    expand the latent cache (up to the static ``read_to`` bound — see
    :func:`attention_chunk`), and score each query against its causal
    prefix.  T=1 dispatches to :func:`mla_decode` (bit-for-bit the decode
    stream); the latent cache stays dense in both layouts (caching only
    ``(B, L, kv_lora_rank)`` latents is already the memory win paging
    chases) — with no paged K/V to walk, the block-table attention
    kernel does not apply here and MLA keeps its dense latent expansion.
    Returns (y (B, T, D), new_cache)."""
    b, t = x.shape[:2]
    if t == 1 and lengths is None:
        return mla_decode(params, x, cache, pos, cfg, active=active)
    nh = cfg.n_heads
    q_nope, q_rope = _mla_q(params, x, cfg)
    down = bitlinear(params["wkv_down"], x, cfg.quant)
    ckv_new = rmsnorm(params["kv_norm"], down[..., : cfg.kv_lora_rank])
    krope_new = down[..., cfg.kv_lora_rank :]
    pos = jnp.asarray(pos, jnp.int32)
    posmat = _pos_matrix(pos, t)
    q_rope = _rope_at(q_rope, posmat, cfg.qk_rope_dim, cfg.rope_theta)
    krope_new = _rope_at(
        krope_new[:, :, None, :], posmat, cfg.qk_rope_dim, cfg.rope_theta
    )[:, :, 0]

    valid = _chunk_valid(b, t, active, lengths)
    rows = jnp.broadcast_to(posmat, (b, t))
    new_ckv = _span_write(cache["ckv"], ckv_new, rows, valid)
    new_krope = _span_write(cache["krope"], krope_new, rows, valid)
    skv = new_ckv.shape[1]
    lim = skv if read_to is None else min(read_to, skv)
    k_nope, v = _mla_expand_kv(params, new_ckv[:, :lim].astype(x.dtype), cfg)
    k = jnp.concatenate(
        [
            k_nope,
            jnp.broadcast_to(
                new_krope[:, :lim].astype(x.dtype)[:, :, None, :],
                (b, lim, nh, cfg.qk_rope_dim),
            ),
        ],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    mask = _span_mask(posmat, lim)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    out = _sdpa(q, k, v, mask, scale=scale)
    subln = params.get("subln")
    y = bitlinear(params["wo"], out.reshape(b, t, -1), cfg.quant, sublayer_norm=subln)
    return y, {"ckv": new_ckv, "krope": new_krope}


def mla_decode(
    params,
    x: Array,
    cache: dict,
    pos: Array,
    cfg: ModelConfig,
    active: Array | None = None,
):
    """MLA decode keeps the dense latent cache in both serving engines —
    caching only ``(B, L, kv_lora_rank)`` latents is already the memory
    win paging chases, so only the write/mask paths learn per-slot ``pos``
    and ``active``."""
    b = x.shape[0]
    nh = cfg.n_heads
    q_nope, q_rope = _mla_q(params, x, cfg)
    down = bitlinear(params["wkv_down"], x, cfg.quant)
    ckv_new = rmsnorm(params["kv_norm"], down[..., : cfg.kv_lora_rank])
    krope_new = down[..., cfg.kv_lora_rank :]
    pos = jnp.asarray(pos, jnp.int32)
    q_rope = _rope_decode(q_rope, pos, cfg.qk_rope_dim, cfg.rope_theta)
    krope_new = _rope_decode(
        krope_new[:, :, None, :], pos, cfg.qk_rope_dim, cfg.rope_theta
    )[:, :, 0]

    if pos.ndim == 0 and active is None:
        new_ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1
        )
        new_krope = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], krope_new.astype(cache["krope"].dtype), pos, axis=1
        )
    else:
        posv = jnp.broadcast_to(pos, (b,))
        new_ckv = _slot_write(cache["ckv"], ckv_new, posv, active)
        new_krope = _slot_write(cache["krope"], krope_new, posv, active)
    skv = new_ckv.shape[1]
    # expand the whole latent cache for scoring (weight-absorption variant is
    # a serving optimisation tracked in EXPERIMENTS.md §Perf)
    k_nope, v = _mla_expand_kv(params, new_ckv.astype(x.dtype), cfg)
    k = jnp.concatenate(
        [
            k_nope,
            jnp.broadcast_to(
                new_krope.astype(x.dtype)[:, :, None, :], (b, skv, nh, cfg.qk_rope_dim)
            ),
        ],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    mask = _decode_mask(pos, skv, ring=False)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    out = _sdpa(q, k, v, mask, scale=scale)
    subln = params.get("subln")
    y = bitlinear(params["wo"], out.reshape(b, 1, -1), cfg.quant, sublayer_norm=subln)
    return y, {"ckv": new_ckv, "krope": new_krope}
