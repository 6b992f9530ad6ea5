"""Pallas paged-attention kernel: block-table attention over the serving
KV pool.

Since the one-serving-forward refactor, every engine tier reads context
through a single path — ``kv_pool.read``'s dense gather followed by a
prefix-masked SDPA (``models.attention``'s paged branches).  At long
context that gather is the serving-path memory amplifier: it materializes
a ``(B, max_blocks * block, H, D)`` copy of the pool *per layer per step*
just so XLA's SDPA can read it.  This kernel consumes the paged layout
directly:

    kpool / vpool : (num_blocks, n_kv_heads, block, head_dim)
    table         : (B, max_blocks) int32  — per-slot block ids
    start         : (B,) int32 — absolute position of the first query token
    kv_lens       : (B,) int32 — resident tokens per slot (after the write)

and computes flash-decoding-style online-softmax attention block-by-block,
walking each slot's table in place — no dense gather ever exists.

Design (one kernel serves all three engine tiers):

* **Grid (B, n_kv_heads, steps)** with the block table and per-slot
  start/length vectors as *scalar prefetch* operands: the K/V page for a
  grid step is selected by indexing the table inside the BlockSpec index
  map (``PrefetchScalarGridSpec``), so the pipeline DMAs pool pages
  HBM->VMEM directly — the classic TPU paged-attention trick.  Heads sit
  ahead of the block axis in the pool, so one page of one KV head is a
  ``(block, head_dim)`` tile: the two minor dims Mosaic tiles.
* **GQA/MQA head grouping.**  Queries are laid out (B, Hkv, T*G, D)
  (G = Hq // Hkv query heads per KV head), so one grid step scores every
  query row of one KV head against one K/V page tile: decode (T=1, rows =
  G), chunked prefill (T>1) and one-shot prefill are the same kernel at
  different T.
* **Causal prefix mask in-kernel.**  Query row r (= t * G + g) sits at
  absolute position ``start[b] + t`` and attends columns ``j <= pos`` —
  exactly ``models.attention._span_mask`` (T=1 degenerates to the decode
  mask), so the kernel is interchangeable with the gather+SDPA fallback.
* **Used-prefix skip.**  Steps whose pages lie entirely beyond
  ``kv_lens[b]`` skip their compute, and their index map clamps to the
  slot's last used page — the mapped block doesn't change, so the
  pipeline issues no new DMA: per-slot work scales with the *live*
  context, not the table capacity.
* **pages_per_step** (the autotuned knob, ``ops.paged_tiles`` /
  ``ops.sweep_paged_tiles``): each grid step fetches P pages via P
  parallel input specs (pages are non-contiguous in the pool, so one
  BlockSpec cannot cover them), widening the per-step score tile to
  ``P * block`` columns.

Numerics: scores, online-softmax state and the output accumulator are
f32 regardless of pool dtype; the result matches the gather+SDPA
reference to float rounding (online softmax re-associates the reduction,
so parity is allclose-at-f32, not bitwise — which is why ``ops``
dispatches the kernel only where the serving tests run it explicitly or
the backend is TPU; see ``ops.paged_attention_enabled``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30

# Queries are padded to the bf16 sublane minimum so (T*G, D) tiles are legal.
_ROW_ALIGN = 16


def _paged_attention_kernel(
    # scalar prefetch
    table_ref,  # (B, MB) int32
    start_ref,  # (B,) int32
    lens_ref,  # (B,) int32
    # tensor inputs: q then P K pages then P V pages
    q_ref,  # (1, 1, TGp, D)
    *refs,
    bs: int,
    pages: int,
    g: int,
    scale: float,
    steps: int,
):
    """One (b, h, s) grid step: online-softmax update of every query row of
    KV head ``h`` against the ``pages`` pool pages covering columns
    ``[s * pages * bs, (s + 1) * pages * bs)`` of slot ``b``."""
    k_refs = refs[:pages]
    v_refs = refs[pages : 2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages :]
    b, s = pl.program_id(0), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # steps whose first column is past the slot's resident length carry no
    # valid key — compute is skipped (their pages weren't re-fetched either:
    # the index map clamps to the last used page)
    @pl.when(s * pages * bs < lens_ref[b])
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)  # (TGp, D)
        k = jnp.concatenate([r[0, 0] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0)
        tg, w = q.shape[0], pages * bs
        # causal prefix: query row r = t*g + gq sits at start[b] + t and
        # attends absolute columns j <= that position (== _span_mask)
        cols = s * w + jax.lax.broadcasted_iota(jnp.int32, (tg, w), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (tg, w), 0) // g
        mask = cols <= start_ref[b] + rows
        sc = (
            jax.lax.dot_general(
                q,
                k.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # p is explicitly re-masked: when a whole tile is masked m_new can
        # stay at NEG_INF and exp(sc - m_new) would be 1, not 0
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p,
            v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(s == steps - 1)
    def _epilogue():
        # l > 0 for every row: column 0 satisfies j <= start + t (start,
        # t >= 0) and page 0 is always processed, so no 0/0 lane exists
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("pages", "scale", "interpret")
)
def paged_attention(
    q: Array,  # (B, T, Hq, D)
    kpool: Array,  # (NB, Hkv, BS, D)
    vpool: Array,  # (NB, Hkv, BS, D)
    table: Array,  # (B, MB) int32
    start: Array,  # (B,) int32 — absolute position of q[:, 0]
    kv_lens: Array,  # (B,) int32 — resident tokens per slot (>= 1)
    *,
    pages: int = 1,
    scale: float | None = None,
    interpret: bool = False,
) -> Array:
    """Block-table attention over the paged KV pool: (B, T, Hq, D) out.

    Query token t of slot b attends pool positions ``j <= start[b] + t``
    (the resident prefix plus its in-chunk causal predecessors — the
    ``forward_chunk`` contract); T=1 is the decode shape.  ``kv_lens``
    bounds the per-slot page walk (normally ``start + T``, or
    ``start + lengths`` for a ragged final slice).  Requires
    ``Hq % Hkv == 0`` (GQA/MQA grouping) and ``pages >= 1`` (autotuned
    via ``ops.paged_tiles``).
    """
    b, t, hq, d = q.shape
    nb, hkv, bs, dk = kpool.shape
    mb = table.shape[1]
    assert d == dk and vpool.shape == kpool.shape, (q.shape, kpool.shape)
    assert hq % hkv == 0, f"GQA grouping needs Hq % Hkv == 0, got {hq}/{hkv}"
    g = hq // hkv
    tg = t * g
    scale = float(d**-0.5) if scale is None else float(scale)
    pages = max(1, min(int(pages), mb))
    steps = -(-mb // pages)

    # (B, T, Hq, D) -> (B, Hkv, T*G, D): one grid step owns every query row
    # of one KV head; rows padded to the sublane minimum (pad rows attend
    # column 0 so their softmax mass is finite — they are sliced off below)
    q5 = (
        q.reshape(b, t, hkv, g, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, tg, d)
    )
    pad = (-tg) % _ROW_ALIGN
    if pad:
        q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, pad), (0, 0)))
    tgp = tg + pad

    def page_index_map(p):
        def index(bi, h, s, table, start, lens):
            i = s * pages + p
            # beyond the used prefix, re-map to the last used page: the
            # mapped block is unchanged from the previous step, so the
            # pipeline skips the DMA instead of streaming dead pages
            last = jnp.maximum((lens[bi] - 1) // bs, 0)
            i = jnp.minimum(jnp.minimum(i, last), mb - 1)
            return (table[bi, i], h, 0, 0)

        return index

    page_spec = [
        pl.BlockSpec((1, 1, bs, d), page_index_map(p)) for p in range(pages)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, steps),
        in_specs=[
            pl.BlockSpec((1, 1, tgp, d), lambda bi, h, s, *_: (bi, h, 0, 0))
        ]
        + page_spec
        + page_spec,
        out_specs=pl.BlockSpec(
            (1, 1, tgp, d), lambda bi, h, s, *_: (bi, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((tgp, 1), jnp.float32),  # running max
            pltpu.VMEM((tgp, 1), jnp.float32),  # running denominator
            pltpu.VMEM((tgp, d), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attention_kernel,
            bs=bs,
            pages=pages,
            g=g,
            scale=scale,
            steps=steps,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, tgp, d), q.dtype),
        interpret=interpret,
    )(
        table.astype(jnp.int32),
        start.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        q5,
        *([kpool] * pages),
        *([vpool] * pages),
    )
    out = out[:, :, :tg]
    return (
        out.reshape(b, hkv, t, g, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, hq, d)
    )
