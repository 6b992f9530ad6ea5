"""Pallas paged-attention kernels: block-table attention over the serving
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

Two kernels share that contract; ``ops`` picks one from the query's
shape alone:

* ``paged_attention_decode`` (T == 1, every decode step): a grid over
  (slot, page-step) that fetches whole all-heads pages, one DMA each, and
  skips the steps past a slot's live pages.
* ``paged_attention`` (T > 1: chunked-prefill slices and one-shot
  prefill): the (slot, KV head, page-step) grid below, with its
  autotuned pages-per-step.

Design of the T > 1 grid kernel:

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

Design of the T == 1 decode kernel.  At one query row the grid kernel is
bound by per-step and per-DMA overhead, not bytes: B * Hkv * MB / pages
grid steps, each moving one head's ``(block, head_dim)`` page (2 KB at
16 x 64 bf16), and steps past the live prefix still pay their overhead.
The decode kernel instead:

* **Fetches all-heads pages.**  A pool page ``(Hkv, block, head_dim)``
  is one contiguous run, so each BlockSpec moves one page of every KV
  head in one DMA; the grid is (B, ceil(MB / pages)), Hkv times fewer
  steps.  ``pages`` per step is derived from the page's bytes and the
  table width (:func:`decode_pages`), not tuned.
* **Walks only the live pages.**  A step past the slot's
  ``ceil(kv_lens[b] / block)`` live pages repeats the last live step's
  blocks (the pipeline then issues no DMA) and skips its compute; inside
  the last live step, a page spec past the live ones keeps its block of
  the step before.  (Manual DMAs in a dynamic-trip-count loop would skip
  even those steps, but Mosaic refuses a DMA slice of an HBM ref whose
  minor dim, head_dim 64 or 80, is not a multiple of 128.)
* **Scores every head of a step at once.**  The step's pages, laid side
  by side, give each KV head ``W = pages * block`` keys in position
  order.  With one query row per head (G = 1) the scores are a VPU
  product and a lane reduction over head_dim, batched over heads (an MXU
  product of one row would pay a weight load per head and page); with G
  rows (GQA) they are a head-batched MXU product.  Heads never mix: at
  the same ``pages``, a KV-head shard under a mesh computes bitwise what
  the whole call does for its heads.  Scores, softmax state
  and accumulator are f32; bf16 queries and keys meet in the MXU as bf16
  (their products are exact in its f32 accumulator).
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


# ---------------------------------------------------------------------------
# T == 1: the decode kernel
# ---------------------------------------------------------------------------

# Bytes of K (and as many of V) one grid step fetches: pages per step
# amortize the per-step overhead; fewer pages keep the last live step's
# dead tail short.  On a TPU v5e at 32 heads of 16 x 64 bf16 (64-KB pages)
# and serving lengths, 4 and 8 pages per step ran within 2% of each other,
# 2 and 16 pages 9% and 5% slower.
_DECODE_STEP_BYTES = 256 * 1024


def decode_pages(page_bytes: int, max_blocks: int) -> int:
    """All-heads pages per grid step of the decode kernel: as many whole
    pages as fit ``_DECODE_STEP_BYTES``, at least one, at most the table
    width."""
    return max(1, min(max_blocks, _DECODE_STEP_BYTES // page_bytes))


def _decode_kernel(
    # scalar prefetch
    table_ref,  # (B, MB) int32
    start_ref,  # (B,) int32
    lens_ref,  # (B,) int32
    # inputs: q, then P K pages, then P V pages
    q_ref,  # (1, Hkv, G, D)
    *refs,  # P x (1, Hkv, BS, D) K pages, P V pages; out; scratch
    bs: int,
    pages: int,
    scale: float,
    steps: int,
):
    """One (b, s) grid step: online-softmax update of slot ``b``'s query
    rows, every KV head at once, against its live pages
    ``[s * pages, (s + 1) * pages)``."""
    k_refs = refs[:pages]
    v_refs = refs[pages : 2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages :]
    b, s = pl.program_id(0), pl.program_id(1)
    live = (lens_ref[b] + bs - 1) // bs  # >= 1: kv_lens >= 1

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s * pages < live)
    def _update():
        q = q_ref[0]  # (Hkv, G, D)
        # (Hkv, W, D), W = pages * bs columns per head, in position order
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        w = k.shape[1]
        if q.shape[1] == 1:
            # one query row per head: a VPU product and a lane reduction
            # over D; scores (Hkv, W, 1), reduced over axis 1
            ax = 1
            sc = jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32),
                         axis=2, keepdims=True)
        else:
            # G rows per head: a head-batched MXU product, scores
            # (Hkv, G, W); bf16 products are exact in the f32 accumulator
            ax = 2
            if not q.dtype == k.dtype == jnp.bfloat16:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            sc = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        sc = sc * scale
        shape = [1, 1, 1]
        shape[ax] = w
        # causal prefix, and no column of a page past the live ones (such
        # a spec holds the step before's page, see the index map)
        last = jnp.minimum(start_ref[b], live * bs - 1)
        cols = s * w + jax.lax.broadcasted_iota(jnp.int32, shape, ax)
        mask = cols <= last
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]  # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=ax, keepdims=True))
        # re-masked as in the grid kernel: a fully masked row keeps m_new
        # at NEG_INF, where exp(sc - m_new) would be 1
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=ax, keepdims=True)
        v = v.astype(jnp.float32)
        if ax == 1:
            pv = jnp.sum(p * v, axis=1, keepdims=True)
        else:
            pv = jax.lax.dot_general(
                p, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(s == steps - 1)
    def _epilogue():
        # l > 0: column 0 (page 0, always live) satisfies j <= start
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("pages", "scale", "interpret")
)
def paged_attention_decode(
    q: Array,  # (B, 1, Hq, D)
    kpool: Array,  # (NB, Hkv, BS, D)
    vpool: Array,  # (NB, Hkv, BS, D)
    table: Array,  # (B, MB) int32
    start: Array,  # (B,) int32 — absolute position of the query
    kv_lens: Array,  # (B,) int32 — resident tokens per slot (>= 1)
    *,
    pages: int | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> Array:
    """:func:`paged_attention` at T == 1, with the same contract and
    result: grid (B, ceil(MB / pages)), each step fetching ``pages``
    all-heads pages of its slot (default :func:`decode_pages`), steps past
    the live pages fetching and computing nothing."""
    b, t, hq, d = q.shape
    nb, hkv, bs, dk = kpool.shape
    mb = table.shape[1]
    assert t == 1, f"the decode kernel takes one query token, got T={t}"
    assert d == dk and vpool.shape == kpool.shape, (q.shape, kpool.shape)
    assert hq % hkv == 0, f"GQA grouping needs Hq % Hkv == 0, got {hq}/{hkv}"
    scale = float(d**-0.5) if scale is None else float(scale)
    if pages is None:
        pages = decode_pages(hkv * bs * d * kpool.dtype.itemsize, mb)
    pages = max(1, min(int(pages), mb))
    steps = -(-mb // pages)
    g = hq // hkv

    def page_index_map(p):
        def index(bi, s, table, start, lens):
            live = (lens[bi] + bs - 1) // bs
            r = jnp.minimum(s, (live - 1) // pages)  # last live step
            i = r * pages + p
            # a page past the live ones keeps the spec's block of the step
            # before (the last live page in step 0), and a step past the
            # live ones repeats the last live step: unchanged blocks, so
            # the pipeline issues no DMA for them
            i = jnp.where(i < live, i, jnp.where(r > 0, i - pages, live - 1))
            return (table[bi, i], 0, 0, 0)

        return index

    page_spec = [
        pl.BlockSpec((1, hkv, bs, d), page_index_map(p))
        for p in range(pages)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, steps),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda bi, s, *_: (bi, 0, 0, 0))
        ]
        + page_spec
        + page_spec,
        out_specs=pl.BlockSpec(
            (1, hkv, g, d), lambda bi, s, *_: (bi, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),  # running max
            pltpu.VMEM((hkv, g, 1), jnp.float32),  # running denominator
            pltpu.VMEM((hkv, g, d), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, bs=bs, pages=pages, scale=scale, steps=steps
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        table.astype(jnp.int32),
        start.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        q.reshape(b, hkv, g, d),  # query head h * G + g serves KV head h
        *([kpool] * pages),
        *([vpool] * pages),
    )
    return out.reshape(b, 1, hq, d)
