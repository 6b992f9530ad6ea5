"""Block-paged KV storage for the serving subsystem.

Dense decode caches are ``(B, max_len, n_kv_heads, head_dim)`` buffers:
every slot owns ``max_len`` positions whether it uses them or not, so a
short request admitted next to a long one pays the long one's memory.  The
paged layout replaces the per-slot buffer with a shared pool

    kpool / vpool : (num_blocks, n_kv_heads, block_size, head_dim)
    table         : (B, max_blocks) int32  — per-slot block ids

where position ``p`` of slot ``b`` lives at ``(table[b, p // bs], :,
p % bs)``.  Heads sit ahead of the block axis so one page of one head is a
``(block_size, head_dim)`` tile for ``kernels.paged_attention``.
Blocks are handed out by the host-side :class:`BlockAllocator` at admission
and chunk boundaries and reclaimed on eviction, so KV memory scales with
the *live* token count, not ``B * max_len``.

This module is deliberately model-agnostic (pure jax + shape arguments, no
``repro.models`` imports): ``repro.models.attention`` calls :func:`write` /
:func:`read` from its decode path, and ``repro.models.transformer`` builds
the per-layer cache dict via :func:`init_paged_attention_cache`.  A cache
dict containing a ``"table"`` key *is* the paged layout — that key is the
cache-adapter discriminator the model stack dispatches on.

Numerics contract: :func:`read` gathers a slot's blocks in table order, so
the gathered ``(B, max_blocks * block_size, H, D)`` view is element-for-
element the dense cache (up to trailing padding that the position mask
excludes).  Decode attention over a paged cache is therefore bit-for-bit
the dense computation — the parity tests in ``tests/test_continuous_
batching.py`` assert exactly that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def blocks_for(length: int, block_size: int) -> int:
    """Number of blocks needed to hold ``length`` positions."""
    return -(-int(length) // int(block_size))


def init_paged_attention_cache(
    batch: int,
    max_len: int,
    n_kv_heads: int,
    head_dim: int,
    num_blocks: int,
    block_size: int,
    dtype,
):
    """(cache, axes) for one paged attention layer.

    ``max_len`` bounds a single slot's sequence (it sizes the table), while
    ``num_blocks`` sizes the shared pool — the whole point is that
    ``num_blocks`` can be far less than ``batch * max_blocks``.
    """
    if max_len % block_size:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of block_size "
            f"({block_size}) so prefill pages tile exactly"
        )
    max_blocks = blocks_for(max_len, block_size)
    pool_shape = (num_blocks, n_kv_heads, block_size, head_dim)
    cache = {
        "kpool": jnp.zeros(pool_shape, dtype),
        "vpool": jnp.zeros(pool_shape, dtype),
        "table": jnp.zeros((batch, max_blocks), jnp.int32),
    }
    axes = {
        # pools carry no batch axis — they are the shared resource
        "kpool": (None, "cache_heads", None, None),
        "vpool": (None, "cache_heads", None, None),
        "table": ("batch", None),
    }
    return cache, axes


# Trailing-dim-aligned logical axes per cache-dict key, for placing a whole
# engine cache tree on a mesh (stacked ring layers carry a leading layer
# dim — pad with None).  Pools shard over KV heads on `model`; per-slot
# tables and dense ring caches follow the `batch` rule, which the serving
# overrides map to None (replicated with the rest of the slot state).
CACHE_KEY_AXES: dict[str, tuple] = {
    "kpool": (None, "cache_heads", None, None),
    "vpool": (None, "cache_heads", None, None),
    "table": ("batch", None),
    "k": ("batch", "cache_seq", "cache_heads", None),
    "v": ("batch", "cache_seq", "cache_heads", None),
    "ckv": ("batch", "cache_seq", None),   # MLA latent caches stay dense
    "kpe": ("batch", "cache_seq", None),
}


def cache_sharding(cache_tree, mesh):
    """NamedShardings for an engine cache tree (per-layer dicts, possibly
    stacked), keyed on the cache-dict key via :data:`CACHE_KEY_AXES`.
    Unknown keys and indivisible dims replicate.  Must run inside
    ``sharding.sharding_rules`` so the serving rule overrides apply."""
    from jax.sharding import NamedSharding, PartitionSpec
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    from repro.distributed import sharding as sh

    leaves, treedef = tree_flatten_with_path(cache_tree)
    out = []
    for path, leaf in leaves:
        key = None
        for entry in reversed(path):
            k = getattr(entry, "key", None)
            if isinstance(k, str):
                key = k
                break
        axes = CACHE_KEY_AXES.get(key)
        if axes is None or len(axes) > leaf.ndim:
            spec = PartitionSpec()
        else:
            padded = (None,) * (leaf.ndim - len(axes)) + tuple(axes)
            spec = sh.relaxed_spec(leaf.shape, padded, mesh)
        out.append(NamedSharding(mesh, spec))
    return tree_unflatten(treedef, out)


def write(
    pool: Array,  # (NB, H, BS, D)
    table: Array,  # (B, MB) int32
    pos: Array,  # (B,) int32 — write position per slot
    val: Array,  # (B, H, D) — one token's K or V per slot
    active: Array | None = None,  # (B,) bool; inactive slots write nothing
) -> Array:
    """Scatter one token per slot into its block.  Inactive slots are
    routed out of bounds and dropped, so a finished request can never
    scribble into a block that has been reclaimed and reassigned."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    if active is not None:
        blk = jnp.where(active, blk, pool.shape[0])  # OOB -> mode="drop"
    return pool.at[blk, :, off].set(val.astype(pool.dtype), mode="drop")


def write_span(
    pool: Array,  # (NB, H, BS, D)
    table: Array,  # (B, MB) int32
    pos: Array,  # (B,) int32 — first write position per slot
    val: Array,  # (B, T, H, D) — T consecutive tokens per slot
    active: Array | None = None,  # (B,) bool; inactive slots write nothing
    lengths: Array | None = None,  # (B,) int32; tokens t >= lengths[b] dropped
) -> Array:
    """Scatter a span of T tokens per slot into its pages: position
    ``pos[b] + t`` lands at ``(table[b, (pos[b]+t) // BS], :, (pos[b]+t) % BS)``.

    This is the multi-token generalisation of :func:`write` that both
    chunked prefill (prompt slices land directly in pool pages) and
    one-shot admission install (a batch-1 prefilled dense cache scattered
    into the slot's pages in one span) run — the single pool write path.
    Masked entries (inactive slot, or ``t >= lengths[b]`` on a ragged
    final slice) are routed out of bounds and dropped, exactly like
    :func:`write`'s inactive slots.
    """
    bs = pool.shape[2]
    t = val.shape[1]
    p = pos[:, None] + jnp.arange(t, dtype=pos.dtype)[None, :]  # (B, T)
    mb = table.shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(p // bs, 0, mb - 1), axis=1)
    ok = p < mb * bs  # masked rows may run past the table; clip + drop
    if lengths is not None:
        ok = ok & (jnp.arange(t)[None, :] < lengths[:, None])
    if active is not None:
        ok = ok & active[:, None]
    blk = jnp.where(ok, blk, pool.shape[0])  # OOB -> mode="drop"
    return pool.at[blk, :, p % bs].set(val.astype(pool.dtype), mode="drop")


def read(pool: Array, table: Array, blocks: int | None = None) -> Array:
    """Gather a dense per-slot view: (B, nb * BS, H, D) in position order,
    where ``nb`` is ``blocks`` (a static used-prefix bound) or the full
    table width.

    Callers that know no position ``>= blocks * BS`` can be attended (the
    prefill path's static ``read_to`` bound, or a pool sized for far more
    blocks than any live slot holds) pass ``blocks`` so the gather stops
    at the used-block prefix instead of materializing the whole table —
    at short contexts that is most of the fallback's memory traffic.
    Unallocated table entries point at block 0; the positions they cover
    sit beyond the slot's ``pos`` and are excluded by the attention mask,
    so the garbage is never read into a softmax lane.
    """
    if blocks is not None:
        table = table[:, : max(1, min(int(blocks), table.shape[1]))]
    g = jnp.take(pool, table, axis=0).swapaxes(2, 3)  # (B, nb, BS, H, D)
    b, nb, bs = g.shape[:3]
    return g.reshape(b, nb * bs, *g.shape[3:])


def hash_block_tokens(parent: int | None, tokens) -> int:
    """Content identity of one FULL block: a chain hash of
    ``(parent_hash, block_tokens)``.

    The hash is computed over the HOST token stream (never over pool
    bytes), so two prompts share a block id exactly when they share the
    token prefix up to and including this block — and the identity is
    independent of dtype, mesh shape, or how the pool happens to be
    sharded.  ``parent`` is ``None`` for the first block of a prompt.
    """
    return hash((parent, tuple(int(t) for t in tokens)))


def prompt_block_hashes(tokens, block_size: int) -> list[int]:
    """Chain hashes for every *full* block of a token stream (the trailing
    partial block has no content identity — it is still being written)."""
    bs = int(block_size)
    out: list[int] = []
    parent: int | None = None
    for i in range(len(tokens) // bs):
        parent = hash_block_tokens(parent, tokens[i * bs : (i + 1) * bs])
        out.append(parent)
    return out


def copy_block(pool: Array, src, dst) -> Array:
    """``pool[dst] = pool[src]`` — one page copied inside the pool.  This
    is the copy-on-write primitive: a slot that must write inside a shared
    block first duplicates the page into a private block, so the shared
    page (and every other slot reading it) is never mutated."""
    return pool.at[dst].set(pool[src])


class BlockAllocator:
    """Host-side ref-counted free list over the pool's block ids, with
    content-hash identity and an LRU of reusable (cached) blocks.

    The allocator is the single source of truth for block ownership: the
    scheduler allocates at admission / chunk boundaries and *unrefs* on
    eviction.  Each block carries a refcount (shared prefix blocks are
    held by several slots at once) and, optionally, a content hash
    registered by the scheduler once the block's pages are fully written.
    A block whose refcount drops to zero is not forgotten: if it has a
    registered hash it parks on an LRU list, still indexed by
    ``lookup``, until :meth:`alloc` reclaims it (never-hashed blocks go
    straight back to the blank free list).  So "free" really means
    "unreferenced", and ``free_count`` counts *allocatable* blocks —
    blank + cached — which keeps the drain invariant
    ``free_count == num_blocks`` (and ``pool_blocks_used == 0``) intact
    even with a warm cache.

    Invariants (pinned by the property suite in ``tests/test_kv_pool.py``):

    * conservation — ``free_count + used_count == num_blocks`` at every
      step, where ``used_count`` counts blocks with refcount > 0;
    * eviction only ever reclaims refcount-0 blocks (live blocks are
      never on the LRU);
    * every hash-map entry points at a live-or-cached block (eviction
      drops the hash entries of the block it reclaims);
    * double-unref detection is O(1) (the refcount is the check — no
      membership scan of a free list).

    ``fail_hook`` is the fault-injection seam (see
    :mod:`repro.serve.faults`): a callable consulted once per ``alloc``
    whose ``True`` forces that call to fail with exhaustion semantics —
    ``None`` returned, no state change.  ``None`` (the default) costs one
    ``is not None`` check per alloc and nothing else.

    ``metrics`` is an optional :class:`repro.serve.metrics.MetricsRegistry`
    (duck-typed — this module stays dependency-free): when set, the
    allocator keeps the ``pool_blocks_used`` gauge exact at every
    alloc/unref (utilization is maintained at the source of truth, so it
    provably returns to zero after a drain) and counts
    ``block_allocs_total`` (blocks handed out),
    ``block_alloc_failures_total`` (exhaustion + injected failures) and
    ``prefix_cache_evictions_total`` (cached blocks reclaimed by alloc).
    Each metric is guarded independently — a registry that hands back
    only some instruments still gets the ones it asked for.
    """

    def __init__(self, num_blocks: int, fail_hook=None, metrics=None):
        self.num_blocks = num_blocks
        self.fail_hook = fail_hook
        self._ref = [0] * num_blocks  # refcount per block id
        self._blank = list(range(num_blocks - 1, -1, -1))  # pop() -> low ids
        # refcount-0 blocks that still hold registered content, in release
        # order (dict preserves insertion order): front = least recently
        # released = first evicted.
        self._lru: dict[int, None] = {}
        self._hash_of: dict[int, int] = {}  # block id -> content hash
        self._block_of: dict[int, int] = {}  # content hash -> block id
        self._g_used = metrics.gauge("pool_blocks_used") if metrics else None
        self._c_allocs = (
            metrics.counter("block_allocs_total") if metrics else None
        )
        self._c_fail = (
            metrics.counter("block_alloc_failures_total") if metrics else None
        )
        self._c_evict = (
            metrics.counter("prefix_cache_evictions_total") if metrics else None
        )

    @property
    def free_count(self) -> int:
        """Allocatable blocks: blank + cached (refcount-0, evictable)."""
        return len(self._blank) + len(self._lru)

    @property
    def used_count(self) -> int:
        """Blocks with refcount > 0 (owned by at least one slot)."""
        return self.num_blocks - self.free_count

    @property
    def cached_count(self) -> int:
        """Blocks with a registered content hash (live or parked)."""
        return len(self._block_of)

    def refcount(self, i: int) -> int:
        return self._ref[i]

    def _mark_fail(self) -> None:
        if self._c_fail is not None:
            self._c_fail.inc()

    def _set_used_gauge(self) -> None:
        if self._g_used is not None:
            self._g_used.set(self.used_count)

    def alloc(self, n: int) -> list[int] | None:
        """n block ids at refcount 1, or None (and no ownership change) if
        the pool is exhausted (or a fault-injection hook says to pretend
        it is).  Blank blocks are handed out first; when they run out the
        least-recently-released cached block is evicted — its hash-map
        entries die with it, so the index never points at a reclaimed
        block.  Refcount>0 blocks are never candidates."""
        if self.fail_hook is not None and self.fail_hook():
            self._mark_fail()
            return None
        if n > self.free_count:
            self._mark_fail()
            return None
        got = []
        for _ in range(n):
            if self._blank:
                i = self._blank.pop()
            else:
                i = next(iter(self._lru))  # least recently released
                del self._lru[i]
                del self._block_of[self._hash_of.pop(i)]
                if self._c_evict is not None:
                    self._c_evict.inc()
            self._ref[i] = 1
            got.append(i)
        self._set_used_gauge()
        if self._c_allocs is not None:
            self._c_allocs.inc(n)
        return got

    def unref(self, ids) -> None:
        """Drop one reference per id.  A block reaching refcount 0 parks
        on the LRU if its content is registered (a future admission can
        still hit it), else returns to the blank list.  Double-unref is an
        error, detected in O(1) from the refcount — no free-list scan."""
        pending: dict[int, int] = {}
        for i in ids:  # validate everything before mutating anything
            if not 0 <= i < self.num_blocks:
                raise ValueError(f"block id {i} out of range")
            pending[i] = pending.get(i, 0) + 1
            if pending[i] > self._ref[i]:
                raise ValueError(f"double free of block {i}")
        for i in ids:
            self._ref[i] -= 1
            if self._ref[i] == 0:
                if i in self._hash_of:
                    self._lru[i] = None  # most recently released -> back
                else:
                    self._blank.append(i)
        self._set_used_gauge()

    # "free" predates the refcounts; release paths still call it, and for
    # never-shared blocks it behaves exactly as before (ref 1 -> blank).
    free = unref

    def ref(self, i: int) -> None:
        """Take one reference on a live or cached block (an admission hit
        calls this for every reused block).  Reviving a cached block pulls
        it off the LRU so it can no longer be evicted."""
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block id {i} out of range")
        if self._ref[i] == 0:
            if i not in self._lru:
                raise ValueError(f"block {i} is blank — nothing to share")
            del self._lru[i]
        self._ref[i] += 1
        self._set_used_gauge()

    def lookup(self, h: int) -> int | None:
        """Block id currently holding content ``h``, or None.  Does not
        take a reference — callers :meth:`ref` each hit before any
        further alloc so their own tail allocation cannot evict it."""
        return self._block_of.get(h)

    def register(self, i: int, h: int) -> bool:
        """Record that live block ``i`` now holds content ``h`` (its pages
        are fully written).  First writer wins: if ``h`` is already mapped
        to another block, this one simply stays private (returns False)
        and will recycle as blank.  Re-registering the same (block, hash)
        is a no-op; re-registering a block under a *different* hash is a
        bug — block content never changes while registered."""
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block id {i} out of range")
        if self._ref[i] <= 0:
            raise ValueError(f"register of unreferenced block {i}")
        cur = self._hash_of.get(i)
        if cur is not None:
            if cur != h:
                raise ValueError(
                    f"block {i} re-registered under a different hash"
                )
            return True
        if h in self._block_of:
            return False
        self._hash_of[i] = h
        self._block_of[h] = i
        return True
