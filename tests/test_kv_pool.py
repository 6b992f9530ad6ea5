"""Paged KV pool unit tests: allocator accounting (incl. a hypothesis
property test over random alloc/free/preemption traces), scatter/gather
roundtrips, masked writes, and the dense-view equivalence the attention
parity tests build on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import kv_pool


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = kv_pool.BlockAllocator(8)
        got = a.alloc(5)
        assert len(got) == 5 and len(set(got)) == 5
        assert a.free_count == 3
        a.free(got)
        assert a.free_count == 8

    def test_exhaustion_returns_none_without_side_effects(self):
        a = kv_pool.BlockAllocator(4)
        first = a.alloc(3)
        assert a.alloc(2) is None
        assert a.free_count == 1  # failed alloc took nothing
        a.free(first)
        assert a.free_count == 4

    def test_double_free_rejected(self):
        a = kv_pool.BlockAllocator(4)
        got = a.alloc(2)
        a.free(got)
        with pytest.raises(ValueError, match="double free"):
            a.free([got[0]])

    def test_foreign_id_rejected(self):
        a = kv_pool.BlockAllocator(4)
        with pytest.raises(ValueError, match="out of range"):
            a.free([99])

    @staticmethod
    def _check_alloc_trace(num_blocks: int, ops) -> None:
        """Invariant driver for one alloc/free/preemption trace: the
        allocator never double-allocates a live block, a failed alloc
        changes nothing, and ``free_count + outstanding == num_blocks``
        holds at every step (conservation — no block leaks, no block
        invented).  ``ops`` is a list of (kind, n, pick) int triples."""
        a = kv_pool.BlockAllocator(num_blocks)
        live: dict[int, list[int]] = {}  # request -> owned blocks
        next_uid = 0
        for kind, n, pick in ops:
            outstanding = sum(len(v) for v in live.values())
            assert a.free_count + outstanding == num_blocks
            if kind == 0:  # admission / per-chunk growth alloc
                got = a.alloc(n)
                if n > num_blocks - outstanding:
                    assert got is None  # exhaustion: and no state change
                    assert a.free_count == num_blocks - outstanding
                    continue
                assert got is not None and len(got) == n
                owned = {b for v in live.values() for b in v}
                # no double allocation: fresh ids only, all in range
                assert not (set(got) & owned)
                assert len(set(got)) == n
                assert all(0 <= b < num_blocks for b in got)
                if pick % 2 and live:  # growth of an existing request
                    live[sorted(live)[pick % len(live)]].extend(got)
                else:
                    live[next_uid] = list(got)
                    next_uid += 1
            elif kind == 1 and live:  # eviction / preemption (free all)
                uid = sorted(live)[pick % len(live)]
                a.free(live.pop(uid))
            elif kind == 2 and live:  # double free must be rejected
                uid = sorted(live)[pick % len(live)]
                blocks = live.pop(uid)
                a.free(blocks)
                if blocks:
                    with pytest.raises(ValueError, match="double free"):
                        a.free(blocks[:1])
        outstanding = sum(len(v) for v in live.values())
        assert a.free_count + outstanding == num_blocks

    def test_property_random_alloc_free_preempt_traces(self):
        """Hypothesis property test over arbitrary op interleavings (the
        shrinking search is what earns its keep on a counterexample)."""
        hypothesis = pytest.importorskip("hypothesis")
        st = hypothesis.strategies

        @hypothesis.given(
            num_blocks=st.integers(1, 24),
            ops=st.lists(
                st.tuples(
                    st.integers(0, 2), st.integers(0, 8), st.integers(0, 7)
                ),
                max_size=60,
            ),
        )
        @hypothesis.settings(deadline=None, max_examples=60)
        def run(num_blocks, ops):
            self._check_alloc_trace(num_blocks, ops)

        run()

    def test_fail_hook_forces_exhaustion_semantics(self):
        """The fault-injection seam: a firing hook makes ``alloc`` return
        None with NO state change (exactly the pool-exhausted contract);
        a quiet hook is invisible."""
        calls = iter([True, False])
        a = kv_pool.BlockAllocator(4, fail_hook=lambda: next(calls))
        assert a.alloc(2) is None  # forced failure
        assert a.free_count == 4  # took nothing
        got = a.alloc(2)  # hook quiet: normal alloc
        assert len(got) == 2 and a.free_count == 2
        a.free(got)
        assert a.free_count == 4

    def test_random_alloc_free_preempt_traces_seeded(self):
        """Seeded-random sweep through the same invariant driver so the
        property is exercised even where hypothesis isn't installed."""
        rng = np.random.default_rng(0)
        for _ in range(50):
            num_blocks = int(rng.integers(1, 25))
            ops = [
                (int(rng.integers(0, 3)), int(rng.integers(0, 9)),
                 int(rng.integers(0, 8)))
                for _ in range(int(rng.integers(0, 61)))
            ]
            self._check_alloc_trace(num_blocks, ops)


class TestPrefixCacheAllocator:
    """Refcounts, the content-hash index and the LRU of cached blocks —
    the allocator surface automatic prefix caching runs on."""

    def test_unref_parks_registered_block_still_hittable(self):
        a = kv_pool.BlockAllocator(4)
        b = a.alloc(1)[0]
        assert a.register(b, 123)
        a.unref([b])
        # "free" means unreferenced: the block counts as allocatable AND
        # its content is still indexed
        assert a.free_count == 4 and a.used_count == 0
        assert a.lookup(123) == b

    def test_hit_ref_revives_cached_block_off_the_lru(self):
        a = kv_pool.BlockAllocator(2)
        b = a.alloc(1)[0]
        a.register(b, 7)
        a.unref([b])
        a.ref(b)  # admission hit
        assert a.refcount(b) == 1 and a.used_count == 1
        got = a.alloc(1)  # must come from the blank block, not evict b
        assert got != [b]
        assert a.alloc(1) is None  # pool genuinely full now
        a.unref([b] + got)
        assert a.free_count == 2 and a.lookup(7) == b

    def test_shared_block_refcounts_and_staged_release(self):
        a = kv_pool.BlockAllocator(4)
        b = a.alloc(1)[0]
        a.register(b, 9)
        a.ref(b)  # second owner
        a.ref(b)  # third owner
        assert a.refcount(b) == 3 and a.used_count == 1
        a.unref([b])
        a.unref([b])
        assert a.refcount(b) == 1  # still owned — not evictable
        blanks = a.alloc(3)
        assert b not in blanks
        a.unref([b])
        assert a.free_count == 1 and a.lookup(9) == b

    def test_lru_evicts_least_recently_released_and_drops_hash(self):
        a = kv_pool.BlockAllocator(2)
        b1 = a.alloc(1)
        a.register(b1[0], 111)
        b2 = a.alloc(1)
        a.register(b2[0], 222)
        a.unref(b1)
        a.unref(b2)  # release order: b1 is the older parkee
        got = a.alloc(1)
        assert got == b1  # LRU: least recently released goes first
        # the evicted block's identity died with it; the survivor's didn't
        assert a.lookup(111) is None
        assert a.lookup(222) == b2[0]

    def test_blank_blocks_allocated_before_cached(self):
        a = kv_pool.BlockAllocator(3)
        b = a.alloc(1)
        a.register(b[0], 1)
        a.unref(b)
        got = a.alloc(2)
        assert b[0] not in got  # blanks first: the cached block survives
        assert a.lookup(1) == b[0]

    def test_double_unref_rejected_via_refcount(self):
        a = kv_pool.BlockAllocator(4)
        got = a.alloc(2)
        a.unref(got)
        with pytest.raises(ValueError, match="double free"):
            a.unref([got[0]])
        # duplicates inside ONE call are caught too (and atomically:
        # validation precedes any mutation)
        b = a.alloc(1)[0]
        with pytest.raises(ValueError, match="double free"):
            a.unref([b, b])
        assert a.refcount(b) == 1

    def test_ref_of_blank_block_rejected(self):
        a = kv_pool.BlockAllocator(2)
        with pytest.raises(ValueError, match="blank"):
            a.ref(0)

    def test_register_requires_live_block_and_stable_hash(self):
        a = kv_pool.BlockAllocator(4)
        b1, b2 = a.alloc(2)
        with pytest.raises(ValueError, match="unreferenced"):
            a.register(3, 5)  # never allocated
        assert a.register(b1, 5)
        assert a.register(b1, 5)  # same (block, hash): idempotent
        with pytest.raises(ValueError, match="different hash"):
            a.register(b1, 6)
        # first writer wins: a duplicate content block stays private
        assert not a.register(b2, 5)
        assert a.lookup(5) == b1
        a.unref([b1, b2])
        # ... and recycles as blank (still allocatable, never indexed)
        assert a.free_count == 4 and a.lookup(5) == b1

    def test_metrics_guards_are_independent(self):
        """Satellite regression: ``alloc`` must count blocks even when the
        registry hands back no gauge — each instrument is guarded on its
        own, not nested under another's ``is not None``."""

        class _Counter:
            def __init__(self):
                self.value = 0

            def inc(self, n=1):
                self.value += n

        class _NoGaugeMetrics:
            def __init__(self):
                self.counters = {}

            def gauge(self, name):
                return None  # this registry has no gauges at all

            def counter(self, name):
                return self.counters.setdefault(name, _Counter())

        m = _NoGaugeMetrics()
        a = kv_pool.BlockAllocator(4, metrics=m)
        got = a.alloc(3)
        assert m.counters["block_allocs_total"].value == 3
        a.unref(got)
        assert a.alloc(9) is None
        assert m.counters["block_alloc_failures_total"].value == 1
        # eviction counting rides the same independent guard
        b = a.alloc(1)
        a.register(b[0], 42)
        a.unref(b)
        a.alloc(4)
        assert m.counters["prefix_cache_evictions_total"].value == 1

    def test_chain_hash_prefix_sensitivity(self):
        bs = 4
        t = list(range(16))
        h = kv_pool.prompt_block_hashes(t, bs)
        assert len(h) == 4
        t2 = list(t)
        t2[0] ^= 1
        h2 = kv_pool.prompt_block_hashes(t2, bs)
        # a first-block change reaches every descendant through the chain
        assert h2[0] != h[0] and h2[3] != h[3]
        t3 = list(t)
        t3[-1] ^= 1
        h3 = kv_pool.prompt_block_hashes(t3, bs)
        # a last-block change leaves the shared prefix ids alone
        assert h3[:3] == h[:3] and h3[3] != h[3]
        # the trailing partial block has no identity yet
        assert len(kv_pool.prompt_block_hashes(t[:15], bs)) == 3
        # host-stream identity: a numpy int32 stream hashes exactly like
        # python ints (what makes hits mesh/dtype-independent)
        assert kv_pool.prompt_block_hashes(np.asarray(t, np.int32), bs) == h

    def test_copy_block_duplicates_page_without_touching_source(self):
        pool = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 2, 3))
        out = np.asarray(kv_pool.copy_block(pool, 1, 3))
        ref = np.asarray(pool)
        np.testing.assert_array_equal(out[3], ref[1])  # dst is the copy
        np.testing.assert_array_equal(out[1], ref[1])  # src untouched
        np.testing.assert_array_equal(out[:1], ref[:1])
        np.testing.assert_array_equal(out[2], ref[2])

    @staticmethod
    def _check_prefix_trace(num_blocks: int, block_size: int, ops) -> None:
        """Invariant driver for one admission/share/release trace over the
        refcounted allocator, mirroring the scheduler's hit-walk protocol
        (lookup -> ref hits -> alloc tail -> register misses).  Checked at
        every step:

        * conservation — ``free_count + #{blocks with refcount>0} ==
          num_blocks``, and the allocator's refcounts match the model's
          outstanding per-block owner counts exactly;
        * no eviction of referenced blocks — every block ``alloc`` hands
          out has model refcount 0;
        * hash-index liveness — every indexed hash maps to the block that
          was registered under it and that block is never blank/reclaimed;
        * failed allocs change no ownership;
        * double-unref raises exactly when the model refcount is 0.

        ``ops`` is a list of (kind, x, y) int triples.  Prompts come from
        a tiny family of 5 token streams so traces actually share
        prefixes."""
        a = kv_pool.BlockAllocator(num_blocks)
        live: dict[int, list[int]] = {}  # uid -> owned block ids (with dups)
        refs = [0] * num_blocks  # model refcounts
        content: dict[int, int] = {}  # block -> hash registered on it
        next_uid = 0

        def check():
            used = sum(1 for r in refs if r > 0)
            assert a.free_count + used == num_blocks, "conservation"
            assert a.used_count == used
            for b in range(num_blocks):
                assert a.refcount(b) == refs[b], f"refcount drift at {b}"
            for h, b in a._block_of.items():
                assert content.get(b) == h, "hash index points off-content"
                assert b not in a._blank, "hash index points at blank block"

        for kind, x, y in ops:
            check()
            if kind == 0:  # admission hit-walk
                length = 1 + x % (3 * block_size)
                fam = y % 5
                tokens = [fam * 1000 + i for i in range(length)]
                hashes = kv_pool.prompt_block_hashes(tokens, block_size)
                nb = kv_pool.blocks_for(length, block_size)
                hits: list[int] = []
                for h in hashes:
                    b = a.lookup(h)
                    if b is None:
                        break
                    hits.append(b)
                for b in hits:
                    a.ref(b)
                    refs[b] += 1
                got = a.alloc(nb - len(hits))
                if got is None:
                    a.unref(hits)
                    for b in hits:
                        refs[b] -= 1
                    continue
                for b in got:
                    assert refs[b] == 0, "alloc stole a referenced block"
                    content.pop(b, None)  # reclaimed: old identity is gone
                    refs[b] = 1
                blocks = hits + got
                for i in range(len(hits), len(hashes)):
                    if a.register(blocks[i], hashes[i]):
                        content[blocks[i]] = hashes[i]
                live[next_uid] = blocks
                next_uid += 1
            elif kind == 1 and live:  # release = unref (blocks stay cached)
                uid = sorted(live)[x % len(live)]
                blocks = live.pop(uid)
                a.unref(blocks)
                for b in blocks:
                    refs[b] -= 1
            elif kind == 2 and live:  # release + double-unref probe
                uid = sorted(live)[x % len(live)]
                blocks = live.pop(uid)
                a.unref(blocks)
                for b in blocks:
                    refs[b] -= 1
                dead = [b for b in blocks if refs[b] == 0]
                if dead:
                    with pytest.raises(ValueError, match="double free"):
                        a.unref(dead[:1])
                elif blocks:
                    # still shared by another request: unref is legal...
                    a.unref(blocks[:1])
                    refs[blocks[0]] -= 1
                    a.ref(blocks[0])  # ...and reversible
                    refs[blocks[0]] += 1
        check()
        for blocks in live.values():  # drain: the pool must reconcile
            a.unref(blocks)
            for b in blocks:
                refs[b] -= 1
        assert all(r == 0 for r in refs)
        assert a.free_count == num_blocks and a.used_count == 0
        check()

    def test_property_random_share_release_traces(self):
        """Hypothesis sweep over arbitrary admission/share/release
        interleavings of the refcount/LRU/hash invariants."""
        hypothesis = pytest.importorskip("hypothesis")
        st = hypothesis.strategies

        @hypothesis.given(
            num_blocks=st.integers(1, 24),
            block_size=st.sampled_from([1, 2, 4, 8]),
            ops=st.lists(
                st.tuples(
                    st.integers(0, 2), st.integers(0, 31), st.integers(0, 7)
                ),
                max_size=60,
            ),
        )
        @hypothesis.settings(deadline=None, max_examples=60)
        def run(num_blocks, block_size, ops):
            self._check_prefix_trace(num_blocks, block_size, ops)

        run()

    def test_random_share_release_traces_seeded(self):
        """Seeded fallback for the same property where hypothesis isn't
        installed."""
        rng = np.random.default_rng(1)
        for _ in range(50):
            num_blocks = int(rng.integers(1, 25))
            block_size = int(rng.choice([1, 2, 4, 8]))
            ops = [
                (int(rng.integers(0, 3)), int(rng.integers(0, 32)),
                 int(rng.integers(0, 8)))
                for _ in range(int(rng.integers(0, 61)))
            ]
            self._check_prefix_trace(num_blocks, block_size, ops)


class TestPagedReadWrite:
    B, MB, BS, H, D, NB = 2, 3, 4, 2, 8, 7

    def _pool_and_table(self):
        pool = jnp.zeros((self.NB, self.H, self.BS, self.D), jnp.float32)
        # slot 0 owns blocks [1, 2, 3]; slot 1 owns [4, 5, 6]
        table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        return pool, table

    def test_write_read_roundtrip_position_order(self):
        pool, table = self._pool_and_table()
        vals = {}
        for p in range(self.MB * self.BS):
            v = jax.random.normal(
                jax.random.PRNGKey(p), (self.B, self.H, self.D)
            )
            pool = kv_pool.write(
                pool, table, jnp.full((self.B,), p, jnp.int32), v, None
            )
            vals[p] = np.asarray(v)
        dense = np.asarray(kv_pool.read(pool, table))
        assert dense.shape == (self.B, self.MB * self.BS, self.H, self.D)
        for p, v in vals.items():
            np.testing.assert_array_equal(dense[:, p], v)

    def test_inactive_slots_write_nothing(self):
        pool, table = self._pool_and_table()
        v = jnp.ones((self.B, self.H, self.D))
        pool2 = kv_pool.write(
            pool, table, jnp.zeros((self.B,), jnp.int32), v,
            jnp.asarray([True, False]),
        )
        dense = np.asarray(kv_pool.read(pool2, table))
        assert (dense[0, 0] == 1.0).all()
        assert (dense[1] == 0.0).all()  # inactive slot untouched

    def test_write_span_installs_dense_prefill_prefix(self):
        """One-shot admission install (the scheduler's _make_install_fn)
        is a batch-1 write_span of the prefilled dense cache, bounded to
        the prompt-covering pages — the pool holds the dense prefix
        element for element (scatter_prefill's old contract, now served
        by the one write path)."""
        pool, table = self._pool_and_table()
        nb = 2  # prompt covers two pages
        L = self.MB * self.BS  # the dense cache is full slot length
        dense = jax.random.normal(
            jax.random.PRNGKey(0), (1, L, self.H, self.D)
        )
        pool = kv_pool.write_span(
            pool, table[:1], jnp.zeros((1,), jnp.int32), dense, None,
            jnp.asarray([nb * self.BS], jnp.int32),
        )
        got = np.asarray(kv_pool.read(pool, table))[0]
        np.testing.assert_array_equal(
            got[: nb * self.BS], np.asarray(dense)[0, : nb * self.BS]
        )
        assert (got[nb * self.BS:] == 0.0).all()  # uncovered pages untouched

    def test_read_clamps_to_used_block_prefix(self):
        """``read(blocks=n)`` gathers only the first n table entries: same
        values on the covered prefix, and the short gather never touches
        the pool rows the dropped entries point at."""
        pool, table = self._pool_and_table()
        for p in range(self.BS + 1):  # spills into the second page
            v = jax.random.normal(
                jax.random.PRNGKey(p), (self.B, self.H, self.D)
            )
            pool = kv_pool.write(
                pool, table, jnp.full((self.B,), p, jnp.int32), v, None
            )
        full = np.asarray(kv_pool.read(pool, table))
        short = np.asarray(kv_pool.read(pool, table, blocks=2))
        assert short.shape == (self.B, 2 * self.BS, self.H, self.D)
        np.testing.assert_array_equal(short, full[:, : 2 * self.BS])
        # the clamp never returns an empty gather and caps at the table
        assert kv_pool.read(pool, table, blocks=0).shape[1] == self.BS
        assert (
            kv_pool.read(pool, table, blocks=99).shape[1]
            == self.MB * self.BS
        )

    def test_write_span_matches_token_loop(self):
        """The multi-token span scatter is elementwise the per-token
        ``write`` loop — chunked prefill's pages are bit-identical to what
        one-shot install would have produced."""
        pool, table = self._pool_and_table()
        t = 6  # crosses a page boundary (BS=4) at different offsets/slot
        pos = jnp.asarray([1, 3], jnp.int32)
        val = jax.random.normal(jax.random.PRNGKey(7), (self.B, t, self.H, self.D))
        got = kv_pool.write_span(pool, table, pos, val)
        want = pool
        for i in range(t):
            want = kv_pool.write(want, table, pos + i, val[:, i], None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_write_span_masks_lengths_and_active(self):
        """Ragged final slices (``lengths``) and inactive slots write
        nothing — the pad tail of a chunked-prefill slice can never
        scribble into someone else's reclaimed page."""
        pool, table = self._pool_and_table()
        t = 4
        val = jnp.ones((self.B, t, self.H, self.D))
        got = kv_pool.write_span(
            pool, table, jnp.zeros((self.B,), jnp.int32), val,
            jnp.asarray([True, False]), jnp.asarray([2, 4], jnp.int32),
        )
        dense = np.asarray(kv_pool.read(got, table))
        assert (dense[0, :2] == 1.0).all()
        assert (dense[0, 2:] == 0.0).all()  # beyond lengths[0]
        assert (dense[1] == 0.0).all()  # inactive slot untouched

    def test_write_span_drops_positions_past_table(self):
        """Masked entries may run past the slot's table (padded slice at
        the end of a full slot): they are clipped + dropped, not wrapped
        into another slot's pages."""
        pool, table = self._pool_and_table()
        cap = self.MB * self.BS
        t = 3
        val = jnp.ones((self.B, t, self.H, self.D))
        got = kv_pool.write_span(
            pool, table, jnp.full((self.B,), cap - 1, jnp.int32), val,
            None, jnp.asarray([1, 1], jnp.int32),
        )
        dense = np.array(kv_pool.read(got, table))
        assert (dense[:, cap - 1] == 1.0).all()
        assert (np.asarray(got)[0] == 0.0).all()  # block 0 never touched
        dense[:, cap - 1] = 0.0
        assert (dense == 0.0).all()

    def test_blocks_for(self):
        assert kv_pool.blocks_for(1, 4) == 1
        assert kv_pool.blocks_for(4, 4) == 1
        assert kv_pool.blocks_for(5, 4) == 2

    def test_init_rejects_ragged_max_len(self):
        with pytest.raises(ValueError, match="multiple of block_size"):
            kv_pool.init_paged_attention_cache(2, 10, 2, 8, 4, 4, jnp.float32)
