"""Paged-attention kernel subsystem: the Pallas block-table kernel against
the gather+SDPA reference (decode, chunked prefill, one-shot prefill; GQA
and MQA; multiple block sizes), the ops-level dispatch gates, autotune
persistence, and the model-stack routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tile_cache
from repro.kernels.paged_attention import (
    decode_pages,
    paged_attention,
    paged_attention_decode,
)

KEY = jax.random.PRNGKey(0)


def _setup(b, hkv, d, bs, mb, nb=None, dtype=jnp.float32, seed=0):
    """Random pools + a scattered (non-identity, per-slot disjoint) block
    table — position order in the table must be what the kernel walks,
    not pool order."""
    rng = np.random.default_rng(seed)
    nb = nb or (b * mb + 3)
    kpool = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), dtype)
    vpool = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), dtype)
    table = jnp.asarray(
        rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32
    )
    return kpool, vpool, table


def _q(b, t, hq, d, dtype=jnp.float32, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, t, hq, d)), dtype)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1), (2, 2)])  # GQA/MQA/MHA
@pytest.mark.parametrize("bs", [8, 16])
def test_decode_shape_matches_reference(hq, hkv, bs):
    """T=1 decode at ragged per-slot positions: kernel == gather+SDPA
    reference at fp32 accumulation, for GQA, MQA and MHA groupings and
    two block sizes."""
    b, d, mb = 3, 16, 4
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    q = _q(b, 1, hq, d)
    start = jnp.asarray([0, bs + 3, mb * bs - 1], jnp.int32)
    got = paged_attention(
        q, kpool, vpool, table, start, start + 1, interpret=True
    )
    want = ref.paged_attention_ref(q, kpool, vpool, table, start, start + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1)])
@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("pages", [1, 2, 4])
def test_chunk_matches_reference_across_page_tiles(hq, hkv, bs, pages):
    """T>1 chunk against a resident prefix: the causal in-chunk mask and
    the prefix mask both hold for every pages-per-step tiling (the
    autotune knob must never change results)."""
    b, t, d, mb = 2, 5, 8, 4
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    q = _q(b, t, hq, d)
    start = jnp.asarray([3, bs - 2], jnp.int32)  # one slot straddles a page
    got = paged_attention(
        q, kpool, vpool, table, start, start + t, pages=pages,
        interpret=True,
    )
    want = ref.paged_attention_ref(q, kpool, vpool, table, start, start + t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_one_shot_prefill_from_empty_cache():
    """T = S from position 0 (one-shot prefill): every query attends only
    its in-chunk causal predecessors."""
    b, t, hq, hkv, d, bs, mb = 2, 12, 4, 2, 16, 8, 2
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    q = _q(b, t, hq, d)
    start = jnp.zeros((b,), jnp.int32)
    got = paged_attention(
        q, kpool, vpool, table, start, start + t, pages=2, interpret=True
    )
    want = ref.paged_attention_ref(q, kpool, vpool, table, start, start + t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_kv_lens_bounds_the_page_walk():
    """Pages past a slot's resident length are skipped (their index map
    clamps to the last used page) — results must not depend on garbage in
    the unreached pages: poisoning them with NaN stays invisible."""
    b, t, hq, hkv, d, bs, mb = 2, 1, 4, 2, 8, 8, 4
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    start = jnp.asarray([2, bs + 1], jnp.int32)
    lens = start + t
    # poison every page beyond each slot's used prefix
    used = [int(-(-int(l) // bs)) for l in lens]
    kp, vp = np.array(kpool), np.array(vpool)
    for s in range(b):
        for pg in range(used[s], mb):
            kp[np.asarray(table)[s, pg]] = np.nan
            vp[np.asarray(table)[s, pg]] = np.nan
    q = _q(b, t, hq, d)
    got = paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), table, start, lens,
        interpret=True,
    )
    want = ref.paged_attention_ref(q, kpool, vpool, table, start, lens)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_bf16_pools_fp32_accumulation():
    """bf16 pools/queries accumulate in f32 in-kernel: the kernel tracks
    the f32 reference to bf16-input rounding, not bf16-accumulation
    error."""
    b, t, hq, hkv, d, bs, mb = 2, 3, 4, 2, 16, 8, 3
    kpool, vpool, table = _setup(b, hkv, d, bs, mb, dtype=jnp.bfloat16)
    q = _q(b, t, hq, d, dtype=jnp.bfloat16)
    start = jnp.asarray([1, 7], jnp.int32)
    got = paged_attention(
        q, kpool, vpool, table, start, start + t, interpret=True
    )
    assert got.dtype == jnp.bfloat16
    want = ref.paged_attention_ref(
        q.astype(jnp.float32),
        kpool.astype(jnp.float32),
        vpool.astype(jnp.float32),
        table,
        start,
        start + t,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.02, rtol=0.02
    )


@pytest.mark.parametrize("pages", [1, 4, None])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])  # G 1, 4, 8
def test_decode_kernel_matches_reference(hq, hkv, dtype, pages):
    """The T == 1 kernel against the gather+SDPA reference over a permuted
    table: slots at length 1, mid-page, exactly on a page boundary and the
    full table in one batch, one page per step, several (with a dead tail
    in the last live step) and the derived count.  Pool pages outside the
    live prefixes hold NaN: a page the walk should skip would show."""
    b, d, mb = 5, 16, 6
    bs = 16 if dtype == jnp.bfloat16 else 8  # a whole sublane tile
    kpool, vpool, table = _setup(b, hkv, d, bs, mb, dtype=dtype)
    lens = np.asarray([1, bs + 3, 2 * bs, mb * bs, 1], np.int32)
    live = {int(table[s, pg]) for s in range(b)
            for pg in range(-(-int(lens[s]) // bs))}
    dead = np.asarray([i for i in range(kpool.shape[0]) if i not in live])
    kp = kpool.at[dead].set(jnp.nan)
    vp = vpool.at[dead].set(jnp.nan)
    q = _q(b, 1, hq, d, dtype=dtype)
    start = jnp.asarray(lens - 1)
    got = paged_attention_decode(
        q, kp, vp, table, start, jnp.asarray(lens), pages=pages,
        interpret=True,
    )
    assert got.shape == q.shape and got.dtype == dtype
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    want = ref.paged_attention_ref(
        f32(q), f32(kpool), f32(vpool), table, start, jnp.asarray(lens))
    # f32 accumulation throughout; a bf16 result is that, rounded once
    rtol = 2.0**-8 if dtype == jnp.bfloat16 else 0.0
    np.testing.assert_allclose(
        np.asarray(f32(got)), np.asarray(want), rtol=rtol, atol=2e-6)


def test_decode_pages_fill_the_step_bytes():
    """Pages per decode step come from the page's bytes and the table
    width alone: the cell's 64 KB bf16 pages (32 heads of 16 x 64) take
    four, one page is the floor, the table width the ceiling."""
    assert decode_pages(32 * 16 * 64 * 2, 64) == 4
    assert decode_pages(36 * 16 * 80 * 2, 64) == 2
    assert decode_pages(1 << 20, 64) == 1
    assert decode_pages(256, 6) == 6


# ---------------------------------------------------------------------------
# ops-level dispatch / autotune
# ---------------------------------------------------------------------------


def test_ops_wrapper_dispatch_and_gates(monkeypatch):
    b, t, hq, hkv, d, bs, mb = 2, 1, 4, 2, 8, 8, 2
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    q = _q(b, t, hq, d)
    start = jnp.asarray([0, 5], jnp.int32)
    got = ops.paged_attention(q, kpool, vpool, table, start, start + 1)
    want = ref.paged_attention_ref(q, kpool, vpool, table, start, start + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # support gate: GQA must divide, block/head_dim must be 8-aligned
    assert ops.paged_attention_supported(8, 16, 4, 2)
    assert not ops.paged_attention_supported(4, 16, 4, 2)  # block % 8
    assert not ops.paged_attention_supported(8, 12, 4, 2)  # head_dim % 8
    assert not ops.paged_attention_supported(8, 16, 4, 3)  # Hq % Hkv
    # enable gate: env forces beat the backend default
    monkeypatch.setenv("REPRO_PAGED_ATTN", "1")
    assert ops.paged_attention_enabled()
    monkeypatch.setenv("REPRO_PAGED_ATTN", "0")
    assert not ops.paged_attention_enabled()
    monkeypatch.delenv("REPRO_PAGED_ATTN")
    assert ops.paged_attention_enabled() == ops.on_tpu()


def test_paged_tiles_heuristic_prefers_dividing_candidates():
    assert ops.paged_tiles(1, 4, 2, 16, 8, 8) == 8
    assert ops.paged_tiles(1, 4, 2, 16, 8, 6) == 2
    assert ops.paged_tiles(1, 4, 2, 16, 8, 3) == 1


def test_sweep_paged_tiles_persists_per_backend(tmp_path, monkeypatch):
    """The paged-attention autotune family rides the same per-backend JSON
    as the GEMV tables: a swept winner survives a (simulated) process
    restart under its (T, Hq, Hkv, D, block, max_blocks) signature."""
    monkeypatch.setenv("REPRO_TILE_CACHE", "1")
    monkeypatch.setenv("REPRO_TILE_CACHE_DIR", str(tmp_path))
    saved = dict(ops._DECODE_TILE_CACHE)
    saved_loaded = ops._TILE_CACHE_LOADED
    ops._DECODE_TILE_CACHE.clear()
    ops._TILE_CACHE_LOADED = False
    try:
        t, hq, hkv, d, bs, mb = 1, 4, 2, 8, 8, 4
        best = ops.sweep_paged_tiles(
            t, hq, hkv, d, bs, mb, candidates=(1, 2), warmup=0, iters=1
        )
        assert best in (1, 2)
        key = ("paged_attn", t, hq, hkv, d, bs, mb)
        assert tile_cache.load("cpu")[key] == (best,)
        # simulated restart: the persisted winner answers paged_tiles
        ops._DECODE_TILE_CACHE.clear()
        ops._TILE_CACHE_LOADED = False
        assert ops.paged_tiles(t, hq, hkv, d, bs, mb) == best
        # GEMV keys coexist in the same file
        tile_cache.store("cpu", {("w1a8_gemv", 8, 64, 32): (16, 32)})
        loaded = tile_cache.load("cpu")
        assert loaded[key] == (best,)
        assert loaded[("w1a8_gemv", 8, 64, 32)] == (16, 32)
    finally:
        ops._DECODE_TILE_CACHE.clear()
        ops._DECODE_TILE_CACHE.update(saved)
        ops._TILE_CACHE_LOADED = saved_loaded


# ---------------------------------------------------------------------------
# model-stack routing
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from repro.configs.base import ModelConfig
    from repro.core.quantization import QuantConfig

    return ModelConfig(
        name="pa", family="decoder", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=48, vocab_size=64,
        quant=QuantConfig(mode="pquant", r=16, num_experts=1),
    )


def _paged_caches(cfg, b, max_len, bs):
    from repro.models import api

    caches, _ = api.init_cache(
        cfg, b, max_len, jnp.float32, layout="paged", block_size=bs
    )
    mb = max_len // bs
    table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)

    def fix(seg):
        return {
            k: (dict(c, table=jnp.broadcast_to(table, c["table"].shape))
                if isinstance(c, dict) and "table" in c else c)
            for k, c in seg.items()
        }

    return [fix(seg) for seg in caches]


def test_model_paged_branches_route_through_kernel(monkeypatch):
    """attention_chunk / the decode fast path produce (allclose) the same
    logits with the kernel forced on as with the gather+SDPA fallback —
    chunked prefill, ragged final slices and decode all ride the one
    kernel."""
    from repro.models import api

    cfg = _tiny_cfg()
    params, _ = api.init_model(KEY, cfg)
    b, max_len, bs = 2, 16, 8
    outs = {}
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", env)
        caches = _paged_caches(cfg, b, max_len, bs)
        active = jnp.asarray([True, True])
        got = []
        # chunked prefill: a full slice then a ragged one
        tok = jax.random.randint(KEY, (b, 4), 0, 64)
        l, caches = api.forward_chunk(
            params, tok, caches, jnp.zeros((b,), jnp.int32), cfg,
            active=active,
        )
        got.append(l)
        tok = jax.random.randint(jax.random.PRNGKey(9), (b, 4), 0, 64)
        l, caches = api.forward_chunk(
            params, tok, caches, jnp.full((b,), 4, jnp.int32), cfg,
            active=active, lengths=jnp.asarray([4, 2], jnp.int32),
            logits_at=jnp.asarray([3, 1], jnp.int32),
        )
        got.append(l)
        # decode fast path at ragged per-slot positions
        pos = jnp.asarray([8, 6], jnp.int32)
        for t in range(3):
            tok = jax.random.randint(jax.random.PRNGKey(20 + t), (b, 1), 0, 64)
            l, caches = api.decode_step(params, tok, caches, pos + t, cfg,
                                        active)
            got.append(l)
        outs[env] = [np.asarray(x) for x in got]
    for off, on in zip(outs["0"], outs["1"]):
        np.testing.assert_allclose(off, on, atol=3e-5)


def test_unsupported_block_size_falls_back(monkeypatch):
    """block_size 4 fails the support gate: forcing the kernel on must
    quietly keep the gather path (bitwise the fallback result)."""
    from repro.models import api

    cfg = _tiny_cfg()
    params, _ = api.init_model(KEY, cfg)
    b, max_len, bs = 2, 16, 4
    outs = {}
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", env)
        caches = _paged_caches(cfg, b, max_len, bs)
        tok = jax.random.randint(KEY, (b, 1), 0, 64)
        l, _ = api.decode_step(
            params, tok, caches, jnp.zeros((b,), jnp.int32), cfg,
            jnp.asarray([True, True]),
        )
        outs[env] = np.asarray(l)
    np.testing.assert_array_equal(outs["0"], outs["1"])


def test_query_length_picks_the_kernel(monkeypatch):
    """``ops.paged_attention`` runs the decode kernel for T == 1 and the
    grid kernel, with its autotuned pages-per-step, for T > 1."""
    calls = []
    for name in ("_paged_attention", "_paged_attention_decode"):
        real = getattr(ops, name)

        def spy(*a, _real=real, _name=name, **k):
            calls.append(_name)
            return _real(*a, **k)

        monkeypatch.setattr(ops, name, spy)
    b, hq, hkv, d, bs, mb = 2, 4, 2, 8, 8, 2
    kpool, vpool, table = _setup(b, hkv, d, bs, mb)
    start = jnp.asarray([0, 5], jnp.int32)
    for t, kernel in ((1, "_paged_attention_decode"), (3, "_paged_attention")):
        calls.clear()
        q = _q(b, t, hq, d)
        got = ops.paged_attention(q, kpool, vpool, table, start, start + t)
        want = ref.paged_attention_ref(q, kpool, vpool, table, start,
                                       start + t)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)
        assert calls == [kernel]


def test_paged_path_counter(monkeypatch):
    """Each traced paged-attention call site counts its path: a T == 1
    trace ``decode``, a T > 1 trace ``chunk``, and a shape the kernel
    refuses (block 4) the ``gather`` fallback."""
    from repro.models import attention

    monkeypatch.setenv("REPRO_PAGED_ATTN", "1")
    b, hq, hkv, d = 2, 4, 2, 8
    pos = jnp.zeros((b,), jnp.int32)
    ops.reset_paged_path_stats()
    try:
        for t, bs in ((1, 8), (3, 8), (1, 4)):
            kpool, vpool, table = _setup(b, hkv, d, bs, 2)
            q = _q(b, t, hq, d)
            posmat = pos[:, None] + jnp.arange(t)[None]
            jax.eval_shape(
                lambda q, kp, vp, tb: attention._paged_scores(
                    q, kp, vp, tb, pos, posmat, t, None),
                q, kpool, vpool, table)
        assert ops.paged_path_stats() == {"decode": 1, "chunk": 1,
                                          "gather": 1}
    finally:
        ops.reset_paged_path_stats()
