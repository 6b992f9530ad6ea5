"""Continuous-batching scheduler: per-request lifecycle over a shared slot
batch and paged KV pool.

``DecodeEngine`` (the lockstep tier) decodes a fixed batch in lockstep:
every request burns the full token budget, and a new request waits for the
whole batch to drain.  ``ContinuousBatchingEngine`` keeps the same compiled
decode program (fixed ``num_slots``-wide batch, ``lax.scan`` chunks,
on-device sampling) but gives every slot its own lifecycle:

* **admission** — with ``prefill_chunk`` set (token-budget chunked
  prefill, Sarathi-style), a queued request only *occupies* a free slot;
  its prompt then streams into the shared caches as fixed-size
  ``forward_chunk`` slices — at most one slice per engine step, written
  directly into pool pages (``kv_pool.write_span``) or dense rows — so a
  long prompt stalls the decode cadence for at most one slice at a time.
  The slice completing the prompt samples the first token with the
  one-shot key-split order, and ONE program is compiled per (budget,
  layout) — ragged final slices are padded and masked, never retraced.
  Configs where slicing would change streams fall back
  (:func:`_chunked_prefill_safe`) to the one-shot path: batch-1 prefill,
  KV prefix installed into the slot.  There, where parity allows
  (:func:`_bucketed_prefill_safe`), the prompt is right-padded to a
  power-of-two bucket so one compiled trace serves every length in the
  bucket; remaining configs retrace per distinct length.
* **decode** — one compiled chunk advances all slots together; per-slot
  positions, EOS/stop-token hits and ``max_new_tokens`` budgets are
  tracked as on-device masks, and finished slots produce **no cache
  writes** (that is what makes reclaiming their blocks safe).
* **eviction** — at the chunk boundary finished requests leave their slot,
  their block references return to the allocator, and the next queued
  request is admitted into the hole.
* **prefix caching** (``prefix_cache=True``, paged layout) — full prompt
  blocks carry a content identity (chain hash of ``(parent_hash,
  block_tokens)`` over the HOST token stream — mesh-shape-independent by
  construction) registered in the allocator once their pages are fully
  written.  Admission walks the prompt's chain through the hash index and
  reuses every leading hit by bumping its refcount; only the unshared
  suffix is prefilled (one padded ``forward_chunk`` slice on the one-shot
  path, or chunked-prefill slices starting at the cached boundary), so a
  cache-hit request's TTFT collapses to its suffix.  Release paths unref:
  a refcount-0 registered block parks on an LRU — still hittable — until
  ``alloc`` evicts it; a fully-cached prompt copies-on-write its final
  hit block before recomputing the last prompt position, so shared pages
  are never mutated.  On release the chain extends over generated tokens,
  so multi-turn follow-ups hit the whole previous conversation.  Streams
  stay bit-for-bit the cold path's (same key-split order, and
  suffix-resume is exactly the chunked-prefill parity property).

Determinism contract: each request carries its own seed, and admission
prefill (one-shot, bucketed or chunked) + per-slot key-splitting reproduce
``DecodeEngine``'s exact key-split order for a batch-1 call.  A request's
token stream is therefore identical to
``DecodeEngine.generate(prompt[None], scfg, seed=seed)`` up to stop-token
truncation — the parity tests assert this bit-for-bit, for both the dense
and paged cache layouts, with and without chunked prefill.

Host-transfer hygiene: one fetch of the packed ``(B, chunk+2)`` token
matrix per decode chunk (the trailing columns are the device's post-chunk
active mask, cross-checked against the host mirror, and the per-slot
quarantine step of the NaN/Inf logit-validity mask), plus one packed
``[token, valid]`` fetch per admission (the prefill-sampled first token).
``host_transfers`` counts them.

Robustness contract (the failure story every later scale PR inherits):

* **request lifecycle** — queued -> prefilling -> decoding ->
  finished(reason), with ``finish_reason`` one of :data:`FINISH_REASONS`;
  every submitted request finishes exactly once.
* **deadlines** — per-request wall-clock ``deadline`` and ``ttft_budget``
  are enforced at chunk boundaries: expired requests are evicted with
  reason ``"deadline"`` (partial tokens kept — a prefix of the fault-free
  stream) and their blocks reclaimed, including mid-chunked-prefill.
* **load shedding** — ``max_queue`` bounds the admission queue;
  ``overload_policy`` picks who is shed (``"reject"`` drops the new
  request, ``"shed_oldest"`` drops the head of the queue) with reason
  ``"shed"``; ``submit`` raises :class:`InadmissibleRequest` for requests
  that can *never* fit instead of deferring the failure to a later stall.
* **NaN/Inf quarantine** — a per-slot logit-validity mask rides the
  existing per-chunk transfer; a slot whose logits go non-finite is
  quarantined and finished with reason ``"error"`` while every other
  stream stays bit-for-bit the fault-free run.
* **watchdog** — a run that stops making progress while work is ready
  raises a diagnosable :class:`SchedulerStall` instead of spinning.
* **fault injection** — a :class:`repro.serve.faults.FaultInjector` can
  deterministically force allocator failures, preemptions, poisoned
  logits and delayed arrivals through no-op-by-default hooks; disabled,
  the compiled programs are byte-identical to the fault-free build.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import api
from repro.models.transformer import build_segments
from repro.serve import kv_pool
from repro.serve.engine import (
    SamplerConfig,
    _hit_stop,
    _make_bucketed_prefill_fn,
    _make_checked_prefill_fn,
    place_params,
    sample_token,
    serving_overrides,
)
from repro.serve.faults import FaultInjector
from repro.serve.metrics import MetricsRegistry, resolve_clock
from repro.serve.tracing import RequestTracer, annotate, maybe_profile

Array = jax.Array

_log = logging.getLogger(__name__)

#: The finish-reason taxonomy.  ``stop`` — stop token; ``length`` — token
#: budget exhausted; ``deadline`` — deadline / TTFT budget expired (queued
#: or live); ``shed`` — dropped by the bounded-queue overload policy;
#: ``rejected`` — dead on arrival at submit (deadline already unmeetable);
#: ``error`` — NaN/Inf logit quarantine.
FINISH_REASONS = frozenset(
    {"stop", "length", "deadline", "shed", "rejected", "error"}
)


class InadmissibleRequest(ValueError):
    """A request that can never be served: prompt + budget exceed the slot
    capacity, or its blocks exceed the whole pool.  Raised by ``submit``
    so impossibility surfaces at the API boundary, not as a later
    scheduler stall."""


class SchedulerStall(RuntimeError):
    """The engine stopped making progress while work was ready (or the
    pool was exhausted with nothing to preempt).  The message carries the
    queue depth, live-slot lifecycle and allocator state so the stall is
    diagnosable from the exception alone."""

# configs whose chunked-prefill decline has already been reported: the
# fallback is a per-config property, so it is logged once per config —
# not once per engine build, and certainly not once per admitted request
_CHUNK_DECLINE_LOGGED: set[tuple] = set()


def _chunk_decline_key(cfg: ModelConfig) -> tuple:
    """The config identity :func:`_chunked_prefill_safe` actually decides
    on — two configs that gate identically share one log line."""
    return (
        cfg.name,
        cfg.family,
        bool(cfg.moe),
        cfg.quant.num_experts,
        cfg.n_image_tokens,
        tuple(
            spec.mixer
            for seg in build_segments(cfg)
            for spec in seg.blocks
        ),
    )


def _log_chunked_prefill_decline(cfg: ModelConfig) -> None:
    key = _chunk_decline_key(cfg)
    if key in _CHUNK_DECLINE_LOGGED:
        return
    _CHUNK_DECLINE_LOGGED.add(key)
    _log.warning(
        "config %r: chunked admission prefill declined (recurrent mixer / "
        "MoE / routed branches / VLM prefix would change streams across "
        "slice boundaries); falling back to one-shot admission prefill",
        cfg.name,
    )


# ---------------------------------------------------------------------------
# Request lifecycle records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``seed`` makes the stream reproducible and
    independent of scheduling; ``arrival``, ``deadline`` (absolute) and
    ``ttft_budget`` (relative to arrival) are in the engine's clock units
    (chunk ticks under the default virtual clock, seconds with a real
    one).  ``None`` deadlines never expire."""

    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    seed: int = 0
    arrival: float = 0.0
    deadline: Optional[float] = None
    ttft_budget: Optional[float] = None


@dataclasses.dataclass
class RequestState:
    """Host mirror of an admitted request (the device holds the arrays).

    Under chunked prefill a request occupies its slot while its prompt
    still streams in: ``prefilled`` counts prompt tokens already resident
    in the cache, and ``n_generated == 0`` marks the slot as admitting
    (inactive in decode chunks) until the final slice samples the first
    token.

    With prefix caching, ``block_hashes`` holds the chain hashes of the
    stream's full blocks (prompt blocks at admission, extended over
    generated tokens at release) and ``registered`` counts the leading
    blocks already present in the allocator's hash index — admission hits
    plus blocks registered once their pages were fully written."""

    request: Request
    slot: int
    blocks: list[int]
    tokens: list[int]
    n_generated: int
    admitted_at: float
    prefilled: int = 0
    first_token_at: float = 0.0
    done: bool = False
    finish_reason: str = ""
    block_hashes: list[int] = dataclasses.field(default_factory=list)
    registered: int = 0

    @property
    def pos(self) -> int:
        """Next write position = prompt_len + generated so far."""
        return len(self.request.prompt) + self.n_generated


@dataclasses.dataclass(frozen=True)
class FinishedRequest:
    uid: int
    tokens: np.ndarray  # (n,) int32, n <= max_new_tokens
    finish_reason: str  # one of FINISH_REASONS
    prompt_len: int
    arrival: float
    admitted_at: float
    # when the first token was sampled (TTFT anchor); for zero-token
    # finishes (shed / rejected / deadline-in-queue / prefill quarantine)
    # it equals finished_at
    first_token_at: float
    finished_at: float


# ---------------------------------------------------------------------------
# Compiled pieces
# ---------------------------------------------------------------------------


def _walk_blocks(cfg: ModelConfig):
    """(segment index, block key, spec, stacked) for every cache dict in
    the tree that :func:`repro.models.api.init_cache` builds."""
    for si, seg in enumerate(build_segments(cfg)):
        for bi, spec in enumerate(seg.blocks):
            yield si, f"b{bi}", spec, seg.repeats > 1


def _map_blocks(cfg: ModelConfig, fn, *trees):
    """Apply ``fn(spec, stacked, *block_dicts)`` over parallel cache trees."""
    out = []
    for si, key, spec, stacked in _walk_blocks(cfg):
        while len(out) <= si:
            out.append({})
        out[si][key] = fn(spec, stacked, *(t[si][key] for t in trees))
    return out


def _row_set(big: Array, small: Array, slot: Array, stacked: bool) -> Array:
    """big[..., slot, ...] = small[..., 0, ...] along the batch axis (index
    1 on layer-stacked leaves, 0 otherwise)."""
    ax = 1 if stacked else 0
    idx = (slice(None),) * ax + (slot,)
    return big.at[idx].set(jnp.take(small, 0, axis=ax).astype(big.dtype))


def _make_install_fn(cfg: ModelConfig, nb: int):
    """Install a batch-1 prefill cache into slot ``slot`` of the big cache
    tree.  ``nb`` (static) is the number of prompt-covering pages for
    paged layers — their dense prefill rows land in the pool through the
    same ``kv_pool.write_span`` span scatter chunked prefill writes with
    (one write path, no separate page-install primitive); dense leaves
    copy the whole row."""

    def install(big, small, slot, table_row):
        def blockfn(spec, stacked, bigc, smallc):
            if "table" in bigc:
                start = jnp.zeros((1,), jnp.int32)

                def scatter(pool, dense):
                    # dense: (1, L, H, D) — the slot's prefilled cache;
                    # span-write exactly its nb prompt-covering pages (the
                    # static slice keeps the scatter O(nb * bs), not
                    # O(max_len))
                    bs = pool.shape[2]
                    return kv_pool.write_span(
                        pool, table_row[None], start, dense[:, : nb * bs]
                    )

                if stacked:
                    scatter = jax.vmap(scatter)
                ax = 1 if stacked else 0
                idx = (slice(None),) * ax + (slot,)
                return {
                    "kpool": scatter(bigc["kpool"], smallc["k"]),
                    "vpool": scatter(bigc["vpool"], smallc["v"]),
                    "table": bigc["table"].at[idx].set(table_row),
                }
            return jax.tree.map(
                lambda b, s: _row_set(b, s, slot, stacked), bigc, smallc
            )

        return _map_blocks(cfg, blockfn, big, small)

    return install


def _make_copy_block_fn(cfg: ModelConfig):
    """Copy one pool page (``src`` -> ``dst``) in every paged layer's K and
    V pools — the engine-level copy-on-write primitive.  A slot about to
    write inside a *shared* block (the fully-cached-prompt case recomputes
    the last prompt position, which lives in the final hit block) first
    duplicates that page into a private block and repoints its table row,
    so a registered page is never mutated while other slots may read it."""

    def copy(big, src, dst):
        def blockfn(spec, stacked, bigc):
            if "table" not in bigc:
                return bigc

            def cp(pool):
                return kv_pool.copy_block(pool, src, dst)

            if stacked:
                cp = jax.vmap(cp)
            return dict(bigc, kpool=cp(bigc["kpool"]), vpool=cp(bigc["vpool"]))

        return _map_blocks(cfg, blockfn, big)

    return copy


def _make_set_tables_fn(cfg: ModelConfig):
    """Rewrite one slot's block-table row in every paged layer (block
    extension at a chunk boundary)."""

    def set_tables(big, slot, table_row):
        def blockfn(spec, stacked, bigc):
            if "table" not in bigc:
                return bigc
            ax = 1 if stacked else 0
            idx = (slice(None),) * ax + (slot,)
            return dict(bigc, table=bigc["table"].at[idx].set(table_row))

        return _map_blocks(cfg, blockfn, big)

    return set_tables


def _make_cb_chunk_fn(cfg: ModelConfig, scfg: SamplerConfig, length: int,
                      poison: bool = False):
    """``length`` decode steps over the slot batch with per-slot positions,
    keys, budgets and stop masks.  Returns (packed (B, length+2), caches,
    state) — the packed matrix's last two columns are the post-chunk
    active mask and the per-slot quarantine step, riding the chunk's
    single device->host transfer.

    NaN/Inf quarantine: each step's (B,) logit-validity mask
    (``isfinite`` over the vocab axis — a cheap reduction of logits the
    step already materialized, no extra sync) gates sampling exactly like
    the active mask, so a slot whose logits go non-finite emits no
    garbage token, writes nothing further, and carries the offending step
    index home in the quarantine column (``length`` = untouched).  For
    finite logits every ``where`` picks the same operand as before the
    mask existed — the fault-free program is bitwise unchanged, which is
    what keeps unaffected streams bit-for-bit under quarantine.

    With ``poison=True`` the chunk takes an extra ``(B,) int32`` operand
    naming the scan step at which each slot's logits are overwritten with
    NaN (-1 = never) — the fault-injection variant, compiled lazily and
    ONLY when a FaultInjector schedules a poison, so the disabled path
    runs the exact program it always did.

    Per-slot sampling vmaps the batch-1 sampler over (key, logits-row)
    pairs, which is bit-for-bit what ``DecodeEngine`` computes for a
    batch-1 call with that key — the determinism contract of the module
    docstring."""

    def chunk(params, caches, state, poison_step=None):
        def step(carry, i):
            caches, st = carry
            split = jax.vmap(jax.random.split)(st["key"])  # (B, 2, 2)
            new_key, sub = split[:, 0], split[:, 1]
            with annotate("serve/decode_step"):
                logits, caches = api.decode_step(
                    params, st["tok"][:, None], caches, st["pos"], cfg,
                    active=st["active"],
                )
            logits = logits[:, -1]  # (B, V)
            if poison:
                logits = jnp.where(
                    (poison_step == i)[:, None],
                    jnp.full_like(logits, jnp.nan),
                    logits,
                )
            finite = jnp.isfinite(logits).all(axis=-1)  # (B,)
            ok = st["active"] & finite
            with annotate("serve/sample"):
                nxt = jax.vmap(
                    lambda s, l: sample_token(s, l[None], scfg)[0]
                )(sub, logits)
            nxt = jnp.where(ok, nxt, st["tok"])
            act = ok.astype(jnp.int32)
            ngen = st["ngen"] + act
            alive = (
                ok
                & ~_hit_stop(nxt, scfg)
                & (ngen < st["budget"])
            )
            quar = jnp.where(
                st["active"] & ~finite & (st["quar"] == length),
                i, st["quar"],
            )
            st = {
                "tok": nxt,
                "pos": st["pos"] + act,
                "key": new_key,
                "active": alive,
                "ngen": ngen,
                "budget": st["budget"],
                "quar": quar,
            }
            return (caches, st), nxt

        st0 = dict(
            state, quar=jnp.full(state["tok"].shape, length, jnp.int32)
        )
        (caches, st), toks = jax.lax.scan(
            step, (caches, st0), jnp.arange(length, dtype=jnp.int32)
        )
        toks = jnp.moveaxis(toks, 0, 1)  # (B, length)
        quar = st.pop("quar")
        packed = jnp.concatenate(
            [toks, st["active"][:, None].astype(toks.dtype),
             quar[:, None]], axis=1,
        )
        return packed, caches, st

    return chunk


def _make_prefill_chunk_fn(cfg: ModelConfig, scfg: SamplerConfig, t: int):
    """One admission-prefill slice: ``t`` prompt tokens for (at most) one
    admitting slot, written straight into the BIG cache tree — dense rows
    or pool pages (``kv_pool.write_span``) — with every other slot masked
    out.  Because ragged final slices are right-padded to ``t`` and gated
    by ``lengths``, ONE compiled program serves every prompt length: the
    trace count is per (budget, layout), not per prompt.

    Sampling reproduces ``_prefill_sample``'s key-split order on the
    admitting slot's row (split after prefill, batch-1 sampler), so the
    first token — and with it the whole stream — is bit-for-bit the
    lockstep engine's.  The sampled token and split key are computed every
    slice but only the slice that completes the prompt is read back by the
    host (one packed ``[token, valid]`` fetch per admission — the
    logit-validity bit rides the same transfer, so prefill quarantine
    costs no extra sync; same budget as one-shot admission).
    """

    def pchunk(params, caches, tokens, pos, active, lengths, slot, key):
        assert tokens.shape[1] == t, "slices must be padded to the budget"
        with annotate("serve/prefill_forward"):
            logits, caches = api.forward_chunk(
                params, tokens, caches, pos, cfg, active=active,
                lengths=lengths, logits_at=jnp.maximum(lengths - 1, 0),
            )
        row = jnp.take(logits, slot, axis=0)
        key, sub = jax.random.split(key)
        tok0 = sample_token(sub, row[None], scfg)[0]
        ok = jnp.isfinite(row).all().astype(jnp.int32)
        return jnp.stack([tok0, ok]), caches, key

    return pchunk


def _chunked_prefill_safe(cfg: ModelConfig) -> bool:
    """Whether admission prefill may be split into fixed-budget slices
    without changing any request's stream.

    Safe exactly when slicing a prompt across ``forward_chunk`` calls is
    invisible: attention mixers (incl. ring-cache sliding-window layers —
    their in-chunk path is already sequential per token, so slice
    boundaries change nothing).  Unsafe, falling back to one-shot
    admission prefill:

    * ssm / rec mixers: the chunk recurrences (SSD chunking, associative
      scan) re-associate float accumulation across slice boundaries;
    * MoE / routed 8-bit branches: Switch-style capacity couples the
      tokens of a slice, so slice size changes real tokens' routing;
    * VLM image prefixes (position offsets are caller-managed).
    """
    if cfg.moe or cfg.quant.num_experts > 1 or cfg.n_image_tokens > 0:
        return False
    for seg in build_segments(cfg):
        for spec in seg.blocks:
            if spec.mixer not in ("attn", "mla"):
                return False
    return True


def _prefix_cache_safe(cfg: ModelConfig) -> bool:
    """Whether shared prompt blocks may be reused across requests without
    changing any request's stream.

    Safe exactly when the *paged pool holds the whole recurrent state of a
    prefix*: every mixer is pure global attention (``window == 0``), so
    reusing the hit blocks and running only the unshared suffix is
    bitwise the full prefill (the chunked-prefill parity property, with
    the prefix slices computed by an earlier request).  Unsafe, declining
    to one-shot cold admission:

    * sliding-window / ssm / rec / MLA mixers: their dense ring or latent
      caches are per-slot — a reused pool block would leave that state
      unpopulated for the hitting slot;
    * MoE / routed branches / VLM prefixes: same coupling that makes
      slicing unsafe (:func:`_chunked_prefill_safe`).
    """
    if cfg.moe or cfg.quant.num_experts > 1 or cfg.n_image_tokens > 0:
        return False
    for seg in build_segments(cfg):
        for spec in seg.blocks:
            if spec.mixer != "attn" or spec.window != 0:
                return False
    return True


_PREFIX_DECLINE_LOGGED: set[tuple] = set()


def _log_prefix_cache_decline(cfg: ModelConfig) -> None:
    key = _chunk_decline_key(cfg) + tuple(
        spec.window for seg in build_segments(cfg) for spec in seg.blocks
    )
    if key in _PREFIX_DECLINE_LOGGED:
        return
    _PREFIX_DECLINE_LOGGED.add(key)
    _log.warning(
        "config %r: prefix caching declined (a mixer keeps per-slot state "
        "outside the paged pool, or routing couples tokens); admissions "
        "run cold",
        cfg.name,
    )


def _bucketed_prefill_safe(cfg: ModelConfig, max_len: int) -> bool:
    """Whether admission prefill may right-pad prompts to a shared bucket
    length without changing any request's stream.

    Safe exactly when pad tokens cannot leak into real positions: causal
    attention confines them to cache slots the decode mask gates until the
    real stream overwrites them.  Unsafe cases fall back to exact-length
    prefill (one retrace per distinct length, the pre-bucketing behavior):

    * ring caches (``window < max_len``): prefill keeps the last W
      positions of the *padded* sequence, evicting real tokens;
    * ssm / rec mixers: the recurrent state integrates the pad suffix;
    * MoE / routed 8-bit branches: Switch-style capacity couples tokens,
      so the pad tokens change real tokens' routing;
    * VLM image prefixes (position offsets are caller-managed).
    """
    if cfg.moe or cfg.quant.num_experts > 1 or cfg.n_image_tokens > 0:
        return False
    for seg in build_segments(cfg):
        for spec in seg.blocks:
            if spec.mixer not in ("attn", "mla"):
                return False
            if 0 < spec.window < max_len:
                return False
    return True


def _admit_state(state, slot, tok0, key, pos0, budget):
    """Write one slot's device-side lifecycle state (ngen starts at 1: the
    prefill-sampled first token is emitted at admission)."""
    return {
        "tok": state["tok"].at[slot].set(tok0),
        "pos": state["pos"].at[slot].set(pos0),
        "key": state["key"].at[slot].set(key),
        "active": state["active"].at[slot].set(True),
        "ngen": state["ngen"].at[slot].set(1),
        "budget": state["budget"].at[slot].set(budget),
    }


def _deactivate(state, slot):
    return dict(state, active=state["active"].at[slot].set(False))


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def _tile_cache_stats() -> dict:
    """Snapshot collector: kernel autotune-cache hit/miss/sweep stats
    (process-wide — they live with the cache, not the engine).  Deferred
    import keeps the scheduler importable without the kernel tier."""
    from repro.kernels import tile_cache

    return {f"tile_cache_{k}": v for k, v in tile_cache.stats().items()}


class ContinuousBatchingEngine:
    """Serving tier 3: request queue + slot admission/eviction over one
    compiled fixed-width decode program (see module docstring).

    Parameters
    ----------
    num_slots : compiled batch width — concurrent in-flight requests.
    max_len : per-slot sequence capacity (prompt + generated).
    scfg : engine-level sampling signature (temperature / top_k /
        stop_tokens).  Per-request knobs are ``max_new_tokens`` and
        ``seed``; the sampler signature is baked into the compiled program.
    layout : "paged" (global-attention KV in a shared block pool) or
        "dense" (per-slot buffers).  Interchangeable — same token streams.
    num_blocks : pool size per paged layer; defaults to full occupancy
        (``num_slots * max_len / block_size``).  Smaller pools admit fewer
        long requests at once; if blocks run out mid-flight the youngest
        request is preempted back to the queue (restart-from-scratch is
        deterministic, so its stream is unchanged).
    prefill_chunk : token budget per engine step for admission prefill
        (Sarathi-style chunked prefill).  ``None`` (default) admits with
        one-shot prefill; an int splits each admitting prompt into
        fixed-size ``forward_chunk`` slices written straight into the
        shared caches (``kv_pool.write_span`` under the paged layout), at
        most one slice per step, so a long prompt never stalls the decode
        cadence for more than one slice.  ONE program is compiled per
        (budget, layout) — slices are padded+masked, never retraced per
        prompt length.  Configs where slicing would change streams
        (recurrent mixers, MoE/routed branches, VLM prefixes — see
        :func:`_chunked_prefill_safe`) fall back to one-shot admission.
    prefix_cache : enable automatic prefix caching (paged layout only —
        requesting it with ``layout="dense"`` raises).  Each full prompt
        block gets a content identity — the chain hash of
        ``(parent_hash, block_tokens)`` over the HOST token stream, so
        hits are mesh-shape-independent by construction — and admission
        walks the prompt's block chain through the allocator's hash
        index: every leading hit is reused by bumping its refcount, and
        only the unshared suffix is prefilled (one padded
        ``forward_chunk`` slice on the one-shot path; chunked prefill
        simply starts its slices at the cached boundary), collapsing
        TTFT for cache-hit requests.  Release paths unref instead of
        freeing — a refcount-0 block with registered content parks on the
        allocator's LRU, still hittable, until ``alloc`` reclaims it.  A
        fully-cached prompt copies-on-write its final hit block before
        recomputing the last prompt position, so a shared page is never
        mutated.  Streams are bit-for-bit the cold path's (the
        chunked-prefill parity property — which is also why configs
        failing :func:`_prefix_cache_safe` decline with a log and run
        cold).  Hit/miss/CoW/eviction land on the
        ``prefix_cache_*_total`` counters and the request trace.
    clock : optional clock — a bare callable returning seconds, or an
        object with ``now()`` and optionally ``sleep(dt)`` (see
        :func:`repro.serve.metrics.resolve_clock`;
        :class:`~repro.serve.metrics.ManualClock` drives tests without
        real sleeping).  By default a virtual clock advances one tick per
        decode chunk and ``Request.arrival`` is in ticks.  Deadline math,
        trace timestamps and the latency histograms all read this one
        clock.
    metrics : optional :class:`repro.serve.metrics.MetricsRegistry` to
        record into (share one across engines / export to Prometheus);
        ``None`` creates a private registry — instrumentation is always
        host-side-only, so this can never change a compiled program.
    tracer : optional :class:`repro.serve.tracing.RequestTracer`; when set
        every request's lifecycle (submitted -> admitted -> prefill ->
        first_token -> decode -> finished(reason)), block alloc/free,
        preemptions and fired faults are emitted as structured events on
        the engine clock.  May also be attached later (``eng.tracer =
        ...``) — benches attach after warm-up.
    max_queue : bound on the admission queue (``None`` = unbounded).  A
        submit into a full queue invokes ``overload_policy`` and the loser
        finishes with reason ``"shed"`` — backpressure is explicit, not an
        unbounded list.
    overload_policy : ``"reject"`` sheds the newly submitted request;
        ``"shed_oldest"`` sheds the head of the queue and admits the new
        one (freshest-work-wins).
    watchdog_steps : consecutive no-progress steps (while work is ready)
        tolerated before ``step`` raises :class:`SchedulerStall`.
    faults : optional :class:`repro.serve.faults.FaultInjector`.  ``None``
        (default) compiles and runs exactly the fault-free programs.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        num_slots: int,
        max_len: int,
        scfg: Optional[SamplerConfig] = None,
        *,
        layout: str = "paged",
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        chunk: int = 8,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = False,
        clock: Optional[Callable[[], float]] = None,
        max_queue: Optional[int] = None,
        overload_policy: str = "reject",
        watchdog_steps: int = 256,
        faults: Optional[FaultInjector] = None,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[RequestTracer] = None,
        mesh=None,
        param_axes=None,
        mesh_overrides: Optional[dict] = None,
    ):
        if cfg.family == "encdec":
            raise NotImplementedError("continuous batching is decoder-only")
        if layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache layout {layout!r}")
        if layout == "paged" and max_len % block_size:
            raise ValueError("max_len must be a multiple of block_size")
        if overload_policy not in ("reject", "shed_oldest"):
            raise ValueError(f"unknown overload policy {overload_policy!r}")
        if prefix_cache and layout != "paged":
            raise ValueError(
                "prefix_cache requires the paged layout (content-hash "
                "identity lives on pool blocks)"
            )
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        # tensor-parallel serving: params go down N-major over the model
        # axis and every compiled program below is traced inside the
        # serving sharding rules (see serve/__init__.py §sharded serving).
        # The host-side scheduler/queue/fault/metrics layers are untouched
        # — they only ever see fetched numpy and per-slot python state.
        self.mesh = mesh
        self._overrides = (
            serving_overrides(cfg, mesh, mesh_overrides)
            if mesh is not None else None
        )
        if mesh is not None:
            params = place_params(params, cfg, mesh, self._overrides,
                                  param_axes)
        self.params, self.cfg = params, cfg
        self.num_slots, self.max_len = num_slots, max_len
        self.scfg = scfg or SamplerConfig()
        self.layout, self.block_size, self.chunk = layout, block_size, chunk
        self.max_blocks = kv_pool.blocks_for(max_len, block_size)
        self.num_blocks = num_blocks or num_slots * self.max_blocks
        self.faults = faults
        # observability: every engine owns a registry (attach your own to
        # share one across engines) — ALL instrumentation is host-side
        # Python at chunk boundaries over data already transferred, so a
        # registry/tracer can never change a compiled program (pinned by
        # tests/test_metrics.py's byte-identical-lowering assert).  The
        # legacy counter attributes (shed_requests, queue_peak, ...) are
        # compatibility aliases over registry metrics — see the property
        # block below __init__.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer
        m = self.metrics
        self._m_submitted = m.counter("requests_submitted_total")
        self._m_finished = {
            r: m.counter("requests_finished_total", reason=r)
            for r in sorted(FINISH_REASONS)
        }
        self._m_shed = m.counter("shed_requests_total")
        self._m_rejected = m.counter("rejected_requests_total")
        self._m_deadline = m.counter("deadline_misses_total")
        self._m_quarantined = m.counter("quarantined_total")
        self._m_preempt = m.counter("preemptions_total")
        self._m_restarts = m.counter("restarts_total")
        self._m_admissions = m.counter("admissions_total")
        self._m_tokens = m.counter("tokens_generated_total")
        self._m_prefill_tokens = m.counter("prefill_tokens_total")
        self._m_transfers = m.counter("host_transfers_total")
        self._m_steps = m.counter("engine_steps_total")
        self._m_queue_depth = m.gauge("admission_queue_depth")
        self._m_queue_peak = m.gauge("admission_queue_peak")
        self._m_occupancy = m.gauge("batch_occupancy")
        # mesh shape as gauges (1/1 when serving single-device) so a
        # metrics snapshot records the parallelism it was measured under
        mesh_shape = dict(mesh.shape) if mesh is not None else {}
        m.gauge("mesh_data_parallelism").set(
            float(mesh_shape.get("data", 1)))
        m.gauge("mesh_model_parallelism").set(
            float(mesh_shape.get("model", 1)))
        self._m_ttft = m.histogram("ttft_seconds")
        self._m_itl = m.histogram("itl_seconds")
        self._m_latency = m.histogram("request_latency_seconds")
        # prefix-cache counters are registered unconditionally (zero when
        # caching is off/declined) so every snapshot — and the CI metrics
        # artifact — carries the hit rate schema-stably
        self._m_pc_hits = m.counter("prefix_cache_hits_total")
        self._m_pc_misses = m.counter("prefix_cache_misses_total")
        self._m_pc_hit_tokens = m.counter("prefix_cache_hit_tokens_total")
        self._m_pc_cow = m.counter("prefix_cache_cow_total")
        m.register_collector(_tile_cache_stats)
        self.allocator = (
            kv_pool.BlockAllocator(
                self.num_blocks,
                fail_hook=faults.on_alloc if faults is not None else None,
                metrics=m,
            )
            if layout == "paged" else None
        )
        self._clock, self._sleep = resolve_clock(clock)
        self._now = 0.0  # virtual clock (chunk ticks) when clock is None
        if faults is not None:
            # fired faults land on the request timeline (checked at fire
            # time, so a tracer attached after construction still sees them)
            faults.on_fire = self._on_fault
        self.max_queue, self.overload_policy = max_queue, overload_policy
        self.watchdog_steps = watchdog_steps
        self._admitted_uids: set[int] = set()  # restart detection
        self._stall_steps = 0
        self._step_idx = 0

        self._queue: collections.deque[Request] = collections.deque()
        # zero-token finishes produced outside step() (shed/rejected at
        # submit); drained into the next step's return value so every
        # request still finishes exactly once through the same channel
        self._pending_finished: list[FinishedRequest] = []
        self._slots: list[Optional[RequestState]] = [None] * num_slots
        self._uid_counter = 0  # monotonic: uids never recycle
        self._stop_set = set(int(t) for t in self.scfg.stop_tokens)

        self._caches = self._init_big_caches()
        b = num_slots
        self._state = {
            "tok": jnp.zeros((b,), jnp.int32),
            "pos": jnp.zeros((b,), jnp.int32),
            "key": jnp.zeros((b, 2), jnp.uint32),
            "active": jnp.zeros((b,), bool),
            "ngen": jnp.zeros((b,), jnp.int32),
            "budget": jnp.zeros((b,), jnp.int32),
        }
        if mesh is not None:
            # paged pools shard over KV heads on `model`; tables, dense
            # ring caches, and per-slot slot state replicate with the
            # host-global scheduler
            from jax.sharding import NamedSharding, PartitionSpec

            with self._mesh_ctx():
                self._caches = jax.device_put(
                    self._caches, kv_pool.cache_sharding(self._caches, mesh)
                )
            self._state = jax.device_put(
                self._state, NamedSharding(mesh, PartitionSpec())
            )

        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        # chunked admission prefill: fixed-budget forward_chunk slices into
        # the big caches, one compiled program per (budget, layout).
        # Stream-unsafe configs fall back to one-shot admission below.
        self.prefill_chunk = (
            prefill_chunk if (prefill_chunk is not None
                              and _chunked_prefill_safe(cfg)) else None
        )
        if prefill_chunk is not None and self.prefill_chunk is None:
            _log_chunked_prefill_decline(cfg)
        # automatic prefix caching: paged-only (checked above), and only
        # where block reuse is stream-invisible (_prefix_cache_safe) —
        # requested-but-unsafe configs decline with a log and run cold
        self.prefix_cache = bool(prefix_cache) and _prefix_cache_safe(cfg)
        if prefix_cache and not self.prefix_cache:
            _log_prefix_cache_decline(cfg)
        # cache-hit admission on the one-shot path: one padded
        # forward_chunk slice over the unshared suffix, compiled per
        # power-of-two suffix bucket (same program family — and the same
        # key-split order — as chunked prefill, so streams are bitwise
        # the cold path's)
        self._suffix_fns: dict[int, Callable] = {}
        self._copy_block_fn = (
            jax.jit(_make_copy_block_fn(cfg), donate_argnums=(0,))
            if self.prefix_cache else None
        )
        self._prefill_chunk = (
            jax.jit(
                _make_prefill_chunk_fn(cfg, self.scfg, self.prefill_chunk),
                donate_argnums=(1,),
            )
            if self.prefill_chunk is not None else None
        )
        # one-shot admission: exact-length prefill retraces per prompt
        # length; where parity allows it (_bucketed_prefill_safe),
        # admission right-pads prompts to power-of-two buckets so one
        # trace covers a whole bucket.  Both return packed [tok, valid]
        # so prefill quarantine rides the admission fetch.
        self._prefill = jax.jit(
            _make_checked_prefill_fn(cfg, max_len, self.scfg)
        )
        self._prefill_bucketed = (
            jax.jit(_make_bucketed_prefill_fn(cfg, max_len, self.scfg))
            if _bucketed_prefill_safe(cfg, max_len) else None
        )
        # the cache tree and slot state are donated: the chunk rewrites
        # them in place instead of copying the full KV pool every chunk
        # (the caller rebinds both from the return value)
        self._chunk_fn = jax.jit(
            _make_cb_chunk_fn(cfg, self.scfg, chunk), donate_argnums=(1, 2)
        )
        # fault-injection variant (extra poison-step operand): compiled
        # lazily and only when a FaultInjector schedules a logit poison,
        # so the fault-free build never traces it
        self._chunk_fn_poison: Optional[Callable] = None
        self._install_fns: dict[int, Callable] = {}
        self._set_tables = jax.jit(_make_set_tables_fn(cfg), donate_argnums=(0,))
        self._admit_jit = jax.jit(_admit_state, donate_argnums=(0,))
        self._deactivate_jit = jax.jit(_deactivate, donate_argnums=(0,))

    def _mesh_ctx(self):
        """Serving sharding rules, active around every compiled-fn call
        (jit traces at call time in the calling thread, so the rule table
        must be installed here, not at construction)."""
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        return shd.sharding_rules(self.mesh, self._overrides)

    # -- observability ------------------------------------------------------
    #
    # Compatibility aliases: the pre-registry counter attributes survive as
    # properties over registry metrics, with setters because benches reset
    # them (``eng.host_transfers = 0``) and tests read them directly.

    def _alias(metric):  # noqa: N805 — descriptor factory, not a method
        def get(self):
            return int(getattr(self, metric).value)

        def set_(self, v):
            getattr(self, metric).value = v

        return property(get, set_)

    shed_requests = _alias("_m_shed")
    rejected_requests = _alias("_m_rejected")
    deadline_misses = _alias("_m_deadline")
    quarantined = _alias("_m_quarantined")
    preemptions = _alias("_m_preempt")
    admissions = _alias("_m_admissions")
    tokens_generated = _alias("_m_tokens")
    prefill_tokens = _alias("_m_prefill_tokens")
    host_transfers = _alias("_m_transfers")
    queue_peak = _alias("_m_queue_peak")
    del _alias

    @property
    def finished_by_reason(self) -> dict[str, int]:
        """Cumulative finished-request totals per ``finish_reason`` — the
        chaos suite's conservation invariant is
        ``sum(finished_by_reason.values()) == submitted``."""
        return {r: int(c.value) for r, c in self._m_finished.items()}

    def snapshot(self) -> dict:
        """The engine's metrics snapshot (see
        :meth:`repro.serve.metrics.MetricsRegistry.snapshot`)."""
        return self.metrics.snapshot()

    def _on_fault(self, kind: str, info: dict) -> None:
        if self.tracer is not None:
            self.tracer.emit(f"fault_{kind}", t=self.now(), **info)

    def _emit_finished(self, fr: FinishedRequest) -> FinishedRequest:
        """The single finish chokepoint: every FinishedRequest — zero-token
        or streamed, any reason — passes through here exactly once, so the
        per-reason totals conserve requests and the latency histograms see
        every finish.  ITL uses the same formula the bench used to compute
        host-side (span / (n - 1)) so engine-sourced rows are comparable."""
        self._m_finished[fr.finish_reason].inc()
        n = len(fr.tokens)
        if n > 0:
            self._m_ttft.observe(max(0.0, fr.first_token_at - fr.arrival))
            self._m_itl.observe(
                max(0.0, fr.finished_at - fr.first_token_at) / max(1, n - 1)
            )
        self._m_latency.observe(max(0.0, fr.finished_at - fr.arrival))
        if self.tracer is not None:
            self.tracer.emit(
                "finished", t=fr.finished_at, uid=fr.uid,
                reason=fr.finish_reason, n_tokens=n,
            )
        return fr

    def _trace(self, event: str, uid: Optional[int] = None, **fields) -> None:
        if self.tracer is not None:
            self.tracer.emit(event, t=self.now(), uid=uid, **fields)

    def _release_blocks(self, blocks: list[int], uid: int) -> None:
        """Drop a request's references on its blocks (the one release
        path, so every reclamation lands on the trace timeline).  With
        prefix caching this is an *unref*: a registered block whose last
        reference drops parks on the allocator's LRU — still hittable —
        instead of being forgotten; shared blocks simply lose one owner."""
        if blocks:
            self.allocator.unref(blocks)
            self._trace("block_free", uid=uid, n_blocks=len(blocks))

    # -- construction -------------------------------------------------------

    def _init_big_caches(self):
        """Big cache tree: shapes from a batch-``num_slots`` init, leaf
        dtypes taken from what prefill actually produces (so installing a
        prefilled row never casts — bit parity with ``DecodeEngine``,
        whose caches come straight out of prefill)."""
        cfg, b = self.cfg, self.num_slots
        dummy = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
        small = jax.eval_shape(
            lambda p, t: api.prefill(p, t, cfg, self.max_len)[1],
            self.params, dummy,
        )

        def blockfn(spec, stacked, smallc):
            ax = 1 if stacked else 0
            if (
                self.layout == "paged"
                and spec.mixer == "attn"
                and spec.window == 0
            ):
                cache, _ = kv_pool.init_paged_attention_cache(
                    b, self.max_len, cfg.n_kv_heads, cfg.head_dim,
                    self.num_blocks, self.block_size, smallc["k"].dtype,
                )
                if stacked:
                    r = smallc["k"].shape[0]
                    cache = jax.tree.map(
                        lambda t: jnp.broadcast_to(t[None], (r,) + t.shape),
                        cache,
                    )
                return cache
            return jax.tree.map(
                lambda l: jnp.zeros(
                    l.shape[:ax] + (b,) + l.shape[ax + 1:], l.dtype
                ),
                smallc,
            )

        return _map_blocks(cfg, blockfn, small)

    # -- host boundary ------------------------------------------------------

    def _fetch(self, x) -> np.ndarray:
        self.host_transfers += 1
        return np.asarray(x)

    def now(self) -> float:
        return self._clock() if self._clock is not None else self._now

    # -- public API ---------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: Optional[int] = None,
        seed: int = 0,
        uid: Optional[int] = None,
        arrival: float = 0.0,
        deadline: Optional[float] = None,
        ttft_budget: Optional[float] = None,
    ) -> int:
        """Queue a request; returns its uid.

        Requests that can *never* be served — prompt + budget beyond a
        slot's capacity, or (paged) beyond the whole pool — raise
        :class:`InadmissibleRequest` here instead of deferring the
        impossibility to a later scheduler stall.  A ``deadline`` already
        unmeetable at submit (``deadline <= arrival``, or a non-positive
        ``ttft_budget``) finishes immediately with reason ``"rejected"``;
        a full bounded queue invokes the overload policy and the shed
        request finishes with reason ``"shed"`` (both surface on the next
        ``step``/``run`` — every request finishes exactly once)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        budget = (
            self.scfg.max_new_tokens if max_new_tokens is None
            else max_new_tokens
        )
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        total = len(prompt) + budget
        if total > self.max_len:
            raise InadmissibleRequest(
                f"prompt ({len(prompt)}) + budget ({budget}) exceeds the "
                f"slot capacity max_len={self.max_len}"
            )
        if self.allocator is not None:
            need = kv_pool.blocks_for(total, self.block_size)
            if need > self.num_blocks:
                raise InadmissibleRequest(
                    f"request needs {need} blocks but the pool has only "
                    f"{self.num_blocks}"
                )
        if uid is None:
            uid = self._uid_counter
        self._uid_counter = max(self._uid_counter, uid + 1)
        if self.faults is not None:
            arrival += self.faults.arrival_delay(uid)
        req = Request(
            uid, prompt, budget, seed=seed, arrival=arrival,
            deadline=deadline, ttft_budget=ttft_budget,
        )
        # counted only once validation passed: raised requests never enter
        # the lifecycle, so submitted == sum(finished_by_reason) conserves
        self._m_submitted.inc()
        self._trace(
            "submitted", uid=uid, arrival=req.arrival,
            prompt_len=len(prompt),
        )
        if (deadline is not None and deadline <= arrival) or (
            ttft_budget is not None and ttft_budget <= 0
        ):
            self.rejected_requests += 1
            self._pending_finished.append(
                self._finish_unstarted(req, "rejected")
            )
            return uid
        if (
            self.max_queue is not None
            and len(self._queue) >= self.max_queue
        ):
            if self.overload_policy == "reject":
                self.shed_requests += 1
                self._pending_finished.append(
                    self._finish_unstarted(req, "shed")
                )
                return uid
            victim = self._queue.popleft()  # shed_oldest: O(1) on the deque
            self.shed_requests += 1
            self._pending_finished.append(
                self._finish_unstarted(victim, "shed")
            )
        self._queue.append(req)
        self.queue_peak = max(self.queue_peak, len(self._queue))
        self._m_queue_depth.set(len(self._queue))
        return uid

    def _finish_unstarted(
        self, req: Request, reason: str
    ) -> FinishedRequest:
        """A zero-token finish for a request that never reached a slot
        (shed / rejected / deadline while queued / prefill quarantine)."""
        assert reason in FINISH_REASONS, reason
        now = self.now()
        return self._emit_finished(FinishedRequest(
            req.uid, np.zeros((0,), np.int32), reason, len(req.prompt),
            req.arrival, now, now, now,
        ))

    def run(self) -> list[FinishedRequest]:
        """Process the queue to completion; FinishedRequests in completion
        order.  With ``REPRO_PROFILE_DIR`` set the whole run is bracketed
        by ``jax.profiler.start_trace/stop_trace`` (see
        :func:`repro.serve.tracing.maybe_profile`)."""
        finished: list[FinishedRequest] = []
        with maybe_profile("serve_run"):
            while self._queue or self._live() or self._pending_finished:
                finished.extend(self.step())
        return finished

    def step(self) -> list[FinishedRequest]:
        """One scheduling tick, spending one token budget: surface pending
        zero-token finishes, enforce deadlines, apply injected
        preemptions, admit arrived requests, advance at most one admitting
        prompt by one prefill slice (chunked prefill), ensure pool blocks
        for the coming chunk, run one compiled decode chunk for the
        decoding slots, evict finished requests.  Returns the requests
        that finished this tick.

        Watchdog: a step that finishes nothing, generates no token and
        advances no prefill while work is ready (live slots, or an
        arrived queued request) counts toward ``watchdog_steps``;
        exceeding it raises :class:`SchedulerStall` with the full
        scheduler state in the message instead of spinning forever."""
        before = (self.tokens_generated, self.prefill_tokens)
        finished = self._step_body()
        self._step_idx += 1
        self._m_steps.inc()
        self._m_queue_depth.set(len(self._queue))
        self._m_occupancy.set(len(self._live()))
        progressed = bool(finished) or (
            (self.tokens_generated, self.prefill_tokens) != before
        )
        now = self.now()
        work_ready = bool(self._live()) or any(
            r.arrival <= now for r in self._queue
        )
        if progressed or not work_ready:
            self._stall_steps = 0
        else:
            self._stall_steps += 1
            if self._stall_steps >= self.watchdog_steps:
                report = self._stall_report()
                self._trace("stall", steps=self._stall_steps, report=report)
                raise SchedulerStall(report)
        return finished

    def _step_body(self) -> list[FinishedRequest]:
        finished = self._drain_pending()
        finished.extend(self._expire_deadlines())
        self._injected_preemptions()
        finished.extend(self._admit_arrived())
        finished.extend(self._prefill_tick())
        if not any(rs.n_generated > 0 for rs in self._live()):
            if self._live():
                # every occupied slot is still admitting: the slice above
                # was this tick's work
                if self._clock is None:
                    self._now += 1.0
            elif self._queue:
                self._advance_clock()
            return finished
        if self.allocator is not None:
            self._ensure_blocks()
        with annotate("serve/decode_chunk"):
            packed = self._fetch(self._run_chunk())
        if self._clock is None:
            self._now += 1.0
        self._trace(
            "decode_chunk", step=self._step_idx,
            n_decoding=sum(1 for rs in self._live() if rs.n_generated > 0),
        )
        finished.extend(self._process_chunk(packed))
        return finished

    def _drain_pending(self) -> list[FinishedRequest]:
        out, self._pending_finished = self._pending_finished, []
        return out

    def _stall_report(self) -> str:
        live = [
            f"(uid={rs.request.uid} slot={rs.slot} ngen={rs.n_generated} "
            f"prefilled={rs.prefilled}/{len(rs.request.prompt)} "
            f"blocks={len(rs.blocks)})"
            for rs in self._live()
        ]
        alloc = (
            f"{self.allocator.free_count}/{self.num_blocks} blocks free"
            if self.allocator is not None else "dense layout (no allocator)"
        )
        return (
            f"scheduler made no progress for {self._stall_steps} steps "
            f"(step {self._step_idx}, t={self.now():.3f}): queue depth "
            f"{len(self._queue)}, live slots [{', '.join(live) or 'none'}], "
            f"{alloc}, preemptions={self.preemptions}"
        )

    def _injected_preemptions(self) -> None:
        """Apply any FaultInjector-scheduled preemptions for this step
        (chunk boundary) — the same ``_preempt`` path pool pressure
        takes."""
        if self.faults is None:
            return
        for uid in self.faults.preempt_uids(self._step_idx):
            live = self._live()
            if not live:
                return
            rs = (
                max(live, key=lambda r: r.admitted_at) if uid is None
                else next((r for r in live if r.request.uid == uid), None)
            )
            if rs is not None:
                self.faults.fire(
                    "force_preempt", uid=rs.request.uid, step=self._step_idx
                )
                self._preempt(rs)

    def _deadline_missed(self, req: Request, now: float,
                         has_first: bool) -> bool:
        if req.deadline is not None and now > req.deadline:
            return True
        return (
            not has_first
            and req.ttft_budget is not None
            and now > req.arrival + req.ttft_budget
        )

    def _expire_deadlines(self) -> list[FinishedRequest]:
        """Chunk-boundary deadline enforcement: expired queued requests
        finish with zero tokens; expired live requests are evicted with
        their partial stream (a prefix of the fault-free stream — the
        scheduler is deterministic per request) and their blocks
        reclaimed, including slots still mid-chunked-prefill."""
        now = self.now()
        finished: list[FinishedRequest] = []
        if any(r.deadline is not None or r.ttft_budget is not None
               for r in self._queue):
            keep: collections.deque[Request] = collections.deque()
            for r in self._queue:
                if self._deadline_missed(r, now, has_first=False):
                    self.deadline_misses += 1
                    finished.append(self._finish_unstarted(r, "deadline"))
                else:
                    keep.append(r)
            self._queue = keep
        for rs in list(self._live()):
            req = rs.request
            if not self._deadline_missed(req, now, rs.n_generated > 0):
                continue
            self.deadline_misses += 1
            if rs.n_generated > 0:  # admitting slots were never activated
                with self._mesh_ctx():
                    self._state = self._deactivate_jit(
                        self._state, jnp.asarray(rs.slot)
                    )
            self._register_blocks(rs)
            self._release_blocks(rs.blocks, req.uid)
            self._slots[rs.slot] = None
            finished.append(self._emit_finished(FinishedRequest(
                req.uid, np.asarray(rs.tokens, np.int32), "deadline",
                len(req.prompt), req.arrival, rs.admitted_at,
                rs.first_token_at if rs.n_generated > 0 else now, now,
            )))
        return finished

    # -- scheduling internals ----------------------------------------------

    def _live(self) -> list[RequestState]:
        return [rs for rs in self._slots if rs is not None]

    def _advance_clock(self) -> None:
        """Nothing in flight: jump (virtual) or wait (real) to the next
        arrival."""
        nxt = min(r.arrival for r in self._queue)
        if self._clock is None:
            self._now = max(self._now, float(nxt))
        else:
            # the clock's own sleep (resolve_clock): a ManualClock test
            # advances virtual time here instead of really sleeping, so
            # deadline math, traces and waiting share one timeline
            self._sleep(max(0.0, min(nxt - self.now(), 0.05)))

    def _admit_arrived(self) -> list[FinishedRequest]:
        """FIFO-admit every arrived request that fits a free slot (and, if
        paged, whose prompt blocks are available).  With chunked prefill
        the slot is only *occupied* here — the prompt streams in via
        :meth:`_prefill_tick` slices.  On the one-shot path, requests
        whose first token already finishes them (budget 1 / instant stop)
        complete here and never occupy a slot."""
        finished = []
        while True:
            free = [i for i, rs in enumerate(self._slots) if rs is None]
            if not free:
                break
            req = self._pop_ready()
            if req is None:
                break
            blocks: list[int] = []
            prefilled0, hashes, n_hit = 0, [], 0
            if self.allocator is not None:
                res = self._alloc_prompt_blocks(req)
                if res is None:
                    # pool full: requeue at the head, wait for evictions
                    self._queue.appendleft(req)
                    break
                blocks, prefilled0, hashes, n_hit = res
                self._trace("block_alloc", uid=req.uid, n_blocks=len(blocks))
            self.admissions += 1
            if req.uid in self._admitted_uids:
                self._m_restarts.inc()  # re-admission after preemption
            self._admitted_uids.add(req.uid)
            self._trace(
                "admitted", uid=req.uid, slot=free[0], n_blocks=len(blocks)
            )
            if self.prefill_chunk is not None:
                self._admit_chunked(
                    req, free[0], blocks, prefilled0, hashes, n_hit
                )
            elif prefilled0 > 0:
                done = self._admit_cached(
                    req, free[0], blocks, prefilled0, hashes, n_hit
                )
                if done is not None:
                    finished.append(done)
            else:
                done = self._admit(req, free[0], blocks, hashes)
                if done is not None:
                    finished.append(done)
        return finished

    def _alloc_prompt_blocks(self, req: Request):
        """Blocks covering an admitting prompt, or None if the pool cannot
        satisfy the request right now (nothing changes beyond LRU recency
        on failure — ownership is untouched).

        With prefix caching this is the admission hit-walk: the prompt's
        full-block chain hashes are looked up in the allocator's index,
        every *leading* hit is reused by taking a reference (before the
        tail allocation, so our own alloc can never evict our hits), and
        only the miss/partial tail is allocated.  A block-aligned fully-
        cached prompt still recomputes its last position (the sampler
        needs those logits), which would write inside the final shared
        block — that block is copied-on-write to a private page first.

        Returns ``(blocks, prefilled0, hashes, n_hit)``: the slot's block
        list, how many leading prompt tokens are already resident,
        the prompt's full-block chain hashes, and how many leading blocks
        came from the cache."""
        s = len(req.prompt)
        nb = kv_pool.blocks_for(s, self.block_size)
        if not self.prefix_cache:
            got = self.allocator.alloc(nb)
            return (got, 0, [], 0) if got is not None else None
        hashes = kv_pool.prompt_block_hashes(req.prompt, self.block_size)
        hits: list[int] = []
        for h in hashes:
            b = self.allocator.lookup(h)
            if b is None:
                break
            hits.append(b)
        for b in hits:
            self.allocator.ref(b)
        cached = len(hits) * self.block_size
        cow = cached == s  # fully cached: last position lives in a hit block
        got = self.allocator.alloc(nb - len(hits) + (1 if cow else 0))
        if got is None:
            self.allocator.unref(hits)
            return None
        self._m_pc_hits.inc(len(hits))
        self._m_pc_misses.inc(len(hashes) - len(hits))
        blocks = hits + got
        prefilled0 = min(cached, s - 1)
        self._m_pc_hit_tokens.inc(prefilled0)
        if cow:
            src, dst = blocks[len(hits) - 1], blocks.pop()
            with annotate("serve/prefix_cow"), self._mesh_ctx():
                self._caches = self._copy_block_fn(
                    self._caches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32),
                )
            blocks[len(hits) - 1] = dst
            self.allocator.unref([src])
            self._m_pc_cow.inc()
            self._trace("block_cow", uid=req.uid, src=src, dst=dst)
        if hits:
            self._trace(
                "prefix_hit", uid=req.uid, n_blocks=len(hits),
                n_tokens=prefilled0,
            )
        return blocks, prefilled0, hashes, len(hits)

    def _register_blocks(self, rs: RequestState) -> None:
        """Register every full block whose pages are fully written (and
        will receive no further writes) in the allocator's hash index, so
        later admissions can hit them.  Prompt blocks register as prefill
        slices cover them; on release the chain extends over *generated*
        tokens too, so a multi-turn follow-up prompt (history + reply)
        hits the whole previous conversation.  The last sampled token's
        KV is written only when the token is fed, so decode coverage
        stops one short of ``n_generated``."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        req = rs.request
        s = len(req.prompt)
        covered = (
            s + rs.n_generated - 1 if rs.n_generated > 0 else rs.prefilled
        )
        n_full = min(covered // bs, len(rs.blocks))
        if rs.registered >= n_full:
            return
        h = rs.block_hashes
        if len(h) < n_full:  # extend the chain over generated tokens
            stream = np.concatenate(
                [req.prompt, np.asarray(rs.tokens, np.int32)]
            )
            while len(h) < n_full:
                i = len(h)
                h.append(kv_pool.hash_block_tokens(
                    h[i - 1] if i else None, stream[i * bs : (i + 1) * bs]
                ))
        while rs.registered < n_full:
            i = rs.registered
            self.allocator.register(rs.blocks[i], h[i])
            rs.registered += 1

    def _pop_ready(self) -> Optional[Request]:
        """Pop the first queued request that has arrived.  The head case is
        the O(1) fast path; the scan only happens when arrival delays have
        put an unarrived request in front of an arrived one."""
        now = self.now()
        for i, r in enumerate(self._queue):
            if r.arrival <= now:
                if i == 0:
                    return self._queue.popleft()
                del self._queue[i]
                return r
        return None

    def _admit_chunked(
        self, req: Request, slot: int, blocks: list[int],
        prefilled0: int = 0, hashes=(), n_hit: int = 0,
    ):
        """Occupy a slot without running prefill: install the slot's block
        table (paged) and let :meth:`_prefill_tick` stream the prompt in.
        The slot stays inactive in decode chunks until the final slice
        samples its first token.  A prefix-cache hit just starts the slice
        cursor at the cached boundary (``prefilled0``) — the tick path is
        oblivious to where the resident prefix came from."""
        if blocks:
            with self._mesh_ctx():
                self._caches = self._set_tables(
                    self._caches, jnp.asarray(slot), self._table_row(blocks)
                )
        self._slots[slot] = RequestState(
            request=req, slot=slot, blocks=blocks, tokens=[],
            n_generated=0, admitted_at=self.now(), prefilled=prefilled0,
            block_hashes=list(hashes), registered=n_hit,
        )

    def _prefill_tick(self) -> list[FinishedRequest]:
        """Advance at most ONE admitting request's prompt by one
        fixed-size ``forward_chunk`` slice, straight into the big caches.
        The decode cadence therefore pays for at most ``prefill_chunk``
        prompt tokens per engine step, however long the prompt.

        The slice that completes the prompt samples the first token with
        the one-shot path's exact key-split order, finishing admission
        (or, for instant-stop / budget-1 requests, the whole request)."""
        if self.prefill_chunk is None:
            return []
        pending = [
            rs for rs in self._live()
            if rs.prefilled < len(rs.request.prompt)
        ]
        if not pending:
            return []
        rs = min(pending, key=lambda r: (r.admitted_at, r.slot))
        t = self.prefill_chunk
        req = rs.request
        s = len(req.prompt)
        n = min(t, s - rs.prefilled)
        b = self.num_slots
        toks = np.zeros((b, t), np.int32)
        toks[rs.slot, :n] = req.prompt[rs.prefilled : rs.prefilled + n]
        pos = np.zeros((b,), np.int32)
        pos[rs.slot] = rs.prefilled
        active = np.zeros((b,), bool)
        active[rs.slot] = True
        lengths = np.zeros((b,), np.int32)
        lengths[rs.slot] = n
        with annotate("serve/chunked_prefill"), self._mesh_ctx():
            tok_d, self._caches, key_d = self._prefill_chunk(
                self.params, self._caches, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(active), jnp.asarray(lengths),
                jnp.asarray(rs.slot, jnp.int32), jax.random.PRNGKey(req.seed),
            )
        rs.prefilled += n
        self.prefill_tokens += n
        # blocks this slice just finished filling become hittable (their
        # writes are dispatched; device program order makes later readers
        # safe even while this prompt is still streaming in)
        self._register_blocks(rs)
        self._trace(
            "prefill_chunk", uid=req.uid, prefilled=rs.prefilled, total=s
        )
        if rs.prefilled < s:
            return []
        # one packed [tok0, finite] fetch per admission — validity rides
        # the transfer that was already happening
        arr = self._fetch(tok_d)
        tok0, ok = int(arr[0]), bool(arr[1])
        now = self.now()
        if not ok:
            self.quarantined += 1
            self._release_blocks(rs.blocks, req.uid)
            self._slots[rs.slot] = None
            return [self._emit_finished(FinishedRequest(
                req.uid, np.zeros((0,), np.int32), "error", s,
                req.arrival, rs.admitted_at, now, now,
            ))]
        self.tokens_generated += 1
        self._trace("first_token", uid=req.uid)
        done = self._finish_at_admission(req, tok0, rs.blocks,
                                         rs.admitted_at)
        if done is not None:
            self._slots[rs.slot] = None
            return [done]
        with self._mesh_ctx():
            self._state = self._admit_jit(
                self._state, jnp.asarray(rs.slot), tok_d[0], key_d,
                jnp.asarray(s, jnp.int32),
                jnp.asarray(req.max_new_tokens, jnp.int32),
            )
        rs.tokens = [tok0]
        rs.n_generated = 1
        rs.first_token_at = now
        return []

    def _finish_at_admission(
        self, req: Request, tok0: int, blocks: list[int], admitted_at: float
    ) -> Optional[FinishedRequest]:
        """The first sampled token already finishes the request (stop hit
        or budget 1): free its blocks and emit the FinishedRequest.  The
        single definition of finish-at-admission semantics, shared by
        one-shot (:meth:`_admit`) and chunked (:meth:`_prefill_tick`)
        admission.  Returns None if the request lives on."""
        if tok0 not in self._stop_set and req.max_new_tokens != 1:
            return None
        reason = "stop" if tok0 in self._stop_set else "length"
        self._release_blocks(blocks, req.uid)
        now = self.now()
        return self._emit_finished(FinishedRequest(
            req.uid, np.asarray([tok0], np.int32), reason, len(req.prompt),
            req.arrival, admitted_at, now, now,
        ))

    def _bucket_len(self, s: int) -> int:
        """Smallest power of two >= s, capped at the slot capacity."""
        b = 1
        while b < s:
            b <<= 1
        return min(b, self.max_len)

    def _suffix_fn(self, t: int) -> Callable:
        """The compiled cache-hit admission slice for suffix bucket ``t``
        (lazily jitted; one trace per power-of-two suffix length)."""
        fn = self._suffix_fns.get(t)
        if fn is None:
            fn = jax.jit(
                _make_prefill_chunk_fn(self.cfg, self.scfg, t),
                donate_argnums=(1,),
            )
            self._suffix_fns[t] = fn
        return fn

    def _admit_cached(
        self, req: Request, slot: int, blocks: list[int],
        prefilled0: int, hashes: list[int], n_hit: int,
    ) -> Optional[FinishedRequest]:
        """One-shot admission on a prefix-cache hit: the first
        ``prefilled0`` prompt tokens are already resident in the reused
        blocks, so only the unshared suffix runs — ONE padded
        ``forward_chunk`` slice into the big caches, exactly the program
        family chunked prefill uses.  The slice samples the first token
        with the one-shot key-split order (split after prefill, batch-1
        sampler), so the stream is bit-for-bit the cold admission's while
        TTFT pays for ``s - prefilled0`` tokens instead of ``s``."""
        s = len(req.prompt)
        with self._mesh_ctx():
            self._caches = self._set_tables(
                self._caches, jnp.asarray(slot), self._table_row(blocks)
            )
        n = s - prefilled0
        t = self._bucket_len(n)
        b = self.num_slots
        toks = np.zeros((b, t), np.int32)
        toks[slot, :n] = req.prompt[prefilled0:]
        pos = np.zeros((b,), np.int32)
        pos[slot] = prefilled0
        active = np.zeros((b,), bool)
        active[slot] = True
        lengths = np.zeros((b,), np.int32)
        lengths[slot] = n
        with annotate("serve/admission_prefill"), self._mesh_ctx():
            tok_d, self._caches, key_d = self._suffix_fn(t)(
                self.params, self._caches, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(active), jnp.asarray(lengths),
                jnp.asarray(slot, jnp.int32), jax.random.PRNGKey(req.seed),
            )
        self.prefill_tokens += n
        # one packed [tok0, finite] fetch per admission
        arr = self._fetch(tok_d)
        tok0, ok = int(arr[0]), bool(arr[1])
        now = self.now()
        if not ok:
            self.quarantined += 1
            self._release_blocks(blocks, req.uid)
            return self._emit_finished(FinishedRequest(
                req.uid, np.zeros((0,), np.int32), "error", s,
                req.arrival, now, now, now,
            ))
        # miss blocks are fully written by the slice above — registered
        # only after the finite check so a poisoned page is never indexed
        for i in range(n_hit, len(hashes)):
            self.allocator.register(blocks[i], hashes[i])
        self.tokens_generated += 1
        self._trace("first_token", uid=req.uid)
        done = self._finish_at_admission(req, tok0, blocks, now)
        if done is not None:
            return done
        with self._mesh_ctx():
            self._state = self._admit_jit(
                self._state, jnp.asarray(slot), tok_d[0], key_d,
                jnp.asarray(s, jnp.int32),
                jnp.asarray(req.max_new_tokens, jnp.int32),
            )
        self._slots[slot] = RequestState(
            request=req, slot=slot, blocks=blocks, tokens=[tok0],
            n_generated=1, admitted_at=now, prefilled=s, first_token_at=now,
            block_hashes=list(hashes), registered=len(hashes),
        )
        return None

    def _admission_prefill(self, req: Request):
        """Batch-1 prefill for admission.  Bucketed where parity-safe (one
        trace per power-of-two length bucket); exact-length otherwise."""
        if self._prefill_bucketed is not None:
            s = len(req.prompt)
            padded = np.zeros((self._bucket_len(s),), np.int32)
            padded[:s] = req.prompt
            with self._mesh_ctx():
                return self._prefill_bucketed(
                    self.params,
                    {"tokens": jnp.asarray(padded[None])},
                    jnp.asarray(s, jnp.int32),
                    jax.random.PRNGKey(req.seed),
                )
        with self._mesh_ctx():
            return self._prefill(
                self.params,
                {"tokens": jnp.asarray(req.prompt[None])},
                jnp.asarray(0, jnp.int32),
                jax.random.PRNGKey(req.seed),
            )

    def _admit(
        self, req: Request, slot: int, blocks: list[int], hashes=()
    ) -> Optional[FinishedRequest]:
        with annotate("serve/admission_prefill"):
            tok0_d, small, pos0, key = self._admission_prefill(req)
        # one packed [tok0, finite] fetch per admission
        arr = self._fetch(tok0_d)
        tok0, ok = int(arr[0]), bool(arr[1])
        now = self.now()
        if not ok:
            self.quarantined += 1
            self._release_blocks(blocks, req.uid)
            return self._emit_finished(FinishedRequest(
                req.uid, np.zeros((0,), np.int32), "error",
                len(req.prompt), req.arrival, now, now, now,
            ))
        self.tokens_generated += 1
        self._trace("first_token", uid=req.uid)
        done = self._finish_at_admission(req, tok0, blocks, now)
        if done is not None:
            # finish-at-admission never installs the prefilled cache into
            # the pool, so the blocks hold no content — nothing registers
            return done
        table_row = self._table_row(blocks)
        nb = len(blocks)
        if nb not in self._install_fns:
            self._install_fns[nb] = jax.jit(
                _make_install_fn(self.cfg, nb), donate_argnums=(0,)
            )
        with self._mesh_ctx():
            self._caches = self._install_fns[nb](
                self._caches, small, jnp.asarray(slot), table_row
            )
            self._state = self._admit_jit(
                self._state, jnp.asarray(slot), tok0_d[0], key, pos0,
                jnp.asarray(req.max_new_tokens, jnp.int32),
            )
        # the install above span-writes every prompt page: full blocks are
        # now content-complete and become hittable
        for i, h in enumerate(hashes):
            self.allocator.register(blocks[i], h)
        self._slots[slot] = RequestState(
            request=req, slot=slot, blocks=blocks, tokens=[tok0],
            n_generated=1, admitted_at=now, prefilled=len(req.prompt),
            first_token_at=now, block_hashes=list(hashes),
            registered=len(hashes),
        )
        return None

    def _table_row(self, blocks: list[int]) -> Array:
        row = np.zeros((self.max_blocks,), np.int32)
        row[: len(blocks)] = blocks
        return jnp.asarray(row)

    def _ensure_blocks(self) -> None:
        """Grow each live slot's block list to cover the coming chunk,
        preempting the youngest request if the pool runs dry."""
        for rs in sorted(self._live(), key=lambda r: r.admitted_at):
            if self._slots[rs.slot] is not rs:
                continue  # preempted by an earlier iteration of this loop
            if rs.n_generated == 0:
                continue  # still admitting: blocks already cover the prompt
            total_cap = len(rs.request.prompt) + rs.request.max_new_tokens
            need = kv_pool.blocks_for(
                min(rs.pos + self.chunk, total_cap), self.block_size
            )
            while need > len(rs.blocks):
                got = self.allocator.alloc(need - len(rs.blocks))
                if got is None:
                    victim = self._pick_victim()
                    if victim is None:
                        raise SchedulerStall(
                            "KV pool exhausted and nothing to preempt — "
                            "pool too small for the admitted working set: "
                            + self._stall_report()
                        )
                    self._preempt(victim)
                    if victim is rs:
                        break  # the requester itself was youngest: requeued
                    continue
                rs.blocks.extend(got)
                self._trace(
                    "block_alloc", uid=rs.request.uid, n_blocks=len(got)
                )
                with self._mesh_ctx():
                    self._caches = self._set_tables(
                        self._caches, jnp.asarray(rs.slot),
                        self._table_row(rs.blocks),
                    )

    def _pick_victim(self):
        """Youngest live request — including the one asking for blocks:
        preempting the youngest always discards the least progress, and it
        guarantees the oldest request keeps advancing (a lone request
        always fits the pool by the submit-time check, so the scheduler
        cannot livelock)."""
        live = self._live()
        return max(live, key=lambda r: r.admitted_at) if live else None

    def _preempt(self, rs: RequestState) -> None:
        """Return a request to the queue head; its blocks are reclaimed and
        it restarts from scratch on re-admission (same seed -> same token
        stream, so preemption is invisible in the output)."""
        self.preemptions += 1
        self._trace(
            "preempted", uid=rs.request.uid, n_generated=rs.n_generated
        )
        with self._mesh_ctx():
            self._state = self._deactivate_jit(
                self._state, jnp.asarray(rs.slot)
            )
        # a preempted stream's blocks stay hittable: the deterministic
        # restart walks the same chain and resumes from the cached prefix
        # instead of re-prefilling from scratch
        self._register_blocks(rs)
        self._release_blocks(rs.blocks, rs.request.uid)
        self._slots[rs.slot] = None
        self._queue.appendleft(rs.request)

    def _run_chunk(self):
        """Run one compiled decode chunk.  If the fault injector has a
        logit poison landing inside this chunk for a live decoding slot,
        dispatch the lazily-compiled poisoning variant instead — the
        fault-free program is never recompiled or perturbed."""
        poison = None
        if self.faults is not None and self.faults.has_poison:
            spec = np.full((self.num_slots,), -1, np.int32)
            hit = False
            for rs in self._live():
                if rs.done or rs.n_generated == 0:
                    continue
                g = self.faults.poison_rel_step(
                    rs.request.uid, rs.n_generated, self.chunk
                )
                if g is not None:
                    spec[rs.slot] = g
                    hit = True
            if hit:
                poison = jnp.asarray(spec)
        if poison is not None:
            if self._chunk_fn_poison is None:
                self._chunk_fn_poison = jax.jit(
                    _make_cb_chunk_fn(
                        self.cfg, self.scfg, self.chunk, poison=True
                    ),
                    donate_argnums=(1, 2),
                )
            with self._mesh_ctx():
                packed, self._caches, self._state = self._chunk_fn_poison(
                    self.params, self._caches, self._state, poison
                )
        else:
            with self._mesh_ctx():
                packed, self._caches, self._state = self._chunk_fn(
                    self.params, self._caches, self._state
                )
        return packed

    def _process_chunk(self, packed: np.ndarray) -> list[FinishedRequest]:
        """Mirror the device's per-step lifecycle over the fetched token
        matrix, then evict finished slots and reclaim their blocks.

        ``packed`` is ``[tokens (chunk cols) | active | quarantine]``; a
        quarantine entry < chunk marks the scan step whose logits went
        non-finite — that slot finishes with ``reason="error"`` at that
        step and its later columns are ignored."""
        steps = packed.shape[1] - 2
        quar_col = packed[:, -1]
        for step in range(steps):
            for rs in self._live():
                if rs.done or rs.n_generated == 0:
                    continue  # finished, or still admitting (no decode)
                if int(quar_col[rs.slot]) == step:
                    rs.done, rs.finish_reason = True, "error"
                    self.quarantined += 1
                    continue
                tok = int(packed[rs.slot, step])
                rs.tokens.append(tok)
                rs.n_generated += 1
                self.tokens_generated += 1
                if tok in self._stop_set:
                    rs.done, rs.finish_reason = True, "stop"
                elif rs.n_generated >= rs.request.max_new_tokens:
                    rs.done, rs.finish_reason = True, "length"
        device_active = packed[:, -2].astype(bool)
        finished = []
        now = self.now()
        for rs in self._live():
            expect_active = (not rs.done) and rs.n_generated > 0
            if bool(device_active[rs.slot]) != expect_active:
                raise AssertionError(
                    f"slot {rs.slot}: device active mask disagrees with "
                    "the host lifecycle mirror"
                )
            if not rs.done:
                continue
            if rs.finish_reason != "error":
                # extend the hash chain over the generated tokens so a
                # multi-turn follow-up (history + reply) hits; quarantined
                # streams register nothing (their pages are suspect)
                self._register_blocks(rs)
            self._release_blocks(rs.blocks, rs.request.uid)
            self._slots[rs.slot] = None
            req = rs.request
            finished.append(
                self._emit_finished(FinishedRequest(
                    req.uid, np.asarray(rs.tokens, np.int32),
                    rs.finish_reason, len(req.prompt), req.arrival,
                    rs.admitted_at, rs.first_token_at, now,
                ))
            )
        return finished
