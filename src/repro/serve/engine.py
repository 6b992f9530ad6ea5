"""Compiled decode engine: the whole generation loop on device.

The legacy ``BatchedServer.generate`` ran a Python per-token loop — every
step launched a jitted decode, synced the sampled token to the host
(``np.asarray``), and re-dispatched.  On a bandwidth-bound W1A8 decode the
dispatch + host-sync overhead dominates the actual GEMV work, so the loop
was Python-bound, not hardware-bound.

``DecodeEngine`` compiles prefill -> ``lax.scan`` of (decode step -> top-k
sample) over the whole token budget into ONE jitted function: sampling runs
on device, the KV caches stay resident as scan carry, and exactly one
device->host transfer happens per ``generate`` call (``host_transfers``
counts them; the engine test asserts the invariant).  ``generate_stream``
is the chunked variant: one transfer per chunk for incremental delivery.

Prefill and decode are the SAME forward: ``api.prefill`` is
``forward_chunk`` from an empty cache and ``api.decode_step`` is
``forward_chunk`` with T=1 (see ``models.transformer``), so this lockstep
tier, the python-loop baseline and the continuous-batching scheduler all
run one cache-resident forward implementation.

Logits contract: prefill and decode both surface ``(B, V)`` next-token
logits (``decode_logits`` normalizes the decode step's ``(B, 1, V)``), so
sampling never branches on step index.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import api
from repro.serve.tracing import annotate

Array = jax.Array


# ---------------------------------------------------------------------------
# Tensor-parallel serving helpers (shared with serve.scheduler)
# ---------------------------------------------------------------------------


def serving_overrides(cfg: ModelConfig, mesh, extra: Optional[dict] = None):
    """Sharding-rule overrides for serving ``cfg`` on ``mesh``: the
    column-parallel base (:data:`repro.distributed.sharding.
    SERVING_OVERRIDES`) plus cfg-driven relaxations — when a head count
    doesn't divide the model axis, the whole head family drops to
    replicated so a flattened ``(heads * head_dim)`` weight dim can never
    shard *within* a head (MQA/GQA on a wide mesh)."""
    ov = dict(shd.SERVING_OVERRIDES)
    ws = int(dict(mesh.shape).get("model", 1))
    if ws > 1:
        if getattr(cfg, "n_kv_heads", 0) % ws:
            ov.update({"kv_heads": None, "cache_heads": None})
        if getattr(cfg, "n_heads", 0) % ws:
            ov.update({"heads": None, "act_heads": None})
    if extra:
        ov.update(extra)
    return ov


def _matching_axes(params, cfg: ModelConfig):
    """The logical-axes tree matching ``params``' structure — latent
    (``api.params_shape_and_axes``) or either packed serving export — or
    None when no candidate matches (caller replicates)."""
    import jax.tree_util as jtu

    want = jtu.tree_structure(params)
    candidates = []
    try:
        candidates.append(api.params_shape_and_axes(cfg))
    except Exception:  # noqa: BLE001 — family without a shape oracle
        pass
    try:
        from repro.train.quantized_serving import serving_params_shape_and_axes

        for packed in (True, False):
            candidates.append(serving_params_shape_and_axes(cfg, packed))
    except Exception:  # noqa: BLE001
        pass
    for shapes, axes in candidates:
        if jtu.tree_structure(shapes) == want:
            return axes
    return None


def place_params(params, cfg: ModelConfig, mesh, overrides,
                 param_axes=None):
    """``device_put`` a parameter tree onto ``mesh`` with the N-major
    (column-parallel) serving placement; unmatched trees replicate."""
    axes = param_axes if param_axes is not None else _matching_axes(params, cfg)
    with shd.sharding_rules(mesh, overrides):
        if axes is None:
            shardings = jax.tree.map(
                lambda _: NamedSharding(mesh, PartitionSpec()), params
            )
        else:
            shardings = shd.nmajor_param_sharding(params, axes, mesh)
    return jax.device_put(params, shardings)


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.8
    top_k: int = 40
    max_new_tokens: int = 32
    # tokens that end a sequence.  ``generate`` still runs the full compiled
    # budget (one fused program, fixed shape); ``generate_stream`` tracks a
    # per-sequence done mask on device and exits its Python chunk loop once
    # every sequence has stopped.  The continuous-batching engine
    # (repro.serve.scheduler) short-circuits per request.
    stop_tokens: tuple[int, ...] = ()


def _hit_stop(tok: Array, scfg: SamplerConfig) -> Array:
    """(B,) bool — did this step's token end its sequence?"""
    if not scfg.stop_tokens:
        return jnp.zeros(tok.shape, bool)
    stop = jnp.asarray(scfg.stop_tokens, jnp.int32)
    return (tok[:, None] == stop[None, :]).any(axis=-1)


def sample_token(key: Array, logits: Array, scfg: SamplerConfig) -> Array:
    """logits (B, V) -> (B,) int32, on device (scan-safe: top_k static)."""
    if scfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / scfg.temperature
    if scfg.top_k > 0 and scfg.top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, scfg.top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def decode_logits(params, tok: Array, caches, pos: Array, cfg: ModelConfig):
    """One decode step under the (B, V) logits contract.

    tok: (B,) int32 current tokens.  Returns ((B, V) logits, new caches).
    """
    logits, caches = api.decode_step(params, tok[:, None], caches, pos, cfg)
    return logits[:, -1], caches


def _scan_decode(params, cfg, tok0, caches, pos0, key, length, scfg,
                 done0=None):
    """length decode steps from tok0: returns (tokens (B, length), carry).

    Key-split order matches the legacy Python loop (split -> sample) so the
    two paths produce identical token streams for a given seed.  This is
    the ONLY definition of the step body: generate, generate_stream chunks
    and the stop-mask tracking all run through it, so the key-split parity
    contract cannot drift between paths.  The carry's trailing ``done``
    mask records which sequences have emitted a stop token (it never
    alters sampling — generate's output stays budget-shaped).
    """
    if done0 is None:
        done0 = jnp.zeros(tok0.shape, bool)

    def step(carry, _):
        tok, caches, pos, key, done = carry
        key, sub = jax.random.split(key)
        with annotate("serve/decode_step"):
            logits, caches = decode_logits(params, tok, caches, pos, cfg)
        with annotate("serve/sample"):
            nxt = sample_token(sub, logits, scfg)
        return (nxt, caches, pos + 1, key, done | _hit_stop(nxt, scfg)), nxt

    carry, toks = jax.lax.scan(
        step, (tok0, caches, pos0, key, done0), None, length=length
    )
    return jnp.moveaxis(toks, 0, 1), carry  # (B, length)


def _prefill_sample(params, batch, pos_off, key, cfg, cache_len, scfg):
    """Prefill + sample the first token.  The single definition of the
    key-split order both generate and generate_stream (and the legacy loop
    equivalence) depend on.  The trailing ``ok`` mask — (B,) bool, are the
    prefill logits finite — is the quarantine signal the continuous
    engine's admission path reads; the lockstep entry points ignore it
    (it is a pure function of logits they already computed, so carrying it
    changes no numerics)."""
    with annotate("serve/prefill_forward"):
        logits, caches = api.prefill(params, batch, cfg, cache_len)
    key, sub = jax.random.split(key)
    tok0 = sample_token(sub, logits, scfg)
    pos0 = jnp.asarray(batch["tokens"].shape[1], jnp.int32) + pos_off
    ok = jnp.isfinite(logits).all(axis=-1)
    return tok0, caches, pos0, key, ok


def _make_generate_fn(cfg: ModelConfig, cache_len: int, scfg: SamplerConfig):
    """The whole generation as one jittable fn: prefill + first sample +
    (T-1)-step scan.  One fused XLA program, no host round-trips inside."""
    t = scfg.max_new_tokens

    def gen(params, batch, pos_off, key):
        tok0, caches, pos0, key, _ = _prefill_sample(
            params, batch, pos_off, key, cfg, cache_len, scfg
        )
        rest, _ = _scan_decode(
            params, cfg, tok0, caches, pos0, key, t - 1, scfg
        )
        return jnp.concatenate([tok0[:, None], rest], axis=1)  # (B, T)

    return gen


def _make_prefill_fn(cfg: ModelConfig, cache_len: int, scfg: SamplerConfig):
    def prefill(params, batch, pos_off, key):
        tok0, caches, pos0, key, _ = _prefill_sample(
            params, batch, pos_off, key, cfg, cache_len, scfg
        )
        return tok0, caches, pos0, key

    return prefill


def _make_checked_prefill_fn(cfg: ModelConfig, cache_len: int,
                             scfg: SamplerConfig):
    """Batch-1 admission prefill with the quarantine signal packed into
    the token fetch: returns ``([tok0, ok] (2,) int32, caches, pos0,
    key)`` so the continuous engine learns about non-finite prefill logits
    on the ONE scalar fetch it already pays per admission — no extra
    device->host sync.  Token and key-split order are exactly
    :func:`_prefill_sample`'s (same fn), preserving stream parity."""

    def prefill(params, batch, pos_off, key):
        tok0, caches, pos0, key, ok = _prefill_sample(
            params, batch, pos_off, key, cfg, cache_len, scfg
        )
        packed = jnp.stack([tok0[0], ok[0].astype(jnp.int32)])
        return packed, caches, pos0, key

    return prefill


def _make_bucketed_prefill_fn(cfg: ModelConfig, cache_len: int,
                              scfg: SamplerConfig):
    """Prefill for bucket-padded prompts: ``batch["tokens"]`` is right-padded
    to a shared bucket length and ``plen`` (traced) is the true prompt
    length, so ONE trace serves every prompt length in the bucket.  Logits
    come from position ``plen - 1`` and ``pos0 = plen``; the key-split
    order matches :func:`_prefill_sample` exactly (split after prefill),
    preserving the per-request determinism contract.  Returns the same
    packed ``[tok0, ok]`` pair as :func:`_make_checked_prefill_fn` (this
    path is only ever the continuous engine's)."""

    def prefill(params, batch, plen, key):
        logits, caches = api.prefill(
            params, batch, cfg, cache_len, last_pos=plen
        )
        key, sub = jax.random.split(key)
        tok0 = sample_token(sub, logits, scfg)
        ok = jnp.isfinite(logits).all(axis=-1)
        packed = jnp.stack([tok0[0], ok[0].astype(jnp.int32)])
        return packed, caches, jnp.asarray(plen, jnp.int32), key

    return prefill


def _make_chunk_fn(cfg: ModelConfig, scfg: SamplerConfig, length: int):
    """Streaming chunk: ``length`` decode steps plus per-sequence done
    tracking.  Returns (packed (B, length+1), carry) where the last packed
    column is the post-chunk done mask — it rides the chunk's single
    device->host transfer so the host loop can early-exit without an extra
    fetch (the transfers-per-chunk invariant test stays honest)."""

    def chunk(params, tok, caches, pos, key, done):
        toks, carry = _scan_decode(
            params, cfg, tok, caches, pos, key, length, scfg, done
        )
        packed = jnp.concatenate(
            [toks, carry[-1][:, None].astype(toks.dtype)], axis=1
        )
        return packed, carry

    return chunk


class DecodeEngine:
    """Fixed-batch compiled generation engine.

    Compiled programs are cached per (max_new_tokens, temperature, top_k)
    sampler signature (jax.jit adds the batch-shape axis underneath), so a
    server reuses one compilation across calls.

    ``mesh`` (a ``(data, model)`` mesh from ``launch.mesh``) turns on
    tensor-parallel serving: parameters are placed N-major over the model
    axis and every compiled program is traced inside the serving sharding
    rules, so the annotations in the model stack become GSPMD constraints
    and the packed-kernel dispatch opens its shard_map islands.  A 1-device
    mesh streams bit-for-bit the meshless engine.
    """

    def __init__(self, params, cfg: ModelConfig, max_len: int, *,
                 mesh=None, param_axes=None, mesh_overrides=None):
        self.cfg, self.max_len = cfg, max_len
        self.mesh = mesh
        self._overrides = (
            serving_overrides(cfg, mesh, mesh_overrides)
            if mesh is not None else None
        )
        if mesh is not None:
            params = place_params(params, cfg, mesh, self._overrides,
                                  param_axes)
        self.params = params
        self._gen_fns: dict = {}
        self._prefill_fns: dict = {}
        self._chunk_fns: dict = {}
        # device->host transfers performed (the engine test asserts exactly
        # one per generate() call)
        self.host_transfers = 0

    def _mesh_ctx(self):
        """Rule context active while a compiled fn is called (tracing runs
        at call time, in the calling thread, so this is where the serving
        rules must be installed)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return shd.sharding_rules(self.mesh, self._overrides)

    # -- compilation caches -------------------------------------------------

    @staticmethod
    def _key(scfg: SamplerConfig):
        return (
            scfg.max_new_tokens,
            float(scfg.temperature),
            int(scfg.top_k),
            tuple(scfg.stop_tokens),
        )

    def _gen_fn(self, scfg: SamplerConfig):
        key = self._key(scfg)
        if key not in self._gen_fns:
            self._gen_fns[key] = jax.jit(
                _make_generate_fn(self.cfg, self.max_len, scfg)
            )
        return self._gen_fns[key]

    def _prefill_fn(self, scfg: SamplerConfig):
        key = self._key(scfg)[1:]  # chunking doesn't depend on T
        if key not in self._prefill_fns:
            self._prefill_fns[key] = jax.jit(
                _make_prefill_fn(self.cfg, self.max_len, scfg)
            )
        return self._prefill_fns[key]

    def _chunk_fn(self, scfg: SamplerConfig, length: int):
        key = self._key(scfg)[1:] + (length,)
        if key not in self._chunk_fns:
            # donate the cache tree: each chunk writes one token per layer
            # into multi-MB KV buffers — without donation XLA copies the
            # whole tree per chunk (the caller always rebinds from the
            # return value, so the donated input is never reused)
            self._chunk_fns[key] = jax.jit(
                _make_chunk_fn(self.cfg, scfg, length), donate_argnums=(2,)
            )
        return self._chunk_fns[key]

    # -- host boundary ------------------------------------------------------

    def _fetch(self, x: Array) -> np.ndarray:
        self.host_transfers += 1
        return np.asarray(x)

    def _batch_and_off(self, prompts, extra_inputs):
        batch = {"tokens": prompts, **(extra_inputs or {})}
        off = (
            self.cfg.n_image_tokens
            if (extra_inputs and "image_embeds" in extra_inputs)
            else 0
        )
        return batch, jnp.asarray(off, jnp.int32)

    # -- public API ---------------------------------------------------------

    def generate(
        self,
        prompts: Array,  # (B, S) int32, right-aligned equal-length prompts
        scfg: Optional[SamplerConfig] = None,
        extra_inputs: Optional[dict] = None,
        seed: int = 0,
    ) -> np.ndarray:
        """(B, max_new_tokens) int32 — one device->host transfer total."""
        scfg = SamplerConfig() if scfg is None else scfg
        if scfg.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {scfg.max_new_tokens}"
            )
        batch, pos_off = self._batch_and_off(prompts, extra_inputs)
        with self._mesh_ctx():
            toks = self._gen_fn(scfg)(
                self.params, batch, pos_off, jax.random.PRNGKey(seed)
            )
        return self._fetch(toks)

    def generate_stream(
        self,
        prompts: Array,
        scfg: Optional[SamplerConfig] = None,
        extra_inputs: Optional[dict] = None,
        seed: int = 0,
        chunk: int = 8,
    ) -> Iterator[np.ndarray]:
        """Chunked streaming: yields arrays whose concatenation equals
        ``generate``'s output, one host transfer per chunk.  The first yield
        is (B, <=chunk+1) — the prefill-sampled token rides with the first
        decode chunk — and later yields are (B, <=chunk).

        With ``scfg.stop_tokens`` set, the chunk loop exits early once
        every sequence has produced a stop token: the on-device done mask
        rides the existing per-chunk transfer as one extra packed column,
        so early exit costs no additional fetches.  (The concatenated
        yields are then a prefix of ``generate``'s output — truncation at
        the stop token itself is the caller's policy.)"""
        scfg = SamplerConfig() if scfg is None else scfg
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        if scfg.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {scfg.max_new_tokens}"
            )
        batch, pos_off = self._batch_and_off(prompts, extra_inputs)
        with self._mesh_ctx():
            tok, caches, pos, key = self._prefill_fn(scfg)(
                self.params, batch, pos_off, jax.random.PRNGKey(seed)
            )
        done = _hit_stop(tok, scfg)  # stays on device (no transfer)
        pending = tok[:, None]  # first token rides with the first chunk
        remaining = scfg.max_new_tokens - 1
        while remaining > 0:
            step = min(chunk, remaining)
            with self._mesh_ctx():
                packed, (tok, caches, pos, key, done) = self._chunk_fn(
                    scfg, step
                )(self.params, tok, caches, pos, key, done)
            if pending is not None:  # device-side concat: one fetch per chunk
                packed = jnp.concatenate([pending, packed], axis=1)
                pending = None
            fetched = self._fetch(packed)
            yield fetched[:, :-1]
            remaining -= step
            if scfg.stop_tokens and fetched[:, -1].all():
                return  # every sequence stopped: skip the remaining chunks
        if pending is not None:  # max_new_tokens == 1: prefill sample only
            yield self._fetch(pending)
