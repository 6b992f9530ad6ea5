#!/usr/bin/env python3
"""Quickest proof that pQuant still runs on a TPU: the packed serving path
and the QAT trainer at the paper's widths, through their normal entry
points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: tensor-parallel only

One chip, in one process:

1. Device check: fails unless JAX's first device is a TPU.
2. Serving: ``pquant-1.3b`` (24 layers, d_model 2048, d_ff 5024, r 384)
   from a random init (``--seed``), exported packed and served by
   ``ContinuousBatchingEngine`` (paged KV, chunked prefill, no prefix
   cache): 8 ragged prompts of 64-512 tokens, 32 new tokens each.  The
   compiled decode and prefill programs must hold the Pallas kernels.
   Then, on the same chip: each packed linear shape of the model and paged
   attention at its head shapes against ``kernels.ref`` (integer
   accumulation is exact; KERNEL_RTOL leaves room for activations that
   round differently when the GEMV prologue and XLA compute x * gamma an
   ulp apart, PAGED_RTOL for bf16 outputs); every packed weight of the
   float32 export, unpacked by the reference, against sign(W - mean W)
   of its latent weight (exact); and one decode step's logits on the
   packed weights against the fake-quant forward of the latent weights
   (float32, highest matmul precision), relative L2 <= LOGIT_REL_L2.  The
   last is a coarse end-to-end check: per-token INT8 activation
   quantization turns the two paths' ~1e-7 differences into rounding
   flips, about 1% of the hidden state per layer, and a fault in one
   layer's export can read within a few times of that; the exact export
   check is what catches such faults.
3. Training: ``repro.launch.train`` on ``pquant-700m`` in bfloat16 at
   sequence 2048, batch TRAIN_BATCH, synthetic data from the seed; the
   loss must be finite.

With ``--chips 4`` it runs only the paths that exist across chips:
``pquant-1.3b`` packed serving on a (data=1, model=4) mesh next to a
one-device run in the same process, and one sharded train step against
the single-device step.  The greedy token streams are compared and
reported; the check feeds the one-device streams back through both
engines' weights, paged caches and sharding rules (prefill slices and
decode steps) and bounds the relative L2 of the logits at every served
position by TP_REL_L2.  Streams are not required to match: the two
placements compile to programs whose bf16 roundings differ, activation
quantization amplifies that as above, and random-init logits are close
to ties, so one flip changes the rest of a stream.

The last line of standard output is, on success only,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed phase exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from collections import Counter
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SERVE_ARCH = "pquant-1.3b"
PROMPT_LENS = (64, 512, 200, 448, 96, 320, 160, 384)
NEW_TOKENS = 32
BLOCK_SIZE = 16
PREFILL_CHUNK = 256
DECODE_KERNELS = {"w1a8_gemv", "decoupled_gemv", "int8_matmul",
                  "paged_attention"}
PREFILL_KERNELS = {"w1a8_matmul", "decoupled_matmul", "int8_matmul",
                   "paged_attention"}
LOGIT_PROMPT = 16
# between the sound reading at full depth (0.0498) and planted one-layer
# export faults (0.31-0.41; a two-group swap reads 0.075, which only the
# exact export check separates)
LOGIT_REL_L2 = 0.12
# between the sound readings (~0.02 at 2 layers on the CPU) and a planted
# fault (1.48: q heads meeting another shard's KV heads)
TP_REL_L2 = 0.3
KERNEL_RTOL = 5e-3
PAGED_RTOL = 1e-2

TRAIN_ARCH = "pquant-700m"
TRAIN_SEQ = 2048
TRAIN_BATCH = 8  # fits 16 GB on v5e; 16 needs 19.7 GB (chip compile)
TRAIN_STEPS = 4


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class ArgRecorder:
    """Wraps a jitted engine program and keeps the shapes and shardings of
    its first call, so the same program can be lowered and compiled again
    afterwards to read its text (a persistent-cache hit)."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax

            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        check(self.args is not None, "engine program never ran")
        return self.fn.lower(*self.args).compile().as_text()


def make_prompts(cfg, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
            for n in PROMPT_LENS]


def serve(cfg, seed: int, mesh=None, check_kernels: bool = False):
    """Serve the ragged prompts; returns ({uid: tokens}, engine)."""
    import numpy as np

    from repro.kernels import ops
    from repro.serve.engine import SamplerConfig
    from repro.serve.scheduler import ContinuousBatchingEngine
    from repro.train.quantized_serving import init_serving_params

    t0 = time.perf_counter()
    params, qaxes = init_serving_params(cfg, seed, packed=True)
    max_len = max(PROMPT_LENS) + NEW_TOKENS
    max_len += (-max_len) % BLOCK_SIZE
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=len(PROMPT_LENS), max_len=max_len,
        scfg=SamplerConfig(temperature=0.0, top_k=0,
                           max_new_tokens=NEW_TOKENS),
        layout="paged", block_size=BLOCK_SIZE, prefill_chunk=PREFILL_CHUNK,
        prefix_cache=False, mesh=mesh, param_axes=qaxes,
    )
    del params
    check(eng.prefill_chunk == PREFILL_CHUNK,
          f"{cfg.name} declined chunked prefill")
    decode, prefill = ArgRecorder(eng._chunk_fn), ArgRecorder(eng._prefill_chunk)
    eng._chunk_fn, eng._prefill_chunk = decode, prefill
    for uid, prompt in enumerate(make_prompts(cfg, seed)):
        eng.submit(prompt, max_new_tokens=NEW_TOKENS, seed=seed + uid,
                   uid=uid)
    finished = eng.run()
    wall = time.perf_counter() - t0
    check(len(finished) == len(PROMPT_LENS),
          f"{len(finished)} of {len(PROMPT_LENS)} requests finished")
    streams = {}
    for f in finished:
        check(f.finish_reason in ("length", "stop"),
              f"request {f.uid} finished with {f.finish_reason!r}")
        toks = np.asarray(f.tokens)
        check(len(toks) == NEW_TOKENS or f.finish_reason == "stop",
              f"request {f.uid}: {len(toks)} tokens")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {f.uid}: token outside the vocab")
        streams[f.uid] = toks.tolist()
    total = sum(len(s) for s in streams.values())
    where = "one device" if mesh is None else f"mesh {dict(mesh.shape)}"
    log(f"serving {cfg.name} packed on {where}: {len(finished)} requests, "
        f"prompts {list(PROMPT_LENS)}, {total} tokens served in "
        f"{wall:.3f} s wall incl. init and compile")
    if check_kernels:
        for name, rec, want in (("decode", decode, DECODE_KERNELS),
                                ("prefill", prefill, PREFILL_KERNELS)):
            with eng._mesh_ctx():
                found = ops.pallas_kernels(rec.compiled_text())
            log(f"{name} program Pallas kernels (tpu_custom_call): "
                f"{sorted(found)}")
            check(want <= found,
                  f"{name} program lacks kernels {sorted(want - found)}")
    eng._chunk_fn, eng._prefill_chunk = decode.fn, prefill.fn
    return streams, eng


def kernel_parity(cfg, seed: int) -> None:
    """Every packed linear shape of ``cfg`` (decode and prefill rows)
    through ``kernels.ops`` against ``kernels.ref`` on the same export."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.packing import pack_signs
    from repro.kernels import ops, ref
    from repro.train.quantized_serving import _binarize_export, _int8_export

    d, f, r = cfg.d_model, cfg.d_ff, cfg.quant.r
    key = jax.random.PRNGKey(seed)
    worst = 0.0
    for m in (8, 256):
        for k, n in ((d, d), (d, f), (f, d)):
            kw, kx, key = jax.random.split(key, 3)
            e = _binarize_export(jax.random.normal(kw, (k, n)) * k ** -0.5,
                                 packed=True)
            lam = e["scale"].reshape(())
            x = jax.random.normal(kx, (m, k))
            got = ops.bit_linear_infer(x, e["packed"], lam, jnp.float32)
            # the reference runs on the export's first K rows: padding
            # rows must add nothing
            unpadded = pack_signs(ref.unpack_ref(e["packed"])[:k])
            want = ref.w1a8_gemv_ref(x, unpadded, lam)
            worst = max(worst, float(jnp.abs(got - want).max()
                                     / jnp.abs(want).max()))
        kw, k8, kx, key = jax.random.split(key, 4)
        e1 = _binarize_export(jax.random.normal(kw, (d, f)), packed=True)
        e8 = _int8_export(jax.random.normal(k8, (d, r)))
        # the kernels take the quant multiplier, 1 / the export's scale
        lam, w8s = e1["scale"].reshape(()), 1.0 / e8["scale"].reshape(())
        one = jnp.ones((), jnp.float32)
        x = jax.random.normal(kx, (m, d))
        got = ops.decoupled_first_gemm(x, e1["packed"], e8["q"], lam, w8s,
                                       one, one, jnp.float32)
        want = ref.decoupled_gemv_ref(x, e1["packed"], e8["q"], lam, w8s,
                                      one, one)
        for g, w in zip(got, want):
            worst = max(worst, float(jnp.abs(g - w).max()
                                     / jnp.abs(w).max()))
    log(f"kernel parity ({cfg.name} linear shapes, M 8 and 256) vs "
        f"kernels.ref: worst rel {worst:.3g} (limit {KERNEL_RTOL})")
    check(np.isfinite(worst) and worst <= KERNEL_RTOL,
          "a packed kernel disagrees with its reference")

    # paged attention over a shuffled bf16 pool: decode steps at the
    # prompts' ends and PREFILL_CHUNK-token chunks ending there
    n = np.array(PROMPT_LENS, np.int32)
    mb = -(-(int(n.max()) + NEW_TOKENS) // BLOCK_SIZE)
    kq, kk, kv, kt = jax.random.split(key, 4)
    pool = (len(n) * mb, cfg.n_kv_heads, BLOCK_SIZE, cfg.head_dim)
    kpool = jax.random.normal(kk, pool, jnp.bfloat16)
    vpool = jax.random.normal(kv, pool, jnp.bfloat16)
    table = jax.random.permutation(kt, len(n) * mb).reshape(len(n), mb)
    worst = 0.0
    for t in (1, PREFILL_CHUNK):
        start = jnp.asarray(np.maximum(n - t, 0), jnp.int32)
        q = jax.random.normal(kq, (len(n), t, cfg.n_heads, cfg.head_dim),
                              jnp.bfloat16)
        args = (q, kpool, vpool, table.astype(jnp.int32), start, start + t)
        got = ops.paged_attention(*args).astype(jnp.float32)
        want = ref.paged_attention_ref(*args, out_dtype=jnp.float32)
        worst = max(worst, float(jnp.abs(got - want).max()
                                 / jnp.abs(want).max()))
    log(f"paged attention parity ({cfg.n_heads} heads of {cfg.head_dim}, "
        f"T 1 and {PREFILL_CHUNK}, bf16) vs kernels.ref: worst rel "
        f"{worst:.3g} (limit {PAGED_RTOL})")
    check(np.isfinite(worst) and worst <= PAGED_RTOL,
          "paged attention disagrees with its reference")


def export_parity(qparams, latent) -> None:
    """Every packed 1-bit weight of the export, unpacked by the reference
    (``kernels.ref.unpack_ref``), against sign(W - mean W) of its latent
    weight per layer slice; rows past the latent K are export padding.
    Exact, except where W - mean W is within float rounding of zero."""
    import jax
    import jax.numpy as jnp
    from jax.tree_util import tree_flatten_with_path

    from repro.kernels import ref

    @jax.jit
    def mismatches(w, packed):
        lead, (k, n) = w.shape[:-2], w.shape[-2:]
        signs = jax.vmap(ref.unpack_ref)(packed.reshape(-1, *packed.shape[-2:]))
        signs = signs.reshape(*lead, -1, n)[..., :k, :]
        d = w - jnp.mean(w, axis=(-2, -1), keepdims=True)
        tie = jnp.abs(d) <= 1e-6 * jnp.mean(jnp.abs(w), axis=(-2, -1),
                                            keepdims=True)
        return jnp.sum((signs != jnp.where(d >= 0, 1, -1)) & ~tie)

    bad, leaves = 0, 0
    for path, packed in tree_flatten_with_path(qparams)[0]:
        if getattr(path[-1], "key", None) != "packed":
            continue
        w = latent
        for p in path[:-1]:
            w = w[p.key if hasattr(p, "key") else p.idx]
        bad += int(mismatches(w.astype(jnp.float32), packed))
        leaves += 1
    log(f"packed export vs sign(W - mean W) of the latent weights: {bad} "
        f"wrong signs in {leaves} packed weights")
    check(leaves > 0 and bad == 0, "the packed export disagrees with the "
          "latent weights")


def decode_logits(params, cfg, seed: int):
    """Logits of one decode step after a LOGIT_PROMPT-token prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api

    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (1, LOGIT_PROMPT + 1), 3, cfg.vocab_size)

    def step(params):
        _, caches = api.prefill(params, {"tokens": toks[:, :LOGIT_PROMPT]},
                                cfg, 2 * LOGIT_PROMPT)
        logits, _ = api.decode_step(
            params, toks[:, LOGIT_PROMPT:], caches,
            jnp.asarray(LOGIT_PROMPT, jnp.int32), cfg)
        return logits

    return np.asarray(jax.jit(step)(params), np.float32)


def compare_logits(what: str, got, want) -> None:
    import numpy as np

    check(np.isfinite(got).all(), f"{what}: logits not finite")
    rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    rel_max = float(np.abs(got - want).max() / np.abs(want).max())
    same_argmax = bool((got.argmax(-1) == want.argmax(-1)).all())
    log(f"logits {what}: rel L2 {rel_l2:.6g} (limit {LOGIT_REL_L2}), "
        f"rel max {rel_max:.6g}, argmax equal: {same_argmax}")
    check(rel_l2 <= LOGIT_REL_L2, f"{what}: logits disagree")


def logits_parity(cfg, seed: int) -> None:
    """One decode step on the packed export vs the fake-quant forward of
    the same latent weights, both in float32 at highest matmul precision."""
    import jax

    from repro.models import api
    from repro.train.quantized_serving import init_serving_params

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        qparams, _ = init_serving_params(cfg32, seed, packed=True)
        got = decode_logits(qparams, cfg32, seed)
        latent = jax.jit(lambda k: api.init_model(k, cfg)[0])(
            jax.random.PRNGKey(seed))
        export_parity(qparams, latent)
        del qparams
        want = decode_logits(latent, cfg32, seed)
        del latent
    gc.collect()
    compare_logits(f"packed vs fake-quant ({cfg.name}, float32)", got, want)


def teacher_forced_logits(eng, prompts, streams):
    """Logits at every served position, with ``streams`` fed back through
    ``eng``'s placed weights, paged caches and sharding rules: the prompts
    in PREFILL_CHUNK-token slices of all slots at once (the prefill kernels
    and paged attention at T > 1), then the streams one token per step
    (the decode tier, T = 1).  Returns (slots, NEW_TOKENS, vocab) float32;
    position i predicts stream token i."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.tree_util import tree_map_with_path

    from repro.models import api
    from repro.serve import kv_pool

    cfg, b = eng.cfg, len(prompts)
    n = np.array([len(p) for p in prompts], np.int32)
    width = -(-int(n.max()) // PREFILL_CHUNK) * PREFILL_CHUNK
    toks = np.zeros((b, width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    forced = np.array([streams[u][:NEW_TOKENS - 1] for u in range(b)],
                      np.int32)
    table = np.arange(b * eng.max_blocks, dtype=np.int32).reshape(b, -1)
    caches = tree_map_with_path(
        lambda path, leaf: (jnp.broadcast_to(table, leaf.shape)
                            if getattr(path[-1], "key", None) == "table"
                            else leaf),
        eng._init_big_caches())

    def prefill(params, caches, toks, pos, lengths):
        logits, caches = api.forward_chunk(
            params, toks, caches, pos, cfg, active=lengths > 0,
            lengths=lengths, logits_at=jnp.maximum(lengths - 1, 0))
        return logits.astype(jnp.float32), caches

    def decode(params, caches, forced, pos):
        def step(caches, x):
            tok, p = x
            logits, caches = api.decode_step(
                params, tok[:, None], caches, p, cfg,
                active=jnp.ones(tok.shape, bool))
            return caches, logits[:, -1].astype(jnp.float32)

        steps = pos[None] + jnp.arange(forced.shape[1])[:, None]
        _, out = jax.lax.scan(step, caches, (forced.T, steps))
        return jnp.moveaxis(out, 0, 1)

    first = np.zeros((b, cfg.vocab_size), np.float32)
    with eng._mesh_ctx():
        if eng.mesh is not None:
            caches = jax.device_put(
                caches, kv_pool.cache_sharding(caches, eng.mesh))
        pf = jax.jit(prefill, donate_argnums=(1,))
        for s in range(0, width, PREFILL_CHUNK):
            lengths = np.clip(n - s, 0, PREFILL_CHUNK).astype(np.int32)
            logits, caches = pf(eng.params, caches,
                                toks[:, s:s + PREFILL_CHUNK],
                                np.full(b, s, np.int32), lengths)
            ends = (n > s) & (n <= s + PREFILL_CHUNK)
            first[ends] = np.asarray(logits)[ends]
        rest = np.asarray(jax.jit(decode)(eng.params, caches, forced, n))
    return np.concatenate([first[:, None], rest], axis=1)


def tp_parity(cfg, seed: int, mesh) -> None:
    """``pquant-1.3b`` served on ``mesh`` and on one device.  The greedy
    streams are compared token for token and reported; the check is on
    the logits of both engines with the one-device streams teacher-forced
    through each (so one early flip cannot hide the rest): relative L2
    per served position <= TP_REL_L2."""
    import numpy as np

    prompts = make_prompts(cfg, seed)
    one, eng = serve(cfg, seed)
    want = teacher_forced_logits(eng, prompts, one)
    del eng
    gc.collect()
    tp, eng = serve(cfg, seed, mesh=mesh)
    got = teacher_forced_logits(eng, prompts, one)
    del eng
    gc.collect()
    agree = {u: next((i for i, (a, b) in enumerate(zip(one[u], tp[u]))
                      if a != b), len(one[u])) for u in one}
    where = f"mesh {tuple(mesh.shape.values())}"
    log(f"token streams {where} vs one device: "
        f"{sum(a == NEW_TOKENS for a in agree.values())} of {len(agree)} "
        f"identical; tokens before the first difference per request "
        f"{agree}")
    check(np.isfinite(got).all(), "mesh logits not finite")
    stream = np.array([one[u] for u in range(len(prompts))])
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    log(f"teacher-forced logits {where} vs one device ({cfg.name}, "
        f"{cfg.dtype}, {rel.size} positions): rel L2 worst {rel.max():.6g} "
        f"(limit {TP_REL_L2}), median {np.median(rel):.6g}; prefill "
        f"positions worst {rel[:, 0].max():.6g}; argmax equal at "
        f"{int((got.argmax(-1) == want.argmax(-1)).sum())} of {rel.size}; "
        f"one-device argmax = its served stream at "
        f"{int((want.argmax(-1) == stream).sum())} of {rel.size}")
    check(rel.max() <= TP_REL_L2, "mesh logits disagree with one device")


def train(seed: int) -> None:
    import numpy as np

    from repro.launch import train as train_entry

    history = train_entry.main([
        "--arch", TRAIN_ARCH, "--dtype", "bfloat16",
        "--steps", str(TRAIN_STEPS), "--seq-len", str(TRAIN_SEQ),
        "--global-batch", str(TRAIN_BATCH), "--seed", str(seed),
        "--log-every", "1",
    ])
    steps = [h for h in history if "event" not in h]
    check(len(steps) == TRAIN_STEPS,
          f"{len(steps)} of {TRAIN_STEPS} train steps recorded")
    losses = [h["loss"] for h in steps]
    times = [h["step_time_s"] for h in steps]
    log(f"training {TRAIN_ARCH} bf16 seq {TRAIN_SEQ} batch {TRAIN_BATCH}: "
        f"losses {losses}, step times s {times} (first incl. compile)")
    check(np.isfinite(losses).all(), "non-finite training loss")


def sharded_train_step(seed: int, mesh) -> None:
    """One train step with state and batch sharded on ``mesh`` vs the same
    step on one device (relative loss difference < 1e-3)."""
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.distributed.sharding import param_sharding_for, sharding_rules
    from repro.train.trainer import init_train_state, make_train_step

    cfg = get_config("pquant-300m")
    b, s = 8, 512
    batch = {k: jax.random.randint(jax.random.PRNGKey(seed + i), (b, s), 0,
                                   cfg.vocab_size)
             for i, k in enumerate(("tokens", "labels"))}
    with sharding_rules(mesh, None):
        state, axes = init_train_state(jax.random.PRNGKey(seed), cfg)
        st_sh = param_sharding_for(state, axes, mesh)
        b_sh = param_sharding_for(
            batch, {"tokens": ("batch", None), "labels": ("batch", None)},
            mesh)
        step = jax.jit(make_train_step(cfg, 10), in_shardings=(st_sh, b_sh))
        _, m = step(jax.device_put(state, st_sh), jax.device_put(batch, b_sh))
        sharded = float(m["loss"])
    del state
    state1, _ = init_train_state(jax.random.PRNGKey(seed), cfg)
    _, m1 = jax.jit(make_train_step(cfg, 10))(state1, batch)
    single = float(m1["loss"])
    rel = abs(sharded - single) / abs(single)
    log(f"train step {cfg.name} batch {b}x{s}: sharded on mesh "
        f"{dict(mesh.shape)} loss {sharded!r}, one device {single!r}, "
        f"rel diff {rel:.3g} (limit 1e-3)")
    check(np.isfinite(sharded) and rel < 1e-3,
          "sharded train step disagrees with one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: no repro package under {SRC}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.update(
            [name] if "compilation_cache" in name else []))

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU; JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}; compile cache {cache_dir}")
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1

    t_start = time.perf_counter()
    cfg = get_config(SERVE_ARCH)
    try:
        if args.chips == 1:
            _, eng = serve(cfg, args.seed, check_kernels=True)
            del eng
            gc.collect()
            log(f"peak_bytes_in_use after serving: {peak_bytes(jax)}")
            kernel_parity(cfg, args.seed)
            logits_parity(cfg, args.seed)
            log(f"peak_bytes_in_use after logits check: {peak_bytes(jax)}")
            train(args.seed)
            log(f"peak_bytes_in_use after training: {peak_bytes(jax)}")
        else:
            from repro.launch.mesh import make_host_mesh

            tp_parity(cfg, args.seed, make_host_mesh(data=1, model=4))
            sharded_train_step(args.seed, make_host_mesh(data=2, model=2))
    except Exception as e:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    log(f"total wall {time.perf_counter() - t_start:.3f} s; persistent "
        f"compile cache events {dict(cache_events)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
