"""Batched serving example: prefill a batch of prompts and decode with
sampling — the paper's edge-inference scenario (W1A8 weights, KV cache).

    PYTHONPATH=src python examples/serve_lm.py [--ckpt results/train100m/ckpt]

Generation runs on the compiled decode engine (prefill + lax.scan + on-device
sampling, one host transfer).  ``--compare`` also times the legacy per-token
Python loop and prints the speedup; ``--stream`` prints tokens chunk by
chunk as the engine produces them; ``--continuous`` serves the same
prompts through the continuous-batching engine instead (ragged prompts,
per-request budgets/seeds, paged KV pool — each request's stream matches
the lockstep engine's for its seed); ``--packed`` serves the bit-packed
integer export, so every decode linear runs the W1A8 GEMV kernel tier.

Without --ckpt it serves a randomly initialised model from --seed at the
arch's full width (tokens are synthetic ids; ``--reduced`` shrinks it);
with a checkpoint from train_lm.py it decodes that model.  The compute
dtype is the config's (bfloat16) unless ``--dtype`` overrides it.
JAX's persistent compile cache is on (``repro.launch.compile_cache``).
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.registry import get_config, reduced
from repro.models import api
from repro.train.serve import BatchedServer, SamplerConfig
from repro.train.trainer import init_train_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="pquant-100m")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="random-init seed when no --ckpt is given")
    ap.add_argument("--dtype", default=None, choices=["float32", "bfloat16"],
                    help="compute dtype override (default: the config's)")
    ap.add_argument("--compare", action="store_true",
                    help="also time the legacy per-token Python loop")
    ap.add_argument("--stream", action="store_true",
                    help="stream tokens chunk by chunk")
    ap.add_argument("--stream-chunk", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the continuous-batching engine "
                         "(ragged prompts, paged KV pool)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable automatic prefix caching (--continuous "
                         "only): shared prompt prefixes are served from "
                         "cached KV blocks; every prompt is submitted "
                         "twice so the second pass demonstrates hits")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="token budget per step for chunked admission "
                         "prefill (--continuous only): long prompts "
                         "stream in as bounded slices instead of "
                         "stalling live decode streams")
    ap.add_argument("--packed", action="store_true",
                    help="export weights to the packed integer serving "
                         "layout first: decode runs the W1A8 GEMV kernel "
                         "tier on stored integers (paper Appendix A)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data, model) device mesh, e.g. 1x2: "
                         "packed weights shard N-major over the model "
                         "axis, paged KV pools shard over KV heads; the "
                         "scheduler and slot state stay replicated.  "
                         "Defaults to REPRO_MESH when set")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh, mesh_from_env

    enable_compile_cache()

    if args.mesh:
        data, model = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data=data, model=model)
    else:
        mesh = mesh_from_env()
    if mesh is not None:
        print(f"serving on mesh {dict(mesh.shape)}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)

    from repro.train.quantized_serving import (
        cast_serving_params,
        init_serving_params,
        quantize_params_for_serving,
    )

    key = jax.random.PRNGKey(args.seed)
    if args.ckpt:
        state, _ = init_train_state(key, cfg)
        restored = Checkpointer(args.ckpt).restore(state._asdict())
        params = restored["params"]
        print(f"restored checkpoint step {int(restored['opt'].step) if hasattr(restored['opt'], 'step') else '?'}")
        if args.packed:
            _, axes = api.params_shape_and_axes(cfg)
            params, _ = quantize_params_for_serving(params, axes, cfg,
                                                    packed=True)
        params = cast_serving_params(params, cfg.dtype)
    elif args.packed:
        params, _ = init_serving_params(cfg, args.seed, packed=True)
    else:
        params = cast_serving_params(api.init_model(key, cfg)[0], cfg.dtype)
    print(f"serving {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.dtype})"
          + ("" if args.ckpt else f", random init from seed {args.seed}"))
    if args.packed:
        print("serving the packed integer export (W1A8 kernel tier)")

    scfg = SamplerConfig(temperature=0.8, top_k=40,
                         max_new_tokens=args.new_tokens)

    if args.continuous:
        from repro.serve.scheduler import ContinuousBatchingEngine

        max_len = args.prompt_len + args.new_tokens
        max_len += (-max_len) % args.block_size
        num_slots = max(2, args.batch // 2)
        # the default pool is sized for full slot occupancy with zero
        # slack — a cache with no headroom evicts every parked block on
        # the next admission, so give the demo a pool that can retain
        nb = num_slots * -(-max_len // args.block_size)
        eng = ContinuousBatchingEngine(
            params, cfg, num_slots=num_slots, max_len=max_len,
            scfg=scfg, layout="paged", block_size=args.block_size,
            prefill_chunk=args.prefill_chunk, mesh=mesh,
            prefix_cache=args.prefix_cache,
            num_blocks=2 * nb if args.prefix_cache else None,
        )
        if args.prefill_chunk and eng.prefill_chunk is None:
            print("note: config is not chunk-safe; one-shot admission")
        if args.prefix_cache and not eng.prefix_cache:
            print("note: config declines prefix caching; running cold")
        rng = jax.random
        t0 = time.time()
        # with --prefix-cache every prompt goes in twice: the repeats
        # (same prompt+seed, fresh uid) hit the blocks the first pass
        # cached and must produce the identical stream
        rounds = 2 if args.prefix_cache else 1
        for r in range(rounds):
            for i in range(args.batch):
                # ragged prompts: each request its own length and seed
                s = max(1, args.prompt_len - i % 4)
                prompt = rng.randint(rng.PRNGKey(i), (s,), 3, cfg.vocab_size)
                eng.submit(prompt, max_new_tokens=args.new_tokens, seed=i,
                           uid=r * args.batch + i)
        finished = eng.run()
        dt = time.time() - t0
        total = sum(len(f.tokens) for f in finished)
        print(f"continuous batching: {len(finished)} requests, {total} "
              f"tokens in {dt:.1f}s ({total / dt:.1f} tok/s incl. compile); "
              f"pool free {eng.allocator.free_count}/{eng.num_blocks}, "
              f"{eng.preemptions} preemptions")
        if eng.prefix_cache:
            c = eng.snapshot()["counters"]
            streams = {}
            match = all(
                streams.setdefault(f.uid % args.batch, f.tokens.tolist())
                == f.tokens.tolist() for f in finished
            )
            print(f"prefix cache: {c['prefix_cache_hits_total']} hits / "
                  f"{c['prefix_cache_misses_total']} misses, "
                  f"{c['prefix_cache_hit_tokens_total']} tokens reused, "
                  f"{c['prefix_cache_cow_total']} CoW; repeat streams "
                  f"identical: {match}")
        for f in sorted(finished, key=lambda f: f.uid)[:4]:
            print(f"  request {f.uid} ({f.finish_reason}): "
                  f"{f.tokens.tolist()}")
        return

    server = BatchedServer(params, cfg,
                           max_len=args.prompt_len + args.new_tokens + 1,
                           mesh=mesh)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 3, cfg.vocab_size
    ).astype(jnp.int32)
    toks = args.batch * args.new_tokens

    if args.stream:
        t0 = time.time()
        chunks = []
        for i, chunk in enumerate(server.generate_stream(
                prompts, scfg, chunk=args.stream_chunk)):
            chunks.append(chunk)
            print(f"  chunk {i}: +{chunk.shape[1]} tokens "
                  f"({time.time() - t0:.1f}s in)")
        import numpy as np
        out = np.concatenate(chunks, axis=1)
    else:
        t0 = time.time()
        out = server.generate(prompts, scfg)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s batched, incl. prefill + compile)")
    for i, row in enumerate(out[: min(4, args.batch)]):
        print(f"  request {i}: {row.tolist()}")

    if args.compare:
        # warm both paths, then time steady-state generation
        server.generate(prompts, scfg)
        server.generate_python_loop(prompts, scfg)
        t0 = time.time()
        server.generate_python_loop(prompts, scfg)
        t_py = time.time() - t0
        t0 = time.time()
        server.generate(prompts, scfg)
        t_en = time.time() - t0
        print(f"python loop: {toks / t_py:.1f} tok/s | compiled engine: "
              f"{toks / t_en:.1f} tok/s | speedup {t_py / t_en:.2f}x")


if __name__ == "__main__":
    main()
