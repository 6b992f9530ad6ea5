"""Pallas kernel tier for the pQuant integer serving path.

Every kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` owns padding,
CPU interpret fallback, and shape-keyed dispatch between the tiers.

Kernel            | File                | Shape regime                  | How ops.py selects it
------------------+---------------------+-------------------------------+----------------------------------------------
w1a8_matmul       | w1a8_matmul.py      | prefill/train, M > 32         | bit_linear_infer, M > DECODE_M_MAX: M-tiled
                  |                     |                               | (bm up to 128) grid, separate act-quant pass
w1a8_gemv         | w1a8_gemv.py        | decode, M <= 32               | bit_linear_infer, M <= DECODE_M_MAX: act-quant
                  |                     |                               | fused in prologue, (N, K)-major grid, wide bn;
                  |                     |                               | tiles from decode_tiles / sweep_decode_tiles
int8_matmul       | int8_matmul.py      | 8-bit branch, any M           | int8_linear_infer (W8A8 branch)
decoupled_matmul  | decoupled_matmul.py | prefill/train dual-branch     | decoupled_first_gemm, M > DECODE_M_MAX
decoupled_gemv    | w1a8_gemv.py        | decode dual-branch, M <= 32   | decoupled_first_gemm, M <= DECODE_M_MAX
rmsnorm_quant     | rmsnorm_quant.py    | norm + act-quant, any M       | fused_rmsnorm_quant
paged_attention   | paged_attention.py  | paged-KV attention: decode    | models.attention._paged_scores whenever the
                  |                     | (T=1), chunked prefill and    | cache is the paged {"kpool","vpool","table"}
                  |                     | one-shot prefill (any T),     | layout AND ops.paged_attention_enabled()
                  |                     | GQA/MQA                       | (REPRO_PAGED_ATTN=1 forces on / =0 forces the
                  |                     |                               | gather+SDPA fallback / default: TPU only) AND
                  |                     |                               | ops.paged_attention_supported (GQA divides,
                  |                     |                               | block_size fills the pool dtype's sublane
                  |                     |                               | tile, head_dim 8-aligned); MLA keeps
                  |                     |                               | its dense latent cache (nothing paged to walk)

Decode-tier tile sizes are answered per (M, K, N) signature by
``ops.decode_tiles`` (Mosaic-legal heuristic: K tiles multiples of 256
or the whole K, N tiles multiples of 128 with a partial last tile) and
can be autotuned on the
current backend with ``ops.sweep_decode_tiles`` — the swept winner is
cached and picked up by later calls with the same signature.  The paged-
attention pages-per-step knob is answered per (T, Hq, Hkv, head_dim,
block_size, max_blocks) by ``ops.paged_tiles`` and autotuned with
``ops.sweep_paged_tiles``; winners for both families persist in the same
per-backend JSON (``repro.kernels.tile_cache``).

Model-stack call sites (since the packed-forward wiring): ``bitlinear``
(attention / MLA projections), ``core.decoupled`` (FFN trunk, fused
dual-branch first GEMMs, 8-bit branch, decoupled projections) and
``models.moe`` (per-expert slices) all dispatch here whenever their
weights are in the ``quantize_params_for_serving(packed=True)`` layout —
``DecodeEngine`` / ``ContinuousBatchingEngine`` decode steps (M = batch
<= DECODE_M_MAX) land on the GEMV row.
"""
