"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three files (see EXAMPLE.md):
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (backend dispatch, layout glue)
  ref.py    — pure-jnp oracle used by tests (interpret=True on CPU)

Kernels: flash_attention (prefill), decode_attention (flash-decoding),
mips_topk (fused retrieval scoring+selection), embedding_bag (recsys
gather-reduce).
"""

import jax

# Precision of every retrieval score matmul — the Pallas ``mips_topk``
# kernel and the jnp paths (dense blocked scoring, sharded shards, IVF
# probes) alike. On a TPU, DEFAULT for f32 is one bf16 pass, which reorders
# the top-k of a million near-orthogonal unit vectors against any f32/f64
# reference; HIGHEST keeps f32 accuracy. On the CPU both are plain f32.
SCORE_PRECISION = jax.lax.Precision.HIGHEST

# Corpus rows per score block. Every score path computes a query's scores
# one block of this many rows at a time — the Pallas kernel's ``block_n``,
# and the batched block matmul of ``repro.retrieval.topk.mips_scores`` —
# so a score's floats do not depend on how many rows share the call: XLA
# tiles a plain ``(q, d) × (d, n)`` matmul by ``n``, which moves the last
# bit between a corpus and its shards.
SCORE_BLOCK = 1024
