"""Top-k primitives: blocked local top-k and hierarchical distributed merge.

TPU adaptation of FAISS's heap-based selection: on TPU the idiomatic form is
(i) blocked scoring on the MXU, (ii) an in-register running top-k per block,
(iii) a tree merge of per-shard candidate lists. Exactness: merging per-shard
top-k lists of length k loses nothing for a global top-k (any global top-k
element is a local top-k element of its shard).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import SCORE_BLOCK, SCORE_PRECISION


def mips_scores(queries: jnp.ndarray, corpus: jnp.ndarray) -> jnp.ndarray:
    """``(nq, d) × (n, d) → (nq, n)`` inner products, block by block.

    Each block of :data:`~repro.kernels.SCORE_BLOCK` corpus rows is one
    matmul of a fixed shape, batched over blocks at
    :data:`~repro.kernels.SCORE_PRECISION`, so a score is the same float
    whether its row is scored in the whole corpus, in a shard, or in the
    Pallas kernel's block. The corpus's last axis is contracted in place (no
    transposed copy). Callers on the hot path pass corpora padded to a
    block multiple; a ragged tail is scored as one zero-padded block.
    """
    nq, d = queries.shape
    n = corpus.shape[0]
    nb, rem = divmod(n, SCORE_BLOCK)
    parts = []
    if nb:
        full = corpus if not rem else corpus[: nb * SCORE_BLOCK]
        blocks = full.reshape(nb, SCORE_BLOCK, d)
        qb = jnp.broadcast_to(queries, (nb, nq, d))
        s = jax.lax.dot_general(
            qb, blocks, (((2,), (2,)), ((0,), (0,))), precision=SCORE_PRECISION
        )  # (nb, nq, SCORE_BLOCK)
        parts.append(jnp.moveaxis(s, 0, 1).reshape(nq, nb * SCORE_BLOCK))
    if rem:
        tail = jnp.pad(corpus[nb * SCORE_BLOCK :], ((0, SCORE_BLOCK - rem), (0, 0)))
        s = jax.lax.dot_general(
            queries, tail, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION
        )
        parts.append(s[:, :rem])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def blocked_topk(scores: jnp.ndarray, k: int, *, block: int = 4096) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over the last axis without materializing a full sort.

    Streams over ``block``-sized column chunks keeping a running candidate
    set of size k — the jnp analogue of the Pallas ``mips_topk`` kernel's
    merge loop (and its oracle for odd sizes).

    Returns (values, indices), both ``(..., k)``, descending.
    """
    n = scores.shape[-1]
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    if n <= block:
        return jax.lax.top_k(scores, k)

    pad = (-n) % block
    if pad:
        fill = jnp.full(scores.shape[:-1] + (pad,), -jnp.inf, scores.dtype)
        scores = jnp.concatenate([scores, fill], axis=-1)
    n_blocks = scores.shape[-1] // block
    blocks = scores.reshape(scores.shape[:-1] + (n_blocks, block))

    def body(carry, xb):
        vals, idxs = carry
        bvals, bidx = xb
        cat_v = jnp.concatenate([vals, bvals], axis=-1)
        cat_i = jnp.concatenate([idxs, bidx], axis=-1)
        v, sel = jax.lax.top_k(cat_v, k)
        i = jnp.take_along_axis(cat_i, sel, axis=-1)
        return (v, i), None

    # per-block top-k first (cheap), then merge via scan
    base = jnp.arange(n_blocks)[:, None] * block
    bv, bi = jax.lax.top_k(blocks, min(k, block))
    bi = bi + base  # global column indices
    # move block axis to scan position
    bv = jnp.moveaxis(bv, -2, 0)
    bi = jnp.moveaxis(bi, -2, 0)
    init_v = jnp.full(scores.shape[:-1] + (k,), -jnp.inf, scores.dtype)
    init_i = jnp.zeros(scores.shape[:-1] + (k,), jnp.int32)
    (vals, idxs), _ = jax.lax.scan(body, (init_v, init_i), (bv, bi))
    return vals, idxs


def merge_topk(
    vals_a: jnp.ndarray, idx_a: jnp.ndarray, vals_b: jnp.ndarray, idx_b: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge two candidate lists into a single descending top-k."""
    cat_v = jnp.concatenate([vals_a, vals_b], axis=-1)
    cat_i = jnp.concatenate([idx_a, idx_b], axis=-1)
    v, sel = jax.lax.top_k(cat_v, k)
    return v, jnp.take_along_axis(cat_i, sel, axis=-1)


def distributed_topk(
    local_vals: jnp.ndarray,
    local_idx: jnp.ndarray,
    k: int,
    axis_name: str,
):
    """Global top-k from per-shard top-k inside ``shard_map``.

    all-gathers the k-candidate lists over ``axis_name`` (k × world bytes,
    tiny vs the corpus) and reduces. Indices must already be global.

    This is the device-resident merge the ``execution="device"`` sharded
    backend fuses into its search program. Tie order is part of the
    contract: ``all_gather(tiled=True)`` concatenates candidates in
    shard-major order and ``lax.top_k`` keeps the *first* of equal values,
    so ties resolve to the lowest shard — and, since in-shard lists are
    already lowest-id-first, to the lowest global id. That is exactly the
    host-side ``merge_topk`` left-to-right order and the unsharded
    ``top_k`` order, which is why sharded results are bit-identical to
    unsharded ones even under tie-heavy score distributions.
    """
    gv = jax.lax.all_gather(local_vals, axis_name, axis=-1, tiled=True)
    gi = jax.lax.all_gather(local_idx, axis_name, axis=-1, tiled=True)
    v, sel = jax.lax.top_k(gv, k)
    return v, jnp.take_along_axis(gi, sel, axis=-1)
