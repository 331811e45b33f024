"""Fused MIPS scoring + running top-k as a Pallas TPU kernel.

The retrieval hot path (paper §V.E FAISS role; recsys ``retrieval_cand``
cell: 1 query × 10⁶ candidates). TPU adaptation of FAISS's scan+heap: heaps
don't vectorize on the VPU, so selection is reformulated as k rounds of
(max, first-match-argmax, mask) over the candidate block — k is small
(≤ 32) and each round is a dense VPU reduction.

Grid: (n_q_blocks, n_corpus_blocks); corpus is the sequential axis. Scratch
carries the running (bq, k) best values/indices; each step fuses:

    scores = q_blk @ c_blkᵀ                     (MXU, bq × bn)
    merge running top-k with block top-k        (k VPU rounds)

so the (Q, N) score matrix never exists in HBM — the kernel's entire
working set is O(bq·bn) VMEM. Final block writes (vals, idx) out.

Why not materialize+sort: at N = 10⁶, Q = 8, f32 scores are 32 MB/query-
block + an O(N log N) sort; the fused form is HBM-bound on the corpus read
only — the roofline minimum for exact MIPS.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import SCORE_PRECISION

NEG_INF = -1e30


def _topk_merge(scores, base_idx, best_v, best_i, k):
    """Merge a (bq, bn) score block into running (bq, k) best lists.

    k rounds of: take row max of the remaining block, compare against the
    current worst of the running list, insert via a rank-shift. To keep it
    simple and fully vectorized we instead select the top-k of the
    *concatenated* candidate set [best (k) | block (bn)] by k rounds of
    (max, first-argmax, mask-out).
    """
    bq, bn = scores.shape
    cat_v = jnp.concatenate([best_v, scores], axis=1)  # (bq, k+bn)
    idx_block = base_idx + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    cat_i = jnp.concatenate([best_i, idx_block], axis=1)
    width = k + bn
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (bq, width), 1)

    new_v = []
    new_i = []
    for _ in range(k):
        m = jnp.max(cat_v, axis=1, keepdims=True)  # (bq, 1)
        hit = cat_v == m
        # first-match argmax via masked iota min
        pos = jnp.min(jnp.where(hit, col_iota, width), axis=1, keepdims=True)
        sel = col_iota == pos
        picked_i = jnp.sum(jnp.where(sel, cat_i, 0), axis=1, keepdims=True)
        new_v.append(m)
        new_i.append(picked_i)
        cat_v = jnp.where(sel, NEG_INF, cat_v)
    return jnp.concatenate(new_v, axis=1), jnp.concatenate(new_i, axis=1)


def _mips_kernel(q_ref, c_ref, v_out, i_out, bv_ref, bi_ref, *, k, bn, n_c, n_valid):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        bv_ref[...] = jnp.full_like(bv_ref, NEG_INF)
        bi_ref[...] = jnp.zeros_like(bi_ref)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    c = c_ref[...].astype(jnp.float32)  # (bn, D)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=SCORE_PRECISION, preferred_element_type=jnp.float32,
    )  # (bq, bn)
    if n_valid < n_c * bn:  # corpus was zero-padded: mask the pad columns out
        col = ic * bn + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(col < n_valid, scores, NEG_INF)
    bv, bi = _topk_merge(scores, ic * bn, bv_ref[...], bi_ref[...], k)
    bv_ref[...] = bv
    bi_ref[...] = bi

    @pl.when(ic == n_c - 1)
    def _store():
        v_out[...] = bv_ref[...]
        i_out[...] = bi_ref[...]


def _mips_kernel_masked(q_ref, c_ref, m_ref, v_out, i_out, bv_ref, bi_ref, *, k, bn, n_c):
    """Variant taking a per-row validity mask as a traced input.

    Needed for the shard_map'd sharded-retrieval path: each shard's residue
    (how many of its rows are real vs zero-pad) depends on
    ``lax.axis_index``, so it is a *traced* value — the static ``n_valid``
    branch of :func:`_mips_kernel` cannot express it. The mask rides the
    same grid as the corpus blocks ((1, bn) per step), so masking stays a
    VPU ``where`` with no extra HBM traffic beyond one f32 row.
    """
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        bv_ref[...] = jnp.full_like(bv_ref, NEG_INF)
        bi_ref[...] = jnp.zeros_like(bi_ref)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    c = c_ref[...].astype(jnp.float32)  # (bn, D)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=SCORE_PRECISION, preferred_element_type=jnp.float32,
    )  # (bq, bn)
    mask = m_ref[...] > 0.0  # (1, bn), broadcasts over query rows
    scores = jnp.where(mask, scores, NEG_INF)
    bv, bi = _topk_merge(scores, ic * bn, bv_ref[...], bi_ref[...], k)
    bv_ref[...] = bv
    bi_ref[...] = bi

    @pl.when(ic == n_c - 1)
    def _store():
        v_out[...] = bv_ref[...]
        i_out[...] = bi_ref[...]


def mips_topk_pallas(
    queries: jnp.ndarray,  # (Q, D)
    corpus: jnp.ndarray,  # (N, D)
    k: int,
    *,
    block_q: int = 8,
    block_n: int = 1024,
    n_valid: int | None = None,
    valid_mask: jnp.ndarray | None = None,
    interpret: bool = False,
):
    """Fused MIPS top-k over a (possibly zero-padded) corpus.

    Two masking modes for padded rows, mutually exclusive:

    * ``n_valid`` (static int) — rows at index >= n_valid are masked to
      -inf; callers pad N up to a block multiple (DenseIndex's auto-pad).
    * ``valid_mask`` (traced ``(N,)`` float array, >0 = real row) — same
      masking as a kernel *input*, for callers whose residue is only known
      at trace time: inside ``shard_map`` each shard's valid-row count
      derives from ``lax.axis_index``, which a static int cannot capture.
      With a traced mask the k-vs-corpus-size check is the caller's job
      (the sharded path clamps k before building the closure).
    """
    q_n, d = queries.shape
    n, _ = corpus.shape
    if valid_mask is not None and n_valid is not None:
        raise ValueError("pass n_valid (static) or valid_mask (traced), not both")
    bq = min(block_q, q_n)
    bn = min(block_n, n)
    if q_n % bq or n % bn:
        raise ValueError(f"(Q={q_n}, N={n}) must divide blocks ({bq}, {bn})")
    if k > bn:
        raise ValueError(f"k={k} must be <= block_n={bn}")
    n_q, n_c = q_n // bq, n // bn

    common = dict(
        grid=(n_q, n_c),
        out_specs=[
            pl.BlockSpec((bq, k), lambda iq, ic: (iq, 0)),
            pl.BlockSpec((bq, k), lambda iq, ic: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mips_topk",
    )
    if valid_mask is not None:
        if valid_mask.shape != (n,):
            raise ValueError(f"valid_mask must be ({n},), got {valid_mask.shape}")
        kernel = functools.partial(_mips_kernel_masked, k=k, bn=bn, n_c=n_c)
        vals, idx = pl.pallas_call(
            kernel,
            in_specs=[
                pl.BlockSpec((bq, d), lambda iq, ic: (iq, 0)),
                pl.BlockSpec((bn, d), lambda iq, ic: (ic, 0)),
                pl.BlockSpec((1, bn), lambda iq, ic: (0, ic)),
            ],
            **common,
        )(queries, corpus, valid_mask.astype(jnp.float32)[None, :])
        return vals, idx

    n_valid = n if n_valid is None else n_valid
    if not 0 < n_valid <= n:
        raise ValueError(f"n_valid={n_valid} must be in (0, {n}]")
    if k > n_valid:
        raise ValueError(f"k={k} > corpus size {n_valid}")
    kernel = functools.partial(_mips_kernel, k=k, bn=bn, n_c=n_c, n_valid=n_valid)
    vals, idx = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec((bq, d), lambda iq, ic: (iq, 0)),
            pl.BlockSpec((bn, d), lambda iq, ic: (ic, 0)),
        ],
        **common,
    )(queries, corpus)
    return vals, idx
