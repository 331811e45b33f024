"""Top-k primitives: exact two-stage local top-k and hierarchical distributed merge.

TPU adaptation of FAISS's heap-based selection: on TPU the idiomatic form is
(i) blocked scoring on the MXU, (ii) an exact two-stage selection that never
sorts the score row — each 128-column group's max, the top-k groups, then the
top-k of those groups' columns (:func:`blocked_topk`), (iii) a tree merge of
per-shard candidate lists. Exactness: merging per-shard top-k lists of length
k loses nothing for a global top-k (any global top-k element is a local top-k
element of its shard).
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


# Rows at least this many times ``k`` groups wide take the two-stage
# selection of :func:`blocked_topk`; narrower rows are one ``lax.top_k``.
_TWO_STAGE_GROUPS = 4


def _order_key(x: jnp.ndarray) -> jnp.ndarray:
    """Integers that order as ``lax.top_k`` orders ``x``: floats map to
    their bits with the magnitude bits of negatives flipped (the total order:
    -NaN < -inf < -0 < +0 < +inf < +NaN); integers are their own keys."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    bits = x.dtype.itemsize * 8
    i = jax.lax.bitcast_convert_type(x, jnp.dtype(f"int{bits}"))
    return i ^ ((i >> (bits - 1)) & jnp.iinfo(i.dtype).max)


def _last(dtype) -> jnp.ndarray:
    """The value ``lax.top_k`` ranks last: the integer minimum, or the float
    whose bits are all ones (a NaN below ``-inf``)."""
    if not jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    ones = jnp.array(-1, jnp.dtype(f"int{jnp.dtype(dtype).itemsize * 8}"))
    return jax.lax.bitcast_convert_type(ones, dtype)


def _topk(x: jnp.ndarray, k: int, group: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``lax.top_k`` of the rows of ``x`` ``(r, n)``, by the two-stage
    selection where a row is at least ``_TWO_STAGE_GROUPS·k`` groups wide."""
    r, n = x.shape
    if n < _TWO_STAGE_GROUPS * k * group:
        return jax.lax.top_k(x, k)
    pad = (-n) % group
    if pad:  # pad columns rank after every real column, ties included
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=_last(x.dtype))
    # (n / group, r, group): at r = 8, group = 128 the bytes of the (8, 128)-
    # tiled (r, n) row as they lie on the TPU, so no transpose is copied
    groups = x.reshape(r, -1, group).transpose(1, 0, 2)
    # the k groups whose maxima rank highest, in ascending column order
    gmax = _order_key(groups).max(axis=-1).T
    gids = jnp.sort(_topk(gmax, k, group)[1], axis=-1)  # (r, k)
    cand = groups[gids, jnp.arange(r)[:, None]].reshape(r, k * group)
    cols = (gids[..., None] * group + jnp.arange(group)).reshape(r, k * group)
    # integer keys sort stably on every backend (the TPU's float top-k is a
    # custom call of its own), so ties keep the lowest column
    sel = jax.lax.top_k(_order_key(cand), k)[1]
    return jnp.take_along_axis(cand, sel, axis=-1), jnp.take_along_axis(cols, sel, axis=-1)


def blocked_topk(scores: jnp.ndarray, k: int, *, block: int = 128) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k over the last axis without sorting the row.

    The row is cut into groups of ``block`` contiguous columns (one lane
    width by default; pad columns past a multiple rank last). Each group's
    max is one lane reduction over the row; ``lax.top_k`` of the group
    maxima picks ``k`` groups; their ``k·block`` columns, gathered in
    ascending column order, give the top-k. The group maxima take the same
    step again while they are wide enough, so every ``lax.top_k`` runs on
    fewer than ``4·k·block`` columns, whatever the row's width: the shape
    alone picks the path. Rows narrower than that are one ``lax.top_k``.

    Exact, tie order included. Order elements by (value descending, column
    ascending), as ``lax.top_k`` does. A top-k element lies in one of the
    ``k`` groups whose maxima rank highest in that order: were its group
    not among them, their ``k`` maxima would all rank ahead of it. The
    candidates are gathered in ascending column order, so ``lax.top_k``'s
    first-of-equal-values rule keeps the lowest column. Groups are ranked by
    integer keys that order as ``lax.top_k`` orders floats (signed zeros and
    NaNs included), so values and ids equal ``lax.top_k`` over the whole
    row, bit for bit, for any input.

    Returns (values, indices), both ``(..., k)``, descending.
    """
    n = scores.shape[-1]
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    lead = scores.shape[:-1]
    vals, ids = _topk(scores.reshape(-1, n), k, block)
    return vals.reshape(lead + (k,)), ids.reshape(lead + (k,))


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
