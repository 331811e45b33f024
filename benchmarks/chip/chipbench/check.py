"""Whether what the timed path produced is correct.

The window's answers are compared, after the window has closed, with the
configuration's plain reference (``configs/<reference>.py``), which imports
nothing of the program:

``score_gap``
    Over a sample of the window's retrieval answers drawn from the seed
    (the longest queries always in it): the widest of (a) how far the
    reference score of a served passage lies below the reference's score
    at that rank, and (b) how far the served retrieval confidence (the
    top score) lies from the reference's top score. A wrong or missing id
    shows in (a), scores computed below the configuration's precision in
    (b).
``malformed``
    Sampled answers whose passage list is not ``k`` distinct corpus rows,
    ``k`` that of its routed bundle. A row served twice would otherwise
    read as a gap of 0 in ``score_gap``'s (a).
"""

from __future__ import annotations

import numpy as np

from chipbench.traffic import rng_for, word_count

SAMPLE = 64  # answers compared per run
LONGEST = 4  # of them, the longest queries of the window


def served_ids(passages: list[str]) -> list[int] | None:
    """Row ids of the placeholder passages (``"synthetic document <id>"``)."""
    try:
        return [int(p.rsplit(" ", 1)[1]) for p in passages]
    except (IndexError, ValueError):
        return None


def sample(queries: list[str], eligible: list[int], seed: int) -> list[int]:
    """Positions to compare: the longest eligible queries, then a seeded
    draw from the rest."""
    by_len = sorted(eligible, key=lambda i: (-word_count(queries[i]), i))
    chosen = by_len[:LONGEST]
    rest = np.asarray(sorted(set(eligible) - set(chosen)), np.int64)
    n_more = min(SAMPLE - len(chosen), rest.size)
    if n_more > 0:
        chosen += rest[rng_for(seed, "sample").choice(rest.size, n_more, replace=False)].tolist()
    return sorted(chosen)


def gaps(corpus: np.ndarray, qvecs: np.ndarray, ids: list[list[int]], tops: list[float],
         reference) -> np.ndarray:
    """Per compared answer, the widest gap of :data:`score_gap`'s two."""
    k_max = max(len(r) for r in ids)
    ref_s, _ = reference.topk(corpus, qvecs, k_max)
    out = np.empty(len(ids))
    for r, (row, top) in enumerate(zip(ids, tops)):
        got = reference.row_scores(corpus, qvecs[r : r + 1], np.asarray([row]))[0]
        rank_gap = np.max(ref_s[r, : len(row)] - got)
        out[r] = max(rank_gap, abs(top - ref_s[r, 0]))
    return out


def retrieval_numbers(corpus, dim: int, responses, catalog, seed: int, reference):
    """``score_gap`` and ``malformed`` over the window's sampled answers."""
    k_of = {b.name: b.top_k for b in catalog}
    queries = [r.record.query for r in responses]
    eligible = [
        i for i, r in enumerate(responses) if not catalog[r.record.bundle].skip_retrieval
    ]
    picked = sample(queries, eligible, seed)
    malformed = 0
    rows, tops, texts = [], [], []
    for i in picked:
        resp = responses[i]
        ids = served_ids(resp.passages)
        k = k_of[resp.record.bundle]
        n_rows = corpus.shape[0]
        if (
            ids is None
            or len(ids) != min(k, n_rows)
            or len(set(ids)) != len(ids)
            or not all(0 <= j < n_rows for j in ids)
        ):
            malformed += 1
            continue
        rows.append(ids)
        tops.append(float(resp.record.retrieval_confidence))
        texts.append(queries[i])
    gap = 0.0
    if rows:
        gap = float(np.max(gaps(corpus, reference.embed(texts, dim), rows, tops, reference)))
    return {"score_gap": gap, "malformed": malformed, "compared": len(picked)}
