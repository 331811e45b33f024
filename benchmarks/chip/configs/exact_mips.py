"""Plain reference of the dense configurations: embed, then exact MIPS.

Written from the semantics, in float64 numpy, importing nothing of the
program under test:

* query embedding: signed feature hashing of lowercase word unigrams
  (weight 2.0) and character trigrams of the space-joined words (weight
  0.5) into ``dim`` buckets, by blake2b (8-byte digest, little-endian, salt
  prefixed: ``"w"`` for words, ``"g"`` for trigrams; bucket = hash mod dim,
  sign = bit 62), then L2-normalised;
* search: inner products of the normalised queries with every corpus row,
  scored in row blocks so no float64 copy of the corpus is ever whole, and
  the top ``k`` rows by descending score, ties to the lowest row id.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np

_WORD = re.compile(r"[A-Za-z0-9']+")
ROW_BLOCK = 1 << 18


def _hash(s: str, salt: str) -> int:
    return int.from_bytes(hashlib.blake2b((salt + s).encode(), digest_size=8).digest(), "little")


def embed(texts: list[str], dim: int) -> np.ndarray:
    """``(len(texts), dim)`` float64 unit query vectors."""
    out = np.zeros((len(texts), dim), np.float64)
    for r, text in enumerate(texts):
        words = _WORD.findall(text.lower())
        for w in words:
            h = _hash(w, "w")
            out[r, h % dim] += 2.0 if (h >> 62) & 1 else -2.0
        joined = " ".join(words)
        grams = [joined[i : i + 3] for i in range(len(joined) - 2)] if len(joined) >= 3 else (
            [joined] if joined else []
        )
        for g in grams:
            h = _hash(g, "g")
            out[r, h % dim] += 0.5 if (h >> 62) & 1 else -0.5
    return out / np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-300)


def topk(corpus: np.ndarray, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Top ``k`` (scores, ids) of every query over all corpus rows,
    descending, ties to the lowest id. Rows are scored one block at a time
    and each block's candidates merged into the running top ``k``."""
    q = np.asarray(queries, np.float64)
    best_s = np.full((q.shape[0], 0), -np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for s in range(0, corpus.shape[0], ROW_BLOCK):
        block = q @ np.asarray(corpus[s : s + ROW_BLOCK], np.float64).T
        kk = min(k, block.shape[1])
        cand = np.argpartition(-block, kk - 1, axis=1)[:, :kk]
        cand_s = np.take_along_axis(block, cand, axis=1)
        all_s = np.concatenate([best_s, cand_s], axis=1)
        all_i = np.concatenate([best_i, cand + s], axis=1)
        order = np.lexsort((all_i, -all_s), axis=1)[:, :k]
        best_s = np.take_along_axis(all_s, order, axis=1)
        best_i = np.take_along_axis(all_i, order, axis=1)
    return best_s, best_i


def row_scores(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 score of row ``ids[q, j]`` for query ``q``."""
    q = np.asarray(queries, np.float64)
    rows = np.asarray(corpus[np.asarray(ids).reshape(-1)], np.float64).reshape(*ids.shape, -1)
    return np.einsum("qd,qjd->qj", q, rows)
