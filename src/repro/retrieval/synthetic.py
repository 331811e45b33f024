"""Seeded synthetic corpora for retrieval-scaling experiments.

The paper corpus is a few hundred passages — enough to pin routing
behaviour, three orders of magnitude too small to say anything about
retrieval *scaling* (the regime RAGO and the RAG systems-tradeoff studies
measure, and the regime the device-sharded backend exists for). This
module fabricates a corpus of any size in seconds: seeded Gaussian
embeddings (already unit-normalized — no text is ever embedded, which is
what makes a million documents constructible at all) plus lightweight
placeholder passages so ``get_passages`` and the assemble stage work
unchanged.

Flagged into the CLI as ``--synthetic-docs N`` (launch/serve.py) and the
benchmarks as the sharding scaling-sweep corpus (benchmarks/micro.py).
Retrieval *quality* over a synthetic corpus is meaningless by
construction; every cell built on one measures systems behaviour (latency,
throughput, counters) — never recall.
"""

from __future__ import annotations

import numpy as np

from repro.retrieval.chunking import Passage
from repro.retrieval.index import DenseIndex

_NORM_ROWS = 1 << 16


def synthetic_dense_index(
    n_docs: int,
    dim: int = 64,
    *,
    seed: int = 0,
    with_passages: bool = True,
) -> DenseIndex:
    """Build a seeded synthetic :class:`DenseIndex` with ``n_docs`` rows.

    Embeddings are ``default_rng(seed)`` Gaussians, L2-normalized on the
    host in float32 and installed with ``assume_normalized=True`` — the
    exact rows are a pure function of ``(n_docs, dim, seed)``, so sharded
    vs unsharded comparisons over a synthetic corpus are as bit-stable as
    over the paper corpus. ``with_passages=False`` skips the placeholder
    payload list for embedding-only workloads (saves ~100 MB at 10⁶ docs).
    """
    if n_docs < 1:
        raise ValueError(f"n_docs must be >= 1, got {n_docs}")
    if dim < 1:
        raise ValueError(f"dim must be >= 1, got {dim}")
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n_docs, dim), dtype=np.float32)
    # normalize in place, in row blocks: each row's norm and quotient are
    # the same floats as one whole-array pass, without the whole-array
    # temporaries (a 6×10⁶×768 corpus is 18 GB by itself)
    for s in range(0, n_docs, _NORM_ROWS):
        block = emb[s : s + _NORM_ROWS]
        block /= np.maximum(np.linalg.norm(block, axis=1, keepdims=True), 1e-9)
    passages = (
        [Passage(i, f"synthetic document {i}") for i in range(n_docs)]
        if with_passages
        else None
    )
    return DenseIndex(emb, passages, assume_normalized=True)
