"""The control of the comparison that decides ``correct``: readings of the
plain reference put in the program's place, one precision below the
configuration's.

The configurations score in float32 at the highest precision (six bf16
passes). The control scores the same queries over the same corpus in
float32 at ``high`` precision: three bf16 passes (hi·hi + hi·lo + lo·hi),
written out here so it computes the same on any backend. It prints, per
seed, the ``score_gap`` that the run's check would read for its answers;
that reading has to exceed the configuration's limit.

    python3 benchmarks/chip/control.py --workload nq768-batch --seeds 11 12 13
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import check, spec  # noqa: E402
from chipbench.traffic import QueryGenerator  # noqa: E402

ROW_BLOCK = 1 << 17
K = 10  # the deepest k the paper catalog routes


def _bf16(x):
    """``x`` rounded to bfloat16, as float32. An explicit rounding: a bare
    cast pair may be skipped where the compiler allows excess precision, as
    it does on the TPU, which turns the three passes into one."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi.astype(jnp.bfloat16), _bf16(x - hi).astype(jnp.bfloat16)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@jax.jit
def scores_high(queries, rows):
    """float32 scores from three bf16 passes."""
    qh, ql = _split(queries)
    rh, rl = _split(rows)
    return _dot(qh, rh) + (_dot(qh, rl) + _dot(ql, rh))


def control_topk(corpus: np.ndarray, queries: np.ndarray, k: int):
    """Top-k (scores, ids) of ``queries`` (float32) at ``high`` precision."""
    best_s = np.full((queries.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((queries.shape[0], 0), np.int64)
    q = jnp.asarray(queries, jnp.float32)
    for s in range(0, corpus.shape[0], ROW_BLOCK):
        block = corpus[s : s + ROW_BLOCK]
        n = block.shape[0]
        if n < ROW_BLOCK:
            block = np.concatenate([block, np.zeros((ROW_BLOCK - n, corpus.shape[1]), np.float32)])
        sc = scores_high(q, jnp.asarray(block))
        sc = jnp.where(jnp.arange(ROW_BLOCK)[None, :] < n, sc, -jnp.inf)
        v, i = jax.lax.top_k(sc, k)
        all_s = np.concatenate([best_s, np.asarray(v)], axis=1)
        all_i = np.concatenate([best_i, np.asarray(i, np.int64) + s], axis=1)
        order = np.lexsort((all_i, -all_s), axis=1)[:, :k]
        best_s = np.take_along_axis(all_s, order, axis=1)
        best_i = np.take_along_axis(all_i, order, axis=1)
    return best_s, best_i


def reading(cell: spec.Cell, corpus: np.ndarray, seed: int) -> float:
    """The ``score_gap`` of the control's answers to the window's first
    queries, sampled as a run samples them."""
    reference = spec.reference(cell.config)
    mix = cell.traffic
    n = int(mix["arrivals"]["batch"])
    queries = QueryGenerator(mix["queries"], seed, "window").next_batch(n)
    picked = check.sample(queries, list(range(len(queries))), seed)
    texts = [queries[i] for i in picked]
    qvecs = reference.embed(texts, corpus.shape[1])
    tops, ids = control_topk(corpus, qvecs.astype(np.float32), K)
    gaps = check.gaps(corpus, qvecs, ids.tolist(), tops[:, 0].astype(np.float64).tolist(),
                      reference)
    return float(np.max(gaps))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from chipbench.corpus import make_rows

    cell = spec.resolve(args.workload)
    for seed in args.seeds:
        corpus = make_rows(int(cell.config["rows"]), int(cell.config["dim"]), seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_score_gap": reading(cell, corpus, seed),
                          "limit": cell.config["limits"]["score_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
