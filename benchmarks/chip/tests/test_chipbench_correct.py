"""The comparison that decides ``correct``: sound runs pass it, the control
and each fault a served cell can have fail it. Runs the harness on the CPU
at a small size, past the check for a chip."""

import dataclasses
import importlib.util

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.corpus import make_rows
from chipbench.runner import run
from chipbench.traffic import QueryGenerator
from repro.retrieval.index import DenseIndex

SEED = 2**33 + 99


def tiny(name, rows=6000, dim=48):
    cell = spec.resolve(name)
    return dataclasses.replace(cell, config=dict(cell.config, rows=rows, dim=dim))


def test_reference_embedding_matches_the_program_within_float32():
    from repro.retrieval import HashedNGramEmbedder

    ref = spec.reference(spec.resolve("nq768-batch").config)
    mix = spec.resolve("hotpot384-batch").traffic
    texts = QueryGenerator(mix["queries"], SEED, "window").next_batch(64) + ["", "a", "Who?"]
    for dim in (384, 768):
        got = np.asarray(HashedNGramEmbedder(dim=dim).embed(texts), np.float64)
        assert np.max(np.abs(got - ref.embed(texts, dim))) < 1e-6


@pytest.mark.parametrize("name", ["nq768-batch", "hotpot384-batch"])
def test_sound_run_is_correct(name):
    result, lines = run(tiny(name), SEED, 0.3, False, 0.0)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert result["checks"]["malformed"] == {"value": 0, "limit": 0}
    assert result["checks"]["score_gap"]["value"] < result["checks"]["score_gap"]["limit"]


def _altered(orig):
    """The top answer of every query replaced by another row, where made."""

    def search_batch(self, query_vecs, k, **kw):
        scores, ids = orig(self, query_vecs, k, **kw)
        ids = np.asarray(ids).copy()
        ids[:, 0] = (ids[:, 0] + 1) % self.size
        return scores, jnp.asarray(ids)

    return search_batch


def _half_left_out(orig):
    """Only the first half of each search's queries is searched; the rest
    get the answers of the searched half."""

    def search_batch(self, query_vecs, k, **kw):
        q = np.asarray(query_vecs)
        half = max(1, q.shape[0] // 2)
        scores, ids = orig(self, q[:half], k, **kw)
        reps = -(-q.shape[0] // half)
        return (jnp.tile(scores, (reps, 1))[: q.shape[0]], jnp.tile(ids, (reps, 1))[: q.shape[0]])

    return search_batch


def _top_repeated(orig):
    """Every query's top row served in each of its k places, as a merge of
    per-block top-k lists that keeps duplicates would serve it."""

    def search_batch(self, query_vecs, k, **kw):
        scores, ids = orig(self, query_vecs, k, **kw)
        scores, ids = np.asarray(scores).copy(), np.asarray(ids).copy()
        scores[:, 1:], ids[:, 1:] = scores[:, :1], ids[:, :1]
        return jnp.asarray(scores), jnp.asarray(ids)

    return search_batch


@pytest.mark.parametrize("name", ["nq768-batch", "hotpot384-batch"])
@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_each_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    monkeypatch.setattr(DenseIndex, "search_batch", fault(DenseIndex.search_batch))
    result, lines = run(tiny(name), SEED, 0.3, False, 0.0)
    assert not result["correct"], lines
    assert result["checks"]["score_gap"]["value"] > result["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("name", ["nq768-batch", "hotpot384-batch"])
def test_a_row_served_twice_makes_the_run_incorrect(monkeypatch, name):
    monkeypatch.setattr(DenseIndex, "search_batch", _top_repeated(DenseIndex.search_batch))
    result, lines = run(tiny(name), SEED, 0.3, False, 0.0)
    assert not result["correct"], lines
    assert result["checks"]["malformed"]["value"] > 0


def test_the_control_fails_the_limit():
    """Scores at `high` (three bf16 passes) read above the limit that
    float32 at the highest precision reads below."""
    path = spec.BENCH_DIR / "control.py"
    mod_spec = importlib.util.spec_from_file_location("chipbench_control", path)
    control = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(control)
    cell = tiny("nq768-batch", rows=40000, dim=768)
    corpus = make_rows(40000, 768, SEED)
    assert control.reading(cell, corpus, SEED) > cell.config["limits"]["score_gap"]
