"""One search per nested backend per batch, and replays served from its row.

The contracts of the search-depth plan in ``serving/stages.py``:

* Every backend that declares ``nested_topk`` returns, for each pair of
  routable depths k' < k, a top-k' row that is the first k' entries of its
  top-k row, bit for bit, through tied scores. Backends without that
  property (hybrid, BM25, IVF, fault injection, remote) do not declare it.
* ``route`` plans one group per nested backend, at the deepest k the
  catalog routes to it; other backends keep one group per (backend, k).
* When finalize's true priors flip a query between depths on the nested
  backend that searched it, the replay is served from the first-pass row:
  ``replay_searches`` stays 0, the ``repro.search`` spans match
  ``search_calls``, and each served execution equals what
  :func:`~repro.serving.stages.execute_one` returns with a real search. A
  flip from ``direct_llm`` still searches, once.
* Records, bills and served passages are byte-identical to the per-k path
  (the same backend with ``nested_topk`` off) at every executor setting.

Flips are forced by skewing the latency priors ``route`` reads while
finalize re-routes with the true ones.
"""

import dataclasses
import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.bundles import BundleCatalog, _paper_bundles, make_catalog
from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.retrieval import (
    CachedBackend,
    DenseBackend,
    DenseIndex,
    DeviceShardedBackend,
    ShardedBackend,
)
from repro.retrieval.backend import is_nested
from repro.retrieval.chunking import Passage
from repro.retrieval.faults import FaultProfile, FaultyBackend
from repro.retrieval.index import l2_normalize
from repro.retrieval.remote import RemoteBackend
from repro.retrieval.topk import blocked_topk
from repro.serving import stages
from repro.serving.engine import build_paper_engine
from repro.serving.resilience import ResilientBackend
from repro.serving.streaming import StreamConfig, serve_stream
from test_retrieval import _tied_rows

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)
# route-time latency multipliers per paper bundle (direct, light, medium,
# heavy): DEEP routes every query to heavy_rag, so true priors flip many to
# medium and light (dense → dense); DIRECT routes some to direct_llm, whose
# flips to a dense bundle must search
DEEP = (1.0, 50.0, 50.0, 1.0)
DIRECT = (1.0, 50.0, 50.0, 50.0)
PAIRS = [(3, 5), (3, 10), (5, 10)]


# --------------------------------------------------------------------------- #
# The prefix property of every declared backend                                #
# --------------------------------------------------------------------------- #
def _repeated_row_index(n=6000, dim=16, vocab=50, seed=0):
    """A corpus of ``vocab`` distinct unit rows repeated ``n`` times over, so
    a query ties across many passages (as in tests/test_retrieval.py), and
    queries that are corpus rows themselves and random ones."""
    rng = np.random.default_rng(seed)
    rows = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(vocab, dim)), jnp.float32)))
    corpus = rows[rng.integers(0, vocab, size=n)]
    passages = [Passage(i, f"passage {i}") for i in range(n)]
    index = DenseIndex(corpus, passages, assume_normalized=True)
    q = np.concatenate([rows[:4], rng.normal(size=(5, dim))]).astype(np.float32)
    return index, jnp.asarray(q)


NESTED = {
    "dense": lambda idx: DenseBackend(idx),
    "dense-pallas": lambda idx: DenseBackend(idx, scorer="pallas", interpret=True),
    "sharded-threads": lambda idx: ShardedBackend.from_dense(idx, n_shards=3),
    "sharded-device": lambda idx: ShardedBackend.from_dense(idx, n_shards=1, execution="device"),
    "sharded-process": lambda idx: ShardedBackend.from_dense(idx, n_shards=2, execution="process"),
    "cached": lambda idx: CachedBackend(DenseBackend(idx), capacity=4),
    "resilient": lambda idx: ResilientBackend(DenseBackend(idx)),
    "resilient-cached-sharded": lambda idx: ResilientBackend(
        CachedBackend(ShardedBackend.from_dense(idx, n_shards=2))),
}


@pytest.fixture(scope="module")
def nested_backends():
    index, q = _repeated_row_index()
    built = {name: make(index) for name, make in NESTED.items()}
    yield built, q
    for b in built.values():
        inner = b
        while inner is not None:
            if hasattr(inner, "shutdown"):
                inner.shutdown()
            inner = getattr(inner, "inner", None)


@pytest.mark.parametrize("name", list(NESTED))
@pytest.mark.parametrize("pair", PAIRS, ids=[f"k{a}-k{b}" for a, b in PAIRS])
def test_a_declared_backend_nests_its_rows(name, pair, nested_backends):
    built, q = nested_backends
    backend = built[name]
    assert backend.nested_topk is True
    short, deep = pair
    s_short, i_short = (np.asarray(x) for x in backend.search_batch(None, q, short))
    s_deep, i_deep = (np.asarray(x) for x in backend.search_batch(None, q, deep))
    np.testing.assert_array_equal(i_short, i_deep[:, :short])
    np.testing.assert_array_equal(s_short.view(np.int32), s_deep[:, :short].view(np.int32))
    # the rows run through ties: a query that is a corpus row ties with its copies
    assert (s_deep[0, 0] == s_deep[0, 1:]).any()


@pytest.mark.parametrize("pair", PAIRS, ids=[f"k{a}-k{b}" for a, b in PAIRS])
@pytest.mark.parametrize("n", [4 * 128 * 10 + 77, 30_001])
def test_blocked_topk_nests_on_tied_rows(pair, n):
    short, deep = pair
    x = jnp.asarray(_tied_rows((8, n), seed=short + deep + n))
    v_short, i_short = blocked_topk(x, short)
    v_deep, i_deep = blocked_topk(x, deep)
    np.testing.assert_array_equal(np.asarray(i_short), np.asarray(i_deep)[:, :short])
    np.testing.assert_array_equal(np.asarray(v_short).view(np.int32),
                                  np.asarray(v_deep)[:, :short].view(np.int32))


def test_backends_without_the_property_do_not_declare_it():
    engine = build_paper_engine(make_policy("router_default", catalog=make_catalog("extended")))
    for name in ("bm25", "ivf", "hybrid"):
        assert not is_nested(engine.backends[name]), name
    dense = engine.backends["dense"]
    # a fault schedule counts calls, so regrouping would move its faults
    assert not is_nested(FaultyBackend(dense, FaultProfile()))
    assert not ResilientBackend(CachedBackend(FaultyBackend(dense, FaultProfile()))).nested_topk
    assert not ShardedBackend.from_bm25(engine.backends["bm25"], n_shards=2).nested_topk
    assert not ShardedBackend.from_ivf(engine.backends["ivf"], n_shards=2).nested_topk
    assert not is_nested(RemoteBackend)
    assert stages.search_depths(engine) == {"dense": 10}


# --------------------------------------------------------------------------- #
# Forced flips through the engine                                              #
# --------------------------------------------------------------------------- #
class _PerKDense(DenseBackend):
    """The dense backend with the property undeclared: today's per-k path."""

    nested_topk = False


class _PerKDevice(DeviceShardedBackend):
    nested_topk = False


VARIANTS = {
    # name → (nested backend, the same backend on the per-k path)
    "dense": (lambda idx: DenseBackend(idx), lambda idx: _PerKDense(idx)),
    "device-sharded": (lambda idx: DeviceShardedBackend(idx, n_shards=1),
                       lambda idx: _PerKDevice(idx, n_shards=1)),
    "cached": (lambda idx: CachedBackend(DenseBackend(idx)),
               lambda idx: CachedBackend(_PerKDense(idx))),
    "resilient": (lambda idx: ResilientBackend(DenseBackend(idx)),
                  lambda idx: ResilientBackend(_PerKDense(idx))),
}


def _engine(make_dense=None, catalog="paper"):
    engine = build_paper_engine(make_policy("router_default", catalog=make_catalog(catalog)))
    if make_dense is not None:
        engine.backends["dense"] = make_dense(engine.index)
    return engine


def _skew_route_priors(engine, skew):
    """``route`` reads ``engine._priors()`` (no store); finalize reads the
    replay clone's. Skew only the former."""
    real = engine._priors

    def priors(telemetry=None):
        lat, cost, rec = real(telemetry)
        if telemetry is None and lat is not None:
            lat = lat * np.asarray(skew, np.float32)
        return lat, cost, rec

    engine._priors = priors


def _forced(engine, skew, monkeypatch, trace_dir=None):
    """A warm batch, then one whose route-time priors are skewed: the counts
    it added, the replays finalize ran (arguments, whether served, the
    execution) and the engine's records."""
    engine.answer_batch(QUERIES, REFS)
    _skew_route_priors(engine, skew)
    replays = []
    real = stages.execute_one

    def spy(engine_, qid, query, choice, ref, **served):
        sub = real(engine_, qid, query, choice, ref, **served)
        replays.append((qid, query, choice, ref, served.get("row") is not None,
                        sub.executions[0]))
        return sub

    monkeypatch.setattr(stages, "execute_one", spy)
    before = dataclasses.replace(engine.counts)
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        engine.answer_batch(QUERIES, REFS)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        monkeypatch.setattr(stages, "execute_one", real)
    c = engine.counts
    return {
        "replayed": c.replayed - before.replayed,
        "replay_searches": c.replay_searches - before.replay_searches,
        "search_calls": c.search_calls - before.search_calls,
        "replays": replays,
        "csv": engine.telemetry.to_csv(),
        "billed": engine.ledger.total_billed,
    }


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_depth_flips_are_served_from_the_first_pass_row(variant, monkeypatch):
    nested_make, per_k_make = VARIANTS[variant]
    nested = _forced(_engine(nested_make), DEEP, monkeypatch)
    per_k = _forced(_engine(per_k_make), DEEP, monkeypatch)
    assert nested["replayed"] == per_k["replayed"] > 0
    assert nested["replay_searches"] == 0
    assert all(served for *_, served, _ in nested["replays"])
    assert nested["search_calls"] == 1  # one dense group at k = 10
    assert per_k["replay_searches"] == per_k["replayed"]
    assert per_k["search_calls"] == 1 + per_k["replayed"]
    assert nested["csv"] == per_k["csv"]
    assert nested["billed"] == per_k["billed"]


@pytest.mark.parametrize("variant", ["dense", "device-sharded"])
def test_a_served_execution_is_what_a_real_search_gives(variant, monkeypatch):
    nested_make, per_k_make = VARIANTS[variant]
    engine, per_k = _engine(nested_make), _engine(per_k_make)
    forced = _forced(engine, DEEP, monkeypatch)
    assert forced["replays"]
    for qid, query, choice, ref, served, ex in forced["replays"]:
        assert served
        # a search at the deepest k, and one at the bundle's own k
        for owner in (engine, per_k):
            searched = stages.execute_one(owner, qid, query, choice, ref)
            assert searched.search_calls == 1
            assert searched.executions[0] == ex


@pytest.mark.parametrize("variant", ["dense", "device-sharded"])
def test_search_spans_match_search_calls(variant, monkeypatch, tmp_path):
    forced = _forced(_engine(VARIANTS[variant][0]), DEEP, monkeypatch, trace_dir=tmp_path)
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    assert sum(n == "repro.search" for n, _ in spans) == forced["search_calls"] == 1
    replays = [ids for n, ids in spans if n == "repro.replay"]
    assert len(replays) == forced["replayed"] > 0
    assert all(ids["searched"] == 0 for ids in replays)
    # a served replay runs assemble and decode, not retrieve or embed
    assert sum(n == "repro.retrieve" for n, _ in spans) == 1
    assert sum(n == "repro.assemble" for n, _ in spans) == 1 + forced["replayed"]


def test_a_direct_to_dense_flip_searches_once(monkeypatch):
    nested = _forced(_engine(), DIRECT, monkeypatch)
    per_k = _forced(_engine(VARIANTS["dense"][1]), DIRECT, monkeypatch)
    direct = [r for r in nested["replays"] if not r[4]]
    assert direct and len(direct) < nested["replayed"]  # both kinds of flip
    assert nested["replay_searches"] == len(direct)
    # one first-pass group, and one search per searched replay
    assert nested["search_calls"] == 1 + len(direct)
    # the per-k path searches every replay, and each routed depth apart
    assert per_k["replay_searches"] == per_k["replayed"] == nested["replayed"]
    assert per_k["search_calls"] > 1 + per_k["replayed"]
    assert nested["csv"] == per_k["csv"]
    assert nested["billed"] == per_k["billed"]


def _two_depth_hybrid_catalog() -> BundleCatalog:
    paper = _paper_bundles()
    light, heavy = paper[1], paper[3]
    return BundleCatalog(paper + (
        dataclasses.replace(light, name="hybrid_light", backend="hybrid"),
        dataclasses.replace(heavy, name="hybrid_heavy", backend="hybrid"),
    ))


def test_a_non_nested_backend_keeps_per_k_groups(monkeypatch):
    catalog = _two_depth_hybrid_catalog()
    engine = build_paper_engine(make_policy("router_default", catalog=catalog))
    n = len(catalog)
    choices = np.arange(len(QUERIES), dtype=np.int32) % n

    def every_bundle(cplx, **_):
        return choices[: len(cplx)], np.zeros((len(cplx), n))

    monkeypatch.setattr(engine.router, "route_batch_np", every_bundle)
    routed = stages.route(engine, QUERIES, REFS)
    assert sorted(routed.retrieval_plan) == [("dense", 10), ("hybrid", 3), ("hybrid", 10)]
    dense = [i for i, c in enumerate(choices) if catalog[int(c)].backend == "dense"
             and not catalog[int(c)].skip_retrieval]
    assert routed.retrieval_plan[("dense", 10)] == dense
    # each position is assembled at its own bundle's k
    admitted = stages.assemble(engine, stages.retrieve(engine, routed))
    for i, c in enumerate(choices):
        bundle = catalog[int(c)]
        if not bundle.skip_retrieval and admitted.final_bundle[i] == int(c):
            assert len(admitted.passages[i]) == bundle.top_k


def test_the_extended_catalog_serves_the_per_k_records(monkeypatch):
    nested = _engine(catalog="extended")
    per_k = _engine(VARIANTS["dense"][1], catalog="extended")
    for engine in (nested, per_k):
        for _ in range(3):
            engine.answer_batch(QUERIES, REFS)
    assert nested.telemetry.to_csv() == per_k.telemetry.to_csv()
    assert nested.ledger.total_billed == per_k.ledger.total_billed
    assert nested.counts.replayed == per_k.counts.replayed
    assert nested.counts.search_calls_by_backend["dense"] < \
        per_k.counts.search_calls_by_backend["dense"]
    for name in ("bm25", "ivf", "hybrid"):
        assert nested.counts.search_calls_by_backend.get(name) == \
            per_k.counts.search_calls_by_backend.get(name)


@pytest.mark.parametrize("depth, workers, microbatch", [
    (1, 1, 4), (2, 1, 4), (3, 2, 2), (4, 2, 7),
])
def test_pipelines_serve_the_per_k_records(depth, workers, microbatch):
    config = StreamConfig(pipeline_depth=depth, retrieval_workers=workers,
                          microbatch_max=microbatch)
    out = {}
    for name, engine in (("nested", _engine()), ("per_k", _engine(VARIANTS["dense"][1]))):
        engine.answer_batch(QUERIES, REFS)
        result = serve_stream(engine, QUERIES, REFS, config=config)
        assert len(result.responses) == len(QUERIES) and not result.rejections
        out[name] = (engine.telemetry.to_csv(), engine.ledger.total_billed,
                     [r.passages for r in result.responses], result.summary())
    assert out["nested"][:3] == out["per_k"][:3]
    assert out["nested"][3]["retrieve_calls"] <= out["per_k"][3]["retrieve_calls"]


# --------------------------------------------------------------------------- #
# Four virtual devices: the row-sharded device backend                          #
# --------------------------------------------------------------------------- #
def test_four_device_sharded_flips_are_served_from_the_row(tmp_path):
    script = textwrap.dedent("""
        import dataclasses, glob, json, sys
        import numpy as np
        import jax
        from jax.profiler import ProfileData
        from repro.core.policies import make_policy
        from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
        from repro.retrieval import DeviceShardedBackend
        from repro.serving import stages
        from repro.serving.engine import build_paper_engine

        assert len(jax.devices()) == 4
        Q, R = list(BENCHMARK_QUERIES), list(REFERENCE_ANSWERS)
        TRACE = sys.argv[1]

        class PerK(DeviceShardedBackend):
            nested_topk = False

        def forced(cls, trace_dir=None):
            engine = build_paper_engine(make_policy("router_default"))
            engine.backends["dense"] = cls(engine.index, n_shards=4)
            engine.answer_batch(Q, R)
            real = engine._priors

            def priors(telemetry=None):
                lat, cost, rec = real(telemetry)
                if telemetry is None and lat is not None:
                    lat = lat * np.asarray(%r, np.float32)
                return lat, cost, rec

            engine._priors = priors
            replays = []
            execute_one = stages.execute_one

            def spy(engine_, qid, query, choice, ref, **served):
                sub = execute_one(engine_, qid, query, choice, ref, **served)
                replays.append((qid, query, choice, ref, sub.executions[0]))
                return sub

            stages.execute_one = spy
            before = dataclasses.replace(engine.counts)
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
            try:
                engine.answer_batch(Q, R)
            finally:
                if trace_dir:
                    jax.profiler.stop_trace()
                stages.execute_one = execute_one
            c = engine.counts
            out = {"replayed": c.replayed - before.replayed,
                   "replay_searches": c.replay_searches - before.replay_searches,
                   "search_calls": c.search_calls - before.search_calls,
                   "dispatch_searches": engine.backends["dense"].counters.searches,
                   "csv": engine.telemetry.to_csv(),
                   # each replay's execution against one with a real search
                   "as_searched": [
                       execute_one(engine, qid, q, ch, r).executions[0] == ex
                       for qid, q, ch, r, ex in replays]}
            if trace_dir:
                (path,) = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")
                names = [e.name for plane in ProfileData.from_file(path).planes
                         if plane.name.startswith("/host:")
                         for line in plane.lines for e in line.events]
                out["search_spans"] = names.count("repro.search")
            return out

        print(json.dumps({"nested": forced(DeviceShardedBackend, TRACE),
                          "per_k": forced(PerK)}))
    """ % (DEEP,))
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", script, str(tmp_path)], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    nested, per_k = out["nested"], out["per_k"]
    assert nested["replayed"] == per_k["replayed"] > 0
    assert nested["replay_searches"] == 0
    assert nested["search_spans"] == nested["search_calls"] == 1
    assert per_k["replay_searches"] == per_k["replayed"]
    assert nested["dispatch_searches"] < per_k["dispatch_searches"]
    assert nested["as_searched"] and all(nested["as_searched"])
    assert nested["csv"] == per_k["csv"]
