"""Device-execution sharded retrieval: bit-identity, counters, guardrails.

Pins the tentpole contracts of the ``execution="device"`` path
(:class:`~repro.retrieval.sharded.DeviceShardedBackend`):

1. **Bit-identity** — scores AND ids exactly equal the unsharded
   :class:`DenseIndex` / :class:`DenseBackend` and the threads-execution
   :class:`ShardedBackend`, including tie-heavy score distributions,
   non-divisible shard sizes, ``k`` ≥ corpus, and the pallas scorer's
   traced residue mask. S=1 runs in-process on any host; multi-shard
   identity runs in a 4-device subprocess (slow tier) because jax fixes the
   device count at first import.
2. **Deterministic counters** — per-shard search executions and merge
   invocations are pure functions of (batch shape, ``q_block``, S): the
   quantities the CI scaling-sweep gate pins.
3. **API guardrails** — device execution rejects threads-only knobs, the
   mesh must match the shard count, and ``corpus_mesh`` explains the
   single-device remediation instead of failing deep inside jax.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hypothesis
import hypothesis.strategies as st

from repro.distributed import corpus_mesh
from repro.retrieval import (
    DenseBackend,
    DenseIndex,
    DeviceShardedBackend,
    ShardedBackend,
)
from repro.retrieval.chunking import Passage


def _tie_corpus(n: int = 37, d: int = 32, seed: int = 0, vocab: int = 7) -> DenseIndex:
    """Corpus whose rows repeat a tiny vocabulary of unit vectors, so every
    search is tie-heavy and merge order is load-bearing."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(vocab, d)).astype(np.float32)
    base /= np.linalg.norm(base, axis=-1, keepdims=True)
    emb = base[rng.integers(0, vocab, size=n)]
    passages = [Passage(i, f"passage {i}") for i in range(n)]
    return DenseIndex(jnp.asarray(emb), passages, assume_normalized=True)


def _queries(nq: int = 6, d: int = 32, seed: int = 1) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))


def _assert_identical(backend, oracle, q, k):
    s, i = backend.search_batch(None, q, k)
    es, ei = oracle.search_batch(None, q, k)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(es, np.float32))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei, np.int32))
    assert np.asarray(s).dtype == np.float32 and np.asarray(i).dtype == np.int32


# --------------------------------------------------------------------------- #
# In-process: S=1 device identity (runs on any host)                           #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("k", [1, 5, 13, 37, 50])
def test_device_s1_identity_tie_heavy(k):
    idx = _tie_corpus()
    dev = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    assert isinstance(dev, DeviceShardedBackend)
    assert dev.execution == "device" and dev.n_shards == 1
    _assert_identical(dev, DenseBackend(idx), _queries(), k)


def test_device_s1_identity_pallas_interpret():
    # the pallas scorer's masked-kernel path, interpret-mode on CPU
    idx = _tie_corpus(n=24)
    dev = ShardedBackend.from_dense(
        idx, n_shards=1, execution="device", scorer="pallas", interpret=True
    )
    _assert_identical(dev, DenseBackend(idx), _queries(nq=3), 5)


def test_device_counters_and_chunking():
    idx = _tie_corpus()
    dev = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    q = _queries(nq=20)  # Q_BLOCK=8 → 3 chunks (8, 8, 4-padded)
    dev.search_batch(None, q, 10)
    assert dev.counters.as_dict() == {
        "searches": 1, "shard_searches": 3, "merges": 3
    }
    # widening q_block to cover the batch collapses dispatch to one chunk
    wide = ShardedBackend.from_dense(
        idx, n_shards=1, execution="device", q_block=32
    )
    wide.search_batch(None, q, 10)
    assert wide.counters.as_dict() == {
        "searches": 1, "shard_searches": 1, "merges": 1
    }
    _assert_identical(wide, dev, q, 10)  # chunk width never moves a result


def test_device_backends_over_one_index_share_its_placed_corpus_and_programs():
    """The placed shards and the compiled programs are the index's: a second
    backend over the same index and mesh (as a second engine builds) places
    and compiles nothing, and its answers are the first's."""
    idx = _tie_corpus()
    first = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    q = _queries(nq=10)
    first.search_batch(None, q, 5)
    first.search_batch(None, q, 13)
    assert len(idx._sharded_corpus) == 1  # one block width for both k
    assert len(idx._sharded_fn_cache) == 2
    (corpus,) = idx._sharded_corpus.values()
    programs = dict(idx._sharded_fn_cache)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    second = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    assert second is not first
    for k in (5, 13):
        _assert_identical(second, first, q, k)
    assert compiles == []
    assert idx._sharded_fn_cache == programs
    assert next(iter(idx._sharded_corpus.values())) is corpus
    # another index places and compiles its own
    other = _tie_corpus(seed=3)
    ShardedBackend.from_dense(other, n_shards=1, execution="device").search_batch(None, q, 5)
    assert len(other._sharded_corpus) == 1 and idx._sharded_fn_cache == programs


def test_device_empty_batch_and_payloads():
    idx = _tie_corpus()
    dev = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    s, i = dev.search_batch(None, _queries(nq=0), 4)
    assert s.shape == (0, 4) and i.shape == (0, 4)
    assert dev.counters.searches == 0  # nothing dispatched
    texts = [p.text for p in dev.get_passages([3, 0])]
    assert texts == ["passage 3", "passage 0"]
    dev.shutdown()  # no-op, must not raise


def test_device_api_guardrails():
    idx = _tie_corpus()
    with pytest.raises(ValueError, match="threads-execution knob"):
        ShardedBackend.from_dense(idx, n_shards=1, execution="device", workers=2)
    with pytest.raises(ValueError, match="device-execution knob"):
        ShardedBackend.from_dense(idx, n_shards=2, execution="threads", q_block=16)
    with pytest.raises(ValueError, match="q_block"):
        DeviceShardedBackend(idx, n_shards=1, q_block=0)
    with pytest.raises(ValueError, match="unknown execution"):
        ShardedBackend.from_dense(idx, n_shards=1, execution="tpu")
    dev = ShardedBackend.from_dense(idx, n_shards=1, execution="device")
    with pytest.raises(AttributeError, match="mesh-resident|no host-side"):
        _ = dev.shards
    with pytest.raises(ValueError, match="requires query_vecs"):
        dev.search_batch(["q"], None, 3)


def test_corpus_mesh_explains_single_device_remediation():
    n = jax.device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        corpus_mesh(n + 1)
    with pytest.raises(ValueError, match="n_shards"):
        corpus_mesh(0)


def test_device_mesh_size_must_match_shards():
    idx = _tie_corpus()
    mesh = corpus_mesh(1)
    if jax.device_count() >= 2:
        with pytest.raises(ValueError, match="mesh has 1 devices"):
            DeviceShardedBackend(idx, n_shards=2, mesh=mesh)
    else:
        # single-device host: the default-mesh path raises the remediation
        with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
            DeviceShardedBackend(idx, n_shards=2)


# --------------------------------------------------------------------------- #
# Property test: triple identity across shard counts (needs >= 4 devices)      #
# --------------------------------------------------------------------------- #
@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="device-path property sweep needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)
@hypothesis.given(
    n=st.integers(5, 48),
    n_shards=st.integers(1, 4),
    k=st.integers(1, 60),
    vocab=st.integers(2, 6),
    seed=st.integers(0, 2**16),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_device_identity_property(n, n_shards, k, vocab, seed):
    """Device path == threads path == unsharded DenseIndex, bit for bit,
    across non-divisible sizes, tie-heavy vocabularies, and k ≥ corpus."""
    if n_shards > n:
        n_shards = n  # shard_bounds rejects S > n for every execution alike
    idx = _tie_corpus(n=n, d=16, seed=seed, vocab=vocab)
    q = _queries(nq=5, d=16, seed=seed + 1)
    dense = DenseBackend(idx)
    dev = ShardedBackend.from_dense(idx, n_shards=n_shards, execution="device")
    thr = ShardedBackend.from_dense(idx, n_shards=n_shards, execution="threads")
    _assert_identical(dev, dense, q, k)
    _assert_identical(dev, thr, q, k)


# --------------------------------------------------------------------------- #
# Subprocess sweep: true multi-shard identity on 4 forced devices (slow)       #
# --------------------------------------------------------------------------- #
# JAX_PLATFORMS=cpu matters: without it jax probes for a TPU backend first
# and a TPU-less container burns ~8 minutes in metadata-fetch retries
# before falling back to CPU.
ENV4 = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def _run4(body: str) -> str:
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=900, env=ENV4)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout[-1500:]}\nSTDERR:\n{proc.stderr[-3000:]}"
    )
    return proc.stdout


@pytest.mark.slow
def test_device_identity_sweep_4_devices():
    _run4("""
        import numpy as np
        import jax.numpy as jnp
        from repro.retrieval import DenseBackend, DenseIndex, ShardedBackend
        from repro.retrieval.chunking import Passage

        def tie_corpus(n, d, seed=0, vocab=5):
            rng = np.random.default_rng(seed)
            base = rng.normal(size=(vocab, d)).astype(np.float32)
            base /= np.linalg.norm(base, axis=-1, keepdims=True)
            emb = base[rng.integers(0, vocab, size=n)]
            return DenseIndex(jnp.asarray(emb), None, assume_normalized=True)

        rng = np.random.default_rng(1)
        for (n, d) in ((9, 16), (37, 32), (200, 64)):
            idx = tie_corpus(n, d)
            dense = DenseBackend(idx)
            q = jnp.asarray(rng.normal(size=(5, d)).astype(np.float32))
            for S in (2, 3, 4):
                if S > n:
                    continue
                dev = ShardedBackend.from_dense(idx, n_shards=S, execution="device")
                thr = ShardedBackend.from_dense(idx, n_shards=S, execution="threads")
                for k in (1, 5, 13, n, n + 20):
                    es, ei = dense.search_batch(None, q, k)
                    for arm in (dev, thr):
                        s, i = arm.search_batch(None, q, k)
                        assert np.array_equal(np.asarray(s), np.asarray(es, np.float32)), (n, S, k, arm.execution)
                        assert np.array_equal(np.asarray(i), np.asarray(ei, np.int32)), (n, S, k, arm.execution)
            # pallas scorer with the traced residue mask, non-divisible S
            dev_p = ShardedBackend.from_dense(
                idx, n_shards=3, execution="device", scorer="pallas", interpret=True
            ) if n >= 3 else None
            if dev_p is not None:
                s, i = dev_p.search_batch(None, q, 7)
                es, ei = dense.search_batch(None, q, 7)
                assert np.array_equal(np.asarray(s), np.asarray(es, np.float32))
                assert np.array_equal(np.asarray(i), np.asarray(ei, np.int32))
        print("device == threads == unsharded across the full sweep")
    """)


@pytest.mark.slow
def test_device_identity_property_under_4_devices():
    """Run the in-file hypothesis property test where it does not skip: a
    pytest subprocess with 4 forced host devices."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q",
         "tests/test_sharded_device.py::test_device_identity_property",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=900, env=ENV4,
    )
    assert proc.returncode in (0, 5), (  # 5 = all collected tests skipped
        f"STDOUT:\n{proc.stdout[-1500:]}\nSTDERR:\n{proc.stderr[-3000:]}"
    )


@pytest.mark.slow
def test_million_doc_synthetic_smoke_4_devices():
    """The config-flagged synthetic corpus path at reduced scale: seeded
    build, S=4 device search, identity + counters (the benchmark sweep's
    cell shape, 10^4 rows so the slow tier stays minutes not hours)."""
    _run4("""
        import numpy as np
        import jax.numpy as jnp
        from repro.retrieval import DenseBackend, ShardedBackend, synthetic_dense_index

        idx = synthetic_dense_index(10_000, 32, seed=7, with_passages=False)
        idx2 = synthetic_dense_index(10_000, 32, seed=7, with_passages=False)
        assert np.array_equal(np.asarray(idx.embeddings), np.asarray(idx2.embeddings))
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
        dev = ShardedBackend.from_dense(idx, n_shards=4, execution="device", q_block=32)
        s, i = dev.search_batch(None, q, 10)
        es, ei = DenseBackend(idx).search_batch(None, q, 10)
        assert np.array_equal(np.asarray(s), np.asarray(es, np.float32))
        assert np.array_equal(np.asarray(i), np.asarray(ei, np.int32))
        assert dev.counters.as_dict() == {"searches": 1, "shard_searches": 4, "merges": 1}
        print("synthetic 10k-doc S=4 device cell identical")
    """)
