"""What the served device programs may and may not carry, and which
process paths refuse on an accelerator.

* The lowered search programs (dense, BM25, IVF) and the decode step take
  their corpus and parameters as arguments: a captured array is compiled
  into the program as a constant, which at a real corpus size passes the
  2 GB serialized-program limit and holds a second copy on the device.
* On an accelerator, the paths that spawn jax-building children refuse
  before spawning, and ``"auto"`` never resolves to them. The tests steer
  the backend query itself; nothing here needs a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.retrieval import (
    BM25Index,
    DenseIndex,
    IVFIndex,
    Passage,
    ShardedBackend,
    resolve_execution,
    synthetic_dense_index,
)
from repro.retrieval.index import Q_BLOCK
from repro.runtime import AcceleratorHeldError, enable_compilation_cache

# a lowered program's text without any corpus-sized constant; a 10⁵-row
# constant alone prints to tens of megabytes
TEXT_BOUND = 200_000


@pytest.fixture(scope="module")
def corpus() -> DenseIndex:
    return synthetic_dense_index(100_000, 64, seed=3, with_passages=False)


@pytest.mark.parametrize("scorer", ["blocked", "pallas"])
def test_dense_search_program_embeds_no_corpus(corpus, scorer):
    fn, placed = corpus._search_fn(5, scorer, interpret=True)
    q = jnp.zeros((Q_BLOCK, corpus.dim), jnp.float32)
    assert len(fn.lower(placed, q).as_text()) < TEXT_BOUND


def test_sharded_search_program_embeds_no_corpus(corpus):
    from repro.distributed import corpus_mesh

    fn, _ = corpus.sharded_search_fn(corpus_mesh(1), 5, ("data",))
    q = jnp.zeros((Q_BLOCK, corpus.dim), jnp.float32)
    assert len(fn.lower(jnp.asarray(corpus.embeddings), q).as_text()) < TEXT_BOUND


def test_bm25_search_program_embeds_no_postings():
    rng = np.random.default_rng(0)
    vocab = [f"term{i}" for i in range(500)]
    passages = [
        Passage(i, " ".join(rng.choice(vocab, size=12))) for i in range(20_000)
    ]
    bm = BM25Index(passages)
    assert bm.post_contrib.size > 100_000
    fn = bm._search_fn(5, 1024)
    sel = jnp.zeros((1024,), jnp.int32)
    assert len(fn.lower(bm.post_contrib, sel, sel).as_text()) < TEXT_BOUND


@pytest.mark.parametrize("impl", ["bag", "padded"])
def test_ivf_search_program_embeds_no_index(corpus, impl):
    ivf = IVFIndex.build(
        corpus.embeddings, n_clusters=16, n_iters=2, key=jax.random.PRNGKey(0)
    )
    fn, arrays = ivf._search_fn(5, 2, impl)
    q = jnp.zeros((Q_BLOCK, corpus.dim), jnp.float32)
    assert len(fn.lower(*arrays, q).as_text()) < TEXT_BOUND


def test_decode_step_embeds_no_parameters():
    from repro.models.transformer import TransformerConfig, init_params, param_count
    from repro.serving.generator import TransformerSlotDecoder

    cfg = TransformerConfig(
        name="decode_text_bound", n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=4, d_ff=512, vocab=4096, compute_dtype=jnp.float32,
        max_seq_len=64,
    )
    assert param_count(cfg) > 1_000_000
    decoder = TransformerSlotDecoder(
        init_params(jax.random.PRNGKey(0), cfg), cfg, n_slots=4
    )
    lowered = decoder._step.lower(decoder.params, decoder.cache, decoder.tokens)
    assert len(lowered.as_text()) < TEXT_BOUND


# --------------------------------------------------------------------------- #
# One process per chip                                                         #
# --------------------------------------------------------------------------- #
@pytest.fixture
def on_tpu(monkeypatch):
    """The backend query answers as it does on a TPU host."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _tiny_index() -> DenseIndex:
    return synthetic_dense_index(40, 8, seed=1)


def test_process_shard_execution_refuses_on_accelerator(on_tpu):
    with pytest.raises(AcceleratorHeldError, match="holds the tpu device"):
        ShardedBackend.from_dense(_tiny_index(), n_shards=2, execution="process")


def test_process_stage_executor_refuses_before_spawning(on_tpu, monkeypatch):
    from repro.serving import procpool

    spawned = []
    monkeypatch.setattr(procpool, "ProcessPoolExecutor", lambda *a, **k: spawned.append(1))
    with pytest.raises(AcceleratorHeldError, match="executor='process'"):
        procpool.ProcessStageExecutor(functools.partial(int, 0))
    assert spawned == []


def test_auto_never_resolves_to_process_on_accelerator(monkeypatch):
    import repro.retrieval.sharded as sharded

    monkeypatch.setattr(sharded.os, "cpu_count", lambda: 8)
    assert resolve_execution("auto", n_shards=4) == "process"  # CPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_execution("auto", n_shards=4) == "threads"
    backend = ShardedBackend.from_dense(_tiny_index(), n_shards=2, execution="auto")
    assert backend.execution == "threads"


def test_compilation_cache_dir_is_fixed_unless_the_environment_sets_it(monkeypatch):
    from repro import runtime

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compilation_cache()
    assert path == str(runtime.CHECKOUT_ROOT / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compilation_cache() == "/elsewhere/cache"
    assert len(updates) == 1  # nothing set in code when the environment says
