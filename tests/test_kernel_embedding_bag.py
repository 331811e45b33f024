"""EmbeddingBag kernel vs oracle: sweeps, unsorted input, empty bags."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def _table(v, d, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (v, d)).astype(dtype)


SWEEP = [
    # (vocab, dim, n_lookups, n_bags, dtype)
    (64, 8, 16, 4, jnp.float32),
    (1024, 128, 64, 16, jnp.float32),
    (512, 32, 100, 10, jnp.bfloat16),
    (128, 16, 1, 1, jnp.float32),  # single lookup
]


@pytest.mark.parametrize("v,d,nl,nb,dtype", SWEEP)
def test_embedding_bag_matches_ref_sorted(v, d, nl, nb, dtype):
    table = _table(v, d, dtype=dtype)
    rng = np.random.default_rng(nl)
    seg = np.sort(rng.integers(0, nb, nl)).astype(np.int32)
    idx = rng.integers(0, v, nl).astype(np.int32)
    out = embedding_bag_pallas(table, jnp.asarray(idx), jnp.asarray(seg), nb, interpret=True)
    # oracle in f32 (the kernel accumulates f32 regardless of table dtype)
    ref = embedding_bag_ref(table.astype(jnp.float32), jnp.asarray(idx), jnp.asarray(seg), nb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_empty_bags_are_zero():
    table = _table(32, 8)
    # bags 0 and 3 get lookups; 1, 2 empty
    idx = jnp.array([5, 6, 7], jnp.int32)
    seg = jnp.array([0, 0, 3], jnp.int32)
    out = embedding_bag(table, idx, seg, 4, use_pallas=True, interpret=True)
    ref = embedding_bag_ref(table, idx, seg, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]), 0.0)
    np.testing.assert_allclose(np.asarray(out[2]), 0.0)


def test_unsorted_segments_handled_by_wrapper():
    table = _table(64, 16, seed=3)
    rng = np.random.default_rng(0)
    seg = rng.integers(0, 8, 40).astype(np.int32)  # unsorted
    idx = rng.integers(0, 64, 40).astype(np.int32)
    out = embedding_bag(table, jnp.asarray(idx), jnp.asarray(seg), 8, use_pallas=True, interpret=True)
    ref = embedding_bag_ref(table, jnp.asarray(idx), jnp.asarray(seg), 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_repeated_index_in_same_bag():
    table = _table(16, 4, seed=4)
    idx = jnp.array([3, 3, 3], jnp.int32)
    seg = jnp.array([0, 0, 0], jnp.int32)
    out = embedding_bag_pallas(table, idx, seg, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), 3 * np.asarray(table[3]), rtol=1e-5)


def test_matches_recsys_module_embedding_bag():
    """kernels path must agree with models.recsys.embedding_bag (sum mode)."""
    from repro.models.recsys import embedding_bag as model_bag

    table = _table(256, 32, seed=5)
    rng = np.random.default_rng(1)
    seg = np.sort(rng.integers(0, 12, 50)).astype(np.int32)
    idx = rng.integers(0, 256, 50).astype(np.int32)
    k_out = embedding_bag(table, jnp.asarray(idx), jnp.asarray(seg), 12, use_pallas=True, interpret=True)
    m_out = model_bag(table, jnp.asarray(idx), jnp.asarray(seg), 12, mode="sum")
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(m_out), rtol=1e-5, atol=1e-6)


@hypothesis.given(st.integers(min_value=1, max_value=60), st.integers(min_value=1, max_value=8), st.integers(0, 5000))
@hypothesis.settings(max_examples=15, deadline=None)
def test_embedding_bag_property(nl, nb, seed):
    table = _table(32, 8, seed=seed % 7)
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, nb, nl)).astype(np.int32)
    idx = rng.integers(0, 32, nl).astype(np.int32)
    out = embedding_bag(table, jnp.asarray(idx), jnp.asarray(seg), nb, use_pallas=True, interpret=True, assume_sorted=True)
    ref = embedding_bag_ref(table, jnp.asarray(idx), jnp.asarray(seg), nb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
