"""Tests for Eq. 1 utilities, routing, policies and guardrails."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bundles import DEFAULT_CATALOG
from repro.core.guardrails import GuardrailConfig, Guardrails
from repro.core.policies import POLICIES, make_policy
from repro.core.router import FixedRouter, Router, RouterConfig
from repro.core.utility import (
    DEFAULT_WEIGHTS,
    RealizedNormalization,
    UtilityWeights,
    minmax_normalize,
    modulated_quality,
    realized_utility,
    selection_utilities,
)

ARRS = DEFAULT_CATALOG.as_arrays()


# --------------------------------------------------------------------------- #
# Utility math                                                                 #
# --------------------------------------------------------------------------- #
def test_minmax_normalize_unit_range():
    x = jnp.array([8.0, 45.0, 60.0, 95.0])
    n = np.asarray(minmax_normalize(x))
    assert n.min() == 0.0 and n.max() == 1.0
    assert n[0] == 0.0 and n[3] == 1.0
    # direct check of one interior point: (45-8)/87
    assert n[1] == pytest.approx((45 - 8) / 87, abs=1e-6)


def test_minmax_normalize_constant_row():
    n = np.asarray(minmax_normalize(jnp.array([5.0, 5.0, 5.0])))
    np.testing.assert_allclose(n, 0.0)


def test_eq1_hand_computed():
    """U_direct at c=c0 (no modulation): 0.6*0.52 - 0 - 0 = 0.312."""
    c0 = 0.30
    u = selection_utilities(ARRS, jnp.array([c0]), gamma=1.2, c0=c0)
    assert np.asarray(u)[0, 0] == pytest.approx(0.6 * 0.52, abs=1e-5)
    # heavy at c0: 0.6*0.82 - 0.2*1 - 0.2*1 = 0.092
    assert np.asarray(u)[0, 3] == pytest.approx(0.6 * 0.82 - 0.4, abs=1e-5)


def test_modulated_quality_direction():
    """Complex queries must inflate deep-bundle quality, deflate shallow."""
    q = modulated_quality(
        ARRS["quality_prior"], ARRS["depth_affinity"], jnp.array([0.0, 1.0]),
        gamma=1.0, c0=0.3, global_decay=0.0,
    )
    q = np.asarray(q)
    # direct_llm: higher at c=0 than c=1; heavy_rag: the reverse.
    assert q[0, 0] > q[1, 0]
    assert q[0, 3] < q[1, 3]
    assert (q >= 0).all()  # floored below; unbounded above (see utility.py)


def test_global_decay_never_changes_argmax():
    """The bundle-uniform decay must not affect routing decisions."""
    c = jnp.linspace(0.0, 1.0, 101)
    u0 = selection_utilities(ARRS, c, global_decay=0.0)
    u2 = selection_utilities(ARRS, c, global_decay=2.5)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(u0, -1)), np.asarray(jnp.argmax(u2, -1))
    )
    # and utilities at high complexity are uniformly lower (Fig. 6 skew)
    assert float(u2[-1].max()) < float(u0[-1].max())


def test_zero_weights_make_constant_utilities():
    w = UtilityWeights(quality=0.0, latency=0.0, cost=0.0)
    u = np.asarray(selection_utilities(ARRS, jnp.array([0.2, 0.8]), weights=w))
    np.testing.assert_allclose(u, 0.0, atol=1e-7)


def test_realized_utility_negative_for_slow_expensive():
    # Paper Appendix H: a 4051 ms direct_llm query has negative realized U.
    ru = realized_utility(
        jnp.array([0.55]), jnp.array([4051.1]), jnp.array([185.0]),
        norm=RealizedNormalization(latency_ref_ms=1000.0, cost_ref_tokens=100.0),
    )
    assert float(ru[0]) < 0.0


def test_realized_utility_monotonicity():
    base = float(realized_utility(jnp.array([0.8]), jnp.array([1000.0]), jnp.array([200.0]))[0])
    slower = float(realized_utility(jnp.array([0.8]), jnp.array([2000.0]), jnp.array([200.0]))[0])
    pricier = float(realized_utility(jnp.array([0.8]), jnp.array([1000.0]), jnp.array([400.0]))[0])
    better = float(realized_utility(jnp.array([0.9]), jnp.array([1000.0]), jnp.array([200.0]))[0])
    assert slower < base and pricier < base and better > base


# --------------------------------------------------------------------------- #
# Router                                                                       #
# --------------------------------------------------------------------------- #
def test_router_simple_query_goes_shallow_complex_goes_deep():
    r = Router()
    simple = r.route("What is RAG?")[0]
    complex_ = r.route(
        "Compare and contrast how large top-k retrieval, reranking stages, and hybrid "
        "dense-sparse fusion interact to determine end-to-end latency and what operational "
        "metrics a team should report when deploying such systems at scale."
    )[0]
    assert simple.bundle.top_k < complex_.bundle.top_k


def test_router_batch_matches_single():
    r = Router()
    qs = ["What is RAG?", "Why is token cost important?", "Describe a municipal RAG use case."]
    batch = r.route(qs)
    for q, d in zip(qs, batch):
        single = r.route(q)[0]
        assert single.bundle.name == d.bundle.name
        assert single.selection_utility == pytest.approx(d.selection_utility, abs=1e-6)


def test_route_batch_arrays_jit_compatible():
    r = Router()
    f = jax.jit(lambda c: r.route_batch_arrays(c))
    idx, util = f(jnp.array([0.1, 0.5, 0.9]))
    assert idx.shape == (3,) and util.shape == (3, 4)
    assert idx.dtype == jnp.int32


def test_selection_is_argmax_of_utilities():
    r = Router()
    for d in r.route(["What is RAG?", "Explain when reranking is worth the extra latency."]):
        assert d.selection_utility == pytest.approx(max(d.utilities.values()), abs=1e-7)


def test_epsilon_greedy_explores():
    r = Router(config=RouterConfig(epsilon=1.0))
    key = jax.random.PRNGKey(0)
    idx, _ = r.route_batch_arrays(jnp.full((512,), 0.2), key=key)
    # with eps=1 every pick is uniform random → all bundles appear
    assert len(np.unique(np.asarray(idx))) == 4


def test_epsilon_requires_key():
    r = Router(config=RouterConfig(epsilon=0.5))
    with pytest.raises(ValueError):
        r.route_batch_arrays(jnp.array([0.5]))


def test_epsilon_zero_is_deterministic():
    r = Router()
    c = jnp.linspace(0, 1, 64)
    i1, _ = r.route_batch_arrays(c)
    i2, _ = r.route_batch_arrays(c)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_telemetry_overrides_shift_selection():
    r = Router()
    c = jnp.array([0.35])
    base_idx, _ = r.route_batch_arrays(c)
    # Make the currently-selected bundle look catastrophically expensive.
    cost = np.array([190.0, 230.0, 260.0, 360.0], np.float32)
    cost[int(base_idx[0])] = 10_000.0
    new_idx, _ = r.route_batch_arrays(c, cost_override=jnp.asarray(cost))
    assert int(new_idx[0]) != int(base_idx[0])


@hypothesis.given(st.floats(min_value=0.0, max_value=1.0))
@hypothesis.settings(max_examples=40, deadline=None)
def test_router_total_order_property(c):
    """At any complexity the argmax utility dominates all bundles."""
    r = Router()
    idx, util = r.route_batch_arrays(jnp.array([c]))
    u = np.asarray(util)[0]
    assert u[int(idx[0])] == pytest.approx(u.max(), abs=1e-7)


# --------------------------------------------------------------------------- #
# Policies                                                                     #
# --------------------------------------------------------------------------- #
def test_policy_registry_has_paper_policies():
    assert set(POLICIES) == {
        "router_default",
        "router_latency_sensitive",
        "router_cost_sensitive",
        "fixed_direct",
        "fixed_light",
        "fixed_medium",
        "fixed_heavy",
    }


def test_fixed_policies_always_pick_their_bundle():
    for name, bundle in [
        ("fixed_direct", "direct_llm"),
        ("fixed_light", "light_rag"),
        ("fixed_medium", "medium_rag"),
        ("fixed_heavy", "heavy_rag"),
    ]:
        p = make_policy(name)
        idx, _ = p.route_batch_arrays(jnp.linspace(0, 1, 16))
        assert (np.asarray(idx) == DEFAULT_CATALOG.index_of(bundle)).all()


def test_latency_sensitive_prefers_shallower():
    """Paper §VII.F: w_L=0.5 shifts mass toward direct/light."""
    c = jnp.linspace(0.0, 1.0, 101)
    default_idx, _ = make_policy("router_default").route_batch_arrays(c)
    lat_idx, _ = make_policy("router_latency_sensitive").route_batch_arrays(c)
    # mean selected depth must not increase
    depth = np.asarray(DEFAULT_CATALOG.as_arrays()["top_k"])
    assert depth[np.asarray(lat_idx)].mean() <= depth[np.asarray(default_idx)].mean()


def test_cost_sensitive_suppresses_heavy():
    c = jnp.linspace(0.0, 1.0, 101)
    default_idx, _ = make_policy("router_default").route_batch_arrays(c)
    cost_idx, _ = make_policy("router_cost_sensitive").route_batch_arrays(c)
    heavy = DEFAULT_CATALOG.index_of("heavy_rag")
    assert (np.asarray(cost_idx) == heavy).sum() <= (np.asarray(default_idx) == heavy).sum()


def test_unknown_policy_raises():
    with pytest.raises(KeyError):
        make_policy("router_yolo")


# --------------------------------------------------------------------------- #
# Guardrails                                                                   #
# --------------------------------------------------------------------------- #
def test_low_confidence_fallback():
    g = Guardrails(DEFAULT_CATALOG, GuardrailConfig(min_retrieval_confidence=0.6))
    heavy = DEFAULT_CATALOG.index_of("heavy_rag")
    out = g.post_retrieval(heavy, retrieval_confidence=0.3)
    assert out.demoted and out.bundle_index == DEFAULT_CATALOG.index_of("direct_llm")
    ok = g.post_retrieval(heavy, retrieval_confidence=0.9)
    assert not ok.demoted and ok.bundle_index == heavy


def test_confidence_fallback_ignores_direct():
    g = Guardrails(DEFAULT_CATALOG, GuardrailConfig(min_retrieval_confidence=0.9))
    direct = DEFAULT_CATALOG.index_of("direct_llm")
    assert not g.post_retrieval(direct, retrieval_confidence=0.0).demoted


def test_cost_ceiling_demotes_to_deepest_affordable():
    g = Guardrails(DEFAULT_CATALOG, GuardrailConfig(max_cost_tokens=280))
    heavy = DEFAULT_CATALOG.index_of("heavy_rag")
    out = g.pre_execution(heavy)
    assert out.demoted and out.reason == "cost_ceiling"
    assert DEFAULT_CATALOG[out.bundle_index].name == "medium_rag"


def test_context_clamp():
    g = Guardrails(DEFAULT_CATALOG, GuardrailConfig(max_context_tokens=100))
    assert g.clamp_context(500) == 100
    assert g.clamp_context(50) == 50
    g2 = Guardrails(DEFAULT_CATALOG)
    assert g2.clamp_context(500) == 500
