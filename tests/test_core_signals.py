"""Unit + property tests for query signals and complexity (paper §V.A)."""

import hypothesis
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.signals import (
    batch_complexity,
    complexity,
    complexity_from_signals,
    extract_signal_matrix,
    extract_signals,
)


def test_extract_signals_basic():
    s = extract_signals("What is RAG?")
    assert s.word_count == 3
    assert s.char_len == len("What is RAG?")
    assert s.cue_count == 1  # "what"


def test_extract_signals_multiple_cues():
    s = extract_signals("Explain how telemetry refines routing estimates with concrete steps.")
    assert s.cue_count == 2  # explain, how
    assert s.word_count == 9


def test_paper_formula_exact():
    # c = clip(0.6 * 3/20 + 0.4 * 1/3, 0, 1) = 0.09 + 0.1333 = 0.22333
    c = complexity("What is RAG?")
    assert c == pytest.approx(0.6 * 3 / 20 + 0.4 * 1 / 3, abs=1e-6)


def test_complexity_clipped_to_unit_interval():
    # 60-word query with many cues must clip at 1.0.
    q = " ".join(["what", "why", "how"] * 20) + "?"
    assert complexity(q) == 1.0


def test_empty_query():
    s = extract_signals("")
    assert s.word_count == 0 and s.cue_count == 0
    assert complexity("") == 0.0


def test_batch_matches_scalar():
    qs = ["What is RAG?", "Why is token cost important?", "", "Define utility-based routing."]
    mat = extract_signal_matrix(qs)
    batch = np.asarray(batch_complexity(mat))
    for i, q in enumerate(qs):
        assert batch[i] == pytest.approx(complexity(q), abs=1e-6)


def test_empty_batch():
    assert extract_signal_matrix([]).shape == (0, 3)
    assert batch_complexity(extract_signal_matrix([])).shape == (0,)


@hypothesis.given(st.text(max_size=300))
@hypothesis.settings(max_examples=50, deadline=None)
def test_complexity_always_in_unit_interval(q):
    c = complexity(q)
    assert 0.0 <= c <= 1.0


@hypothesis.given(
    st.integers(min_value=0, max_value=500), st.integers(min_value=0, max_value=50)
)
@hypothesis.settings(max_examples=50, deadline=None)
def test_complexity_monotone_in_signals(words, cues):
    c0 = float(complexity_from_signals(words, cues))
    c_w = float(complexity_from_signals(words + 1, cues))
    c_k = float(complexity_from_signals(words, cues + 1))
    assert c_w >= c0 - 1e-7 and c_k >= c0 - 1e-7


def test_signals_deterministic():
    q = "Contrast direct LLM answers with retrieval-grounded answers for policy questions."
    assert extract_signals(q) == extract_signals(q)


def test_case_insensitive_cues():
    assert extract_signals("WHAT is this?").cue_count == extract_signals("what is this?").cue_count
