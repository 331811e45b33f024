"""The general traffic generator: deterministic in the seed, the source's
mean query length, distinct queries, the same work under every seed."""

import json
import pathlib

import numpy as np
import pytest

from chipbench.traffic import QueryGenerator, stratified_lengths, word_count

MIXES = pathlib.Path(__file__).resolve().parents[1] / "traffic"
# the mean query length in words of each mix's source (BEIR, arXiv:2104.08663,
# Table 1); a batch of generated queries must hit it within a tenth of a word
SOURCE_MEAN_WORDS = {
    "nq-questions-batch": 9.16,
    "hotpot-questions-batch": 17.61,
}
BIG_SEED = 2**33 + 17
LITERALS = {w for m in SOURCE_MEAN_WORDS for w in json.loads(
    (MIXES / f"{m}.json").read_text())["queries"]["forms"][0]["pattern"].split()} | {
    "who", "what", "when", "where", "which", "why", "how", "many", "are", "and", "both",
    "or", "of", "the", "that", "by", "is", "in", "was"}


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(SOURCE_MEAN_WORDS))
def test_mix_hits_its_source_mean_length(name):
    qs = QueryGenerator(mix(name)["queries"], BIG_SEED, "window").next_batch(256)
    mean = np.mean([word_count(q) for q in qs])
    assert abs(mean - SOURCE_MEAN_WORDS[name]) <= 0.1


@pytest.mark.parametrize("name", sorted(SOURCE_MEAN_WORDS))
def test_same_seed_same_queries_other_seed_other_words(name):
    spec = mix(name)["queries"]
    a = QueryGenerator(spec, BIG_SEED, "window").next_batch(64)
    b = QueryGenerator(spec, BIG_SEED, "window").next_batch(64)
    c = QueryGenerator(spec, BIG_SEED + 1, "window").next_batch(64)
    assert a == b
    assert a != c
    # the seed draws the words; the shapes come in the same order under every
    # seed: lengths, literal words, and one billed token per pseudo-word
    assert list(map(word_count, a)) == list(map(word_count, c))
    literal = [[w for w in q.split() if w in LITERALS] for q in a]
    assert literal == [[w for w in q.split() if w in LITERALS] for q in c]
    assert all(len(w) <= 7 for q in a + c for w in q.split() if w not in LITERALS)


def test_queries_are_distinct_within_a_run():
    gen = QueryGenerator(mix("nq-questions-batch")["queries"], 5, "window")
    qs = gen.next_batch(2000) + gen.next_batch(2000)
    assert len(set(qs)) == len(qs)


def test_hotpot_questions_name_two_entities():
    qs = QueryGenerator(mix("hotpot-questions-batch")["queries"], 3, "window").next_batch(128)
    for q in qs:
        entities = [w for w in q.split() if w[0].isupper()]
        assert 2 <= len(entities) <= 4, q


def test_stratified_lengths_sum_to_the_mean():
    lengths = stratified_lengths({"mean": 9.16, "sd": 2.6, "min": 4, "max": 20}, 256)
    assert lengths.sum() == round(9.16 * 256)
    assert lengths.min() >= 4 and lengths.max() <= 20
