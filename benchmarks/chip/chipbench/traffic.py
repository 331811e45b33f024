"""The one general traffic generator: query texts from a mix's parameters.

A traffic mix is a data file (``traffic/<name>.json``) of parameters; this
module is the only code that reads its ``queries``:
``words``: ``{"mean", "sd", "min", "max"}`` of the word count (a
lognormal, clipped). ``forms``: ``[{"weight", "pattern"}]``, where a
pattern is literal words plus ``{E}`` (an entity: ``entity_words``
capitalised pseudo-words) and at most one ``{...}`` (filler words from
the seeded vocabulary, as many as the drawn length leaves; appended at
the end when the pattern has none). ``vocab`` and ``entities`` size the
two seeded pseudo-word vocabularies. No query repeats in a run.
The mix's ``arrivals`` name a driver, ``arrivals/<kind>.py``, and its
parameters.

Every draw of a shape (a length, a form, an entity's word count) is
stratified and put in an order that depends on the stream and not on the
seed; the seed draws the words and the corpus. The program's router
refines its priors from the billed tokens of earlier queries and
re-executes, one query at a time, those whose route the refined priors
change, so the order of query shapes is part of the work: with the shapes
in a seeded order, the index reads a batch of 256 made ranged from 34 to
227 between seeds. Pseudo-words are at most seven letters, so each bills
one token whatever the seed.
"""

from __future__ import annotations

import math
import re
import statistics

import numpy as np

# Words a pseudo-word must never spell: a filler that reads as an
# interrogative or a common function word would change the query's shape.
_RESERVED = frozenset(
    """what why how when where which who whom whose explain describe compare
    contrast list define derive is are was were the a an of in on to for and
    or by with from at as be it its""".split()
)
_ONSETS = ("b", "d", "f", "g", "k", "l", "m", "n", "p", "r", "s", "t", "v", "z", "br", "st")
_VOWELS = ("a", "e", "i", "o", "u")
_CODAS = ("", "", "n", "r", "s", "k", "l")
_WORD_RE = re.compile(r"[A-Za-z0-9']+")


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream.encode()])


def word_count(text: str) -> int:
    """Words as a query-length statistic counts them (alphanumeric runs)."""
    return len(_WORD_RE.findall(text))


def _pseudo_words(rng: np.random.Generator, n: int, *, capitalise: bool) -> list[str]:
    """``n`` distinct pseudo-words of two or three syllables, at most seven
    letters, none spelling a reserved word."""
    out: list[str] = []
    seen: set[str] = set()
    sizes = [len(_ONSETS), len(_VOWELS), len(_CODAS)]
    while len(out) < n:
        m = 2 * (n - len(out)) + 64
        syllables = rng.integers(2, 4, size=m)
        parts = rng.integers(0, sizes, size=(m, 3, 3))
        for count, p in zip(syllables, parts):
            w = "".join(_ONSETS[o] + _VOWELS[v] + _CODAS[c] for o, v, c in p[:count])
            if len(w) > 7 or w in seen or w in _RESERVED:
                continue
            seen.add(w)
            out.append(w.capitalize() if capitalise else w)
            if len(out) == n:
                break
    return out


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` word counts: lognormal quantiles at the midpoints of ``n``
    equal strata, rounded and clipped to ``[min, max]``, then nudged one
    word at a time so they sum to ``round(mean * n)``. The same ``n``
    always gives the same multiset."""
    mean, sd = float(spec["mean"]), float(spec["sd"])
    lo, hi = int(spec["min"]), int(spec["max"])
    s2 = math.log1p((sd / mean) ** 2)
    mu = math.log(mean) - s2 / 2
    inv = statistics.NormalDist().inv_cdf
    x = np.array([math.exp(mu + math.sqrt(s2) * inv((i + 0.5) / n)) for i in range(n)])
    lengths = np.clip(np.rint(x), lo, hi).astype(np.int64)
    diff = int(round(mean * n)) - int(lengths.sum())
    step = 1 if diff > 0 else -1
    i = 0
    while diff and i < 4 * n * (hi - lo + 1):
        j = i % n if step > 0 else n - 1 - (i % n)
        if lo <= lengths[j] + step <= hi:
            lengths[j] += step
            diff -= step
        i += 1
    return lengths


def _counts(weights: list[float], n: int) -> list[int]:
    """Largest-remainder split of ``n`` by ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    base = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - base), kind="stable")
    base[order[: n - int(base.sum())]] += 1
    return base.tolist()


class QueryGenerator:
    """Distinct queries of one mix, from one seed.

    ``next_batch(n)`` returns the next ``n`` queries of the stream named
    ``stream`` (e.g. the window's or the warm-up's), never one already
    returned by this generator."""

    def __init__(self, spec: dict, seed: int, stream: str):
        self.spec = spec
        self.rng = rng_for(seed, "words/" + stream)
        self.shape_rng = rng_for(0, "shapes/" + stream)  # the same for every seed
        vocab_rng = rng_for(seed, "vocab")  # one vocabulary per seed
        self.vocab = _pseudo_words(vocab_rng, int(spec["vocab"]), capitalise=False)
        self.entities = _pseudo_words(vocab_rng, int(spec["entities"]), capitalise=True)
        self.entity_words = list(spec.get("entity_words", [1]))
        self.forms = [(f["pattern"].split(), float(f["weight"])) for f in spec["forms"]]
        most_fixed = max(
            sum(max(self.entity_words) if t == "{E}" else 1 for t in p if t != "{...}")
            for p, _ in self.forms
        )
        if most_fixed > int(spec["words"]["min"]):
            raise ValueError(
                f"a form holds {most_fixed} fixed words, more than words.min "
                f"{spec['words']['min']}: lengths would drift above the mix's"
            )
        self.seen: set[str] = set()

    def _one(self, length: int, pattern: list[str], entity_words: list[int]) -> str:
        rng = self.rng
        words: list[str] = []
        filler_at = None
        sizes = iter(entity_words)
        for tok in pattern:
            if tok == "{E}":
                words += [self.entities[i] for i in rng.integers(len(self.entities), size=next(sizes))]
            elif tok == "{...}":
                filler_at = len(words)
            else:
                words.append(tok)
        n_fill = max(0, length - len(words))
        fill = [self.vocab[i] for i in rng.integers(len(self.vocab), size=n_fill)]
        at = len(words) if filler_at is None else filler_at
        return " ".join(words[:at] + fill + words[at:])

    def next_batch(self, n: int) -> list[str]:
        lengths = stratified_lengths(self.spec["words"], n)
        forms = [i for i, c in enumerate(_counts([w for _, w in self.forms], n)) for _ in range(c)]
        lengths = lengths[self.shape_rng.permutation(n)]
        forms = [forms[i] for i in self.shape_rng.permutation(n)]
        out = []
        for length, f in zip(lengths, forms):
            pattern = self.forms[f][0]
            ents = self.shape_rng.choice(self.entity_words, size=pattern.count("{E}")).tolist()
            for _ in range(1000):
                q = self._one(int(length), pattern, ents)
                if q not in self.seen:
                    break
            else:
                raise RuntimeError("the mix's vocabulary is too small for distinct queries")
            self.seen.add(q)
            out.append(q)
        return out
