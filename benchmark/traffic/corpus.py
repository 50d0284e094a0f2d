"""Seeded Zipf corpus at 20 Newsgroups shape.

Drawn like the program's ``io.text.synthetic_corpus_lines`` (Poisson
document lengths with a floor, Zipf words, one document per string), with
two changes: words are Zipf over exactly ``n_words`` ranks (the program's
copy folds an unbounded Zipf draw by a modulo), and the multiset of
document lengths comes from the configuration's own ``lengths_seed``, the
run seed only permuting it.  So every seed gives the same number of
documents and tokens, the program compiles the same shapes, and the seed
changes the words and their order, not the amount of work.
"""

from __future__ import annotations

import numpy as np


def word(ids: np.ndarray) -> np.ndarray:
    """Word strings ``w<id>`` of integer ids (one token each)."""
    return np.char.add("w", ids.astype(np.int64).astype(str))


def lengths(corpus: dict, seed: int) -> np.ndarray:
    """Tokens per document: the fixed multiset, in the seed's order."""
    base = np.random.default_rng(corpus["lengths_seed"]).poisson(
        corpus["mean_doc_tokens"], corpus["n_docs"])
    base = np.maximum(base, corpus["min_doc_tokens"]).astype(np.int64)
    return np.random.default_rng(seed).permutation(base)


def zipf_ranks(exponent: float, n_words: int, size: int,
               rng: np.random.Generator) -> np.ndarray:
    """``size`` word ranks in ``[0, n_words)``, rank ``r`` drawn with
    probability proportional to ``(r + 1) ** -exponent``."""
    cdf = np.cumsum(np.arange(1, n_words + 1, dtype=np.float64) ** -exponent)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1], side="right")


def documents(corpus: dict, seed: int) -> list[str]:
    """``corpus["n_docs"]`` documents of Zipf(``zipf``) words over
    ``n_words`` words; the same seed gives the same documents."""
    lens = lengths(corpus, seed)
    rng = np.random.default_rng([seed, 1])
    words = word(zipf_ranks(corpus["zipf"], corpus["n_words"], int(lens.sum()), rng))
    ends = np.cumsum(lens)
    return [" ".join(words[e - n:e]) for n, e in zip(lens, ends)]


def n_tokens(corpus: dict) -> int:
    """Tokens in every corpus of this configuration, whatever the seed."""
    return int(lengths(corpus, 0).sum())
