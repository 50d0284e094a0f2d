"""Plain TF-IDF, the reference for the text cells.

Built from the raw document strings with its own tokenizer and its own
64-bit FNV-1a hash, the semantics of the program's TF-IDF path (PR 21's
``chip_smoke.tfidf_reference``, without its use of the program's
tokenizer): tokens are runs of ``[a-z0-9]`` after
lowercasing, hashed and masked into ``2**vocab_bits`` ids; TF is the raw
count of a (term, doc) pair; IDF is ``log(N / df)`` ("classic"), ``log((N +
1) / (df + 1))`` ("mllib") or ``log((1 + N) / (1 + df)) + 1`` ("smooth");
rows are L2-normalised when asked.

Every quantity is computed in ``dtype`` (float64 for the reference; the
control passes a lower one, and every intermediate is rounded to it and
sums accumulate in it).  Imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

_TOKEN = re.compile(r"[a-z0-9]+")
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def fnv1a64(token: str) -> int:
    h = _FNV_OFFSET
    for b in token.encode("utf-8"):
        h = ((h ^ b) * _FNV_PRIME) & _MASK64
    return h


class Hasher:
    """Token -> hashed term id, memoised per distinct token."""

    def __init__(self, vocab_bits: int):
        self.mask = (1 << vocab_bits) - 1
        self.ids: dict[str, int] = {}

    def __call__(self, text: str) -> list[int]:
        ids = self.ids
        out = []
        for tok in _TOKEN.findall(text.lower()):
            t = ids.get(tok)
            if t is None:
                t = ids[tok] = fnv1a64(tok) & self.mask
            out.append(t)
        return out


@dataclasses.dataclass(frozen=True)
class Index:
    """(term, doc)-sorted TF-IDF pairs."""

    n_docs: int
    vocab_bits: int
    term: np.ndarray  # int64 [nnz]
    doc: np.ndarray  # int64 [nnz]
    weight: np.ndarray  # [nnz] in the dtype it was built in


def _idf(df: np.ndarray, n: int, mode: str) -> np.ndarray:
    safe = np.maximum(df, 1.0)
    if mode == "classic":
        idf = np.log(n / safe)
    elif mode == "mllib":
        idf = np.log((n + 1.0) / (df + 1.0))
    elif mode == "smooth":
        idf = np.log((1.0 + n) / (1.0 + df)) + 1.0
    else:
        raise ValueError(f"unknown idf mode {mode!r}")
    return np.where(df > 0, idf, 0.0)


def tfidf(docs: list[str], *, vocab_bits: int, idf_mode: str, l2_normalize: bool,
          dtype=np.float64) -> Index:
    """Raw-count TF x IDF over hashed unigrams."""
    hasher = Hasher(vocab_bits)
    per_doc = [hasher(d) for d in docs]
    n = len(docs)
    lens = np.fromiter((len(t) for t in per_doc), np.int64, count=n)
    term_tok = np.fromiter((t for ts in per_doc for t in ts), np.int64, count=int(lens.sum()))
    doc_tok = np.repeat(np.arange(n, dtype=np.int64), lens)
    keys, counts = np.unique(term_tok * n + doc_tok, return_counts=True)
    term, doc = keys // n, keys % n
    df = np.bincount(term, minlength=1 << vocab_bits).astype(np.float64)
    idf = _idf(df, n, idf_mode).astype(dtype)
    w = (counts.astype(dtype) * idf[term]).astype(dtype)
    if l2_normalize:
        sq = np.zeros(n, dtype)
        np.add.at(sq, doc, (w * w).astype(dtype))
        norm = np.sqrt(np.maximum(sq.astype(np.float64), 1e-30)).astype(dtype)
        w = (w / norm[doc]).astype(dtype)
    return Index(n_docs=n, vocab_bits=vocab_bits, term=term, doc=doc, weight=w)
