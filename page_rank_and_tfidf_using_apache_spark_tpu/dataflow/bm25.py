"""BM25 — the second ranker over the SAME postings arrays (ISSUE 9
workload 4; ROADMAP "BM25 scoring beside TF-IDF ... the serving layer
gets an A/B-able second ranker").

Okapi BM25 with the Lucene idf variant (non-negative for every df)::

    idf(t)    = ln(1 + (N - df + 0.5) / (df + 0.5))
    w(d, t)   = idf(t) * c * (k1 + 1) / (c + k1 * (1 - b + b * |d|/avgdl))

where ``c`` is the raw (doc, term) count the TF-IDF pipeline already
materializes (``TfidfOutput.count`` — no second corpus pass), ``|d|``
the document length and ``avgdl`` the corpus mean.  The weights land in
the SAME (term, doc)-sorted COO slots as the TF-IDF weights, so the
serving artifact stores them as one extra array and
``ops.score_query_batch`` serves either ranker from the same compiled
program — the weight table is a traced argument, so per-request ranker
selection costs zero recompiles (serving/server.py ``submit(...,
ranker="bm25")``).
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import Bm25Config

obs.watch_compiles()


@functools.partial(jax.jit, static_argnames=("n_docs", "k1", "b"))
def bm25_weights(
    doc,  # int32 [nnz]
    term,  # int32 [nnz]
    count,  # f[nnz] raw per-pair counts
    doc_lengths,  # int32 [n_docs]
    df,  # f[vocab]
    *,
    n_docs: int,
    k1: float,
    b: float,
):
    """Per-(doc, term) BM25 weights over the postings COO: one gather of
    the per-doc length, one gather of the per-term df (the broadcast
    join), pure elementwise math — compiles once per nnz shape."""
    import jax.numpy as jnp

    dl = doc_lengths[doc].astype(count.dtype)
    avgdl = jnp.maximum(
        jnp.sum(doc_lengths.astype(count.dtype)) / n_docs, 1.0
    )
    n = jnp.asarray(float(n_docs), count.dtype)
    df_pair = df[term]
    idf = jnp.log1p((n - df_pair + 0.5) / (df_pair + 0.5))
    tf = count * (k1 + 1.0) / (count + k1 * (1.0 - b + b * dl / avgdl))
    return idf * tf


def bm25_from_tfidf(output, cfg: Bm25Config = Bm25Config()) -> np.ndarray:
    """BM25 weight array aligned with a :class:`~..models.tfidf
    .TfidfOutput`'s postings rows.  Needs the raw counts/doc lengths the
    pipeline now exports; an output predating that field fails loudly
    rather than inverting finalized weights (lossy where idf is 0)."""
    if output.count is None or output.doc_lengths is None:
        raise ValueError(
            "TfidfOutput carries no raw counts/doc lengths — rebuild the "
            "index with this version (BM25 re-weights counts, not tf-idf "
            "weights)"
        )
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.resilience import (
        executor as rx,
    )

    w = bm25_weights(
        jnp.asarray(output.doc), jnp.asarray(output.term),
        jnp.asarray(output.count.astype(output.weight.dtype)),
        jnp.asarray(output.doc_lengths.astype(np.int32)),
        jnp.asarray(output.df),
        n_docs=max(int(output.n_docs), 1), k1=float(cfg.k1), b=float(cfg.b),
    )
    with obs.span("bm25.weights", nnz=int(output.nnz)):
        return rx.device_get(w, site="bm25_weights_pull")
