"""Closed loop of back-to-back TF-IDF index builds over one corpus.

Set-up generates the configuration's corpus from the seed and runs one
build (which compiles, or loads the program from the persistent cache).
A build is the program's normal entry, ``models.tfidf.run_tfidf(docs,
cfg)``: raw strings to the host ``TfidfOutput``.  The check compares every
build's (term, doc) pairs and weights with a plain float64 TF-IDF built
from the same strings.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import harness
from reference import tfidf as ref
from traffic import corpus as corpus_gen

SPANS = frozenset({harness.JOB})


@dataclasses.dataclass
class State:
    config: dict
    docs: list
    cfg: object


def program_config(c: dict):
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig

    return TfidfConfig(vocab_bits=c["vocab_bits"], tf_mode=c["tf_mode"],
                       idf_mode=c["idf_mode"], l2_normalize=c["l2_normalize"],
                       dtype=c["dtype"])


def setup(cell) -> State:
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf

    c = cell.config
    docs = corpus_gen.documents(c["corpus"], cell.seed)
    cfg = program_config(c)
    run_tfidf(docs, cfg)  # warm-up: compiles or loads the pipeline
    return State(config=c, docs=docs, cfg=cfg)


def window(state: State, seconds: float) -> harness.Window:
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf

    def build():
        out = run_tfidf(state.docs, state.cfg)
        return out.term, out.doc, out.weight

    t0, ends, outs, host = harness.closed_loop(seconds, build)
    tokens = corpus_gen.n_tokens(state.config["corpus"]) * len(outs)
    return harness.Window(
        t0=t0, t1=ends[-1], attempted=len(outs), failed=0,
        end_to_end={"index_tokens_per_s": tokens / (ends[-1] - t0)},
        counts={"builds": len(outs), "tokens": tokens, "host": host}, outputs=outs)


def release(state: State) -> None:
    pass


def reference(state: State, dtype=np.float64) -> ref.Index:
    c = state.config
    return ref.tfidf(state.docs, vocab_bits=c["vocab_bits"], idf_mode=c["idf_mode"],
                     l2_normalize=c["l2_normalize"], dtype=dtype)


def gaps(index: ref.Index, outputs: list) -> tuple[float, float]:
    """(pairs in one set and not the other, largest weight gap on shared
    pairs), worst over the builds."""
    n = index.n_docs
    want = index.term * n + index.doc
    missing, worst = 0, 0.0
    for term, doc, weight in outputs:
        got = np.asarray(term, np.int64) * n + np.asarray(doc, np.int64)
        weight = np.asarray(weight, np.float64)
        if np.array_equal(got, want):  # the program's (term, doc) order
            diff = np.abs(weight - index.weight)
        else:
            missing = max(missing, int(np.setxor1d(got, want).size))
            _, i_got, i_want = np.intersect1d(got, want, return_indices=True)
            diff = np.abs(weight[i_got] - index.weight[i_want])
        if diff.size:
            worst = max(worst, float(diff.max()))
    return float(missing), worst


def check(state: State, win: harness.Window) -> dict:
    missing, worst = gaps(reference(state), win.outputs)
    limits = state.config["limits"]
    return {"tfidf_pairs_differing": (missing, 0.0),
            "tfidf_weight_gap": (worst, limits["tfidf_weight_gap"])}


def controls(state: State, win: harness.Window) -> dict:
    """The reference in a precision below the configuration's, put in the
    program's place: ``bf16`` computes in bfloat16 throughout;
    ``bf16_weights`` rounds the float64 weights to bfloat16 (a program that
    stores or pulls its weights in bfloat16)."""
    import ml_dtypes

    low = reference(state, ml_dtypes.bfloat16)
    exact = reference(state)
    held = exact.weight.astype(ml_dtypes.bfloat16).astype(np.float64)
    return {"bf16": [(low.term, low.doc, low.weight)],
            "bf16_weights": [(exact.term, exact.doc, held)]}
