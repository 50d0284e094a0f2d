"""The plain references agree with the program at small sizes (CPU)."""

import numpy as np

from reference import pagerank as ref_pr
from reference import tfidf as ref_tfidf
from traffic import corpus, graph

CORPUS = {"n_docs": 300, "mean_doc_tokens": 40, "min_doc_tokens": 8, "zipf": 1.0,
          "n_words": 2000, "lengths_seed": 5}
GRAPH = {"in_exponent": 2.1, "in_max": 300, "out_exponent": 2.72, "out_max": 60,
         "dangling_share": 0.15}


def test_fnv1a64_known_values():
    assert ref_tfidf.fnv1a64("") == 0xCBF29CE484222325
    assert ref_tfidf.fnv1a64("a") == 0xAF63DC4C8601EC8C


def test_pagerank_reference_matches_program():
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_edges
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    src, dst = graph.web_edges(2000, 16000, seed=3, **GRAPH)
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform")
    got = run_pagerank(from_edges(src, dst, dedup=False, compact_ids=False), cfg).ranks
    want = ref_pr.pagerank(src, dst, 2000, 20, 0.85)
    assert abs(want.sum() - 1.0) < 1e-12
    assert np.abs(got - want).sum() < 1e-5
    f32 = ref_pr.pagerank(src, dst, 2000, 20, 0.85, dtype=np.float32)
    assert np.abs(f32 - want).sum() < 1e-5


def test_tfidf_reference_matches_program():
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig

    docs = corpus.documents(CORPUS, 9)
    docs[0] = "Mixed CASE, punctuation... and digits 42x!"
    out = run_tfidf(docs, TfidfConfig(vocab_bits=12, l2_normalize=True))
    want = ref_tfidf.tfidf(docs, vocab_bits=12, idf_mode="classic", l2_normalize=True)
    assert np.array_equal(out.term, want.term) and np.array_equal(out.doc, want.doc)
    assert np.abs(out.weight - want.weight).max() < 1e-6

