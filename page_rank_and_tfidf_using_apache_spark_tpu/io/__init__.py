from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
    Graph,
    from_edges,
    from_sorted_arcs,
    load_snap,
    parse_snap_text,
    save_ranks,
    synthetic_powerlaw,
    synthetic_zipf,
)
from page_rank_and_tfidf_using_apache_spark_tpu.io.text import (
    TokenizedCorpus,
    iter_corpus_chunks,
    load_corpus_dir,
    load_corpus_lines,
    tokenize,
    tokenize_corpus,
)

__all__ = [
    "Graph",
    "from_edges",
    "from_sorted_arcs",
    "load_snap",
    "parse_snap_text",
    "save_ranks",
    "synthetic_powerlaw",
    "synthetic_zipf",
    "TokenizedCorpus",
    "iter_corpus_chunks",
    "load_corpus_dir",
    "load_corpus_lines",
    "tokenize",
    "tokenize_corpus",
]
