"""Native C++ ingest kernels == numpy fallbacks, bit for bit.

SURVEY.md §7 flags the host-side parse/tokenize loops as the scale
bottleneck; utils/native.py binds the C++ kernels and io/{graph,text}.py
fall back to numpy when they're unavailable.  These tests pin the two
implementations equal on the same inputs — the graceful-degradation
contract only holds if the fast path is indistinguishable.
"""

from __future__ import annotations

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.io import graph as gio
from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio
from page_rank_and_tfidf_using_apache_spark_tpu.utils import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++?)"
)

SNAP_TEXT = (
    "# Directed graph (each unordered pair of nodes is saved once)\n"
    "# FromNodeId\tToNodeId\n"
    "0\t1\n"
    "1\t2\n"
    "  \n"
    "2\t0\n"
    "2\t1\r\n"
    "   # indented comment\n"
    "3 3\n"
    "0\t1\n"  # duplicate edge — dedup happens downstream in from_edges
    "10    7\n"  # multi-space separator, dangling node 7
)


def _numpy_pairs(text: str) -> np.ndarray:
    lines = [ln for ln in text.splitlines() if ln and not ln.lstrip().startswith("#")]
    flat = " ".join(lines).split()
    return np.array(flat, dtype=np.int64).reshape(-1, 2)


def test_edge_parser_matches_numpy(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text(SNAP_TEXT)
    got = native.parse_edge_file(str(p))
    assert got is not None
    np.testing.assert_array_equal(got, _numpy_pairs(SNAP_TEXT))


def test_edge_parser_no_trailing_newline(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text("0 1\n2 3")
    got = native.parse_edge_file(str(p))
    np.testing.assert_array_equal(got, [[0, 1], [2, 3]])


def test_edge_parser_empty_and_comment_only(tmp_path):
    p = tmp_path / "empty.txt"
    p.write_text("")
    assert native.parse_edge_file(str(p)).shape == (0, 2)
    p.write_text("# nothing here\n#\n")
    assert native.parse_edge_file(str(p)).shape == (0, 2)


def test_edge_parser_rejects_garbage(tmp_path):
    # Inputs the numpy path raises on must make the native path bail (None)
    # so load_snap falls through and surfaces the numpy error.
    p = tmp_path / "bad.txt"
    # int64-overflowing ids also bail (numpy raises OverflowError there).
    p.write_text("99999999999999999999 3\n")
    assert native.parse_edge_file(str(p)) is None
    for bad in ["0 1\n2 x\n", "0 1 2\n", "12abc 3\n"]:
        p.write_text(bad)
        assert native.parse_edge_file(str(p)) is None
        with pytest.raises(ValueError):
            gio.load_snap(str(p))


def test_load_snap_uses_native(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text(SNAP_TEXT)
    g_native = gio.load_snap(str(p))
    g_numpy = gio.parse_snap_text(SNAP_TEXT)
    assert g_native.n_nodes == g_numpy.n_nodes
    np.testing.assert_array_equal(g_native.src, g_numpy.src)
    np.testing.assert_array_equal(g_native.dst, g_numpy.dst)
    np.testing.assert_array_equal(g_native.out_degree, g_numpy.out_degree)
    np.testing.assert_array_equal(g_native.node_ids, g_numpy.node_ids)


DOCS = [
    "The quick brown fox jumps over the lazy dog",
    "to be or not to be, that is the question!",
    "",
    "   punctuation-only:  ...!!!   ",
    "MiXeD CaSe 123 abc123def 42",
    "café naïve résumé",  # multi-byte UTF-8 acts as separator
    "İstanbul is large",  # U+0130: lower() -> 'i' + combining dot (token break)
    "300K is hot, AKB too",  # U+212A KELVIN: lower() -> ASCII 'k'
    "İİ double dotted-İ edge İ",
    "a bb ccc dddd",
    "single",
]


def _numpy_tokenize(docs, *, vocab_bits, ngram, lowercase, min_token_len):
    per_doc = [
        tio.add_ngrams(tio.tokenize(d, lowercase=lowercase, min_token_len=min_token_len), ngram)
        for d in docs
    ]
    doc_lengths = np.fromiter((len(p) for p in per_doc), dtype=np.int32, count=len(per_doc))
    flat = [t for p in per_doc for t in p]
    term_ids = tio.hash_to_vocab(tio.fnv1a_64(flat), vocab_bits)
    doc_ids = np.repeat(np.arange(len(docs), dtype=np.int32), doc_lengths)
    return doc_ids, term_ids, doc_lengths


@pytest.mark.parametrize("ngram", [1, 2, 3])
@pytest.mark.parametrize("lowercase", [True, False])
@pytest.mark.parametrize("min_token_len", [1, 2])
def test_tokenizer_matches_numpy(ngram, lowercase, min_token_len):
    kw = dict(vocab_bits=18, ngram=ngram, lowercase=lowercase, min_token_len=min_token_len)
    got = native.tokenize_and_hash(DOCS, **kw)
    assert got is not None
    want = _numpy_tokenize(DOCS, **kw)
    for g, w, name in zip(got, want, ["doc_ids", "term_ids", "doc_lengths"]):
        np.testing.assert_array_equal(g, w, err_msg=name)


def test_tokenizer_empty_batch():
    got = native.tokenize_and_hash([], vocab_bits=18, ngram=1, lowercase=True, min_token_len=1)
    doc_ids, term_ids, doc_lengths = got
    assert doc_ids.size == 0 and term_ids.size == 0 and doc_lengths.size == 0


def test_tokenizer_small_vocab_bits():
    got = native.tokenize_and_hash(DOCS, vocab_bits=4, ngram=2, lowercase=True, min_token_len=1)
    want = _numpy_tokenize(DOCS, vocab_bits=4, ngram=2, lowercase=True, min_token_len=1)
    np.testing.assert_array_equal(got[1], want[1])
    assert got[1].size == 0 or got[1].max() < 16


def test_tokenize_corpus_native_equals_fallback(monkeypatch):
    """tokenize_corpus must give identical TokenizedCorpus either way."""
    kw = dict(vocab_bits=12, ngram=2, lowercase=True, min_token_len=1)
    tc_native = tio.tokenize_corpus(DOCS, **kw)
    monkeypatch.setattr(native, "tokenize_and_hash", lambda *a, **k: None)
    tc_numpy = tio.tokenize_corpus(DOCS, **kw)
    np.testing.assert_array_equal(tc_native.doc_ids, tc_numpy.doc_ids)
    np.testing.assert_array_equal(tc_native.term_ids, tc_numpy.term_ids)
    np.testing.assert_array_equal(tc_native.doc_lengths, tc_numpy.doc_lengths)


@pytest.mark.parametrize("dedup", [True, False])
def test_sort_dedup_edges_matches_lexsort(dedup):
    """The C++ radix sort must reproduce numpy's (dst, src) lexsort layout
    bit-for-bit, including duplicate handling and self-loops."""
    rng = np.random.default_rng(3)
    src = rng.integers(0, 500, 20_000).astype(np.int64)
    dst = rng.integers(0, 500, 20_000).astype(np.int64)
    src[::97] = dst[::97]  # self-loops
    src[1000:1100] = src[:100]  # guaranteed duplicates
    dst[1000:1100] = dst[:100]

    # the native call mutates its inputs in place — compare against copies
    got = native.sort_dedup_edges(src.copy(), dst.copy(), dedup=dedup)
    assert got is not None
    order = np.lexsort((src, dst))
    s, d = src[order], dst[order]
    if dedup:
        keep = np.empty(s.shape, bool)
        keep[0] = True
        keep[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
        s, d = s[keep], d[keep]
    np.testing.assert_array_equal(got[0], s)
    np.testing.assert_array_equal(got[1], d)


def test_from_edges_native_equals_fallback(monkeypatch):
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_edges

    rng = np.random.default_rng(9)
    src = rng.integers(0, 2000, 50_000)
    dst = rng.integers(0, 2000, 50_000)
    g_native = from_edges(src, dst)
    monkeypatch.setattr(native, "sort_dedup_edges", lambda *a, **k: None)
    g_numpy = from_edges(src, dst)
    np.testing.assert_array_equal(g_native.src, g_numpy.src)
    np.testing.assert_array_equal(g_native.dst, g_numpy.dst)
    np.testing.assert_array_equal(g_native.out_degree, g_numpy.out_degree)


def test_library_is_keyed_on_source_and_flags(monkeypatch):
    """A library built from other source or flags is never loaded: the
    file name carries a hash of both."""
    from page_rank_and_tfidf_using_apache_spark_tpu.utils import native

    path = native.library_path()
    assert path == native.library_path()
    monkeypatch.setattr(native, "_CXX_FLAGS", native._CXX_FLAGS + ("-DX",))
    assert native.library_path() != path
    assert "-march=native" not in native._CXX_FLAGS
