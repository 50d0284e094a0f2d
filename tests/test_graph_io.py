"""Golden-file + unit tests for SNAP ingest (SURVEY.md A2/A3, §4)."""

import os

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.io import (
    from_edges,
    from_sorted_arcs,
    load_snap,
    parse_snap_text,
    save_ranks,
    synthetic_powerlaw,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "tiny.txt")


def test_parse_snap_fixture():
    g = load_snap(FIXTURE)
    # ids 0,1,2,4,5 → compacted to 0..4 (id 3 absent in input)
    assert g.n_nodes == 5
    assert list(g.node_ids) == [0, 1, 2, 4, 5]
    # duplicate edge 1→2 deduped; self-loop 5→5 kept
    assert g.n_edges == 7
    # destination-sorted invariant
    assert (np.diff(g.dst) >= 0).all()
    # out-degrees on original ids: 0→{1,2,4}, 1→{2}, 2→{0,4}, 4 dangling, 5→{5}
    assert list(g.out_degree) == [3, 1, 2, 0, 1]
    assert list(g.dangling_mask) == [False, False, False, True, False]


def test_parse_equivalence_text_vs_file():
    with open(FIXTURE, "rb") as f:
        g2 = parse_snap_text(f.read())
    g1 = load_snap(FIXTURE)
    np.testing.assert_array_equal(g1.src, g2.src)
    np.testing.assert_array_equal(g1.dst, g2.dst)


def test_dedup_and_self_loops():
    g = from_edges(np.array([1, 1, 2, 2]), np.array([2, 2, 2, 1]))
    assert g.n_edges == 3  # (1,2) deduped, (2,2) self-loop kept
    g2 = from_edges(np.array([1, 1, 2, 2]), np.array([2, 2, 2, 1]), drop_self_loops=True)
    assert g2.n_edges == 2


def test_empty_graph():
    g = parse_snap_text("# only comments\n")
    assert g.n_nodes == 0 and g.n_edges == 0


def test_odd_token_count_raises():
    with pytest.raises(ValueError, match="odd token count"):
        parse_snap_text("1 2 3\n")


def test_compact_ids_roundtrip():
    g = from_edges(np.array([100, 7]), np.array([7, 2000]))
    assert g.n_nodes == 3
    assert list(g.node_ids) == [7, 100, 2000]


def test_save_ranks(tmp_path):
    g = load_snap(FIXTURE)
    ranks = np.arange(g.n_nodes, dtype=np.float32)
    out = tmp_path / "ranks.txt"
    save_ranks(str(out), g, ranks, top_k=2)
    lines = out.read_text().splitlines()
    assert len(lines) == 2
    # highest rank first, mapped back to original node ids
    nid, r = lines[0].split("\t")
    assert int(nid) == g.node_ids[g.n_nodes - 1]


def test_synthetic_powerlaw_shape():
    g = synthetic_powerlaw(1000, 5000, seed=1)
    assert g.n_nodes <= 1000
    assert g.n_edges <= 5000  # dedup may shrink
    # power-law: max in-degree far above mean
    indeg = np.bincount(g.dst, minlength=g.n_nodes)
    assert indeg.max() > 10 * indeg.mean()


@pytest.mark.parametrize("chunk", [1 << 24, 7])
def test_from_sorted_arcs_is_from_edges(monkeypatch, chunk):
    """Arcs already sorted and unique build the Graph ``from_edges`` does,
    out-degrees counted in chunks (7 arcs: runs cross the chunk bounds)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.io import graph as graph_io

    monkeypatch.setattr(graph_io, "_ARC_CHUNK", chunk)
    want = synthetic_powerlaw(300, 2400, seed=11)
    got = from_sorted_arcs(want.src, want.dst, want.n_nodes)
    assert got.n_nodes == want.n_nodes and got.src is want.src and got.dst is want.dst
    np.testing.assert_array_equal(got.out_degree, want.out_degree)
    assert got.out_degree.dtype == np.int32
    np.testing.assert_array_equal(got.node_ids, np.arange(want.n_nodes))
    empty = from_sorted_arcs(np.zeros(0, np.int32), np.zeros(0, np.int32), 3)
    assert empty.n_edges == 0 and list(empty.out_degree) == [0, 0, 0]


@pytest.mark.parametrize("fault", ["unsorted", "duplicate", "src_order", "range", "int64"])
def test_from_sorted_arcs_refuses_broken_arcs(monkeypatch, fault):
    from page_rank_and_tfidf_using_apache_spark_tpu.io import graph as graph_io

    monkeypatch.setattr(graph_io, "_ARC_CHUNK", 4)  # the fault sits across a chunk bound
    src = np.array([1, 2, 0, 2, 0, 1, 3, 0, 1], np.int32)
    dst = np.array([0, 0, 1, 1, 2, 2, 2, 3, 3], np.int32)
    assert list(from_sorted_arcs(src, dst, 4).out_degree) == [3, 3, 2, 1]
    if fault == "unsorted":
        dst[4] = 0
    elif fault == "duplicate":
        src[4] = src[3] = 2
        dst[4] = 1
    elif fault == "src_order":
        src[3], src[4], dst[4] = 2, 0, 1
    elif fault == "range":
        src[5] = 4
    else:
        src = src.astype(np.int64)
    with pytest.raises(ValueError):
        from_sorted_arcs(src, dst, 4)
