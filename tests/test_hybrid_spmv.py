"""Degree-aware hybrid + sort-based static-shuffle SpMV tests (ISSUE 7).

The acceptance bars:

- property-based equivalence of ``spmv_hybrid`` and ``spmv_sort_shuffle``
  against ``spmv_segment`` on random power-law (Zipf) graphs — dangling
  nodes included by construction — plus the empty-head / empty-tail /
  empty-graph edge cases;
- the static layouts account for every edge exactly once (the layout IS
  the graph, re-blocked);
- ``plan_partition(strategy="hybrid")`` reports ``pad_frac <= 0.25`` on
  the web-Google-scale graph at 8 devices, under the padding floor of any
  layout that keeps each node's in-arcs on one device;
- chip-count invariance of the sharded ``hybrid`` layout lives in
  tests/test_parallel.py next to the owned layout.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from page_rank_and_tfidf_using_apache_spark_tpu.io import (
    from_edges,
    synthetic_powerlaw,
)
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pallas_kernels as pk
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
    plan_partition,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

F64 = dict(dangling="redistribute", init="uniform", dtype="float64")


def _spmv(graph, impl: str, w: np.ndarray) -> np.ndarray:
    dg = ops.put_graph(graph, "float64", layout=ops.layout_for_impl(impl))
    return np.asarray(ops.spmv(dg, jnp.asarray(w), graph.n_nodes, impl))


def _assert_impls_match_segment(graph, w=None):
    rng = np.random.default_rng(0)
    if w is None:
        w = rng.random(graph.n_nodes)
    want = _spmv(graph, "segment", w)
    # sort_shuffle is in segment's exact accuracy class (blocked per-node
    # sums); hybrid's tail rides the prefix-sum path, whose f64 error is
    # ~E*eps — far under 1e-9 at test scale, bounded at 1e-12 exactly only
    # for the shuffle layout
    got = _spmv(graph, "sort_shuffle", w)
    np.testing.assert_allclose(got, want, atol=1e-12, rtol=1e-12)
    got = _spmv(graph, "hybrid", w)
    np.testing.assert_allclose(got, want, atol=1e-9, rtol=1e-9)


# ------------------------------------------------------- direct equivalence


def test_equivalence_on_powerlaw_fixture():
    _assert_impls_match_segment(synthetic_powerlaw(300, 2400, seed=5))


def test_equivalence_empty_head():
    """A ring has uniform in-degree 1 — no node qualifies for the dense
    head (nor fills a bucket), so hybrid degenerates to the pure tail."""
    n = 40
    g = from_edges(np.arange(n), (np.arange(n) + 1) % n)
    hl = ops.build_hybrid_layout(g)
    assert hl.head_ids.size == 0 and hl.tail_src.size == g.n_edges
    _assert_impls_match_segment(g)


def test_equivalence_empty_tail():
    """A star pushes every edge into one hub: the whole graph is head,
    the tail is empty (and the leaves are dangling)."""
    g = from_edges(np.arange(1, 64), np.zeros(63, int))
    hl = ops.build_hybrid_layout(g)
    assert hl.tail_src.size == 0 and hl.head_ids.tolist() == [0]
    assert (g.out_degree == 0).sum() == 1  # the hub itself dangles
    _assert_impls_match_segment(g)


def test_layout_builders_handle_empty_graph():
    g = from_edges(np.empty(0, np.int64), np.empty(0, np.int64))
    hl = ops.build_hybrid_layout(g)
    assert hl.head_ids.size == 0 and hl.tail_src.size == 0
    bucket_src, bucket_node, _ = ops.build_shuffle_layout(g)
    assert bucket_src.shape[0] == 0 and bucket_node.size == 0


def test_hybrid_layout_accounts_every_edge_once():
    g = synthetic_powerlaw(200, 1600, seed=9)
    hl = ops.build_hybrid_layout(g)
    n = g.n_nodes
    pairs = []
    for row, slot in zip(hl.head_src, hl.head_row_node):
        dst = int(hl.head_ids[slot])
        for s in row[row != n]:
            pairs.append((int(s), dst))
    assert int((hl.head_src == n).sum()) == hl.pad_slots
    pairs += list(zip(hl.tail_src.tolist(), hl.tail_dst.tolist()))
    want = sorted(zip(g.src.tolist(), g.dst.tolist()))
    assert sorted(pairs) == want
    # the head really is the high-in-degree end: every member's in-degree
    # >= the adaptive row width (no mostly-padding dense rows)
    indeg = np.diff(g.csr_indptr())
    if hl.head_ids.size:
        assert indeg[hl.head_ids].min() >= hl.head_src.shape[1]


def test_shuffle_layout_accounts_every_edge_once():
    g = synthetic_powerlaw(150, 900, seed=4)
    bucket_src, bucket_node, _ = ops.build_shuffle_layout(g, bucket_width=8)
    assert (np.diff(bucket_node) >= 0).all()
    pairs = []
    for row, dst in zip(bucket_src, bucket_node):
        for s in row[row != g.n_nodes]:
            pairs.append((int(s), int(dst)))
    assert sorted(pairs) == sorted(zip(g.src.tolist(), g.dst.tolist()))


def test_rowsum_pallas_interpret_matches_dense():
    rng = np.random.default_rng(1)
    for r, w in ((1, 8), (7, 128), (2048, 128), (2049, 128)):
        mat = rng.random((r, w)).astype(np.float32)
        got = np.asarray(pk.rowsum_pallas(jnp.asarray(mat), interpret=True))
        np.testing.assert_allclose(got, mat.sum(axis=1), rtol=1e-6)


# -------------------------------------------------- property-based (Zipf)
# hypothesis drives the example search when available; without it the same
# properties run over a fixed deterministic seed sweep (only the search
# strategy degrades — this file must never skip wholesale).


def _check_zipf_equivalence(seed: int, zipf_a: float) -> None:
    """Random power-law graphs (Zipf destinations, uniform sources —
    dangling nodes and duplicate edges arise naturally) — both new impls
    must agree with segment_sum to f64 round-off."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 120))
    e = int(rng.integers(1, 600))
    g = synthetic_powerlaw(n, e, seed=seed % (2**31), zipf_a=zipf_a)
    _assert_impls_match_segment(g, w=rng.random(g.n_nodes))


def _check_full_run_equivalence(seed: int) -> None:
    """End-to-end fixpoint runs (donated carry, scan loop, dangling
    redistribution) agree across impls in f64."""
    g = synthetic_powerlaw(80, 500, seed=seed % (2**31))
    base = run_pagerank(g, PageRankConfig(iterations=20, **F64)).ranks
    for impl in ("hybrid", "sort_shuffle"):
        got = run_pagerank(
            g, PageRankConfig(iterations=20, spmv_impl=impl, **F64)
        ).ranks
        np.testing.assert_allclose(got, base, atol=1e-9)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    _SWEEP = [7, 193, 4040, 91823, 777_777, 2**30 + 3]

    @pytest.mark.parametrize("seed", _SWEEP)
    def test_property_equivalence_on_zipf_graphs(seed):
        _check_zipf_equivalence(seed, zipf_a=1.2 + (seed % 19) / 10.0)

    @pytest.mark.parametrize("seed", _SWEEP[:3])
    def test_property_full_run_equivalence(seed):
        _check_full_run_equivalence(seed)
else:

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.floats(1.2, 3.0))
    def test_property_equivalence_on_zipf_graphs(seed, zipf_a):
        _check_zipf_equivalence(seed, zipf_a)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_property_full_run_equivalence(seed):
        _check_full_run_equivalence(seed)


# ----------------------------------------------- plan-level padding pins


def test_preprocess_time_is_recorded():
    g = synthetic_powerlaw(100, 600, seed=2)
    res = run_pagerank(g, PageRankConfig(iterations=2, spmv_impl="hybrid", **F64))
    (rec,) = [r for r in res.metrics.records if r.get("event") == "put_graph"]
    assert rec["spmv_impl"] == "hybrid" and rec["preprocess_secs"] >= 0


def test_hybrid_plan_beats_pad_ceiling_at_webgoogle_scale():
    """The ISSUE 7 acceptance pin, statically checkable on CPU: at the
    bench's web-Google scale (875K nodes / 5.1M edges, 8 devices) the
    hybrid plan's padding waste is ~1e-4 — far under the 0.25 ceiling the
    registry enforces, and under the node-granularity floor that a
    780K-in-degree hub forces on any layout that cannot split one node's
    run across devices (the r05 dryrun measured 0.61 for such a layout)."""
    g = synthetic_powerlaw(875_000, 5_100_000, seed=7)
    plan = plan_partition(g, 8, strategy="hybrid")
    assert plan.pad_frac <= 0.25, plan
    head_k, w, rows, rows_dev = plan.head
    assert head_k >= 1 and rows_dev * 8 * w >= plan.head[2] * w
    # the floor of a node-granular layout: the hub's in-degree on one
    # device sets every device's padded width; hybrid splits dense rows
    indeg_max = int(np.diff(g.csr_indptr()).max())
    floor = (8 * indeg_max - g.n_edges) / (8 * indeg_max)
    assert plan.pad_frac < floor
