"""PageRank parity tests (SURVEY.md §4): networkx oracle for the textbook
semantics, the pure-python RDD-semantics oracle for Spark parity, both at
the L1 ≤ 1e-6 bar BASELINE.json:5 sets (float64 on CPU backend)."""

import numpy as np
import networkx as nx
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import PageRankConfig, pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.io import from_edges, synthetic_powerlaw

from tests.spark_oracle import spark_pagerank

EDGES_SMALL = [(0, 1), (0, 2), (1, 2), (2, 0), (2, 4), (5, 5), (0, 4), (3, 2)]


def _graph(edges):
    a = np.array(edges)
    return from_edges(a[:, 0], a[:, 1])


def _nx_ranks(edges, n, **kw):
    G = nx.DiGraph()
    G.add_nodes_from(range(n))
    G.add_edges_from(edges)
    d = nx.pagerank(G, alpha=0.85, max_iter=500, tol=1e-14, **kw)
    return np.array([d[i] for i in range(n)])


@pytest.mark.parametrize("edges", [EDGES_SMALL])
def test_parity_networkx_redistribute(edges):
    g = _graph(edges)
    res = pagerank(
        g, iterations=200, dangling="redistribute", init="uniform", dtype="float64"
    )
    expect = _nx_ranks([(int(a), int(b)) for a, b in zip(g.src, g.dst)], g.n_nodes)
    # graph node order == compacted ids here (ids are 0..5 contiguous)
    assert np.abs(res.ranks - expect).sum() <= 1e-6
    assert abs(res.ranks.sum() - 1.0) < 1e-9


def test_parity_networkx_synthetic():
    g = synthetic_powerlaw(300, 1500, seed=3)
    res = pagerank(
        g, iterations=300, dangling="redistribute", init="uniform", dtype="float64"
    )
    edges = list(zip(g.src.tolist(), g.dst.tolist()))
    expect = _nx_ranks(edges, g.n_nodes)
    assert np.abs(res.ranks - expect).sum() <= 1e-6


def test_spark_exact_matches_rdd_oracle():
    g = _graph(EDGES_SMALL)
    res = pagerank(g, PageRankConfig(iterations=7, spark_exact=True, dtype="float64"))
    oracle = spark_pagerank(EDGES_SMALL, 7)
    for i in range(g.n_nodes):
        nid = int(g.node_ids[i])
        if nid in oracle:
            assert res.ranks[i] == pytest.approx(oracle[nid], abs=1e-9), nid
        else:
            assert res.ranks[i] == 0.0, nid


def test_spark_exact_matches_rdd_oracle_synthetic():
    g = synthetic_powerlaw(200, 600, seed=5)
    edges = [(int(g.node_ids[a]), int(g.node_ids[b])) for a, b in zip(g.src, g.dst)]
    res = pagerank(g, PageRankConfig(iterations=10, spark_exact=True, dtype="float64"))
    oracle = spark_pagerank(edges, 10)
    got = {int(g.node_ids[i]): res.ranks[i] for i in range(g.n_nodes) if res.ranks[i] != 0.0}
    assert set(got) == set(oracle)
    l1 = sum(abs(got[k] - oracle[k]) for k in oracle)
    assert l1 <= 1e-6


def test_drop_mode_loses_mass():
    g = _graph(EDGES_SMALL)  # node 4 dangling
    res = pagerank(g, iterations=50, dangling="drop", init="uniform", dtype="float64")
    assert res.ranks.sum() < 1.0  # dangling mass vanished, by design


def test_personalized_matches_networkx():
    g = _graph(EDGES_SMALL)
    src_node = 0
    res = pagerank(
        g,
        iterations=300,
        dangling="redistribute",
        init="uniform",
        personalize=(src_node,),
        dtype="float64",
    )
    edges = [(int(a), int(b)) for a, b in zip(g.src, g.dst)]
    expect = _nx_ranks(
        edges, g.n_nodes, personalization={i: float(i == src_node) for i in range(g.n_nodes)}
    )
    assert np.abs(res.ranks - expect).sum() <= 1e-6


def test_tolerance_early_stop():
    g = _graph(EDGES_SMALL)
    res = pagerank(
        g, iterations=500, tol=1e-10, dangling="redistribute", init="uniform", dtype="float64"
    )
    assert res.iterations < 500
    assert res.l1_delta <= 1e-10


@pytest.mark.parametrize("impl", ["bcoo", "cumsum", "cumsum_mxu", "pallas"])
def test_spmv_impls_match_segment(impl):
    g = synthetic_powerlaw(100, 400, seed=7)
    r1 = pagerank(g, iterations=20, dangling="redistribute", init="uniform",
                  spmv_impl="segment", dtype="float64")
    r2 = pagerank(g, iterations=20, dangling="redistribute", init="uniform",
                  spmv_impl=impl, dtype="float64")
    assert np.abs(r1.ranks - r2.ranks).max() < 1e-12


@pytest.mark.parametrize("impl", ["cumsum", "cumsum_mxu"])
def test_cumsum_impl_f32_accuracy(impl):
    """The fast prefix-sum SpMVs must stay rank-accurate in float32 at a
    scale where their accumulated error could plausibly bite."""
    g = synthetic_powerlaw(20_000, 100_000, seed=9)
    exact = pagerank(g, iterations=20, dangling="redistribute", init="uniform",
                     spmv_impl="segment", dtype="float64")
    fast = pagerank(g, iterations=20, dangling="redistribute", init="uniform",
                    spmv_impl=impl, dtype="float32")
    assert np.abs(fast.ranks - exact.ranks).sum() < 1e-3


def _check_sorted_segment_sum(ids, vals, num_segments, scan):
    """``sorted_segment_sum`` in f32, through the CSR-pointer scan or the
    scatter of run ends, against a float64 ``np.bincount``."""
    import jax
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.ops.pagerank import (
        sorted_segment_sum,
    )

    indptr = None
    if scan:
        indptr = jnp.asarray(
            np.searchsorted(ids, np.arange(num_segments + 1)).astype(np.int32))
    ref = np.bincount(ids, weights=vals.astype(np.float64), minlength=num_segments)
    with jax.enable_x64(False):
        got = np.asarray(sorted_segment_sum(
            jnp.asarray(vals), jnp.asarray(ids), num_segments, indptr=indptr))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-12)


_HUB_LENGTHS = [1, 511, 512, 513, 800_000]


@pytest.mark.parametrize(
    "e,scan",
    [pytest.param(e, False, id=str(e)) for e in _HUB_LENGTHS]
    + [pytest.param(e, True, id=f"scan-{e}") for e in _HUB_LENGTHS],
)
def test_sorted_segment_sum_f32_hub(e, scan):
    """A hub run of 790K heavy-tailed f32 values sums to within 1e-5
    relative of float64 (a plain scatter-add drifts ~8e-4 on this data),
    and every other segment matches too; ragged lengths around the row
    width included, with the CSR pointers (scan) and without (scatter)."""
    rng = np.random.default_rng(e)
    ids = np.sort(rng.integers(0, 1000, e)).astype(np.int32)
    ids[: max(e - 10_000, e // 2)] = 0  # the hub: one long leading run
    vals = rng.lognormal(-16.0, 2.0, e).astype(np.float32)
    _check_sorted_segment_sum(ids, vals, 1000, scan)


def _runs(*runs):
    """Ascending segment ids from ``(id, length)`` runs."""
    return np.concatenate([np.full(k, s, np.int32) for s, k in runs])


# Ragged layouts for the carry across the 512-wide rows of the scan.
_CARRY_CASES = {
    # id 1 from mid-row 0 to mid-row 4: four row boundaries
    "run_crosses_four_rows": (_runs((0, 100), (1, 2000), (2, 50)), 3),
    # row 1 is all id 1, between rows 0 and 2 that also hold id 1
    "whole_row_between": (_runs((0, 300), (1, 212 + 512 + 100), (2, 7)), 3),
    # three whole rows of one id, aligned to the rows
    "aligned_whole_rows": (_runs((5, 3 * 512), (6, 10)), 7),
    # E = 1024: id 0 ends at row 0's end, id 1 at row 1's (the last edge)
    "runs_end_at_row_ends": (_runs((0, 512), (1, 512)), 2),
    "run_ends_at_last_edge": (_runs((0, 200), (1, 824)), 2),
    # ids 0-2, 5-6 and 9-11 receive nothing; 12 > largest id + 1
    "empty_start_middle_end": (_runs((3, 700), (4, 3), (7, 600), (8, 1)), 12),
    "segments_beyond_largest_id": (_runs((0, 1), (1, 1100)), 5000),
}


@pytest.mark.parametrize("scan", [False, True], ids=["scatter", "scan"])
@pytest.mark.parametrize("case", sorted(_CARRY_CASES))
def test_sorted_segment_sum_carry_cases(case, scan):
    """Runs that cross, fill or end at row boundaries, and segments with
    no values anywhere, sum like float64 on both paths."""
    ids, num_segments = _CARRY_CASES[case]
    vals = np.random.default_rng(ids.size).lognormal(
        -16.0, 2.0, ids.size).astype(np.float32)
    _check_sorted_segment_sum(ids, vals, num_segments, scan)


@pytest.mark.parametrize("nodes,edges", [(300, 1500), (2000, 9000)])
def test_spmv_segment_lowers_without_scatter(nodes, edges):
    """With the graph's CSR pointers the segment SpMV lowers to no
    scatter; without them the scatter of run ends is still there."""
    import jax
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    g = synthetic_powerlaw(nodes, edges, seed=3)
    dg = ops.put_graph(g, "float32")
    assert ops.segment_reduce(dg) == "scan"
    assert ops.segment_reduce(dg._replace(indptr=None)) == "scatter"
    w = jnp.zeros(g.n_nodes, jnp.float32)

    def hlo(graph):
        return jax.jit(lambda d, x: ops.spmv_segment(d, x, g.n_nodes)).lower(
            graph, w).as_text()

    assert "scatter" not in hlo(dg)
    assert "scatter" in hlo(dg._replace(indptr=None))


@pytest.mark.parametrize(
    "impl,edges,reduce",
    [("segment", 1500, "scan"), ("segment", 400, "scatter"), ("hybrid", 1500, None)],
)
def test_put_graph_record_names_segment_reduce(impl, edges, reduce):
    """The put_graph record says which reduction the segment SpMV lowered:
    the scan for the default config, a scatter for a graph of one row of
    edges, none for a layout impl."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank

    cfg = PageRankConfig(iterations=2) if impl == "segment" else PageRankConfig(
        iterations=2, spmv_impl=impl)
    res = run_pagerank(synthetic_powerlaw(300, edges, seed=3), cfg)
    (rec,) = [r for r in res.metrics.records if r.get("event") == "put_graph"]
    assert rec["segment_reduce"] == reduce
    # the layout impls gather from their own layout, not through _edge_values
    assert rec["gather_chunks"] == (1 if impl == "segment" else None)


@pytest.mark.parametrize(
    "shape,cap,chunks",
    [
        ((1000,), None, 1),  # under the module's cap: the plain gather
        ((1000,), 1000, 1),  # at the cap
        ((1000,), 250, 4),  # chunks that divide the leading axis
        ((1001,), 300, 4),  # ragged: the last chunk overlaps its predecessor
        ((999,), 100, 10),
        ((60, 8), 160, 3),  # 2-D: whole rows a chunk, dividing
        ((61, 8), 160, 4),  # 2-D ragged
        ((5, 8), 4, 5),  # a row alone above the cap: one row a chunk
    ],
)
def test_gather_rows_equals_plain_gather(shape, cap, chunks, monkeypatch):
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    if cap is not None:
        monkeypatch.setattr(ops, "GATHER_CAP", cap)
    rng = np.random.default_rng(sum(shape))
    table = rng.standard_normal(777).astype(np.float32)
    idx = rng.integers(0, table.size, shape).astype(np.int32)
    assert ops.gather_chunks(shape) == chunks
    got = jax.jit(ops.gather_rows)(table, idx)
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(got), table[idx])


def test_runner_at_webgoogle_shape_keeps_its_gather_whole(monkeypatch):
    """At web-Google's 5,105,039 edges the segment runner's gather is under
    the cap: its lowering is the one it has with no cap at all, with one
    while (the iteration loop).  A cap under the edge count adds the
    chunk loop."""
    import jax
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    n, e = 875_713, 5_105_039

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    dg = ops.DeviceGraph(
        src=shape(e, dtype=jnp.int32), dst=shape(e, dtype=jnp.int32),
        inv_outdeg=shape(n), dangling=shape(n), has_outlinks=shape(n),
        indptr=shape(n + 1, dtype=jnp.int32),
    )
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float32")

    def lowered(cap):
        monkeypatch.setattr(ops, "GATHER_CAP", cap)
        return ops.make_pagerank_runner(n, cfg).lower(
            dg, shape(n), shape(n)).as_text()

    today = lowered(ops.GATHER_CAP)
    assert ops.gather_chunks((e,)) == 1
    assert today == lowered(1 << 62)
    assert today.count("stablehlo.while") == 1
    assert lowered(1 << 20).count("stablehlo.while") == 2


def test_one_chip_chunked_gather_keeps_ranks(monkeypatch):
    """The segment SpMV's gather in chunks gives the ranks of the whole
    gather, bit for bit, and the put_graph record counts the chunks."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    g = synthetic_powerlaw(300, 1500, seed=3)
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform")
    whole = run_pagerank(g, cfg)
    monkeypatch.setattr(ops, "GATHER_CAP", 200)
    chunked = run_pagerank(g, cfg)
    np.testing.assert_array_equal(chunked.ranks, whole.ranks)
    (rec,) = [r for r in chunked.metrics.records if r.get("event") == "put_graph"]
    assert rec["gather_chunks"] == ops.gather_chunks((g.n_edges,)) > 1


@pytest.mark.parametrize("n", [0, 1, 5, 512, 513, 128 * 9, 40_001])
def test_cumsum_blocked_matches_jnp(n):
    """The MXU-blocked prefix sum must agree with jnp.cumsum for every
    length class: empty, below the recursion base, exact multiples of the
    block, stragglers, and multi-level recursion."""
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.ops.pagerank import cumsum_blocked

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float64))
    np.testing.assert_allclose(
        np.asarray(cumsum_blocked(x)), np.cumsum(np.asarray(x)),
        rtol=1e-12, atol=1e-12,
    )


def test_spark_default_config_shape():
    """Reference defaults: 20 iters, d=0.85, init ONE, drop (BASELINE.json:7)."""
    cfg = PageRankConfig()
    assert cfg.iterations == 20 and cfg.damping == 0.85
    g = _graph(EDGES_SMALL)
    res = pagerank(g, cfg)
    assert res.iterations == 20
    assert res.ranks.shape == (g.n_nodes,)


def test_personalize_duplicate_ids_mass():
    """Duplicate restart ids must accumulate, not overwrite: e sums to 1."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops.pagerank import restart_vector

    cfg = PageRankConfig(personalize=(3, 3, 5), dtype="float64")
    e = restart_vector(10, cfg)
    assert e.sum() == 1.0
    assert e[3] == 2 / 3 and e[5] == 1 / 3


def test_from_edges_large_noncompact_ids():
    """Dedup must be overflow-safe for big raw ids under compact_ids=False."""
    big = 2**30
    g = from_edges(np.array([big - 2, big - 2]), np.array([big - 1, big - 1]),
                   compact_ids=True)
    assert g.n_edges == 1  # duplicate removed


def test_zero_iterations():
    g = _graph(EDGES_SMALL)
    res = pagerank(g, iterations=0)
    np.testing.assert_allclose(res.ranks, 1.0)


def test_personalize_uses_original_node_ids():
    """SNAP inputs have id gaps; --personalize takes ORIGINAL ids and must
    hit exactly those nodes after compaction."""
    # ids 10, 20, 30, 40 — compacted to rows 0..3
    edges = [(10, 20), (20, 30), (30, 10), (40, 10)]
    g = _graph(edges)
    res = pagerank(g, iterations=200, tol=1e-12, dangling="redistribute",
                   init="uniform", personalize=(30,), dtype="float64")
    G = nx.DiGraph(edges)
    want = nx.pagerank(G, alpha=0.85, personalization={30: 1.0}, tol=1e-12,
                       max_iter=500)
    got = {int(g.node_ids[i]): res.ranks[i] for i in range(g.n_nodes)}
    for node, w in want.items():
        assert abs(got[node] - w) < 1e-9

    with pytest.raises(ValueError, match="not present"):
        pagerank(g, iterations=5, personalize=(15,))


@pytest.mark.parametrize("impl", ["cumsum", "pallas"])
def test_spark_exact_rejects_prefix_sum_impls(impl):
    with pytest.raises(ValueError, match="spark_exact requires"):
        PageRankConfig(spark_exact=True, dangling="drop", spmv_impl=impl)


def test_pallas_cumsum_multi_chunk_carry(monkeypatch):
    """The Pallas kernel's scalar carry must thread the prefix sum across
    grid steps; shrink the chunk so a modest graph spans several chunks."""
    import jax.numpy as jnp

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_CHUNK", 1024)
    pk.cumsum_pallas.clear_cache()
    try:
        g = synthetic_powerlaw(800, 5000, seed=11)
        dg = ops.put_graph(g, "float64")
        w = jnp.asarray(np.random.default_rng(2).random(g.n_nodes))
        ref = ops.spmv_segment(dg, w, g.n_nodes)
        got = pk.spmv_pallas(dg.src, dg.indptr, w, n=g.n_nodes, interpret=True)
        assert int(np.ceil(g.n_edges / 1024)) > 3  # really multi-chunk
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-9)
    finally:
        pk.cumsum_pallas.clear_cache()


# TPU lowering pins (incl. the Mosaic pipeline for the Pallas kernel) live
# in tests/test_tpu_lowering.py.
