"""Distributed correctness on 8 simulated devices (SURVEY.md §4): the key
test is chip-count invariance — same ranks/weights on 1, 2, 4, 8 devices —
over the real psum/all_gather/shard_map code paths."""

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import PageRankConfig, TfidfConfig
from page_rank_and_tfidf_using_apache_spark_tpu.io import from_edges, synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf_streaming
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
    make_mesh,
    partition_graph,
    run_pagerank_sharded,
    run_tfidf_sharded,
)

CFG = PageRankConfig(
    iterations=30, dangling="redistribute", init="uniform", dtype="float64"
)


@pytest.fixture(scope="module")
def graph():
    return synthetic_powerlaw(500, 3000, seed=42)


@pytest.fixture(scope="module")
def single_chip_ranks(graph):
    return run_pagerank(graph, CFG).ranks


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
@pytest.mark.parametrize(
    "strategy",
    ["edges", "nodes", "nodes_balanced", "src", "src_ring", "hybrid",
     "owned"])
def test_chip_count_invariance(graph, single_chip_ranks, n_devices, strategy):
    res = run_pagerank_sharded(graph, CFG, n_devices=n_devices, strategy=strategy)
    assert np.abs(res.ranks - single_chip_ranks).sum() <= 1e-9


@pytest.mark.parametrize("impl", ["cumsum", "cumsum_mxu"])
@pytest.mark.parametrize(
    "strategy", ["edges", "nodes", "nodes_balanced", "src", "src_ring"])
def test_sharded_cumsum_impl_matches_single_chip(
        graph, single_chip_ranks, strategy, impl):
    """The scatter-free monotone-diff SpMVs must agree with segment_sum in
    every sharded layout (local_indptr correctness incl. padding slots —
    and the indptr must actually be BUILT for every prefix-sum impl)."""
    cfg = PageRankConfig(iterations=30, dangling="redistribute", init="uniform",
                         dtype="float64", spmv_impl=impl)
    res = run_pagerank_sharded(graph, cfg, n_devices=8, strategy=strategy)
    assert np.abs(res.ranks - single_chip_ranks).sum() <= 1e-9


def test_sharded_drop_and_one_init(graph):
    """Spark-convention flags work sharded too (init ONE, dangling drop)."""
    cfg = PageRankConfig(iterations=10, dtype="float64")
    base = run_pagerank(graph, cfg).ranks
    res = run_pagerank_sharded(graph, cfg, n_devices=4)
    assert np.abs(res.ranks - base).sum() <= 1e-9


def test_sharded_personalized(graph):
    cfg = PageRankConfig(
        iterations=40, dangling="redistribute", init="uniform",
        personalize=(3, 17), dtype="float64",
    )
    base = run_pagerank(graph, cfg).ranks
    res = run_pagerank_sharded(graph, cfg, n_devices=8, strategy="nodes")
    assert np.abs(res.ranks - base).sum() <= 1e-9


def test_sharded_tolerance(graph):
    cfg = PageRankConfig(
        iterations=500, tol=1e-10, dangling="redistribute", init="uniform",
        dtype="float64",
    )
    res = run_pagerank_sharded(graph, cfg, n_devices=4)
    assert res.iterations < 500
    assert res.l1_delta <= 1e-10


def test_sharded_checkpoint_resume(graph, tmp_path):
    ckdir = str(tmp_path / "ck")
    full = run_pagerank_sharded(graph, CFG, n_devices=4)
    partial = PageRankConfig(
        iterations=10, dangling="redistribute", init="uniform", dtype="float64",
        checkpoint_every=5, checkpoint_dir=ckdir,
    )
    run_pagerank_sharded(graph, partial, n_devices=4)
    resume_cfg = PageRankConfig(
        iterations=30, dangling="redistribute", init="uniform", dtype="float64",
        checkpoint_every=5, checkpoint_dir=ckdir,
    )
    res = run_pagerank_sharded(graph, resume_cfg, n_devices=4, resume=True)
    np.testing.assert_allclose(res.ranks, full.ranks, atol=1e-12)


def test_partition_edges_balanced(graph):
    sg = partition_graph(graph, 8, strategy="edges")
    # perfect balance: every device's slice is full except the last tail
    assert sg.pad_frac < 8 / max(graph.n_edges, 1) + 0.01
    assert (np.diff(sg.dst.ravel()[sg.valid.ravel() > 0]) >= 0).all()


@pytest.mark.parametrize("strategy", ["nodes", "nodes_balanced"])
def test_partition_nodes_covers_all_edges(graph, strategy):
    sg = partition_graph(graph, 8, strategy=strategy)
    assert int(sg.valid.sum()) == graph.n_edges
    # dst_local within block bounds
    assert (sg.dst >= 0).all() and (sg.dst < sg.block).all()
    # node_map is a bijection into per-device slots
    assert len(np.unique(sg.node_map)) == graph.n_nodes


def test_partition_nodes_balanced_evens_powerlaw_edges():
    """A hub-heavy graph: equal-node blocks concentrate in-edges on one
    device; equal-edge boundaries must spread them to near-parity."""
    rng = np.random.default_rng(0)
    # 2000 nodes; node 0..3 receive ~90% of all edges (celebrities)
    hubs = rng.integers(0, 4, 9000)
    tail = rng.integers(4, 2000, 1000)
    dst = np.concatenate([hubs, tail])
    src = rng.integers(0, 2000, dst.size)
    g = from_edges(src, dst)
    plain = partition_graph(g, 8, strategy="nodes")
    balanced = partition_graph(g, 8, strategy="nodes_balanced")

    def max_real_edges(sg):
        return int(sg.valid.sum(axis=1).max())

    # plain 'nodes' puts ~all hub edges on device 0; balanced caps a device
    # at roughly the largest single node's in-degree
    assert max_real_edges(balanced) <= max_real_edges(plain) / 2
    res_b = run_pagerank_sharded(
        g, PageRankConfig(iterations=15, dangling="redistribute",
                          init="uniform", dtype="float64"),
        n_devices=8, strategy="nodes_balanced",
    )
    res_1 = run_pagerank(
        g, PageRankConfig(iterations=15, dangling="redistribute",
                          init="uniform", dtype="float64"),
    )
    assert np.abs(res_b.ranks - res_1.ranks).sum() <= 1e-9


def test_partition_src_covers_all_edges(graph):
    sg = partition_graph(graph, 8, strategy="src")
    assert int(sg.valid.sum()) == graph.n_edges
    # sources are block-local; destinations are global padded ids, sorted
    # per device row (pads at n_pad-1 keep the tail sorted)
    assert (sg.src >= 0).all() and (sg.src < sg.block).all()
    assert all((np.diff(row) >= 0).all() for row in sg.dst)


def test_ring_reduce_scatter_matches_psum_scatter():
    """The explicit ppermute-ring exchange must agree with XLA's
    psum_scatter bit-for-bit in f64 on every mesh size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import collectives as coll

    rng = np.random.default_rng(3)
    for d in (1, 2, 4, 8):
        mesh = make_mesh(d)
        axis = mesh.axis_names[0]
        x = rng.random((d, d * 16))  # one [D*B] partial per device
        ring = shard_map(
            lambda v: coll.ring_reduce_scatter(v[0], axis)[None, :],
            mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None),
            check_vma=False,
        )
        ref = shard_map(
            lambda v: coll.reduce_scatter(v[0], axis)[None, :],
            mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None),
            check_vma=False,
        )
        got = np.asarray(jax.jit(ring)(x))
        want = np.asarray(jax.jit(ref)(x))
        np.testing.assert_allclose(got, want, atol=1e-12)
        # and both equal the plain sum-then-shard
        np.testing.assert_allclose(
            got.ravel(), x.sum(axis=0), atol=1e-12)


def test_auto_select_strategy(graph, single_chip_ranks):
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        auto_select_strategy,
    )

    # hub-heavy powerlaw graph, generous budget -> degree-aware 'hybrid'
    # (the no-head and starved-budget pins live in test_hybrid_spmv.py)
    assert auto_select_strategy(graph, 8) == "hybrid"
    # starved budget -> the owned-slices layout (ISSUE 15: replicated-
    # state-doesn't-fit is the owned trigger)
    assert auto_select_strategy(graph, 8, hbm_bytes=10_000) == "owned"
    res = run_pagerank_sharded(graph, CFG, n_devices=4, strategy="auto")
    assert any(r.get("event") == "auto_strategy" for r in res.metrics.records)
    assert np.abs(res.ranks - single_chip_ranks).sum() <= 1e-9


def test_spark_exact_sharded_raises(graph):
    cfg = PageRankConfig(iterations=2, spark_exact=True)
    with pytest.raises(NotImplementedError):
        run_pagerank_sharded(graph, cfg, n_devices=2)


def test_tfidf_sharded_matches_streaming():
    docs = [f"w{i % 7} w{i % 3} common tail{i}" for i in range(40)]
    chunks = [docs[i : i + 5] for i in range(0, 40, 5)]
    cfg = TfidfConfig(vocab_bits=12, idf_mode="smooth", l2_normalize=True)
    base = run_tfidf_streaming(iter(chunks), cfg)
    for d in (2, 8):
        out = run_tfidf_sharded(iter(chunks), cfg, n_devices=d)
        assert out.n_docs == base.n_docs
        np.testing.assert_array_equal(out.df, base.df)
        np.testing.assert_allclose(out.to_dense(), base.to_dense(), atol=1e-6)


def test_tfidf_sharded_uneven_tail():
    """Last super-chunk smaller than the device count must still work."""
    docs = [f"a b c d{i}" for i in range(11)]
    chunks = [docs[i : i + 2] for i in range(0, 11, 2)]  # 6 chunks, d=4
    cfg = TfidfConfig(vocab_bits=10)
    base = run_tfidf_streaming(iter(chunks), cfg)
    out = run_tfidf_sharded(iter(chunks), cfg, n_devices=4)
    np.testing.assert_allclose(out.to_dense(), base.to_dense(), atol=1e-6)


def test_make_mesh_too_many_devices():
    with pytest.raises(ValueError, match="available"):
        make_mesh(99)
