"""Distributed correctness on 8 simulated devices (SURVEY.md §4): the key
test is chip-count invariance — same ranks/weights on 1, 2, 4, 8 devices —
over the real psum/ppermute/shard_map code paths."""

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
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import pagerank_sharded as ps

CFG = PageRankConfig(
    iterations=30, dangling="redistribute", init="uniform", dtype="float64"
)


@pytest.fixture(scope="module")
def graph():
    return synthetic_powerlaw(500, 3000, seed=42)


def _uniform_graph(n=500, k=6, seed=7):
    """Every node takes at most ``k`` in-arcs, under the smallest dense row
    width (8): the replicated layout plans no head."""
    rng = np.random.default_rng(seed)
    return from_edges(rng.integers(0, n, n * k), np.repeat(np.arange(n), k))


# (layout, graph): the replicated layout with its dense head, without one,
# and the owned slices
LAYOUTS = {"hybrid": ("hybrid", "powerlaw"),
           "hybrid-headless": ("hybrid", "uniform"),
           "owned": ("owned", "powerlaw")}
MODES = {
    "redistribute": CFG,
    "drop-one": PageRankConfig(iterations=10, dtype="float64"),
    "personalized": PageRankConfig(
        iterations=40, dangling="redistribute", init="uniform",
        personalize=(3, 17), dtype="float64",
    ),
}


@pytest.fixture(scope="module")
def graphs(graph):
    return {"powerlaw": graph, "uniform": _uniform_graph()}


def _layout(graphs, name):
    strategy, which = LAYOUTS[name]
    g = graphs[which]
    # the fixture gives the layout it is named for
    assert (ps.plan_partition(g, 4).head[0] > 0) is (which == "powerlaw"), name
    return strategy, g


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_chip_count_invariance(graphs, layout, n_devices, mode):
    """Same ranks as one chip on 1, 2, 4 and 8 devices, under the
    semantics flags: dangling mass redistributed from a uniform start,
    the Spark convention (dangling mass dropped, ranks start at one) and
    a personalized restart."""
    strategy, g = _layout(graphs, layout)
    cfg = MODES[mode]
    base = run_pagerank(g, cfg).ranks
    res = run_pagerank_sharded(g, cfg, n_devices=n_devices, strategy=strategy)
    assert np.abs(res.ranks - base).sum() <= 1e-9


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_sharded_tolerance(graphs, layout):
    """A tolerance run stops with its delta under ``tol``.  The owned
    layout's convergence gauge rides the head psum one step late, so it
    stops at most one iteration after the one-chip run would."""
    strategy, g = _layout(graphs, layout)
    cfg = PageRankConfig(
        iterations=500, tol=1e-10, dangling="redistribute", init="uniform",
        dtype="float64",
    )
    one = run_pagerank(g, cfg)
    res = run_pagerank_sharded(g, cfg, n_devices=4, strategy=strategy)
    assert res.iterations < 500
    assert res.l1_delta <= 1e-10
    assert res.iterations <= one.iterations + 2


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_sharded_checkpoint_resume(graphs, layout, tmp_path):
    strategy, g = _layout(graphs, layout)
    ckdir = str(tmp_path / "ck")
    full = run_pagerank_sharded(g, CFG, n_devices=4, strategy=strategy)
    partial = PageRankConfig(
        iterations=10, dangling="redistribute", init="uniform", dtype="float64",
        checkpoint_every=5, checkpoint_dir=ckdir,
    )
    run_pagerank_sharded(g, partial, n_devices=4, strategy=strategy)
    resume_cfg = PageRankConfig(
        iterations=30, dangling="redistribute", init="uniform", dtype="float64",
        checkpoint_every=5, checkpoint_dir=ckdir,
    )
    res = run_pagerank_sharded(g, resume_cfg, n_devices=4, strategy=strategy,
                               resume=True)
    np.testing.assert_allclose(res.ranks, full.ranks, atol=1e-12)


def test_partition_edges_balanced():
    """The headless replicated layout deals equal contiguous slices of
    the dst-sorted arcs: every device's slice is full but the last."""
    g = _uniform_graph()
    sg = partition_graph(g, 8, strategy="hybrid")
    assert (sg.head_src == sg.n_pad).all()  # no head
    assert int(sg.valid.sum()) == g.n_edges
    assert sg.pad_frac < 8 / max(g.n_edges, 1) + 0.01
    assert (np.diff(sg.dst.ravel()[sg.valid.ravel() > 0]) >= 0).all()


def _head_graph(hub_in: int):
    """A hub taking ``hub_in`` in-arcs beside 1,000 arcs spread over 2,000
    nodes (in-degree under the row width): the head is the hub alone."""
    rng = np.random.default_rng(0)
    dst = np.concatenate([np.zeros(hub_in, np.int64), np.arange(1, 1001)])
    src = np.concatenate([rng.permutation(np.arange(1, 2000))[:hub_in],
                          rng.integers(0, 2000, 1000)])
    return from_edges(src, dst, compact_ids=False)


def _weighted(g):
    w = np.random.default_rng(1).uniform(0.5, 2.0, g.n_edges)
    return from_edges(g.src, g.dst, weight=w, compact_ids=False)


# graph, devices, per-chip budget -> the layout auto picks, and whether
# that hybrid layout plans a dense head (None: ValueError)
AUTO_TABLE = {
    "head-28pct-d4": ("head400", 4, None, "hybrid", True),
    "head-28pct-d8": ("head400", 8, None, "hybrid", True),
    "head-17pct-d4": ("head200", 4, None, "hybrid", False),
    "weighted-d8": ("weighted400", 8, None, "hybrid", False),
    "fits-d3": ("head400", 3, None, "hybrid", True),
    "starved-d4": ("head400", 4, 10_000, "owned", None),
    "starved-d8": ("head400", 8, 10_000, "owned", None),
    "starved-weighted-d8": ("weighted400", 8, 10_000, "owned", None),
    "starved-d3": ("head400", 3, 10_000, None, None),
    "starved-d6": ("head400", 6, 10_000, None, None),
}


@pytest.mark.parametrize("case", list(AUTO_TABLE))
def test_auto_select_strategy(case):
    """``auto``: ``hybrid`` while the replicated state fits half a chip,
    else ``owned`` on a power-of-two mesh, else an error naming the
    sizes; ``hybrid`` keeps a dense head only for an unweighted graph
    whose head covers a quarter of the arcs (400 of 1,400: yes; 200 of
    1,200: no)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        auto_select_strategy,
    )

    which, d, hbm, want, has_head = AUTO_TABLE[case]
    hub = int(which[-3:])
    g = _head_graph(hub)
    if which.startswith("weighted"):
        g = _weighted(g)
    if want is None:
        with pytest.raises(ValueError, match="power-of-two") as err:
            auto_select_strategy(g, d, hbm_bytes=hbm)
        assert f"{d} devices" in str(err.value)
        return
    assert auto_select_strategy(g, d, hbm_bytes=hbm) == want
    if want == "hybrid":
        plan = ps.plan_partition(g, d, strategy="hybrid")
        assert (plan.head[0] > 0) is has_head, plan.head


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
