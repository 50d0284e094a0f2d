"""The resident sharded graph (``ShardedPageRank``) and the scan reduce on
each shard, on a 4-device mesh of the 8 simulated CPU devices: jobs on
one resident object match ``run_pagerank_sharded`` and the one-chip path,
a second job partitions, puts and compiles nothing, and with their CSR
pointers the shards' segment sums lower to no scatter, however a node's
edges fall across the device slices."""

import re

import jax
import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import PageRankConfig
from page_rank_and_tfidf_using_apache_spark_tpu.io import from_edges, synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
    ShardedPageRank,
    make_mesh,
    partition_graph,
    run_pagerank_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import pagerank_sharded as ps

CFG = PageRankConfig(
    iterations=30, dangling="redistribute", init="uniform", dtype="float64"
)
POINTER_STRATEGIES = ["edges", "nodes", "nodes_balanced", "src", "src_ring", "hybrid"]


@pytest.fixture(scope="module")
def graph():
    # 4 devices hold ~3,000 edges each: more than one 512-edge row, so
    # each shard's segment sum takes the scan
    return synthetic_powerlaw(2000, 12000, seed=42)


@pytest.fixture(scope="module")
def single_chip_ranks(graph):
    return run_pagerank(graph, CFG).ranks


def _records(metrics, event):
    return [r for r in metrics.records if r.get("event") == event]


@pytest.mark.parametrize("strategy", ["edges", "hybrid", "owned", "auto"])
def test_resident_jobs_match_one_shot_and_one_chip(graph, single_chip_ranks, strategy):
    job = ShardedPageRank(graph, CFG, n_devices=4, strategy=strategy)
    first, second = job.run(), job.run()
    once = run_pagerank_sharded(graph, CFG, n_devices=4, strategy=strategy)
    for res in (first, second):
        assert res.iterations == 30
        assert np.abs(res.ranks - once.ranks).sum() <= 1e-12
        assert np.abs(res.ranks - single_chip_ranks).sum() <= 1e-9
    (part,) = _records(job.metrics, "partition")
    assert part["segment_reduce"] == ("scatter" if job.strategy == "owned" else "scan")
    jobs = _records(job.metrics, "sharded_job")
    assert [j["iterations"] for j in jobs] == [30, 30]
    assert all(j["strategy"] == job.strategy and j["devices"] == 4 for j in jobs)


@pytest.mark.parametrize("strategy", ["hybrid", "owned"])
def test_second_job_partitions_puts_and_compiles_nothing(graph, strategy):
    compiles = []

    def listen(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        job = ShardedPageRank(graph, CFG, n_devices=4, strategy=strategy)
        dev = job.exec.dev
        job.compile()
        n_compiled = len(compiles)
        assert n_compiled >= 1
        first = job.run().ranks
        second = job.run().ranks
        assert len(compiles) == n_compiled  # neither job compiled
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert job.exec.dev is dev  # the graph arrays put once
    assert len(_records(job.metrics, "partition")) == 1
    np.testing.assert_array_equal(first, second)



@pytest.mark.parametrize("strategy", ["hybrid", "owned"])
def test_sharded_job_dispatches_under_its_span(graph, strategy):
    """Each job dispatches under ``pagerank.dispatch`` and syncs under
    ``pagerank.delta_sync``, both inside its ``pagerank.sharded_job``
    span: the first job's fresh runner compiles inside the dispatch, the
    second job compiles nothing."""
    from page_rank_and_tfidf_using_apache_spark_tpu import obs

    obs.watch_compiles()
    sink = obs.MemorySink()
    obs.bus().attach(sink)
    try:
        job = ShardedPageRank(graph, CFG, n_devices=4, strategy=strategy)
        job.run()
        job.run()
    finally:
        obs.bus().detach(sink)
    ends = sink.of_kind("span_end")
    parent = {e["span"]: e["parent"] for e in ends}

    def within(e, span):
        at = e["parent"]
        while at is not None and at != span:
            at = parent.get(at)
        return at == span

    jobs = [e["span"] for e in ends if e["name"] == "pagerank.sharded_job"]
    dispatch = [e for e in ends if e["name"] == "pagerank.dispatch"]
    sync = [e for e in ends if e["name"] == "pagerank.delta_sync"]
    assert len(jobs) == len(dispatch) == len(sync) == 2
    for i, span in enumerate(jobs):
        assert within(dispatch[i], span) and within(sync[i], span)
    compiles = [e for e in ends if e["name"] == "jax.compile"]
    assert [e for e in compiles if within(e, dispatch[0]["span"])]
    assert not [e for e in compiles if within(e, jobs[1])]


def test_size_bucket_pads_under_a_thousandth():
    for x in (1, 7, 2047, 2049, 4_265_201, 130_900_447, 130_901_943):
        b = ps._size_bucket(x)
        assert x <= b <= x + x / 1024 and ps._size_bucket(b) == b
    # two graph500-25 draws' tail slices share one width, in whole tiles
    assert ps._size_bucket(130_900_447) == ps._size_bucket(130_901_943) == 130_940_928


@pytest.mark.parametrize("strategy", ["edges", "hybrid"])
def test_replicated_layouts_share_shapes_across_near_graphs(strategy):
    """Graphs a few arcs apart (two draws of one generator) partition to
    the same shapes under the replicated layouts, so one compiled program
    serves both."""
    g = synthetic_powerlaw(50_000, 400_000, seed=8)
    near = from_edges(g.src[2:], g.dst[2:], compact_ids=False)
    assert near.n_nodes == g.n_nodes and near.n_edges == g.n_edges - 2
    a = partition_graph(g, 4, strategy=strategy, dtype="float32")
    b = partition_graph(near, 4, strategy=strategy, dtype="float32")
    mesh = make_mesh(4)
    assert [x.shape for x, _ in ps.sharded_graph_layout(a, mesh)] == \
        [x.shape for x, _ in ps.sharded_graph_layout(b, mesh)]
    assert a.src.shape[1] == ps._size_bucket(a.src.shape[1]) > -(-int((a.valid > 0).sum()) // 4)

def _scatter_updates(text: str) -> list[int]:
    """The update lengths of each 1-D scatter in lowered StableHLO."""
    return [int(m) for m in re.findall(
        r"\(tensor<\d+xf\d+>, tensor<\d+x1xi32>, tensor<(\d+)xf\d+>\) -> tensor<\d+xf\d+>",
        text)]


def _lowered(sg, mesh):
    cfg = PageRankConfig(iterations=3, dangling="redistribute", init="uniform",
                         dtype="float32")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            for a, sh in ps.sharded_graph_layout(sg, mesh)]
    vec = jax.ShapeDtypeStruct((sg.n_pad,), np.float32)
    with jax.enable_x64(False):
        return ps.make_sharded_runner(sg, cfg, mesh).lower(vec, *args, vec).as_text()


@pytest.mark.parametrize("strategy", POINTER_STRATEGIES)
def test_shard_reduce_lowers_without_scatter(graph, strategy):
    """Through the slices' CSR pointers no shard scatters its segment sum.
    ``hybrid``'s one scatter left is its head rows' sums, one per dense
    row of the device."""
    mesh = make_mesh(4)
    sg = partition_graph(graph, 4, strategy=strategy, dtype="float32")
    assert ps.shard_segment_reduce(sg, "segment") == "scan"
    head = [sg.head_node.shape[1]] if strategy == "hybrid" else []
    assert _scatter_updates(_lowered(sg, mesh)) == head


def _hub_graph():
    """Node 7 takes 8,000 in-edges, more than a device's slice of the
    ~24,000 edges; the other nodes' runs are short, so slice boundaries
    fall inside runs of the hub and of small nodes."""
    rng = np.random.default_rng(5)
    n = 20_000
    src = np.concatenate([rng.permutation(n)[:8000], rng.integers(0, n, 16_000)])
    dst = np.concatenate([np.full(8000, 7), rng.integers(0, n, 16_000)])
    return from_edges(src, dst, compact_ids=False)


@pytest.mark.parametrize("strategy", ["edges", "hybrid"])
def test_ragged_slices_hub_spans_devices(strategy):
    g = _hub_graph()
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float64", head_coverage=0.05)
    sg = partition_graph(g, 4, strategy=strategy, dtype="float64")
    real = sg.valid > 0
    dst = np.where(real, sg.dst, -1)
    hub_devices = int((dst == 7).any(axis=1).sum())
    if strategy == "edges":
        assert hub_devices >= 2  # the hub's run spans devices
    # some device's slice starts inside a run its left neighbour holds
    starts_mid_run = [i for i in range(1, 4)
                      if real[i, 0] and real[i - 1, -1] and sg.dst[i, 0] == sg.dst[i - 1, -1]]
    assert starts_mid_run
    # each slice's pointers reproduce its own per-node sums
    vals = np.random.default_rng(1).random(sg.dst.shape) * sg.valid
    for i in range(4):
        ip = sg.local_indptr[i].astype(np.int64)
        c = np.concatenate([[0.0], np.cumsum(vals[i])])
        want = np.bincount(sg.dst[i], weights=vals[i], minlength=sg.n_pad)
        np.testing.assert_allclose(c[ip[1:]] - c[ip[:-1]], want, rtol=0, atol=1e-11)
    base = run_pagerank(g, cfg).ranks
    res = ShardedPageRank(g, cfg, n_devices=4, strategy=strategy).run()
    assert np.abs(res.ranks - base).sum() <= 1e-12


def test_scan_reduce_holds_float32(graph):
    """float32 ranks through the shards' scans stay as close to float64 as
    the one-chip path's."""
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float32")
    exact = run_pagerank(graph, PageRankConfig(
        iterations=20, dangling="redistribute", init="uniform", dtype="float64")).ranks
    with jax.enable_x64(False):
        one = run_pagerank(graph, cfg).ranks
        got = ShardedPageRank(graph, cfg, n_devices=4, strategy="edges").run().ranks
    assert np.abs(got - exact).sum() <= 3 * max(np.abs(one - exact).sum(), 1e-7)


@pytest.mark.parametrize("strategy", ["hybrid", "owned"])
def test_chunked_gathers_keep_ranks_bit_identical(graph, strategy, monkeypatch):
    """With a cap under each shard's tail and head index counts, every
    per-edge gather of the step runs in chunks (ragged ones included: 967
    tail arcs a device under ``hybrid``, 1,930 head slots under ``owned``),
    and the ranks are those of the whole gathers, bit for bit."""
    whole = ShardedPageRank(graph, CFG, n_devices=4, strategy=strategy)
    ranks = whole.run().ranks
    monkeypatch.setattr(ops, "GATHER_CAP", 100)
    chunked = ShardedPageRank(graph, CFG, n_devices=4, strategy=strategy)
    np.testing.assert_array_equal(chunked.run().ranks, ranks)
    (part,) = _records(whole.metrics, "partition")
    assert part["gather_chunks"] == {"tail": 1, "head": 1}
    (part,) = _records(chunked.metrics, "partition")
    assert part["gather_chunks"]["tail"] > 1 and part["gather_chunks"]["head"] > 1
