"""Owned rank slices + sparse boundary exchange (ISSUE 15).

Covers the tentpole and its satellites: boundary-planner properties
(every cut edge covered exactly once, pad gauges pinned at web-Google
scale), chip-count invariance and semantics flags under ``strategy=
'owned'``, weighted-edge PageRank (networkx-oracle-pinned, owned
included), the elastic shrink ladder 4->2->1 with re-owned slices and
rebuilt boundary sets, the exact-count Zipf generator, sharded HITS /
connected components / query-sharded PPR equivalence pins, the per-step
comm-bytes gauge with its sublinear scaling, and the trace_diff comm
regression gate.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.components import (
    run_components,
)
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.hits import run_hits
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.partition import (
    OwnedArray,
)
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.ppr import (
    run_ppr_batch,
)
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
    from_edges,
    synthetic_powerlaw,
    synthetic_zipf,
)
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import (
    run_pagerank,
)
from page_rank_and_tfidf_using_apache_spark_tpu.ops import boundary as ob
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
    partition_graph,
    plan_partition,
    run_pagerank_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.workloads_sharded import (
    build_owned_pair,
    run_components_sharded,
    run_hits_sharded,
    run_ppr_sharded,
    transpose_graph,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos, elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    ComponentsConfig,
    GRAFT_ENV_KNOBS,
    HitsConfig,
    PageRankConfig,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
    MetricsRecorder,
)

REPO = Path(__file__).resolve().parents[1]

F64 = dict(dangling="redistribute", init="uniform", dtype="float64")
F32 = dict(dangling="redistribute", init="uniform", dtype="float32")


@pytest.fixture(autouse=True)
def _fresh_health():
    elastic.reset_health()
    yield
    elastic.reset_health()


@pytest.fixture(scope="module")
def graph():
    return synthetic_powerlaw(600, 3600, seed=33)


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- boundary planner props


def _numpy_owned_step(graph, shard, ranks_g):
    """One owned iteration simulated in PURE numpy from the materialized
    shard arrays — exchange, lookup, both segment combines, psum — so the
    per-edge index construction is verified against a dense reference,
    not against itself."""
    d, block, h_pad = shard.d, shard.block, shard.h_pad
    strength = graph.out_strength()
    inv = np.where(strength > 0, 1.0 / np.where(strength > 0, strength, 1), 0)
    tail, head = ob.split_global(shard, ranks_g * inv, "float64")
    # the exchange: every owner's packed boundary buffer, all-gathered
    btable = np.concatenate([
        tail[j * block:(j + 1) * block][shard.out_idx[j]] for j in range(d)
    ])
    contribs = np.zeros(graph.n_nodes)
    hbuf = np.zeros(h_pad + 2)
    for i in range(d):
        local = tail[i * block:(i + 1) * block]
        lk = np.concatenate([local, btable, head, [0.0]])
        per = lk[shard.tail_src_idx[i]] * shard.tail_w[i]
        # tail combine into this device's owned rows
        tgt = np.zeros(block)
        np.add.at(tgt, shard.tail_dst[i], per)
        mask = shard.tail_map >= 0
        slots = shard.tail_map[mask]
        sel = (slots >= i * block) & (slots < (i + 1) * block)
        ids = np.flatnonzero(mask)[sel]
        contribs[ids] += tgt[slots[sel] - i * block]
        # head partial (summed across devices = the psum)
        perh = lk[shard.head_src_idx[i]] * shard.head_w[i]
        np.add.at(hbuf, shard.head_slot[i], perh)
    contribs[shard.head_ids] += hbuf[: shard.h]
    # dense reference: contribs[v] = sum_u w(u,v) * ranks[u] / s(u)
    ref = np.zeros(graph.n_nodes)
    w = graph.weight if graph.weight is not None else np.ones(graph.n_edges)
    np.add.at(ref, graph.dst, (ranks_g * inv)[graph.src] * w)
    return contribs, ref


@pytest.mark.parametrize("d", [2, 4, 8])
def test_boundary_covers_every_cut_edge(graph, d):
    """The money property: the numpy-simulated owned step (exchange +
    host-precomputed lookup indices + both combines) reproduces the dense
    SpMV exactly — every cut edge is covered through the boundary table,
    none twice."""
    plan = plan_partition(graph, d, strategy="owned")
    shard = ob.build_owned_shard(graph, plan.owned, "float64")
    rng = np.random.default_rng(1)
    ranks = rng.random(graph.n_nodes)
    got, ref = _numpy_owned_step(graph, shard, ranks)
    np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-12)


def test_boundary_sets_unique_and_remote_only(graph):
    """No double-count: each owner's boundary set is strictly sorted
    (unique), contains only nodes that owner owns, and every member IS
    read by some other device (no dead freight in the exchange)."""
    plan = plan_partition(graph, 4, strategy="owned")
    ow = plan.owned
    n = graph.n_nodes
    starts = np.concatenate([[0], np.cumsum(ow.boundary_counts)])
    shard = ob.build_owned_shard(graph, ow, "float32")
    for j in range(4):
        seg = ow.boundary_keys[starts[j]:starts[j + 1]]
        srcs = seg - j * np.int64(n)
        assert (np.diff(seg) > 0).all()  # unique within the owner
        # owned by j: padded slot falls inside j's block
        slots = shard.tail_map[srcs]
        assert ((slots >= j * shard.block)
                & (slots < (j + 1) * shard.block)).all()
    # every boundary member is actually referenced by a remote reader:
    # the lookup-index space region [block, block + d*b_pad) of OTHER
    # devices must name each packed position at least once
    referenced = set()
    for i in range(4):
        for idx in (shard.tail_src_idx[i].ravel(),
                    shard.head_src_idx[i].ravel()):
            inb = idx[(idx >= shard.block)
                      & (idx < shard.block + 4 * shard.b_pad)]
            referenced.update((inb - shard.block).tolist())
    expect = {
        int(j * shard.b_pad + p)
        for j in range(4) for p in range(int(ow.boundary_counts[j]))
    }
    assert expect <= referenced


def test_owned_plan_pinned_at_webgoogle_scale():
    """Plan-gauge pin at web-Google scale (875k nodes / 5.1M edges, the
    bench graph): edge-slot padding is the ceil remainder (~3e-6) and the
    boundary buffers stay under 20% padding — the numbers the tier-3
    ceiling budgets must keep honest."""
    g = synthetic_powerlaw(875_000, 5_100_000, seed=7)
    p = plan_partition(g, 8, strategy="owned")
    assert p.pad_frac < 1e-5
    assert p.owned.boundary_pad_frac == pytest.approx(0.1785, rel=0.02)
    assert p.owned.h == 4096  # the max_head cap binds on this graph
    assert p.comm_entries_per_step == pytest.approx(924_675, rel=0.02)


def test_owned_partition_covers_all_edges(graph):
    """Slot accounting: real (nonzero-coefficient) edge slots across both
    edge classes equal the edge count exactly."""
    sg = partition_graph(graph, 8, strategy="owned")
    sh = sg.owned
    real = int((sh.tail_w != 0).sum() + (sh.head_w != 0).sum())
    assert real == graph.n_edges


# ------------------------------------------- owned PageRank equivalence


def test_owned_chip_count_invariance(graph):
    cfg = PageRankConfig(iterations=30, **F64)
    base = run_pagerank(graph, cfg).ranks
    for d in (1, 2, 4, 8):
        res = run_pagerank_sharded(graph, cfg, n_devices=d, strategy="owned")
        assert np.abs(res.ranks - base).sum() <= 1e-9, d


def test_owned_rejects_cumsum_impl(graph):
    cfg = PageRankConfig(iterations=2, spmv_impl="cumsum", **F64)
    with pytest.raises(NotImplementedError, match="segment"):
        run_pagerank_sharded(graph, cfg, n_devices=2, strategy="owned")


def test_owned_rejects_non_pow2_devices(graph):
    """The boundary butterfly is recursive doubling — a non-pow2 mesh
    must be rejected at plan time, not deep inside shard_map tracing."""
    with pytest.raises(ValueError, match="power-of-two"):
        plan_partition(graph, 3, strategy="owned")


# --------------------------------------------------- weighted PageRank


def _weighted_graph(n=250, e=2000, seed=3):
    rng = np.random.default_rng(seed)
    return from_edges(
        rng.integers(0, n, e), rng.integers(0, n, e),
        weight=rng.uniform(0.2, 3.0, e),
    )


def test_weighted_oracle_networkx_all_impls():
    """Weighted-edge PageRank pinned against ``networkx.pagerank(
    weight=)`` for every single-chip SpMV impl — the last unopened
    workload from the original list."""
    nx = pytest.importorskip("networkx")
    g = _weighted_graph()
    G = nx.DiGraph()
    G.add_nodes_from(int(i) for i in g.node_ids)
    for s, d2, w in zip(g.src, g.dst, g.weight):
        G.add_edge(int(g.node_ids[s]), int(g.node_ids[d2]), weight=float(w))
    pr = nx.pagerank(G, alpha=0.85, tol=1e-12, max_iter=500, weight="weight")
    want = np.array([pr[int(i)] for i in g.node_ids])
    for impl in ("segment", "bcoo", "cumsum", "cumsum_mxu", "hybrid",
                 "sort_shuffle", "pallas"):
        cfg = PageRankConfig(iterations=200, spmv_impl=impl, **F64)
        res = run_pagerank(g, cfg)
        assert np.abs(res.ranks - want).max() < 1e-8, impl


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
@pytest.mark.parametrize("strategy", ["owned", "hybrid"])
def test_weighted_sharded_matches_single_chip(strategy, n_devices):
    """Weights ride in the owned coefficient arrays, and in the headless
    replicated layout's tail coefficients."""
    g = _weighted_graph()
    cfg = PageRankConfig(iterations=30, **F64)
    base = run_pagerank(g, cfg).ranks
    res = run_pagerank_sharded(g, cfg, n_devices=n_devices, strategy=strategy)
    assert np.abs(res.ranks - base).sum() <= 1e-9


def test_weighted_sharded_hybrid_refuses(graph):
    """The replicated layout refuses a weighted graph a dense head (its
    rows carry no weights): every arc goes to the tail, whose ``valid``
    slots carry the weights — though the same arcs unweighted plan one."""
    w = np.random.default_rng(4).uniform(0.2, 3.0, graph.n_edges)
    g = from_edges(graph.src, graph.dst, weight=w, compact_ids=False)
    assert plan_partition(graph, 2).head[0] > 0
    assert plan_partition(g, 2).head[0] == 0
    sg = partition_graph(g, 2, strategy="hybrid", dtype="float64")
    assert (sg.head_src == sg.n_pad).all()
    np.testing.assert_array_equal(np.sort(sg.valid[sg.valid > 0]),
                                  np.sort(g.weight))


def test_weight_dedup_sums_duplicates():
    g = from_edges([0, 0, 1], [1, 1, 0], weight=[1.0, 2.5, 4.0])
    assert g.n_edges == 2
    assert g.weight[g.src == 0][0] == pytest.approx(3.5)
    assert g.out_strength()[0] == pytest.approx(3.5)


def test_weight_must_be_positive():
    with pytest.raises(ValueError, match="positive"):
        from_edges([0], [1], weight=[0.0])


# --------------------------------------------------- elastic shrink 4->2->1


def test_owned_elastic_shrink_ladder_4_2_1(tmp_path):
    """Acceptance: stacked device losses walk the owned strategy down
    4 -> 2 -> 1 — each lap re-owns the slices and rebuilds the boundary
    sets from host state — converging to the uninterrupted ranks at
    1e-6, with zero reprocessed committed iterations and one mesh.shrink
    span per loss."""
    g = synthetic_powerlaw(900, 3600, seed=21)
    cfg = PageRankConfig(iterations=8, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **F32)
    base = run_pagerank(g, PageRankConfig(iterations=8, **F32))
    m = MetricsRecorder()
    obs.start_run("owned_elastic", str(tmp_path / "tr"))
    try:
        with chaos.inject(
            "pagerank_step:device_lost@dev:1;"
            "pagerank_elastic_rerun:device_lost@dev:2"
        ):
            res = run_pagerank_sharded(g, cfg, n_devices=4,
                                       strategy="owned", metrics=m)
    finally:
        obs.end_run()
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    assert res.iterations == 8
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [(d["devices_old"], d["devices_new"]) for d in degraded] == \
        [(4, 2), (2, 1)]
    assert [d["ladder"] for d in degraded] == ["mesh_shrink", "single_device"]
    # zero reprocessed committed iterations
    iters = [r["iter"] for r in m.records if "iter" in r and "l1_delta" in r]
    assert iters == sorted(set(iters))
    # re-owned slices: one partition per mesh shape, boundary sets rebuilt
    parts = [r for r in m.records if r.get("event") == "partition"]
    assert [p["devices"] for p in parts] == [4, 2, 1]
    assert all(p["comm_bytes_per_step"] is not None for p in parts)
    trace = next((tmp_path / "tr").glob("owned_elastic.*.trace.jsonl"))
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py")
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    rep = tr.report(str(trace))
    assert len(rep["mesh_shrinks"]) == 2  # one span per loss
    assert not rep["exhausted"]


def test_owned_device_loss_at_result_pull(tmp_path):
    g = synthetic_powerlaw(700, 2800, seed=11)
    cfg = PageRankConfig(iterations=8, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **F32)
    base = run_pagerank(g, PageRankConfig(iterations=8, **F32))
    m = MetricsRecorder()
    with chaos.inject("pagerank_result_pull:device_lost@dev:1"):
        res = run_pagerank_sharded(g, cfg, n_devices=2, strategy="owned",
                                   metrics=m)
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [d["ladder"] for d in degraded] == ["single_device"]
    assert degraded[0]["site"] == "pagerank_result_pull"


# ------------------------------------------------------- zipf generator


def test_synthetic_zipf_exact_counts_and_determinism():
    g1 = synthetic_zipf(1500, 9000, seed=4)
    g2 = synthetic_zipf(1500, 9000, seed=4)
    assert g1.n_nodes == 1500 and g1.n_edges == 9000
    np.testing.assert_array_equal(g1.src, g2.src)
    np.testing.assert_array_equal(g1.dst, g2.dst)
    g3 = synthetic_zipf(1500, 9000, seed=5)
    assert not np.array_equal(g1.src, g3.src)


def test_synthetic_zipf_exponent_knob_shapes_the_head():
    flat = synthetic_zipf(2000, 12000, seed=2, exponent=3.0)
    steep = synthetic_zipf(2000, 12000, seed=2, exponent=1.3)
    # a steeper (smaller) exponent spreads mass into the tail; 3.0
    # concentrates it — the hot head's in-degree must reflect the knob
    assert np.diff(flat.csr_indptr()).max() > np.diff(steep.csr_indptr()).max()


def test_synthetic_zipf_src_exponent_concentrates_sources():
    """Zipf sources are what make the owned boundary sublinear: distinct
    sources (and with them the cut) must be a small fraction of n."""
    uni = synthetic_zipf(4000, 24000, seed=2)
    zipf = synthetic_zipf(4000, 24000, seed=2, src_exponent=1.5)
    assert np.unique(zipf.src).size < np.unique(uni.src).size / 3
    p_u = plan_partition(uni, 4, strategy="owned")
    p_z = plan_partition(zipf, 4, strategy="owned")
    assert (p_z.owned.boundary_counts.sum()
            < p_u.owned.boundary_counts.sum() / 3)


def test_synthetic_zipf_rejects_impossible_targets():
    with pytest.raises(ValueError, match="capacity"):
        synthetic_zipf(10, 1000)


# ------------------------------------------------ comm gauge + trace_diff


def test_comm_bytes_gauge_published_and_sublinear():
    """The partition event carries the per-step comm footprint, and on
    Zipf-source graphs it scales sublinearly with node count (the small
    in-repo version of the MULTICHIP sweep)."""
    pts = []
    for n in (4000, 16000):
        g = synthetic_zipf(n, n * 6, seed=9, src_exponent=1.5)
        m = MetricsRecorder()
        res = run_pagerank_sharded(
            g, PageRankConfig(iterations=2, **F32), n_devices=8,
            strategy="owned", metrics=m,
        )
        assert np.isfinite(res.ranks).all()
        part = next(r for r in m.records if r.get("event") == "partition")
        assert part["comm_bytes_per_step"] > 0
        pts.append((n, part["comm_bytes_per_step"]))
    expo = (np.log(pts[1][1] / pts[0][1]) / np.log(pts[1][0] / pts[0][0]))
    assert expo < 1.0, pts


def test_owned_comm_beats_replicated_psum():
    """The point of the exchange: on a Zipf-source graph the owned comm
    footprint undercuts the replicated strategies' dense psum."""
    g = synthetic_zipf(16000, 96000, seed=9, src_exponent=1.5)
    owned = plan_partition(g, 8, strategy="owned")
    replicated = plan_partition(g, 8, strategy="hybrid")
    assert owned.comm_entries_per_step < replicated.comm_entries_per_step / 4


def _bench_round(tmp_path, name, comm):
    rec = {"metric": "x", "value": 1.0,
           "extra": {"breakdown": {"phase": 1.0},
                     "breakdown_wall_secs": 1.0,
                     "comm_bytes_per_step": comm}}
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return str(p)


def test_trace_diff_comm_gate(tmp_path):
    td = _load_tool("trace_diff")
    old = _bench_round(tmp_path, "BENCH_r01.json",
                       {"owned-1x": 100_000, "owned-10x": 400_000})
    # within threshold + floor: clean
    ok = _bench_round(tmp_path, "BENCH_r02.json",
                      {"owned-1x": 101_000, "owned-10x": 401_000})
    assert td.main([old, ok, "--threshold", "0.10"]) == 0
    # a point regressing past threshold AND the absolute floor: rc 1
    bad = _bench_round(tmp_path, "BENCH_r03.json",
                       {"owned-1x": 100_000, "owned-10x": 800_000})
    assert td.main([old, bad, "--threshold", "0.10"]) == 1
    # old round without the map (pre-ISSUE-15): skips cleanly
    legacy = tmp_path / "BENCH_r00.json"
    legacy.write_text(json.dumps(
        {"metric": "x", "value": 1.0,
         "extra": {"breakdown": {"phase": 1.0}}}))
    assert td.main([str(legacy), bad, "--threshold", "0.10"]) == 0
    # new round LOSING the map while the old had it: flagged
    assert td.main([old, str(legacy), "--threshold", "0.10"]) == 1


def test_owned_budget_knob_declared():
    assert "GRAFT_OWNED_BUDGET_S" in GRAFT_ENV_KNOBS


# ------------------------------------------------- owned-slice workloads


def test_owned_array_roundtrip(graph):
    plan = plan_partition(graph, 4, strategy="owned")
    shard = ob.build_owned_shard(graph, plan.owned, "float64")
    arr = OwnedArray.from_shard(shard)
    rng = np.random.default_rng(0)
    v = rng.random(graph.n_nodes)
    put = arr.put(v, "float64")
    out = put.pull()
    np.testing.assert_array_equal(out, v)


def test_transpose_graph_invariants(graph):
    tg = transpose_graph(graph)
    assert tg.n_nodes == graph.n_nodes and tg.n_edges == graph.n_edges
    assert (np.diff(tg.dst) >= 0).all()
    fwd = set(zip(graph.src.tolist(), graph.dst.tolist()))
    rev = set(zip(tg.dst.tolist(), tg.src.tolist()))
    assert fwd == rev


def test_owned_pair_shares_ownership(graph):
    sf, sr = build_owned_pair(graph, 4, "float32")
    np.testing.assert_array_equal(sf.tail_map, sr.tail_map)
    assert sf.block == sr.block and sf.n_pad == sr.n_pad


@pytest.mark.parametrize("d", [2, 8])
def test_hits_sharded_matches_single_chip(graph, d):
    cfg = HitsConfig(iterations=50, tol=1e-10, dtype="float64")
    base = run_hits(graph, cfg)
    res = run_hits_sharded(graph, cfg, n_devices=d)
    np.testing.assert_allclose(res.hubs, base.hubs, atol=1e-6)
    np.testing.assert_allclose(res.authorities, base.authorities, atol=1e-6)


@pytest.mark.parametrize("d", [2, 8])
def test_components_sharded_matches_single_chip(d):
    # several disconnected clusters so labels are non-trivial
    rng = np.random.default_rng(5)
    parts = []
    for c in range(6):
        lo = c * 120
        parts.append((rng.integers(lo, lo + 120, 300),
                      rng.integers(lo, lo + 120, 300)))
    src = np.concatenate([p[0] for p in parts])
    dst = np.concatenate([p[1] for p in parts])
    g = from_edges(src, dst)
    base = run_components(g)
    res = run_components_sharded(g, ComponentsConfig(), n_devices=d)
    np.testing.assert_array_equal(res.labels, base.labels)
    assert res.n_components == base.n_components
    assert res.converged


def test_ppr_sharded_query_axis(graph):
    cfg = PageRankConfig(iterations=40, **F64)
    queries = [[1], [5, 9], [17], [3, 4, 5], [250]]
    base = run_ppr_batch(graph, cfg, queries)
    res = run_ppr_sharded(graph, cfg, queries, n_devices=4)
    assert np.abs(res.ranks - base.ranks).max() <= 1e-9
    assert res.ranks.shape == (5, graph.n_nodes)


def test_ppr_sharded_uneven_batch(graph):
    """B not a device multiple: the pad queries must not leak into the
    returned batch."""
    cfg = PageRankConfig(iterations=20, **F64)
    queries = [[2], [7], [11]]
    base = run_ppr_batch(graph, cfg, queries)
    res = run_ppr_sharded(graph, cfg, queries, n_devices=4)
    assert res.ranks.shape == (3, graph.n_nodes)
    assert np.abs(res.ranks - base.ranks).max() <= 1e-9


def test_hits_sharded_weighted(graph):
    """Weighted edges ride the owned exchange in HITS too (the tail_w
    coefficient arrays carry them)."""
    g = _weighted_graph(n=200, e=1600, seed=8)
    cfg = HitsConfig(iterations=30, tol=0.0, dtype="float64")
    base = run_hits(g, cfg)
    res = run_hits_sharded(g, cfg, n_devices=4)
    np.testing.assert_allclose(res.hubs, base.hubs, atol=1e-6)
