"""Elastic mesh degradation tests (ISSUE 5): chaos-killed devices are
survived by shrink-and-resume.

The acceptance bar: with ``GRAFT_CHAOS="*:device_lost@dev:1"`` on a
2-device mesh, BOTH sharded runners complete via the mesh-shrink rung (no
``ResilienceExhausted``), match uninterrupted outputs to atol 1e-6 f32
with zero recomputed committed iterations/chunks, and the trace artifact
shows exactly one ``mesh.shrink`` span with devices 2->1.  (The conftest
backend simulates 8 CPU devices; a 2-device mesh over devices [0, 1] is
the same code path as ``--xla_force_host_platform_device_count=2``, which
``tools/chaos.sh``'s device_lost scenario exercises end to end.)
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.io import from_edges, synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
    run_pagerank_sharded,
    run_tfidf_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import mesh as pmesh
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import (
    chaos,
    elastic,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.resilience.executor import (
    ResilienceExhausted,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    DEGRADE_LADDER,
    GRAFT_ENV_KNOBS,
    PageRankConfig,
    TfidfConfig,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder

REPO = Path(__file__).resolve().parents[1]

GRAPH_KW = dict(dangling="redistribute", init="uniform", dtype="float32")


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_health():
    """Device health is process-global (a real dead chip stays dead); every
    test starts and ends with a clean slate."""
    elastic.reset_health()
    yield
    elastic.reset_health()


# ------------------------------------------------------------ chaos grammar


def test_parse_device_lost_plan():
    (inj,) = chaos.parse_plan("*:device_lost@dev:1")
    assert inj.kind == "device_lost" and inj.when == "dev"
    assert inj.param == 1.0
    assert inj.matches("any_site", 1) and inj.matches("any_site", 99)
    (inj2,) = chaos.parse_plan("pagerank_step:device_lost@dev:0")
    assert not inj2.matches("other_site", 1)


@pytest.mark.parametrize(
    "bad",
    ["a:device_lost@1", "a:device_lost@dev", "a:device_lost@dev:x",
     "a:device_lost@dev:1:2", "a:device_lost@%2:1"],
)
def test_parse_device_lost_rejects(bad):
    with pytest.raises(ValueError):
        chaos.parse_plan(bad)


def test_device_lost_fires_until_acknowledged():
    """The injection behaves like a real dead chip: every guarded call
    fails until the elastic runtime marks the device dead, then the
    survivors work again."""
    with chaos.inject("s:device_lost@dev:3"):
        for _ in range(2):
            with pytest.raises(chaos.DeviceLostError) as ei:
                chaos.on_call("s")
            assert ei.value.device == 3
        elastic.health().mark_lost(3)
        chaos.on_call("s")  # acknowledged: no further injection


# --------------------------------------------------- planner + health units


def test_largest_pow2_and_shrink_devices():
    assert [pmesh.largest_pow2(n) for n in (0, 1, 2, 3, 5, 8)] == [0, 1, 2, 2, 4, 8]

    class Dev:
        def __init__(self, i):
            self.id = i

    survivors = pmesh.shrink_devices([Dev(0), Dev(2), Dev(5)])
    assert [d.id for d in survivors] == [0, 2]
    assert pmesh.shrink_devices([]) == []


def test_device_health_registry():
    h = elastic.DeviceHealth()
    assert h.mark_lost(4) and not h.mark_lost(4)
    assert h.is_lost(4) and not h.is_lost(0)
    assert h.lost() == frozenset({4})
    h.reset()
    assert h.lost() == frozenset()


def test_plan_shrink_rungs():
    import jax

    devs = jax.devices()[:4]
    elastic.health().mark_lost(devs[3].id)
    plan = elastic.plan_shrink(devs)
    assert (plan.old_count, plan.new_count) == (4, 2)
    assert plan.rung == "mesh_shrink"
    assert all(not elastic.health().is_lost(d.id) for d in plan.devices)

    elastic.health().mark_lost(devs[1].id)
    plan2 = elastic.plan_shrink(list(plan.devices))
    assert (plan2.old_count, plan2.new_count) == (2, 1)
    assert plan2.rung == "single_device"


def test_plan_shrink_halves_on_unattributed_loss():
    """A persistent device-loss error that names no device still makes
    progress: the plan halves rather than rebuilding the same mesh."""
    import jax

    plan = elastic.plan_shrink(jax.devices()[:4])
    assert (plan.old_count, plan.new_count) == (4, 2)


def test_ladder_rungs_are_declared():
    """Every rung the elastic planner can take is a declared ladder name,
    and the elastic knob is a declared env knob."""
    assert {"mesh_shrink", "single_device", "cpu"} <= set(DEGRADE_LADDER)
    assert "GRAFT_ELASTIC" in GRAFT_ENV_KNOBS


# ------------------------------------------------------- executor fallbacks


def test_run_guarded_walks_fallback_rungs_in_order():
    calls = []
    pol = rx.RetryPolicy(max_retries=0, backoff_base_s=0.001)
    m = MetricsRecorder()

    def rung_a(exc):
        calls.append(("a", type(exc).__name__))
        raise RuntimeError("rung a cannot help")

    def rung_b(exc):
        calls.append(("b", type(exc).__name__))
        return "recovered"

    with chaos.inject("fb:lost@1+"):
        out = rx.run_guarded(
            lambda: 1, site="fb", policy=pol, metrics=m,
            fallbacks=[(None, rung_a), ("cpu", rung_b)],
        )
    assert out == "recovered"
    assert calls == [("a", "DeviceLostError"), ("b", "RuntimeError")]
    # only the NAMED rung publishes degraded (the unnamed one owns its own
    # emission, and here it declined)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert len(degraded) == 1 and degraded[0]["ladder"] == "cpu"


def test_run_guarded_all_rungs_fail_exhausts():
    pol = rx.RetryPolicy(max_retries=0, backoff_base_s=0.001)

    def declines(exc):
        raise exc

    with chaos.inject("fb2:lost@1+"):
        with pytest.raises(ResilienceExhausted):
            rx.run_guarded(lambda: 1, site="fb2", policy=pol,
                           fallbacks=[(None, declines)])


# ----------------------------------------- end-to-end: sharded PageRank


@pytest.mark.parametrize("strategy", ["hybrid", "owned"])
def test_pagerank_sharded_survives_device_loss_2to1(tmp_path, strategy):
    """Acceptance: 2-device mesh, chaos kills logical device 1 -> the run
    completes via mesh shrink (no ResilienceExhausted), matches the
    uninterrupted ranks to atol 1e-6, recomputes zero committed
    iterations, and the trace shows exactly one mesh.shrink span 2->1."""
    g = synthetic_powerlaw(900, 3600, seed=21)
    cfg = PageRankConfig(iterations=9, checkpoint_every=3,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    base = run_pagerank(g, PageRankConfig(iterations=9, **GRAPH_KW))

    m = MetricsRecorder()
    obs.start_run("elastic_pr", str(tmp_path / "tr"))
    try:
        with chaos.inject("*:device_lost@dev:1"):
            res = run_pagerank_sharded(g, cfg, n_devices=2, metrics=m,
                                       strategy=strategy)
    finally:
        obs.end_run()
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    assert res.iterations == 9

    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert len(degraded) == 1
    assert degraded[0]["ladder"] == "single_device"
    assert (degraded[0]["devices_old"], degraded[0]["devices_new"]) == (2, 1)
    # zero recomputed committed iterations: every segment commit advanced
    # the iteration counter; nothing was resumed or replayed
    iters = [r["iter"] for r in m.records if "iter" in r and "l1_delta" in r]
    assert iters == sorted(set(iters))
    assert not [r for r in m.records if r.get("event") == "resume"]

    trace = next((tmp_path / "tr").glob("elastic_pr.*.trace.jsonl"))
    rep = _trace_report().report(str(trace))
    assert len(rep["mesh_shrinks"]) == 1
    s = rep["mesh_shrinks"][0]
    assert (s["devices_old"], s["devices_new"]) == (2, 1)
    assert s["site"] == "pagerank_step"
    assert not rep["exhausted"]


def test_pagerank_sharded_shrinks_4to2_nodes_balanced(tmp_path):
    """A 4-device mesh losing one device lands on the mesh_shrink rung at
    2 devices — the partition is planned again for the surviving count
    (name kept from the node-block layout this test once covered)."""
    g = synthetic_powerlaw(800, 3200, seed=13)
    base = run_pagerank(g, PageRankConfig(iterations=8, **GRAPH_KW))
    m = MetricsRecorder()
    with chaos.inject("*:device_lost@dev:3"):
        res = run_pagerank_sharded(
            g, PageRankConfig(iterations=8, **GRAPH_KW),
            n_devices=4, strategy="hybrid", metrics=m,
        )
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [d["ladder"] for d in degraded] == ["mesh_shrink"]
    assert (degraded[0]["devices_old"], degraded[0]["devices_new"]) == (4, 2)
    parts = [r for r in m.records if r.get("event") == "partition"]
    assert [p["devices"] for p in parts] == [4, 2]  # repartitioned once


@pytest.mark.parametrize("strategy", ["hybrid-headless", "hybrid"])
def test_pagerank_sharded_device_loss_at_result_pull(tmp_path, strategy):
    """Carried-forward hardening (a), ISSUE 7: a device loss FIRST
    surfacing at the sharded result-pull site (every segment already
    committed, nothing left to dispatch) used to exhaust the ladder — it
    must now walk the elastic rung: salvage the newest checkpoint,
    rebuild the mesh over the survivor, re-run only the uncommitted
    iterations there, and pull from the rebuilt mesh."""
    g = synthetic_powerlaw(700, 2800, seed=11)
    if strategy == "hybrid-headless":  # weighted arcs plan no dense head
        w = np.random.default_rng(5).uniform(0.5, 2.0, g.n_edges)
        g = from_edges(g.src, g.dst, weight=w, compact_ids=False)
    cfg = PageRankConfig(iterations=8, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    base = run_pagerank(g, PageRankConfig(iterations=8, **GRAPH_KW))
    m = MetricsRecorder()
    with chaos.inject("pagerank_result_pull:device_lost@dev:1"):
        res = run_pagerank_sharded(g, cfg, n_devices=2, metrics=m,
                                   strategy="hybrid")
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    assert res.iterations == 8
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [d["ladder"] for d in degraded] == ["single_device"]
    assert degraded[0]["site"] == "pagerank_result_pull"
    assert (degraded[0]["devices_old"], degraded[0]["devices_new"]) == (2, 1)
    # the salvage repartitioned once onto the survivor
    parts = [r for r in m.records if r.get("event") == "partition"]
    assert [p["devices"] for p in parts] == [2, 1]


def test_pagerank_result_pull_loss_without_checkpoint_reruns(tmp_path):
    """No checkpoint_dir: the pull rung restarts the fixpoint from init
    on the shrunk mesh — slower, but still converging to the
    uninterrupted answer instead of exhausting."""
    g = synthetic_powerlaw(300, 1200, seed=6)
    base = run_pagerank(g, PageRankConfig(iterations=6, **GRAPH_KW))
    m = MetricsRecorder()
    with chaos.inject("pagerank_result_pull:device_lost@dev:1"):
        res = run_pagerank_sharded(
            g, PageRankConfig(iterations=6, **GRAPH_KW), n_devices=2,
            metrics=m,
        )
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    assert [d["ladder"] for d in m.records
            if d.get("event") == "degraded"] == ["single_device"]


def test_pagerank_sharded_elastic_disabled_exhausts(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_ELASTIC", "0")
    g = synthetic_powerlaw(400, 1600, seed=3)
    cfg = PageRankConfig(iterations=6, checkpoint_every=3,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    with chaos.inject("*:device_lost@dev:1"):
        with pytest.raises(ResilienceExhausted):
            run_pagerank_sharded(g, cfg, n_devices=2)


def test_shrink_checkpoint_is_mesh_tagged_and_cross_readable(tmp_path):
    """The checkpoint the shrink writes carries the mesh shape that wrote
    it, and resumes under a different device count (here: single-chip)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt

    g = synthetic_powerlaw(500, 2000, seed=9)
    cfg = PageRankConfig(iterations=6, checkpoint_every=2,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    with chaos.inject("*:device_lost@dev:1"):
        run_pagerank_sharded(g, cfg, n_devices=2)
    metas = [
        ckpt.peek_meta(str(p))
        for p in sorted((tmp_path / "ck").glob("ckpt_*.npz"))
    ]
    assert any(m["extra"].get("devices") for m in metas)
    base = run_pagerank(g, PageRankConfig(iterations=6, **GRAPH_KW))
    res = run_pagerank(g, cfg, resume=True)  # single-chip reads it fine
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


# ------------------------------------------- end-to-end: sharded TF-IDF


def _chunks(n_chunks: int, docs_per_chunk: int = 2) -> list[list[str]]:
    docs = [f"tok{i} tok{i % 5} shared word extra{i % 3}"
            for i in range(n_chunks * docs_per_chunk)]
    return [docs[i:i + docs_per_chunk]
            for i in range(0, len(docs), docs_per_chunk)]


def test_tfidf_sharded_survives_device_loss_2to1(tmp_path):
    """Acceptance: sharded TF-IDF on a 2-device mesh survives losing
    device 1 — scores match the uninterrupted run to atol 1e-6, zero
    chunks are reprocessed, and the trace shows one mesh.shrink 2->1."""
    chunks = _chunks(12)
    base = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                             n_devices=2)
    elastic.reset_health()

    cfg = TfidfConfig(vocab_bits=10, checkpoint_every=4,
                      checkpoint_dir=str(tmp_path / "ck"))
    m = MetricsRecorder()
    obs.start_run("elastic_tf", str(tmp_path / "tr"))
    try:
        with chaos.inject("*:device_lost@dev:1"):
            res = run_tfidf_sharded(iter(chunks), cfg, n_devices=2,
                                    metrics=m)
    finally:
        obs.end_run()
    assert res.n_docs == base.n_docs
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)

    # zero reprocessed chunks: the committed super-chunks cover each of
    # the 12 input chunks exactly once (the in-flight group the loss
    # interrupted was re-sliced, never committed twice)
    sc = [r for r in m.records if r.get("event") == "super_chunk"]
    assert sum(r["devices"] for r in sc) == 12
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert len(degraded) == 1
    assert (degraded[0]["devices_old"], degraded[0]["devices_new"]) == (2, 1)

    trace = next((tmp_path / "tr").glob("elastic_tf.*.trace.jsonl"))
    rep = _trace_report().report(str(trace))
    assert len(rep["mesh_shrinks"]) == 1
    s = rep["mesh_shrinks"][0]
    assert (s["devices_old"], s["devices_new"]) == (2, 1)
    # the staged pipeline attributes the shrink to the site the loss
    # surfaced at: one of the ISSUE 10 H2D staging sites, or the guarded
    # drain pull for a loss first seen there
    assert s["site"] in ("ingest_h2d_put", "ingest_h2d_wait",
                         "tfidf_shard_sync")
    assert not rep["exhausted"]


def test_tfidf_sharded_custom_axis_mesh_survives():
    """The shrink rung must preserve a caller-provided mesh's axis name —
    rebuilding under the default DATA_AXIS used to crash the rung (and so
    the run) for any custom-named mesh."""
    chunks = _chunks(8)
    base = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                             n_devices=2)
    elastic.reset_health()
    custom = pmesh.make_mesh(2, "batch")
    with chaos.inject("*:device_lost@dev:1"):
        res = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                                mesh=custom)
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)


# -------------------------------- stacked losses inside the shrink-rerun


def test_pagerank_second_loss_inside_shrink_rerun_reenters(tmp_path):
    """Elastic gap (ISSUE 8): the FIRST loss enters the shrink rung; the
    SECOND fires at the rerun site (``pagerank_elastic_rerun``) while the
    rebuilt mesh is re-running the failed segment — it must re-enter the
    ladder (second shrink) instead of exhausting.  Two stacked
    ``device_lost`` injections, two ``mesh.shrink`` spans, exact ranks."""
    g = synthetic_powerlaw(800, 3200, seed=17)
    cfg = PageRankConfig(iterations=8, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    base = run_pagerank(g, PageRankConfig(iterations=8, **GRAPH_KW))
    m = MetricsRecorder()
    obs.start_run("elastic_stack", str(tmp_path / "tr"))
    try:
        with chaos.inject(
            "pagerank_step:device_lost@dev:1;"
            "pagerank_elastic_rerun:device_lost@dev:2"
        ):
            res = run_pagerank_sharded(g, cfg, n_devices=4, metrics=m)
    finally:
        obs.end_run()
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    assert res.iterations == 8
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [(d["devices_old"], d["devices_new"]) for d in degraded] == \
        [(4, 2), (2, 1)]
    assert [d["ladder"] for d in degraded] == ["mesh_shrink", "single_device"]
    trace = next((tmp_path / "tr").glob("elastic_stack.*.trace.jsonl"))
    rep = _trace_report().report(str(trace))
    assert len(rep["mesh_shrinks"]) == 2
    assert not rep["exhausted"]


def test_pagerank_second_loss_during_salvage_is_absorbed(tmp_path):
    """A wildcard double injection: the second loss fires during the
    salvage pull (pagerank_ckpt_pull) — the rung acknowledges it, retries
    the salvage against the health registry, and ONE shrink absorbs both
    dead devices.  The run completes either way; exhausting is the only
    wrong answer."""
    g = synthetic_powerlaw(700, 2800, seed=23)
    cfg = PageRankConfig(iterations=8, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    base = run_pagerank(g, PageRankConfig(iterations=8, **GRAPH_KW))
    m = MetricsRecorder()
    with chaos.inject("*:device_lost@dev:1;*:device_lost@dev:2"):
        res = run_pagerank_sharded(g, cfg, n_devices=4, metrics=m)
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert degraded  # shrank at least once, exhausted never
    assert elastic.health().lost() == frozenset({1, 2})


def test_tfidf_second_loss_inside_reslice_reenters(tmp_path):
    """The sharded-ingest counterpart: a second device dying while the
    re-sliced in-flight super-chunk drains re-enters the shrink ladder
    (4 -> 2 -> 1), commits every chunk exactly once, and matches the
    uninterrupted output."""
    chunks = _chunks(12)
    base = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                             n_devices=4)
    elastic.reset_health()
    m = MetricsRecorder()
    with chaos.inject("*:device_lost@dev:1;*:device_lost@dev:2"):
        res = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                                n_devices=4, metrics=m)
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)
    sc = [r for r in m.records if r.get("event") == "super_chunk"]
    assert sum(r["devices"] for r in sc) == 12  # zero reprocessed chunks
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert [(d["devices_old"], d["devices_new"]) for d in degraded] == \
        [(4, 2), (2, 1)]


# --------------------------------------- adaptive sync deadline satellites


def test_sync_p99_from_trace(tmp_path):
    tr = tmp_path / "x.trace.jsonl"
    events = [{"kind": "run_start", "t": 0.0, "thread": "m"}]
    for i in range(100):
        events.append({
            "kind": "span_end", "t": float(i), "name": "tfidf.chunk",
            "secs": 0.01 * (i + 1),
        })
    events.append({"kind": "span_end", "t": 200.0, "name": "bench.warm",
                   "secs": 99.0})  # not a sync span: must not count
    tr.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    mod = _trace_report()
    p99 = mod.sync_p99(str(tr))
    assert p99 == pytest.approx(0.99)
    empty = tmp_path / "y.trace.jsonl"
    empty.write_text(json.dumps({"kind": "run_start", "t": 0.0}) + "\n")
    assert mod.sync_p99(str(empty)) is None


def test_effective_sync_deadline_math():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location("bench_mod", REPO / "bench.py")
    bench = ilu.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench._effective_sync_deadline(120.0, None) == 120.0
    assert bench._effective_sync_deadline(120.0, 10.0) == 120.0  # knob wins
    assert bench._effective_sync_deadline(120.0, 90.0) == 270.0  # 3 x p99
    assert bench._effective_sync_deadline(0.0, 90.0) == 0.0  # 0 = disabled
