"""Multi-process serving fabric (ISSUE 17): consistent-hash ring
stability, the enforced generation floor, the idempotent request-id
replay, router retry under chaos (``fabric_route:net_partition`` /
``fabric_route:net_hang``), process-level chaos grammar (``proc_kill``),
replica and ``cli.serve`` graceful SIGTERM, the end-to-end fleet
(SIGKILL → respawn → rolling restart, dropped=0 / double_served=0), the
fleet soak scenario, and the trace_report / trace_diff fabric surfaces.
"""

from __future__ import annotations

import importlib.util
import json
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.obs.export import (
    MetricsExporter,
)
from page_rank_and_tfidf_using_apache_spark_tpu.obs.metrics import MetricsHub
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import (
    segments as sgm,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

FIXTURE = Path(__file__).parent / "fixtures" / "tiny.txt"
SCFG = TfidfConfig(vocab_bits=10)
REPO = Path(__file__).resolve().parents[1]


def _tool(name: str):
    spec = importlib.util.spec_from_file_location(
        f"fabric_test_{name}", REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seal(d, docs, base=0):
    out = run_tfidf(docs, SCFG)
    ref = sgm.seal_segment(str(d), out, SCFG, doc_base=base,
                           ranks=np.ones(out.n_docs, np.float32),
                           bm25=Bm25Config())
    return sgm.commit_append(str(d), ref, SCFG.config_hash()), out.n_docs


def _docs():
    return FIXTURE.read_text().splitlines()


# ------------------------------------------------------------------ ring


def test_ring_remap_bound_on_replica_loss():
    """The consistent-hash property the sharded cache rides: removing a
    replica remaps ONLY the keys it owned — every key owned by a
    survivor keeps its owner, and the remapped fraction stays near 1/N
    instead of the ~(N-1)/N a modulo router would reshuffle."""
    n = 4
    full = fabric._Ring(range(n), slots=64)
    survivors = fabric._Ring([1, 2, 3], slots=64)
    keys = [f"key-{i}" for i in range(600)]
    owner_full = {k: full.route(k)[0] for k in keys}
    owner_after = {k: survivors.route(k)[0] for k in keys}
    remapped = 0
    for k in keys:
        if owner_full[k] == 0:
            remapped += 1
        else:
            # survivor-owned keys NEVER move
            assert owner_after[k] == owner_full[k]
    # expected ~1/N; allow generous vnode variance, still far from 1/2
    assert remapped / len(keys) < 0.45


def test_ring_preference_order_and_exclude():
    ring = fabric._Ring(range(3), slots=32)
    order = ring.route("some-key")
    assert sorted(order) == [0, 1, 2]  # every replica appears once
    primary = order[0]
    excluded = ring.route("some-key", exclude={primary})
    # the suspect moves to the BACK, it does not vanish
    assert sorted(excluded) == [0, 1, 2]
    assert excluded[-1] == primary
    assert excluded[0] == order[1]
    # with everyone suspect the caller still gets candidates
    assert sorted(ring.route("some-key", exclude={0, 1, 2})) == [0, 1, 2]


def test_affinity_key_canonicalization():
    a = fabric.affinity_key(["graph", "directed", "graph"], "tfidf")
    b = fabric.affinity_key(["directed", "graph"], "tfidf")
    assert a == b  # order- and duplicate-insensitive, like the LRU key
    assert a != fabric.affinity_key(["directed", "graph"], "bm25")


# ----------------------------------------------------------------- floor


def test_floor_round_trip_and_corruption(tmp_path):
    d = str(tmp_path)
    assert fabric.read_floor(d) == 0  # never committed: everything servable
    fabric.commit_floor(d, 3)
    assert fabric.read_floor(d) == 3
    fabric.commit_floor(d, 5)
    assert fabric.read_floor(d) == 5
    # a torn/garbage floor file reads as 0, never raises into serving
    (tmp_path / fabric.FLOOR_FILE).write_text("{not json")
    assert fabric.read_floor(d) == 0


def test_replica_refuses_pre_floor_artifact_then_catches_up(tmp_path):
    """The floor is ENFORCED: a replica restarted mid-rolling-swap that
    can only see a pre-floor manifest comes up UNREADY and 503s queries;
    once the fleet's generation lands on disk its poll loop catches up
    and it starts serving."""
    docs = _docs()
    v1, n1 = _seal(tmp_path, docs[:5])
    assert v1 == 1
    fabric.commit_floor(str(tmp_path), 2)  # the fleet committed gen 2
    rep = fabric._Replica(str(tmp_path), replica_id=0, top_k=5,
                          max_batch=None, scoring="coo", poll_s=0.05)
    rep.start()
    try:
        assert not rep.ready()
        code, _, body = rep.handle_query(json.dumps(
            {"rid": "r1", "terms": ["node"], "ranker": "tfidf"}
        ).encode())
        assert code == 503
        assert json.loads(body)["floor"] == 2
        # generation 2 commits; the poll loop picks it up
        _seal(tmp_path, docs[5:], base=n1)
        deadline = time.monotonic() + 10.0
        while not rep.ready() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rep.ready()
        code, _, body = rep.handle_query(json.dumps(
            {"rid": "r2", "terms": ["node"], "ranker": "tfidf"}
        ).encode())
        assert code == 200
        assert json.loads(body)["generation"] == 2
    finally:
        rep.stop()


def test_replica_rid_replay_is_idempotent(tmp_path):
    """A re-dispatched request id REPLAYS the cached bytes instead of
    re-executing — the cross-process double-serve guard."""
    _seal(tmp_path, _docs())
    rep = fabric._Replica(str(tmp_path), replica_id=0, top_k=5,
                          max_batch=None, scoring="coo", poll_s=5.0)
    rep.start()
    try:
        body = json.dumps({"rid": "dup-1", "terms": ["node"],
                           "ranker": "tfidf"}).encode()
        first = rep.handle_query(body)
        again = rep.handle_query(body)
        assert first == again  # byte-identical replay
        assert rep._executions == 1 and rep._replays == 1
        rep.handle_query(json.dumps({"rid": "dup-2", "terms": ["node"],
                                     "ranker": "tfidf"}).encode())
        assert rep._executions == 2
    finally:
        rep.stop()


def test_crash_harness_covers_floor_commit():
    """The tier-5 kill-point harness sweeps the floor-commit boundary
    (the 'floor' scenario) and the static enumeration declares it."""
    ch = _tool("crash_harness")
    assert "floor" in ch._SCENARIOS
    from page_rank_and_tfidf_using_apache_spark_tpu.analysis.persistence import (
        CRASH_ENTRIES,
    )
    assert any(e.endswith("serving/fabric.py::commit_floor")
               for e in CRASH_ENTRIES)


# --------------------------------------------------- chaos grammar (proc)


def test_chaos_proc_kill_schedule(monkeypatch):
    """``proc_kill`` SIGKILLs the CURRENT process at the scheduled call
    — observed here by monkeypatching os.kill (the documented test
    seam): ``replica_query:proc_kill@2`` fires on call 2 only."""
    kills: list[tuple] = []
    monkeypatch.setattr("os.kill", lambda pid, sig: kills.append((pid, sig)))
    with chaos.inject("replica_query:proc_kill@2"):
        chaos.on_call("replica_query")
        assert kills == []
        chaos.on_call("replica_query")
    assert len(kills) == 1
    assert kills[0][1] == signal.SIGKILL


def test_chaos_proc_kill_mid_swap(monkeypatch):
    """``replica_swap:proc_kill@1`` — the kill-during-hot-swap scenario:
    the kill lands inside the guarded swap attempt, before the new
    generation is published."""
    kills: list[tuple] = []
    monkeypatch.setattr("os.kill", lambda pid, sig: kills.append((pid, sig)))
    with chaos.inject("replica_swap:proc_kill@1"):
        chaos.on_call("replica_swap")
    assert len(kills) == 1


def test_chaos_net_hang_param_is_milliseconds():
    plan = chaos.parse_plan("fabric_route:net_hang@1:80")
    assert plan[0].kind == "net_hang" and plan[0].param == 80.0
    # default: a 500 ms stall a request timeout should absorb
    assert chaos.parse_plan("fabric_route:net_hang@1")[0].param == 500.0
    t0 = time.perf_counter()
    with chaos.inject("fabric_route:net_hang@1:80"):
        chaos.on_call("fabric_route")  # sleeps 80 ms, then proceeds
    assert time.perf_counter() - t0 >= 0.07


def test_chaos_net_partition_is_transient_chaos_error():
    with chaos.inject("fabric_route:net_partition@1"):
        with pytest.raises(chaos.PartitionError):
            chaos.on_call("fabric_route")
    assert issubclass(chaos.PartitionError, chaos.ChaosError)


# ------------------------------------------------- router (stub replicas)


class _StubFleet:
    """In-process stand-ins for replica processes: each 'replica' is a
    MetricsExporter serving the SAME (method, path) route contract the
    real replica registers, so the router code under test is exercised
    byte-for-byte — minus the fork."""

    def __init__(self, handlers):
        self.exporters = [
            MetricsExporter(MetricsHub(), port=0,
                            routes={("POST", "/query"): h}).start()
            for h in handlers
        ]

    def ports(self):
        return [e.port for e in self.exporters]

    def stop(self):
        for e in self.exporters:
            e.stop()


def _stub_router(tmp_path, ports, **cfg_overrides):
    cfg = fabric.FabricConfig(replicas=len(ports), retry_pause_s=0.01,
                              request_timeout_s=5.0, **cfg_overrides)
    fab = fabric.ServingFabric(str(tmp_path), cfg)
    # routed without start(): no child processes (id-keyed since ISSUE 19)
    fab._ports = dict(enumerate(ports))
    return fab


def _ok_handler(replica_id, seen=None):
    def handle(body: bytes):
        req = json.loads(body.decode())
        if seen is not None:
            seen.append(req["rid"])
        return (200, "application/json", json.dumps({
            "rid": req["rid"], "replica": replica_id, "generation": 1,
            "scores": [1.0], "docs": [0],
        }))
    return handle


def _unready_handler(body: bytes):
    return (503, "application/json",
            json.dumps({"error": "replica below generation floor"}))


def test_router_retries_sibling_on_unready_replica(tmp_path):
    """One replica 503s (below floor / shutting down): the router tries
    the sibling under the SAME rid — served, not dropped, not suspect."""
    seen: list[str] = []
    stubs = _StubFleet([_unready_handler, _ok_handler(1, seen)])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=8)
        for _ in range(4):
            scores, docs = fab.query(["alpha", "beta"])
            assert scores.dtype == np.float32 and docs.dtype == np.int32
        audit = fab.audit()
        assert audit["delivered"] == 4 and audit["dropped"] == 0
        assert audit["double_served"] == 0
        assert len(seen) == len(set(seen)) == 4  # fresh rid per query
    finally:
        stubs.stop()


def test_router_partition_reroutes_to_sibling(tmp_path):
    """``fabric_route:net_partition@1``: the first router→replica hop
    partitions; the target is marked suspect and the query re-dispatches
    to the sibling under the same rid."""
    seen: list[str] = []
    stubs = _StubFleet([_ok_handler(0, seen), _ok_handler(1, seen)])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=8)
        with chaos.inject("fabric_route:net_partition@1"):
            fab.query(["gamma"])
        audit = fab.audit()
        assert audit["delivered"] == 1 and audit["dropped"] == 0
        assert len(fab._suspect) == 1  # the partitioned hop's target
        assert len(seen) == 1  # exactly one replica executed it
    finally:
        stubs.stop()


def test_router_survives_net_hang(tmp_path):
    """``fabric_route:net_hang@1:80``: the hop stalls 80 ms inside the
    guarded attempt, then completes — absorbed, not failed."""
    stubs = _StubFleet([_ok_handler(0), _ok_handler(1)])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=8)
        t0 = time.perf_counter()
        with chaos.inject("fabric_route:net_hang@1:80"):
            fab.query(["delta"])
        assert time.perf_counter() - t0 >= 0.07
        assert fab.audit()["dropped"] == 0
    finally:
        stubs.stop()


def test_router_exhaustion_is_typed(tmp_path):
    """Every replica unready for the whole retry window: the caller gets
    a typed FabricExhausted — never a silent drop — and the audit counts
    the request as dropped."""
    stubs = _StubFleet([_unready_handler, _unready_handler])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=4)
        with pytest.raises(fabric.FabricExhausted):
            fab.query(["epsilon"])
        audit = fab.audit()
        assert audit["dropped"] == 1 and audit["delivered"] == 0
    finally:
        stubs.stop()


def test_router_bad_request_raises_value_error(tmp_path):
    def bad_handler(body: bytes):
        return (400, "application/json",
                json.dumps({"error": "unknown ranker 'nope'"}))

    stubs = _StubFleet([bad_handler, bad_handler])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=4)
        with pytest.raises(ValueError, match="unknown ranker"):
            fab.query(["zeta"], ranker="nope")
    finally:
        stubs.stop()


def test_router_affinity_routes_same_key_to_same_replica(tmp_path):
    """The sharded-cache property end to end: the same logical query
    (same affinity key) always lands on the same healthy replica.

    Runs under its own (empty) chaos plan: a ``fabric_route`` fault from
    the ambient tools/chaos.sh gate makes the router CORRECTLY reroute
    one hop to the sibling, which is exactly what the strict (6,0)/(0,6)
    stickiness assertion exists to rule out in the fault-free case —
    retry-under-chaos has its own tests above."""
    seen0: list[str] = []
    seen1: list[str] = []
    stubs = _StubFleet([_ok_handler(0, seen0), _ok_handler(1, seen1)])
    try:
        fab = _stub_router(tmp_path, stubs.ports(), retry_limit=4)
        with chaos.inject(""):
            for _ in range(6):
                fab.query(["stable", "key"])
        assert (len(seen0), len(seen1)) in ((6, 0), (0, 6))
    finally:
        stubs.stop()


# ------------------------------------------------ subprocess: one replica


def test_replica_process_handshake_query_and_sigterm(tmp_path):
    """One REAL replica process: ready handshake on stdout, a /query
    round-trip over HTTP, graceful SIGTERM exit (rc 0)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.resilience import (
        process as procs,
    )

    _seal(tmp_path, _docs())
    handle = procs.ProcessHandle([
        sys.executable, "-m",
        "page_rank_and_tfidf_using_apache_spark_tpu.serving.fabric",
        "--replica", str(tmp_path), "--replica-id", "0", "--port", "0",
        "--top-k", "3",
    ], ready_timeout_s=120.0).spawn()
    try:
        assert handle.ready["ready"] is True
        port = int(handle.ready["port"])
        assert handle.ready["generation"] == 1
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query",
            data=json.dumps({"rid": "t-1", "terms": ["node"],
                             "ranker": "tfidf"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            resp = json.loads(r.read())
        assert resp["rid"] == "t-1" and resp["generation"] == 1
        # /healthz is the same surface the router health-checks
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            assert r.status == 200
        rc = handle.terminate(grace_s=20.0)  # SIGTERM, graceful path
        assert rc == 0
    finally:
        handle.kill()


def test_cli_serve_sigterm_graceful(tmp_path):
    """``cli.serve`` under a supervisor's SIGTERM: answers the in-flight
    request, exits rc 0, and stamps ``"shutdown": "sigterm"`` into its
    stats line — the typed-drain satellite of ISSUE 17."""
    from page_rank_and_tfidf_using_apache_spark_tpu import serving
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
        run_tfidf as _run,
    )

    out = _run(_docs(), SCFG)
    idx = tmp_path / "idx"
    serving.save_index(str(idx), out, SCFG)
    proc = subprocess.Popen([
        sys.executable, "-m",
        "page_rank_and_tfidf_using_apache_spark_tpu.cli.serve",
        str(idx), "--top-k", "3",
    ], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        proc.stdin.write("directed graph\n")
        proc.stdin.flush()
        line = proc.stdout.readline()  # interactive mode: answer now
        assert line and "\t" in line
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=60)
        assert proc.returncode == 0
        stats = json.loads(err.strip().splitlines()[-1])
        assert stats["shutdown"] == "sigterm"
        assert stats["requests"] >= 1
    finally:
        proc.kill()


# -------------------------------------------------- subprocess: the fleet


@pytest.mark.slow
def test_fabric_end_to_end_kill_respawn_and_rolling_restart(tmp_path):
    """The tentpole acceptance scenario at test scale: a 2-replica fleet
    serves under per-replica chaos (``replica_query:proc_kill@3`` kills
    replica 1 mid-query), a SIGKILL on replica 0 recovers through
    sibling retry + supervisor respawn with dropped=0/double_served=0,
    and a rolling restart under a committed generation floor leaves the
    whole fleet at the new generation.  The run is traced and the
    trace_report fabric section must parse out of it."""
    docs = _docs()
    v1, n1 = _seal(tmp_path, docs[:5])
    trace_dir = tmp_path / "trace"
    with obs.run("fabrictest", trace_dir=str(trace_dir)) as r:
        fab = fabric.ServingFabric(str(tmp_path), fabric.FabricConfig(
            replicas=2, poll_s=0.1, health_period_s=0.2,
            retry_limit=100, retry_pause_s=0.1, request_timeout_s=10.0,
            grace_s=10.0,
            # deterministic process-level chaos INSIDE a real replica:
            # replica 1 SIGKILLs itself on its 3rd executed query
            replica_chaos=((1, "replica_query:proc_kill@3"),),
        ))
        with fab:
            for _ in range(8):
                scores, docs_out = fab.query(["node"])
                assert len(scores) > 0
            # hard SIGKILL on replica 0 mid-traffic
            fab.kill_replica(0)
            for _ in range(20):
                fab.query(["directed", "graph"])
            # the supervisor respawned at least one dead replica by now
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if (fab.audit()["respawns"] >= 1
                        and all(s is not None and s.get("ready")
                                for s in fab.statuses())):
                    break
                time.sleep(0.2)
            audit = fab.audit()
            assert audit["respawns"] >= 1
            assert audit["dropped"] == 0 and audit["double_served"] == 0

            # rolling restart under a committed floor at generation 2
            _seal(tmp_path, docs[5:], base=n1)
            assert fab.await_fleet_generation(2, timeout=60.0)
            fab.rolling_restart(timeout=60.0)
            assert fabric.read_floor(str(tmp_path)) == 2
            statuses = fab.statuses()
            assert all(s is not None and s.get("ready")
                       and s.get("generation") >= 2 for s in statuses)
            assert all(s.get("floor") == 2 for s in statuses)
            fab.query(["node"])  # still serving after the roll
            audit = fab.audit()
            assert audit["rolled"] == 2
            assert audit["dropped"] == 0 and audit["double_served"] == 0
    rep = _tool("trace_report").report(r.trace_path)
    fb = rep["fabric"]
    assert fb is not None
    assert fb["replicas"] == 2
    assert fb["kills"] >= 1 and len(fb["respawns"]) >= 1
    assert fb["rolls"] == 2
    assert fb["floor_timeline"] and fb["floor_timeline"][-1]["floor"] == 2
    assert fb["totals"]["dropped"] == 0
    assert fb["totals"]["double_served"] == 0


@pytest.mark.slow
def test_fleet_soak_scenario(tmp_path):
    """The soak harness's fleet scenario: N=2 replicas under continuous
    ingest + closed-loop clients, one SIGKILL and one rolling restart
    mid-run, scored on the SAME slo record shape the single-process soak
    publishes (trace_report/trace_diff work unchanged)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.serving.soak import (
        FleetSoakConfig,
        run_fleet_soak,
    )

    trace_dir = tmp_path / "trace"
    with obs.run("fleettest", trace_dir=str(trace_dir)) as r:
        rec = run_fleet_soak(FleetSoakConfig(
            duration_s=18.0, qps=6.0, clients=2, replicas=2,
            rebuild_every_s=6.0, kill_at_s=5.0, roll_at_s=11.0,
        ))
    assert rec["requests"] > 10
    assert rec["dropped"] == 0 and rec["double_served"] == 0
    assert rec["recovery"]["losses_injected"] == 1
    assert rec["recovery"]["time_to_recover_s"] is not None
    assert rec["fleet"]["respawns"] >= 1
    assert rec["fleet"]["rolled"] == 2 and rec["fleet"]["roll"]["ok"]
    assert rec["fleet"]["floor"] >= 1
    assert rec["served_p99_ms"] is not None
    assert rec["error_budget"]["availability"]["total"] > 0
    # the slo event landed in the trace where trace_report renders it
    # and trace_diff regresses it — SAME record shape as run_soak
    rep = _tool("trace_report").report(r.trace_path)
    assert rep["slo"] is not None
    assert rep["slo"]["dropped"] == 0
    assert rep["slo"]["fleet"]["rolled"] == 2


# ------------------------------------------------- trace_diff fabric gate


def _bench(tmp_path, name, extra):
    p = tmp_path / name
    p.write_text(json.dumps({"extra": extra}))
    return str(p)


def test_trace_diff_fabric_regressions(tmp_path):
    td = _tool("trace_diff")
    old = td.load_fabric(_bench(tmp_path, "old.json", {
        "fabric_qps": {"n1": 100.0, "n4": 180.0},
        "fabric_recovery_s": 2.0, "fabric_dropped": 0,
        "fabric_double_served": 0,
    }))
    # QPS collapse at one fleet size regresses
    new = td.load_fabric(_bench(tmp_path, "new.json", {
        "fabric_qps": {"n1": 98.0, "n4": 90.0},
        "fabric_recovery_s": 2.1, "fabric_dropped": 0,
        "fabric_double_served": 0,
    }))
    rows = td.diff_fabric(old, new, threshold=0.25)
    assert [r["key"] for r in rows] == ["fabric.qps.n4"]
    # dropped/double-served are invariants: ANY increase regresses
    worse = td.load_fabric(_bench(tmp_path, "worse.json", {
        "fabric_qps": {"n1": 100.0, "n4": 180.0},
        "fabric_recovery_s": 2.0, "fabric_dropped": 1,
        "fabric_double_served": 0,
    }))
    keys = {r["key"] for r in td.diff_fabric(old, worse, threshold=0.25)}
    assert keys == {"fabric.dropped"}
    # recovery growth must clear BOTH the relative threshold and the
    # absolute jitter floor
    slow = td.load_fabric(_bench(tmp_path, "slow.json", {
        "fabric_qps": {"n1": 100.0, "n4": 180.0},
        "fabric_recovery_s": 7.5, "fabric_dropped": 0,
        "fabric_double_served": 0,
    }))
    keys = {r["key"] for r in td.diff_fabric(old, slow, threshold=0.25)}
    assert keys == {"fabric.recovery_s"}


def test_trace_diff_fabric_nulls_and_absence(tmp_path):
    td = _tool("trace_diff")
    # a failed fabric child records nulls: comparisons skip, no crash
    old = td.load_fabric(_bench(tmp_path, "o.json", {
        "fabric_qps": {"n1": None, "n4": 180.0},
        "fabric_recovery_s": None, "fabric_dropped": None,
        "fabric_double_served": None,
    }))
    new = td.load_fabric(_bench(tmp_path, "n.json", {
        "fabric_qps": {"n1": 50.0, "n4": 170.0},
        "fabric_recovery_s": 3.0, "fabric_dropped": 0,
        "fabric_double_served": 0,
    }))
    assert td.diff_fabric(old, new, threshold=0.25) == []
    # pre-fabric rounds: no gate until the first new round
    assert td.load_fabric(_bench(tmp_path, "pre.json", {"qps": 1})) is None
    assert td.diff_fabric(None, new, threshold=0.25) == []
    # a round LOSING its fabric numbers is itself a finding
    rows = td.diff_fabric(new, None, threshold=0.25)
    assert rows and rows[0]["key"] == "fabric.missing"


def test_fabric_refuses_a_second_replica_on_a_tpu_host(tmp_path, monkeypatch):
    """Each replica process claims every chip it sees: on a TPU host the
    fabric refuses a fleet of more than one replica before spawning any,
    and scale_up refuses to grow past one."""
    spawned = []
    monkeypatch.setattr(fabric, "on_tpu_host", lambda: True)
    monkeypatch.setattr(fabric.ServingFabric, "_spawn",
                        lambda self, i, **kw: spawned.append(i))
    fab = fabric.ServingFabric(str(tmp_path), fabric.FabricConfig(replicas=2))
    with pytest.raises(RuntimeError, match="only one replica"):
        fab.start()
    with pytest.raises(RuntimeError, match="2 replica processes on a TPU host"):
        fab.scale_up(2)
    assert spawned == []
    fabric.check_chip_budget(1)  # one replica process is fine


def test_cpu_replicas_are_not_on_a_tpu_host(monkeypatch):
    """Replicas that run on the CPU have no chip budget."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not fabric.on_tpu_host()
    fabric.check_chip_budget(4)
