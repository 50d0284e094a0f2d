#!/usr/bin/env bash
# Chaos gate: run the tier-1 suite under an aggressive fault-injection
# profile — every 5th guarded call (dispatch or host sync) at EVERY site
# raises a transient device error, and a generous sync deadline arms the
# watchdog thread on each guarded call.  The suite must pass unchanged:
# the resilience executor's retries make injected transients invisible to
# callers, which is exactly the property this gate pins.
#
# Tests that install their own chaos plan (resilience.chaos.inject) are
# unaffected: an explicit plan overrides the GRAFT_CHAOS env plan.
#
# A second scenario then kills logical device 1 of a forced 2-device CPU
# mesh (GRAFT_CHAOS="*:device_lost@dev:1") and requires both sharded
# runners to finish via the elastic mesh-shrink rung with outputs matching
# an uninterrupted run — the ISSUE 5 acceptance bar.
#
# The CPU backend is forced: these gates check code, never
# the chip.
set -euo pipefail
cd "$(dirname "$0")/.."
env JAX_PLATFORMS=cpu \
    GRAFT_CHAOS='*:fail@%5' \
    GRAFT_RETRY_MAX=4 \
    GRAFT_BACKOFF_BASE_S=0.01 \
    GRAFT_SYNC_DEADLINE_S=60 \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly "$@"

# ---------------------------------------------------------------------------
# device_lost sharded scenario (ISSUE 5 acceptance): on a forced 2-device
# CPU mesh with logical device 1 chaos-killed, BOTH sharded runners must
# finish via the elastic mesh-shrink rung (no ResilienceExhausted), match
# the uninterrupted outputs to atol 1e-6 f32, and leave a trace artifact
# holding exactly ONE mesh.shrink span with devices 2->1.
echo "== chaos: device_lost sharded scenario (2-device mesh, dev 1 dies) =="
scenario_dir=$(mktemp -d)
trap 'rm -rf "$scenario_dir"' EXIT
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    GRAFT_TRACE_DIR="$scenario_dir" \
    SCENARIO_DIR="$scenario_dir" \
    python - <<'EOF'
import glob
import os
import sys

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.io import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
    run_pagerank_sharded,
    run_tfidf_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    PageRankConfig,
    TfidfConfig,
)

sys.path.insert(0, "tools")  # chaos.sh runs from the repo root
import trace_report

kw = dict(dangling="redistribute", init="uniform", dtype="float32")
g = synthetic_powerlaw(800, 3200, seed=5)
chunks = [[f"tok{i} tok{i % 5} shared word extra{i % 3}"
           for i in range(j * 2, (j + 1) * 2)] for j in range(12)]

# uninterrupted references, BEFORE the chaos plan is installed
base_pr = run_pagerank(g, PageRankConfig(iterations=10, **kw))
base_tf = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                            n_devices=2)

os.environ["GRAFT_CHAOS"] = "*:device_lost@dev:1"

run = obs.start_run("chaos_device_lost", os.environ["SCENARIO_DIR"])
res = run_pagerank_sharded(g, PageRankConfig(iterations=10, **kw),
                           n_devices=2)
np.testing.assert_allclose(res.ranks, base_pr.ranks, atol=1e-6)

elastic.reset_health()  # fresh loss for the second runner
tf = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10), n_devices=2)
np.testing.assert_allclose(tf.to_dense(), base_tf.to_dense(), atol=1e-6)

# the owned strategy (ISSUE 15): the shrink rung must re-own the rank
# slices and rebuild the boundary sets for the surviving mesh
elastic.reset_health()
res_o = run_pagerank_sharded(g, PageRankConfig(iterations=10, **kw),
                             n_devices=2, strategy="owned")
np.testing.assert_allclose(res_o.ranks, base_pr.ranks, atol=1e-6)
obs.end_run()

rep = trace_report.report(glob.glob(
    os.path.join(os.environ["SCENARIO_DIR"], "chaos_device_lost.*.trace.jsonl")
)[0])
shrinks = rep["mesh_shrinks"]
assert len(shrinks) == 3, shrinks  # one per runner (pagerank/tfidf/owned)
for s in shrinks:
    assert (s["devices_old"], s["devices_new"]) == (2, 1), s
assert not rep["exhausted"], rep["exhausted"]
print("device_lost scenario: OK — all three sharded runners survived via "
      f"mesh-shrink ({[s['site'] for s in shrinks]})")
EOF

# ---------------------------------------------------------------------------
# dataflow-core fixpoint scenario (ISSUE 9): the fixpoint primitive that
# every workload now runs over (dataflow.fixpoint.iterate inside the jit,
# dataflow.fixpoint.run_segments + the elastic ladder on the host side) is
# exercised AS a tolerance (while-loop) fixpoint on a 2-device mesh with
# logical device 1 chaos-killed mid-run: the run must finish via the
# mesh-shrink rung with ranks matching the uninterrupted fixpoint, and a
# batched personalized-PageRank fixpoint must survive a single-chip
# device loss at its delta-sync site through the same shared wiring.
echo "== chaos: dataflow fixpoint under device_lost (2-device mesh) =="
dflow_dir=$(mktemp -d)
trap 'rm -rf "$scenario_dir" "$dflow_dir"' EXIT
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    GRAFT_TRACE_DIR="$dflow_dir" \
    SCENARIO_DIR="$dflow_dir" \
    python - <<'EOF'
import glob
import os
import sys

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.ppr import run_ppr_batch
from page_rank_and_tfidf_using_apache_spark_tpu.io import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import run_pagerank_sharded
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

sys.path.insert(0, "tools")  # chaos.sh runs from the repo root
import trace_report

kw = dict(dangling="redistribute", init="uniform", dtype="float32")
g = synthetic_powerlaw(800, 3200, seed=9)
# tolerance run: the while-loop branch of dataflow.fixpoint.iterate
cfg = PageRankConfig(iterations=200, tol=1e-8, **kw)
base = run_pagerank_sharded(g, cfg, n_devices=2)
queries = [[int(g.node_ids[0])], [int(g.node_ids[10])]]
base_ppr = run_ppr_batch(g, PageRankConfig(iterations=30, **kw), queries)

os.environ["GRAFT_CHAOS"] = "*:device_lost@dev:1"
run = obs.start_run("chaos_dataflow_fixpoint", os.environ["SCENARIO_DIR"])
res = run_pagerank_sharded(g, cfg, n_devices=2)
np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)

# single-chip dataflow fixpoint: device 0 dies at the PPR delta sync ->
# the checkpoint-salvage rung re-runs on the CPU backend
elastic.reset_health()
os.environ["GRAFT_CHAOS"] = "ppr_delta_sync:device_lost@dev:0"
ppr = run_ppr_batch(g, PageRankConfig(iterations=30, **kw), queries)
np.testing.assert_allclose(ppr.ranks, base_ppr.ranks, atol=1e-6)
obs.end_run()

rep = trace_report.report(glob.glob(os.path.join(
    os.environ["SCENARIO_DIR"], "chaos_dataflow_fixpoint.*.trace.jsonl"
))[0])
shrinks = rep["mesh_shrinks"]
assert len(shrinks) == 1 and (
    shrinks[0]["devices_old"], shrinks[0]["devices_new"]) == (2, 1), shrinks
# the INNER guarded delta fetch exhausts by design (its own ladder has no
# rungs — the outer segment ladder owns recovery); anything else
# exhausting means the salvage rung failed
assert set(rep["exhausted"]) <= {"ppr_delta_sync"}, rep["exhausted"]
assert any(d == "ppr_step" for d in rep["degraded"]), rep["degraded"]
print("dataflow fixpoint scenario: OK — sharded tol-fixpoint shrank 2->1 "
      "and the batched-PPR fixpoint salvaged through the shared ladder")
EOF

# ---------------------------------------------------------------------------
# staged-ingest H2D scenario (ISSUE 10): device_lost injected at the new
# ingest_h2d_put staging site — a fault on an IN-FLIGHT staged chunk —
# must walk the elastic rung on both ingest paths: the single-chip
# streaming pipeline rolls back to its last commit and replays the
# retained host chunks on the CPU rung; the 2-device sharded pipeline
# shrinks its mesh and re-slices the in-flight staged groups over the
# survivor.  Outputs must match uninterrupted runs; the trace must carry
# the per-stage ingest accounting (h2d_overlap_frac) for both.
echo "== chaos: device_lost at ingest_h2d_put (staged ingest, both paths) =="
ingest_dir=$(mktemp -d)
trap 'rm -rf "$scenario_dir" "$dflow_dir" "$ingest_dir"' EXIT
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    GRAFT_TRACE_DIR="$ingest_dir" \
    SCENARIO_DIR="$ingest_dir" \
    python - <<'EOF'
import glob
import os
import sys

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import run_tfidf_sharded
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig

sys.path.insert(0, "tools")  # chaos.sh runs from the repo root
import trace_report

chunks = [[f"tok{i} tok{i % 5} shared word extra{i % 3}"
           for i in range(j * 2, (j + 1) * 2)] for j in range(12)]

# uninterrupted references, BEFORE the chaos plan is installed
cfg = TfidfConfig(vocab_bits=10, prefetch=2, pipeline_depth=2)
base_stream = run_tfidf_streaming(iter(chunks), cfg)
base_shard = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                               n_devices=2)

run = obs.start_run("chaos_ingest_h2d", os.environ["SCENARIO_DIR"])

# single-chip: device 0 dies at the H2D put -> CPU rung, rollback+replay
os.environ["GRAFT_CHAOS"] = "ingest_h2d_put:device_lost@dev:0"
res = run_tfidf_streaming(iter(chunks), cfg)
assert res.to_dense().tobytes() == base_stream.to_dense().tobytes()

# 2-device sharded: device 1 dies at the sharded put -> mesh shrink 2->1,
# in-flight staged groups re-sliced from retained host corpora
elastic.reset_health()
os.environ["GRAFT_CHAOS"] = "ingest_h2d_put:device_lost@dev:1"
tf = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10), n_devices=2)
np.testing.assert_allclose(tf.to_dense(), base_shard.to_dense(), atol=1e-6)
obs.end_run()

rep = trace_report.report(glob.glob(os.path.join(
    os.environ["SCENARIO_DIR"], "chaos_ingest_h2d.*.trace.jsonl"))[0])
shrinks = rep["mesh_shrinks"]
assert len(shrinks) == 1 and (
    shrinks[0]["devices_old"], shrinks[0]["devices_new"]) == (2, 1), shrinks
assert shrinks[0]["site"] == "ingest_h2d_put", shrinks
assert rep["degraded"].get("ingest_h2d_put", 0) >= 2, rep["degraded"]
assert not rep["exhausted"], rep["exhausted"]
assert rep["ingest"] and all("h2d_overlap_frac" in r for r in rep["ingest"])
print("staged-ingest scenario: OK — single-chip rolled back+replayed on "
      "the cpu rung, sharded shrank 2->1 re-slicing staged groups "
      f"(ingest runs traced: {len(rep['ingest'])})")
EOF

# ---------------------------------------------------------------------------
# segment hot-swap scenario (ISSUE 13): live traffic against a segmented
# server while delta segments commit and the background merge compacts —
# under transient dispatch chaos AND a transient merge fault.  Every
# logical request must be served exactly once (zero dropped, zero
# double-served via the abandoned-future audit), the post-start segment
# must answer with its global doc id, and the injected merge fault must
# be retried by the resilience executor (not surface, not skip the merge).
echo "== chaos: segment hot-swap under dispatch chaos + merge fault =="
seg_dir=$(mktemp -d)
trap 'rm -rf "$scenario_dir" "$dflow_dir" "$ingest_dir" "$seg_dir"' EXIT
env JAX_PLATFORMS=cpu \
    GRAFT_RETRY_MAX=4 \
    GRAFT_BACKOFF_BASE_S=0.01 \
    SEG_DIR="$seg_dir" \
    python - <<'EOF'
import os
import threading
import time

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import serving
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig

d = os.path.join(os.environ["SEG_DIR"], "idx")
scfg = TfidfConfig(vocab_bits=10)
docs = [f"doc{i} shared word tok{i % 7}" for i in range(12)]
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(d, out, scfg, doc_base=0)
sgm.commit_append(d, ref, scfg.config_hash())
srv = serving.TfidfServer(
    sgm.load_segment_set(d),
    serving.ServeConfig(top_k=3, max_batch=4, scoring="impacted"),
).start()

stop = threading.Event()
records = []

def client(idx):
    rng = np.random.default_rng(idx)
    while not stop.is_set():
        rec = {"ok": False, "abandoned": []}
        records.append(rec)
        for _ in range(50):
            fut = None
            try:
                fut = srv.submit([f"tok{int(rng.integers(0, 7))}", "shared"])
                fut.result(5.0)
                rec["ok"] = True
                break
            except Exception:
                if fut is not None and not fut.done:
                    rec["abandoned"].append(fut)
                time.sleep(0.01)
        time.sleep(0.005)

with chaos.inject("serve_dispatch:fail@%5;segment_merge:fail@1") as plan:
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    base = out.n_docs
    for i in range(3):
        o = run_tfidf([f"swap{i} fresh shared"], scfg)
        r = sgm.seal_segment(d, o, scfg, doc_base=base)
        sgm.commit_append(d, r, scfg.config_hash())
        base += o.n_docs
        srv.refresh_segments(sgm.load_segment_set(d))
        time.sleep(0.1)
    s, i2 = srv.query(["swap2"])
    assert float(s[0]) > 0 and int(i2[0]) == base - 1, (s, i2)
    merger = sgm.SegmentMerger(d, scfg, max_segments=1)
    while merger.merge_once():
        pass
    srv.refresh_segments(sgm.load_segment_set(d))
    s, i3 = srv.query(["swap2"])
    assert int(i3[0]) == int(i2[0])
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    assert plan.call_count("segment_merge") >= 2  # injected fail + retry
time.sleep(0.2)
srv.stop()
finished = [r for r in records if r["ok"] or len(r["abandoned"]) >= 1]
dropped = double = 0
for r in finished:
    served = int(r["ok"]) + sum(
        1 for f in r["abandoned"] if f.done and f.error is None)
    dropped += served == 0
    double += max(served - 1, 0)
assert dropped == 0 and double == 0, (dropped, double)
assert len(sgm.latest_manifest(d).segments) == 1
print("segment hot-swap scenario: OK — "
      f"{len(finished)} requests audited across 4 hot swaps + merge, "
      "dropped=0 double_served=0, merge fault retried")
EOF

# ---------------------------------------------------------------------------
# crash-recovery scenario (ISSUE 14): SIGKILL a committing ingest child at
# EVERY enumerated write boundary of the seal+commit_append protocol (the
# streaming delta-segment commit path) via tools/crash_harness.py.  After
# each kill the reloaded segment set must serve byte-identically to the
# pre-kill generation (a kill anywhere before the final LATEST flip) or
# the committed one — never a torn set — and a post-recovery
# serving.segments.gc_orphans pass must leave zero orphan tmp/unnamed
# dirs (a second sweep and an independent re-scan both find nothing).
echo "== chaos: SIGKILL mid-commit_append at every write boundary (crash harness) =="
python - <<'EOF'
import json
import subprocess
import sys

proc = subprocess.run(
    [sys.executable, "tools/crash_harness.py", "--scenarios", "append",
     "--json"],
    capture_output=True, text=True, timeout=300,
)
if proc.returncode != 0:
    sys.stderr.write(proc.stderr[-3000:])
    raise SystemExit("crash harness failed")
rep = json.loads(proc.stdout)["append"]
assert rep["boundaries"] >= 4, rep  # seal (2 renames) + commit (2 renames)
assert len(rep["kills"]) == rep["boundaries"], rep
# every pre-flip kill must serve the PRE-kill generation byte-identically
assert rep["served_pre"] >= 1 and rep["served_pre"] + rep["served_post"] \
    == rep["boundaries"], rep
print("crash-recovery scenario: OK — "
      f"{rep['boundaries']} SIGKILL point(s) through commit_append, "
      f"{rep['served_pre']} served the pre-kill generation / "
      f"{rep['served_post']} the committed one, 0 torn, 0 orphans "
      "after recovery GC")
EOF

# ---------------------------------------------------------------------------
# malformed-message fabric scenario (ISSUE 18): the wire-protocol harness
# guards the router<->replica message surface; this scenario re-runs its
# malformed / duplicate-rid / stale-floor matrix and then replays
# malformed messages at a LIVE faulted fleet — fabric_route:net_partition@2
# faults the router->replica link mid-retry, replica_query:proc_kill@3
# SIGKILLs a real replica mid-query, and replica_swap:proc_kill@1 kills a
# process at its hot-swap seam — asserting typed 400s (never a 500, never
# a hang) and a clean dropped=0 / double_served=0 audit throughout.
echo "== chaos: malformed messages at a faulted fleet (fabric_route / replica_query / replica_swap) =="
python tools/protocol_harness.py
python - <<'EOF'
import json
import subprocess
import sys
import tempfile
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

scfg = TfidfConfig(vocab_bits=10)
docs = ["node edge graph rank walk", "graph node directed edge weight",
        "rank walk teleport damping node", "edge list sparse matrix graph"]
tmp = tempfile.mkdtemp(prefix="chaos-proto-")
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(tmp, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(tmp, ref, scfg.config_hash())

MALFORMED = [b"{not json", b"[]", b"null", b'{"terms": ["node"]}']


def post_raw(port, body):
    """None = the port is dead (a SIGKILLed replica mid-respawn: that IS
    the chaos, not a protocol violation).  A live port must answer a
    typed status within the timeout — never hang, never crash."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=5.0) as r:
            return r.status
    except urllib.error.HTTPError as exc:
        return exc.code
    except urllib.error.URLError:
        return None


# a real 2-replica fleet: replica 1 SIGKILLs itself mid-query
# (replica_query:proc_kill@3); the router link is partitioned every 2nd
# hop (fabric_route:net_partition@2) while malformed bodies land at the
# live replica ports between valid routed queries
fab = fabric.ServingFabric(tmp, fabric.FabricConfig(
    replicas=2, poll_s=0.1, health_period_s=0.2, retry_limit=100,
    retry_pause_s=0.1, request_timeout_s=10.0, grace_s=10.0,
    replica_chaos=((1, "replica_query:proc_kill@3"),),
))
typed_rejections = 0
with fab:
    with chaos.inject("fabric_route:net_partition@2"):
        for i in range(10):
            scores, _ = fab.query(["node"])
            assert len(scores) > 0
            port = fab._ports[i % len(fab._ports)]
            code = post_raw(port, MALFORMED[i % len(MALFORMED)])
            assert code in (400, None), (
                f"malformed message answered {code}, want typed 400")
            if code == 400:
                typed_rejections += 1
    assert typed_rejections >= 4, typed_rejections
    audit = fab.audit()
    assert audit["dropped"] == 0, audit
    assert audit["double_served"] == 0, audit

# the hot-swap kill seam: replica_swap:proc_kill@1 must SIGKILL the
# process at its FIRST swap call — a malformed-timing fault the
# supervisor absorbs in the fleet scenario above
probe = subprocess.run(
    [sys.executable, "-c",
     "from page_rank_and_tfidf_using_apache_spark_tpu.resilience import "
     "chaos\n"
     "ctx = chaos.inject('replica_swap:proc_kill@1'); ctx.__enter__()\n"
     "chaos.on_call('replica_swap')\n"],
    timeout=60,
)
assert probe.returncode == -9, probe.returncode

print("malformed-message fabric scenario: OK — typed 400s under "
      "fabric_route:net_partition@2 + replica_query:proc_kill@3, "
      "replica_swap:proc_kill@1 kill seam verified, "
      "dropped=0 double_served=0")
EOF

# ---------------------------------------------------------------------------
# scrape-chaos scenario (ISSUE 19): the fleet observability plane must
# degrade to STALENESS, never to routing impact.  fed_scrape:net_partition
# severs every scrape mid-traffic — queries keep routing, the audit stays
# dropped=0 / double_served=0, the partitioned replicas are LABELED stale
# (never dropped from the board, last-known state kept in the aggregate)
# and recover to fresh once the partition lifts; fed_scrape:net_hang then
# stalls scrapes on the scraper thread while the query path stays live.
echo "== chaos: fleet scrape partition/hang (fed_scrape) =="
python - <<'EOF'
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

# fast scrape cadence so staleness (3 missed scrapes) is observable in
# a bounded scenario: stale after 0.6s
os.environ["GRAFT_FED_SCRAPE_S"] = "0.2"

scfg = TfidfConfig(vocab_bits=10)
docs = ["node edge graph rank walk", "graph node directed edge weight",
        "rank walk teleport damping node", "edge list sparse matrix graph"]
tmp = tempfile.mkdtemp(prefix="chaos-scrape-")
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(tmp, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(tmp, ref, scfg.config_hash())

fab = fabric.ServingFabric(tmp, fabric.FabricConfig(
    replicas=2, poll_s=0.1, health_period_s=0.2, retry_limit=100,
    retry_pause_s=0.1, grace_s=10.0,
))
with fab:
    for _ in range(6):
        scores, _ = fab.query(["node"])
        assert len(scores) > 0
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        snap = fab.fleet.snapshot()
        if (snap["counters"].get("serve.requests", {}).get("total", 0) >= 1
                and not snap["fleet"]["stale"]):
            break
        time.sleep(0.2)
    assert len(snap["fleet"]["replicas"]) == 2, snap["fleet"]
    assert not snap["fleet"]["stale"], snap["fleet"]
    base_total = snap["counters"]["serve.requests"]["total"]
    assert base_total >= 1, snap["counters"]

    # every scrape severed: routing must not notice, the board must
    # label (never drop) the unreachable replicas and keep their
    # last-known contribution in the aggregate
    with chaos.inject("fed_scrape:net_partition@1+"):
        for _ in range(10):
            scores, _ = fab.query(["graph"])
            assert len(scores) > 0
        time.sleep(1.0)  # > stale_after_s (0.6): three missed scrapes
        snap2 = fab.fleet.snapshot()
        assert snap2["fleet"]["replicas"] == snap["fleet"]["replicas"], \
            snap2["fleet"]  # partitioned replicas never dropped
        assert len(snap2["fleet"]["stale"]) == 2, snap2["fleet"]
        assert snap2["fleet"]["per_replica"]["0"]["stale"], snap2["fleet"]
        kept = snap2["counters"]["serve.requests"]["total"]
        assert kept >= base_total, (kept, base_total)  # last-known kept
    assert snap2["fleet"]["scrape_errors"] >= 2, snap2["fleet"]

    # partition lifted: the scraper recovers the fleet to fresh
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if not fab.fleet.snapshot()["fleet"]["stale"]:
            break
        time.sleep(0.2)
    assert not fab.fleet.snapshot()["fleet"]["stale"]

    # hung scrapes stall the scraper thread, not the query path
    with chaos.inject("fed_scrape:net_hang@1+:400"):
        for _ in range(10):
            scores, _ = fab.query(["rank"])
            assert len(scores) > 0
    audit = fab.audit()

assert audit["dropped"] == 0, audit
assert audit["double_served"] == 0, audit
assert audit["requests"] == 26 and audit["delivered"] == 26, audit

print("scrape-chaos scenario: OK — 26/26 delivered under "
      "fed_scrape:net_partition@1+ + net_hang@1+:400, both replicas "
      "labeled stale (never dropped), aggregate kept last-known state, "
      "fleet recovered to fresh, dropped=0 double_served=0")
EOF


# cache-partition scenario (ISSUE 20): the sharded result cache must
# degrade to LOCAL COMPUTE, never to blocking or wrong bytes.  Replica 1
# boots with cache_peek:net_partition@1 + net_hang@2:2000 +
# cache_fill:net_partition@1+ in ITS environment (replica_chaos): its
# first peek at the owner partitions, the consecutive fill failure trips
# the per-peer breaker within GRAFT_CACHE_BREAKER_TRIP=2, later queries
# fail fast (no peer I/O), the half-open probe eats the 2s hang bounded
# by the 0.4s peek deadline, and the NEXT probe recloses the breaker
# with a real peer hit — byte-identical to the owner's answer.  Routed
# traffic never notices: audit dropped=0 / double_served=0.
echo "== chaos: sharded-cache peer partition/hang (cache_peek / cache_fill) =="
python - <<'EOF'
import json
import os
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

os.environ["GRAFT_CACHE_BREAKER_TRIP"] = "2"
os.environ["GRAFT_CACHE_BREAKER_PROBE_S"] = "1.0"
os.environ["GRAFT_CACHE_PEEK_DEADLINE_S"] = "0.4"

scfg = TfidfConfig(vocab_bits=10)
docs = ["node edge graph rank walk", "graph node directed edge weight",
        "rank walk teleport damping node", "edge list sparse matrix graph"]
tmp = tempfile.mkdtemp(prefix="chaos-cache-")
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(tmp, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(tmp, ref, scfg.config_hash())

SPEC = ("cache_peek:net_partition@1;cache_peek:net_hang@2:2000;"
        "cache_fill:net_partition@1+")
fab = fabric.ServingFabric(tmp, fabric.FabricConfig(
    replicas=2, poll_s=0.1, health_period_s=0.2, retry_limit=100,
    retry_pause_s=0.1, grace_s=10.0, federation=False,
    replica_chaos=((1, SPEC),),
))

def post(port, path, doc, timeout=5.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())

def status(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=5.0) as resp:
        return json.loads(resp.read())

# single-word keys the cache ring routes to replica 0 (the owner):
# driving them at replica 1 directly exercises the non-owner peek path
ring = fabric._Ring([0, 1], 64)
owned = [[w] for w in (f"k{i}" for i in range(200))
         if ring.route(fabric.affinity_key([w], "tfidf"))[0] == 0]
assert len(owned) >= 4, len(owned)
k_hot, k_open, k_hang, k_heal = owned[0], owned[1], owned[2], owned[3]

with fab:
    p1 = fab._ports[1]
    # warm the owner through the router (affinity routes k_hot to 0)
    ref_scores, ref_docs = fab.query(k_hot)

    # peek#1 partitions, the consecutive fill failure trips the breaker
    t0 = time.perf_counter()
    r1 = post(p1, "/query", {"rid": "cc-1", "terms": k_hot,
                             "ranker": "tfidf"})
    assert time.perf_counter() - t0 < 2.0  # bounded: deadline + compute
    assert r1["scores"] == [float(s) for s in ref_scores], r1
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and status(p1)["breaker_open"] == 0:
        time.sleep(0.05)  # the tripping fill is asynchronous
    st = status(p1)
    assert st["breaker_open"] == 1, st
    assert st["peek_timeouts"] >= 1, st

    # breaker open: no peer I/O at all — fast local compute, and the
    # routed path keeps serving correct bytes mid-partition
    t0 = time.perf_counter()
    post(p1, "/query", {"rid": "cc-2", "terms": k_open, "ranker": "tfidf"})
    assert time.perf_counter() - t0 < 1.0
    for _ in range(5):
        scores, _ = fab.query(k_hot)
        assert [float(s) for s in scores] == [float(s) for s in ref_scores]

    # half-open probe #1 eats the 2s hang but blocks only for the 0.4s
    # peek deadline before falling back to local compute (re-opens)
    time.sleep(1.2)
    t0 = time.perf_counter()
    post(p1, "/query", {"rid": "cc-3", "terms": k_hang, "ranker": "tfidf"})
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.5, elapsed  # NOT the 2s hang
    st = status(p1)
    assert st["breaker_open"] == 1, st
    assert st["peek_timeouts"] >= 2, st

    # half-open probe #2 is clean: warms through the owner, recloses
    time.sleep(1.2)
    fab.query(k_heal)  # router warms the owner first
    r4 = post(p1, "/query", {"rid": "cc-4", "terms": k_heal,
                             "ranker": "tfidf"})
    st = status(p1)
    assert st["breaker_open"] == 0, st
    assert st["peer_hits"] >= 1, st
    audit = fab.audit()

assert audit["dropped"] == 0, audit
assert audit["double_served"] == 0, audit
assert audit["failed"] == 0, audit

print("cache-partition scenario: OK — non-owner served correct bytes "
      "under cache_peek:net_partition/net_hang + cache_fill:net_partition, "
      "blocking bounded by the 0.4s peek deadline (2s hang absorbed), "
      "breaker tripped at 2 consecutive failures, half-open probe "
      "reclosed it with a real peer hit, dropped=0 double_served=0")
EOF
