#!/usr/bin/env bash
# Full CI pipeline: tier-1 tests, all six graftlint tiers, and the chaos
# gate.
#
# The semantic lint tier (tier 2: CPU-only jaxpr tracing of every
# registered jit entry point) carries a wall-clock budget —
# GRAFT_SEMANTIC_BUDGET_S, default 60s — so trace-time regressions (an
# entry point ballooning, a registry builder doing real work) fail CI
# instead of silently eating the loop.
#
# The CPU backend is forced throughout: these gates check code, never
# the chip.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 tests =="
env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly

echo "== graftlint tier 1 (lexical) =="
tools/lint.sh --tier 1

echo "== graftlint tier 2 (semantic, budget ${GRAFT_SEMANTIC_BUDGET_S:-60}s) =="
t0=$(date +%s)
tools/lint.sh --tier 2
dt=$(( $(date +%s) - t0 ))
echo "semantic tier: ${dt}s"
if [ "$dt" -gt "${GRAFT_SEMANTIC_BUDGET_S:-60}" ]; then
    echo "FAIL: semantic tier exceeded its ${GRAFT_SEMANTIC_BUDGET_S:-60}s budget (${dt}s)" >&2
    exit 1
fi

echo "== graftlint tier 3 (cost model, budget ${GRAFT_COST_BUDGET_S:-10}s) =="
# Static cost analysis (intensity floors / pad_frac budgets / donation
# verifier) is all trace-time work and must stay interactive-fast: a cost
# run that stops fitting its budget is itself a regression (a registry
# builder started doing real work).
t0=$(date +%s)
tools/lint.sh --tier 3
dt=$(( $(date +%s) - t0 ))
echo "cost tier: ${dt}s"
if [ "$dt" -gt "${GRAFT_COST_BUDGET_S:-10}" ]; then
    echo "FAIL: cost tier exceeded its ${GRAFT_COST_BUDGET_S:-10}s budget (${dt}s)" >&2
    exit 1
fi

echo "== autotune smoke (dry-run prune plan + committed-profile round-trip, budget ${GRAFT_TUNE_BUDGET_S:-60}s) =="
# The cost model that tier 3 audits with also DRIVES the tuner (ISSUE
# 16): the dry-run must show static pruning discarding >=30% of the raw
# knob grid before anything is measured, every group must keep at least
# one survivor (a group pruned to zero would make the real sweep
# unrunnable), and the committed per-backend profile must parse AND
# round-trip through the same utils/config loader the runners resolve
# knobs from — all inside the tuner's own declared budget knob.
t0=$(date +%s)
env JAX_PLATFORMS=cpu \
    python tools/autotune.py --dry-run --json > /tmp/_autotune_plan.json
python - /tmp/_autotune_plan.json <<'EOF'
import json
import os
import sys
import tempfile

with open(sys.argv[1]) as f:
    plan = json.load(f)["plan"]
frac = plan["prune_frac"]
assert frac >= 0.30, (
    f"static pruning discarded only {frac:.1%} of the raw grid — the "
    "cost model stopped doing the tuner's first-pass work")
assert plan["raw_points"] == plan["pruned_points"] + plan["survivor_points"]
for g, gp in plan["groups"].items():
    assert gp["survivors"], f"group {g!r} pruned to zero survivors"

from page_rank_and_tfidf_using_apache_spark_tpu.utils import config

prof = config.load_tuned_profile(backend="cpu")
assert prof is not None, "committed tuned_profile_cpu.json did not load"
assert prof.backend == "cpu" and prof.source == "committed"
assert set(prof.knobs) == set(config.TUNABLE_DEFAULTS), (
    sorted(set(config.TUNABLE_DEFAULTS) ^ set(prof.knobs)))
with tempfile.TemporaryDirectory() as d:
    p = os.path.join(d, "tuned_profile_cpu.json")
    config.write_tuned_profile(p, "cpu", prof.knobs, measured={"smoke": True})
    back = config.load_tuned_profile(path=p)
    assert back.knobs == prof.knobs, "loader round-trip changed the knobs"
print(f"autotune smoke: OK ({plan['pruned_points']}/{plan['raw_points']} "
      f"points pruned statically = {frac:.1%}, committed cpu profile "
      f"round-trips {len(prof.knobs)} knobs)")
EOF
rm -f /tmp/_autotune_plan.json
dt=$(( $(date +%s) - t0 ))
echo "autotune smoke: ${dt}s"
if [ "$dt" -gt "${GRAFT_TUNE_BUDGET_S:-60}" ]; then
    echo "FAIL: autotune smoke exceeded its ${GRAFT_TUNE_BUDGET_S:-60}s budget (${dt}s)" >&2
    exit 1
fi

echo "== graftlint tier 4 (concurrency, budget ${GRAFT_CONC_BUDGET_S:-10}s; incl. lock-graph smoke) =="
# Interprocedural concurrency & buffer-lifetime analysis (lock-order
# cycles, blocking-under-lock, use-after-donate, chaos-coverage drift,
# thread/lock registry drift) is pure AST — stdlib-only like tier 1 —
# and must stay interactive-fast under its own declared budget knob.
# ONE invocation serves both gates: its exit code is the findings gate
# (set -e aborts on failure) and its captured stdout is the --lock-graph
# DOT smoke — the graph must stay emittable for human inspection
# (tools/trace_report.py-style), naming at least the serving drain lock.
t0=$(date +%s)
lock_dot=$(tools/lint.sh --tier 4 --lock-graph)
dt=$(( $(date +%s) - t0 ))
echo "concurrency tier: ${dt}s"
if [ "$dt" -gt "${GRAFT_CONC_BUDGET_S:-10}" ]; then
    echo "FAIL: concurrency tier exceeded its ${GRAFT_CONC_BUDGET_S:-10}s budget (${dt}s)" >&2
    exit 1
fi
case "$lock_dot" in
    *"digraph lock_graph"*"TfidfServer._lock"*) ;;
    *) echo "FAIL: --lock-graph emitted no usable DOT graph" >&2
       printf '%s\n' "$lock_dot" | head -20 >&2
       exit 1 ;;
esac
echo "lock-graph smoke: OK ($(printf '%s\n' "$lock_dot" | grep -c ' -> ') edge(s) emitted)"

echo "== graftlint tier 5 (persistence, budget ${GRAFT_PERSIST_BUDGET_S:-10}s; incl. crash-point smoke) =="
# Persistence & crash-consistency analysis (atomic-write drift,
# pointer-flip ordering, generation-deferred GC, ARTIFACT_SCHEMAS
# writer/reader drift, commit-lock drift) is pure AST — stdlib-only like
# tiers 1/4 — under its own declared budget knob.  ONE invocation serves
# both gates: exit code = findings gate, captured stdout = the
# --crash-points smoke — the derived crash-surface enumeration must stay
# emittable and must still contain the two commit_append rename
# boundaries the crash harness SIGKILLs.
t0=$(date +%s)
crash_json=$(tools/lint.sh --tier 5 --crash-points --json)
dt=$(( $(date +%s) - t0 ))
echo "persistence tier: ${dt}s"
if [ "$dt" -gt "${GRAFT_PERSIST_BUDGET_S:-10}" ]; then
    echo "FAIL: persistence tier exceeded its ${GRAFT_PERSIST_BUDGET_S:-10}s budget (${dt}s)" >&2
    exit 1
fi
crash_tmp=$(mktemp)
printf '%s\n' "$crash_json" > "$crash_tmp"
python - "$crash_tmp" <<'EOF'
import json
import sys

with open(sys.argv[1]) as f:
    doc = json.load(f)
assert doc["ok"] is True, doc.get("findings")
cps = doc["crash_points"]
# validate the commit_append entry SPECIFICALLY (a null entry or marker
# strings borrowed from commit_replace's chains must not pass)
entry = next((k for k in cps if k.endswith("::commit_append")), None)
assert entry is not None, sorted(cps)
pts = cps[entry]
assert pts, f"{entry} enumeration is empty/null — the harness's kill schedule is gone"
bounds = [p for p in pts if p["boundary"]]
assert [b["op"] for b in bounds] == ["replace", "replace"], bounds
assert "_write_manifest()" in bounds[0]["via"], bounds[0]
assert "_write_pointer()" in bounds[1]["via"], bounds[1]
total = sum(1 for e in cps.values() if e for _p in e)
print(f"crash-point smoke: OK ({len(bounds)} commit_append boundary point(s), "
      f"{total} enumerated op(s) across {len(cps)} commit sequences)")
EOF
rm -f "$crash_tmp"

echo "== crash-harness smoke (SIGKILL at 3 commit_append boundaries) =="
# The dynamic half of tier 5 (ISSUE 14), bounded for CI: replay the real
# seal+commit_append protocol with a SIGKILL at 3 of its enumerated write
# boundaries (spread across the window) and require reload to serve a
# consistent generation — old or new, never torn — with zero orphans
# after the recovery GC pass.  tools/chaos.sh runs the full kill matrix.
python tools/crash_harness.py --scenarios append --max-kills 3

echo "== graftlint tier 6 (wire protocol, budget ${GRAFT_PROTO_BUDGET_S:-10}s; incl. wire-probe smoke) =="
# Distributed wire-protocol analysis (endpoint/status-code/key drift
# against WIRE_SCHEMAS, status-class drift against the router's retry
# logic, retry-unsafe effects ahead of the rid dedup guard, floor
# monotonicity) is pure AST — stdlib-only like tiers 1/4/5 — under its
# own declared budget knob.  ONE invocation serves both gates: exit
# code = findings gate, captured stdout = the --wire-probes smoke — the
# derived message-space enumeration must stay emittable and must still
# contain the duplicate-rid and stale-floor probes the conformance
# harness replays.
t0=$(date +%s)
wire_json=$(tools/lint.sh --tier 6 --wire-probes --json)
dt=$(( $(date +%s) - t0 ))
echo "protocol tier: ${dt}s"
if [ "$dt" -gt "${GRAFT_PROTO_BUDGET_S:-10}" ]; then
    echo "FAIL: protocol tier exceeded its ${GRAFT_PROTO_BUDGET_S:-10}s budget (${dt}s)" >&2
    exit 1
fi
wire_tmp=$(mktemp)
printf '%s\n' "$wire_json" > "$wire_tmp"
python - "$wire_tmp" <<'EOF'
import json
import sys

with open(sys.argv[1]) as f:
    doc = json.load(f)
assert doc["ok"] is True, doc.get("findings")
probes = doc["wire_probes"]
kinds = {p["kind"] for p in probes}
# the two probes the harness's core invariants ride on must be derivable
assert "duplicate-rid" in kinds, sorted(kinds)
assert "stale-floor" in kinds, sorted(kinds)
assert any(p["kind"] == "unknown-path" for p in probes), sorted(kinds)
print(f"wire-probe smoke: OK ({len(probes)} probe(s), "
      f"{len(kinds)} kind(s) enumerated)")
EOF
rm -f "$wire_tmp"

echo "== protocol-harness smoke (declared message space at a live replica) =="
# The dynamic half of tier 6: replay the enumerated malformed /
# out-of-contract / duplicate-rid / stale-floor matrix at a live replica
# and through the router — typed rejection everywhere, zero hangs, zero
# double executions, byte-identical replay.  Shares the protocol tier's
# budget knob: the whole matrix is a bounded smoke, not a soak.
t0=$(date +%s)
python tools/protocol_harness.py
dt=$(( $(date +%s) - t0 ))
echo "protocol harness: ${dt}s"
if [ "$dt" -gt "${GRAFT_PROTO_BUDGET_S:-10}" ]; then
    echo "FAIL: protocol harness exceeded its ${GRAFT_PROTO_BUDGET_S:-10}s budget (${dt}s)" >&2
    exit 1
fi

echo "== drain kill-matrix smoke (SIGKILL at 3 handoff points, budget ${GRAFT_DRAIN_BUDGET_S:-40}s) =="
# The drain handoff's kill-point discipline, exercised for real: a
# 1-replica fleet rolls via SO_REUSEPORT socket handoff while SIGKILL
# lands (a) on the predecessor pre-drain (mid-successor-spawn), (b) on
# the predecessor mid-drain (right after the swap), (c) on the healthy
# successor post-roll.  After every point exactly ONE process serves the
# pinned port — repeated /status polls see a single pid — and the
# closed-loop audit stays dropped=0 / double_served=0.
t0=$(date +%s)
if env JAX_PLATFORMS=cpu python - > /tmp/_drain_matrix.log 2>&1 <<'EOF'
import json
import os
import signal
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path.cwd()))
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.obs.export import (
    reuse_port_supported,
)
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

if not reuse_port_supported():
    print("drain kill-matrix: SKIP (platform lacks SO_REUSEPORT)")
    sys.exit(0)

scfg = TfidfConfig(vocab_bits=10)
docs = ["node edge graph rank walk", "graph node directed edge weight",
        "rank walk teleport damping node", "edge list sparse matrix graph"]
tmp = tempfile.mkdtemp(prefix="drain-matrix-")
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(tmp, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(tmp, ref, scfg.config_hash())

fab = fabric.ServingFabric(tmp, fabric.FabricConfig(
    replicas=1, poll_s=0.1, health_period_s=0.2, retry_limit=200,
    retry_pause_s=0.1, grace_s=10.0, federation=False,
))


def kill(pid):
    try:
        os.kill(pid, signal.SIGKILL)
        return True
    except ProcessLookupError:
        return False  # already exited — the point degenerates upward


def settle(expect_new_vs=None, timeout=30.0):
    """Wait until exactly one live serving process, return its pid."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        h = fab._handles.get(0)
        if h is not None and h.alive() and \
                (expect_new_vs is None or h.pid != expect_new_vs):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{fab._ports[0]}/status",
                        timeout=2.0) as resp:
                    st = json.loads(resp.read())
                if st["ready"]:
                    return h.pid
            except OSError:
                pass
        time.sleep(0.1)
    raise AssertionError("no healthy replica settled in time")


def poll_pids(n=15):
    pids = set()
    for _ in range(n):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fab._ports[0]}/status",
                timeout=2.0) as resp:
            pids.add(json.loads(resp.read())["pid"])
        time.sleep(0.02)
    return pids


def roll_with_kill(trigger):
    """Roll in a thread; `trigger(old_pid)` decides when to SIGKILL."""
    old_pid = fab._handles[0].pid
    errs = []

    def run():
        try:
            fab.rolling_restart(timeout=60.0)
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errs.append(exc)

    t = threading.Thread(target=run)
    t.start()
    trigger(old_pid)
    t.join(90.0)
    assert not t.is_alive(), "roll wedged"
    return old_pid, errs


with fab:
    stop = threading.Event()
    failures = []

    def load():
        while not stop.is_set():
            try:
                fab.query(["node", "graph"])
            except Exception as exc:  # noqa: BLE001 — audited below
                failures.append(exc)

    loader = threading.Thread(target=load, daemon=True)
    loader.start()
    try:
        # (a) pre-drain: predecessor dies while the successor is still
        # spawning — the handoff swap must replace it, not race a
        # supervisor respawn onto the same port
        old, errs = roll_with_kill(lambda pid: kill(pid))
        assert not errs, errs
        pid_a = settle(expect_new_vs=old)
        assert poll_pids() == {pid_a}, "more than one listener serving"

        # (b) mid-drain: SIGKILL the predecessor right after the swap
        # (its drain is cut short; in-flight requests retry typed)
        def mid_drain(pid):
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                h = fab._handles.get(0)
                if h is not None and h.pid != pid:
                    break
                time.sleep(0.01)
            kill(pid)

        old, errs = roll_with_kill(mid_drain)
        assert not errs, errs
        pid_b = settle(expect_new_vs=old)
        assert poll_pids() == {pid_b}, "more than one listener serving"

        # (c) post-successor-healthy: the freshly rolled replica dies —
        # ordinary unplanned failure, the supervisor path takes it
        old, errs = roll_with_kill(lambda pid: None)
        assert not errs, errs
        pid_c = settle(expect_new_vs=old)
        kill(pid_c)
        pid_d = settle(expect_new_vs=pid_c)
        assert poll_pids() == {pid_d}, "more than one listener serving"
    finally:
        stop.set()
        loader.join(10.0)
    audit = fab.audit()

assert not failures, failures[:3]
assert audit["dropped"] == 0, audit
assert audit["double_served"] == 0, audit
assert audit["rolled"] == 3, audit
print("drain kill-matrix: OK — SIGKILL pre-drain / mid-drain / "
      "post-successor left exactly one listener each time "
      f"({audit['requests']} closed-loop requests, dropped=0 "
      "double_served=0)")
EOF
then
    tail -1 /tmp/_drain_matrix.log
else
    echo "FAIL: drain kill-matrix smoke; its output:" >&2
    cat /tmp/_drain_matrix.log >&2
    exit 1
fi
dt=$(( $(date +%s) - t0 ))
echo "drain kill-matrix: ${dt}s"
if [ "$dt" -gt "${GRAFT_DRAIN_BUDGET_S:-40}" ]; then
    echo "FAIL: drain kill-matrix exceeded its ${GRAFT_DRAIN_BUDGET_S:-40}s budget (${dt}s)" >&2
    exit 1
fi

echo "== trace-diff gate (per-phase regression across committed rounds) =="
# Compare the two newest committed BENCH rounds: a per-phase wall-time
# regression past GRAFT_TRACE_DIFF_THRESHOLD (default 35%) in the
# committed trajectory fails CI — the round that paid it must explain
# itself before the next one lands on top.  ENFORCING since ISSUE 8: the
# two newest committed rounds (r06+) carry extra.breakdown, so rc=2 — a
# round missing its breakdown — is itself a regression (the bench lost
# its accounting), not a soft skip.  Since ISSUE 10 trace_diff folds the
# overlapped staged-ingest phases (ingest.h2d + ingest.compute) before
# comparing, so wall time moving from compute into overlapped H2D — the
# double-buffering landing — can never read as a false regression.
# `|| true`: zero matching rounds must take the skip branch below, not
# kill the script via set -e/pipefail; sort -V keeps r100 after r99
rounds=$(ls BENCH_r*.json 2>/dev/null | sort -V | tail -2 || true)
if [ "$(echo "$rounds" | grep -c .)" -eq 2 ]; then
    prev=$(echo "$rounds" | head -1)
    cur=$(echo "$rounds" | tail -1)
    set +e
    python tools/trace_diff.py "$prev" "$cur" \
        --threshold "${GRAFT_TRACE_DIFF_THRESHOLD:-0.35}"
    diff_rc=$?
    set -e
    if [ "$diff_rc" -eq 1 ]; then
        echo "FAIL: $cur regressed a phase past ${GRAFT_TRACE_DIFF_THRESHOLD:-0.35} vs $prev" >&2
        exit 1
    elif [ "$diff_rc" -eq 2 ]; then
        echo "FAIL: $prev/$cur are not comparable (missing extra.breakdown)" >&2
        echo "      — committed rounds must carry their per-phase accounting" >&2
        exit 1
    fi
else
    echo "trace-diff gate: skipped (fewer than two committed rounds)"
fi

echo "== traced-run smoke (obs + trace_report) =="
# A tiny streaming TF-IDF run under GRAFT_TRACE_DIR must leave a JSONL
# trace + manifest that tools/trace_report.py turns into a per-phase
# breakdown with a completed chunk timeline — the artifact path bench.py's
# accounting depends on.
smoke_dir=$(mktemp -d)
trap 'rm -rf "$smoke_dir"' EXIT
printf 'alpha beta gamma\nbeta gamma delta\nepsilon zeta alpha\ngamma gamma beta\nalpha delta epsilon\nzeta zeta beta\n' \
    > "$smoke_dir/corpus.txt"
# GRAFT_TUNED_PROFILE=off: the committed profile's pack_target_tokens
# would re-pack this 6-doc corpus into one chunk; this smoke pins the
# 3-chunk timeline, so it runs on dataclass defaults.
if ! env JAX_PLATFORMS=cpu GRAFT_TRACE_DIR="$smoke_dir" \
    GRAFT_TUNED_PROFILE=off \
    python -m page_rank_and_tfidf_using_apache_spark_tpu.cli.tfidf \
        "$smoke_dir/corpus.txt" --lines --streaming --chunk-docs 2 \
        --vocab-bits 8 --prefetch 0 > "$smoke_dir/cli.log" 2>&1; then
    echo "FAIL: traced tfidf CLI run; its output:" >&2
    cat "$smoke_dir/cli.log" >&2
    exit 1
fi
trace_file=$(ls "$smoke_dir"/tfidf.*.trace.jsonl)
python tools/trace_report.py "$trace_file" --json > "$smoke_dir/report.json"
python - "$smoke_dir/report.json" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
assert rep["complete"], f"traced run did not finish: {rep}"
assert "tfidf.stream" in rep["breakdown"], rep["breakdown"]
assert len(rep["chunks"]) == 3 and all(c["complete"] for c in rep["chunks"]), rep["chunks"]
assert rep["manifest"] and rep["manifest"]["status"] == "ok", rep["manifest"]
# the staged ingest pipeline (ISSUE 10) must leave its per-stage
# accounting in the artifact: one ingest_overlap record per run with the
# tokenize/h2d/compute split and the h2d_overlap_frac gauge
assert rep.get("ingest"), rep.get("ingest")
assert all("h2d_overlap_frac" in r for r in rep["ingest"]), rep["ingest"]
print("traced-run smoke: OK "
      f"({rep['events']} events, {len(rep['chunks'])} chunks, "
      f"wall {rep['wall_secs']:.3f}s, "
      f"h2d_overlap {rep['ingest'][-1]['h2d_overlap_frac']})")
EOF

echo "== soak smoke (bounded SLO gate: ~${GRAFT_SOAK_DURATION_S:-20}s CPU soak under *:fail@%5 chaos) =="
# A bounded production soak (ISSUE 11): continuous streaming ingest +
# index rebuild/hot-swap + mixed tfidf/bm25/@prior closed-loop traffic +
# ONE injected device loss, all under *:fail@%5 transient chaos, must
# produce a parseable SLO record with a non-null served p99 and a
# measured time-to-recover, and the zero-dropped / zero-double-served
# invariants must hold.  This is the "heavy traffic" claim as a CI gate.
if ! env JAX_PLATFORMS=cpu \
    GRAFT_CHAOS="*:fail@%5" \
    GRAFT_SOAK_DURATION_S="${GRAFT_SOAK_DURATION_S:-20}" \
    GRAFT_SOAK_QPS="${GRAFT_SOAK_QPS:-15}" \
    python bench.py --soak > "$smoke_dir/soak.json" 2> "$smoke_dir/soak.log"; then
    echo "FAIL: soak child; its stderr tail:" >&2
    tail -30 "$smoke_dir/soak.log" >&2
    exit 1
fi
python - "$smoke_dir/soak.json" <<'EOF'
import json, sys
rec = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
assert rec.get("served_p99_ms") is not None, f"null p99: {rec}"
recov = rec.get("recovery") or {}
assert recov.get("losses_injected", 0) >= 1, f"no loss injected: {recov}"
assert recov.get("time_to_recover_s") is not None, f"no recovery time: {recov}"
assert rec.get("dropped") == 0, f"dropped requests: {rec['dropped']}"
assert rec.get("double_served") == 0, f"double-served: {rec['double_served']}"
assert (rec.get("ingest") or {}).get("chunks", 0) > 0, "no ingest ran"
print("soak smoke: OK "
      f"({rec['requests']} req at {rec['qps']} qps, "
      f"p99 {rec['served_p99_ms']}ms, "
      f"recovered in {recov['time_to_recover_s']}s, "
      f"{rec['ingest']['rebuilds']} rebuild(s))")
EOF

echo "== fabric smoke (N=${GRAFT_FABRIC_REPLICAS:-2} replica fleet: SIGKILL mid-traffic + respawn, budget ${GRAFT_FABRIC_BUDGET_S:-25}s) =="
# The ISSUE 17 serving fabric as a bounded CI gate: an N-replica fleet
# of real child processes mmap-loads the same sealed segments, one
# replica is hard-SIGKILLed mid-traffic, and the router's sibling retry
# + supervisor respawn must deliver every request exactly once
# (dropped=0, double_served=0) — then the run's trace must parse into
# tools/trace_report.py's fabric section (replicas/kills/respawns/totals).
t0=$(date +%s)
if ! env JAX_PLATFORMS=cpu \
    GRAFT_FABRIC_REPLICAS="${GRAFT_FABRIC_REPLICAS:-2}" \
    FABRIC_SMOKE_DIR="$smoke_dir" \
    python - > "$smoke_dir/fabric.log" 2>&1 <<'EOF'
import importlib.util
import os
import time

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

d = os.path.join(os.environ["FABRIC_SMOKE_DIR"], "fabidx")
scfg = TfidfConfig(vocab_bits=9)
docs = [f"alpha beta doc{i} shared word graph node" for i in range(8)]
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(d, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(d, ref, scfg.config_hash())
n = int(os.environ.get("GRAFT_FABRIC_REPLICAS", "2"))
trace_dir = os.path.join(os.environ["FABRIC_SMOKE_DIR"], "fabtrace")
with obs.run("fabric_smoke", trace_dir=trace_dir) as r:
    cfg = fabric.FabricConfig(replicas=n, poll_s=0.1, health_period_s=0.2,
                              retry_limit=100, retry_pause_s=0.1,
                              grace_s=10.0)
    with fabric.ServingFabric(d, cfg) as fab:
        for _ in range(5):
            fab.query(["alpha", "beta"])
        fab.kill_replica(0)  # hard SIGKILL mid-traffic
        for _ in range(10):
            fab.query(["shared", "word"])
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if (fab.audit()["respawns"] >= 1
                    and all(s is not None and s.get("ready")
                            for s in fab.statuses())):
                break
            time.sleep(0.2)
        audit = fab.audit()
assert audit["respawns"] >= 1, audit
assert audit["dropped"] == 0 and audit["double_served"] == 0, audit
spec = importlib.util.spec_from_file_location("tr", "tools/trace_report.py")
tr = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tr)
rep = tr.report(r.trace_path)
fb = rep["fabric"]
assert fb is not None and fb["replicas"] == n, fb
assert fb["kills"] >= 1 and len(fb["respawns"]) >= 1, fb
assert fb["totals"]["dropped"] == 0, fb
assert fb["totals"]["double_served"] == 0, fb
print(f"fabric smoke: OK — N={n} fleet survived a SIGKILL "
      f"({audit['requests']} req, {audit['retries']} sibling retries, "
      f"{len(fb['respawns'])} respawn(s), dropped=0, double_served=0)")
EOF
then
    echo "FAIL: fabric smoke; its output:" >&2
    cat "$smoke_dir/fabric.log" >&2
    exit 1
fi
tail -1 "$smoke_dir/fabric.log"
dt=$(( $(date +%s) - t0 ))
echo "fabric smoke: ${dt}s"
if [ "$dt" -gt "${GRAFT_FABRIC_BUDGET_S:-25}" ]; then
    echo "FAIL: fabric smoke exceeded its ${GRAFT_FABRIC_BUDGET_S:-25}s budget (${dt}s) — replica spawn/respawn stopped being interactive" >&2
    exit 1
fi

echo "== federation smoke (fleet scrape → merged board → forced scale-up, budget ${GRAFT_FED_BUDGET_S:-25}s) =="
# The ISSUE 19 observability plane as a bounded CI gate: a 1-replica
# fleet with the router-side FleetHub, one real scrape sweep, the
# router's OWN /snapshot.json must serve a parseable merged fleet board
# (replica rows + counters folded exactly), then one forced scale-up
# through the autoscaler's own spawn path — and the run's trace must
# render tools/trace_report.py's autoscale timeline.
t0=$(date +%s)
if ! env JAX_PLATFORMS=cpu \
    FED_SMOKE_DIR="$smoke_dir" \
    python - > "$smoke_dir/federation.log" 2>&1 <<'EOF'
import importlib.util
import json
import os
import urllib.request

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf
from page_rank_and_tfidf_using_apache_spark_tpu.serving import fabric
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    Bm25Config,
    TfidfConfig,
)

d = os.path.join(os.environ["FED_SMOKE_DIR"], "fedidx")
scfg = TfidfConfig(vocab_bits=9)
docs = [f"alpha beta doc{i} shared word graph node" for i in range(8)]
out = run_tfidf(docs, scfg)
ref = sgm.seal_segment(d, out, scfg, doc_base=0,
                       ranks=np.ones(out.n_docs, np.float32),
                       bm25=Bm25Config())
sgm.commit_append(d, ref, scfg.config_hash())
trace_dir = os.path.join(os.environ["FED_SMOKE_DIR"], "fedtrace")
with obs.run("fed_smoke", trace_dir=trace_dir) as r:
    cfg = fabric.FabricConfig(replicas=1, poll_s=0.1, health_period_s=0.2,
                              retry_limit=100, retry_pause_s=0.1,
                              grace_s=10.0, latency_slo_s=0.5,
                              availability_target=0.999)
    with fabric.ServingFabric(d, cfg) as fab:
        for _ in range(8):
            fab.query(["alpha", "beta"])
        fab.fleet.scrape_once()
        # the router's OWN exporter serves the merged fleet board
        with urllib.request.urlopen(fab.fleet_url + "/snapshot.json",
                                    timeout=5) as resp:
            snap = json.loads(resp.read())
        assert snap["fleet"]["replicas"], snap["fleet"]
        total = snap["counters"]["serve.requests"]["total"]
        assert total >= 8, snap["counters"]
        # one forced scale-up through the autoscaler's own spawn path
        scaler = fabric.Autoscaler(fab, fabric.AutoscaleConfig(
            min_replicas=1, max_replicas=2, cooldown_s=0.0))
        action = scaler.tick(
            {"budgets": {"availability": {"burn_rate": 10.0}}})
        assert action == "up", action
        assert len(fab.replica_ids()) == 2, fab.replica_ids()
        for _ in range(4):
            fab.query(["shared", "word"])
        audit = fab.audit()
assert audit["dropped"] == 0 and audit["double_served"] == 0, audit
assert audit["scale_ups"] >= 1, audit
spec = importlib.util.spec_from_file_location("tr", "tools/trace_report.py")
tr = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tr)
rep = tr.report(r.trace_path)
a = rep["autoscale"]
assert a is not None and a["ups"] >= 1 and a["actions"] >= 1, a
spec = importlib.util.spec_from_file_location("sw", "tools/slo_watch.py")
sw = importlib.util.module_from_spec(spec)
spec.loader.exec_module(sw)
board = sw.render_fleet(snap)
assert "fleet:" in board, board
print(f"federation smoke: OK — scraped {len(snap['fleet']['replicas'])} "
      f"replica(s), merged {int(total)} requests exactly, forced "
      f"scale-up to {audit['scale_ups'] + 1} replicas, autoscale "
      f"timeline rendered ({a['actions']} action(s))")
EOF
then
    echo "FAIL: federation smoke; its output:" >&2
    cat "$smoke_dir/federation.log" >&2
    exit 1
fi
tail -1 "$smoke_dir/federation.log"
dt=$(( $(date +%s) - t0 ))
echo "federation smoke: ${dt}s"
if [ "$dt" -gt "${GRAFT_FED_BUDGET_S:-25}" ]; then
    echo "FAIL: federation smoke exceeded its ${GRAFT_FED_BUDGET_S:-25}s budget (${dt}s) — the fleet scrape/scale path stopped being interactive" >&2
    exit 1
fi

echo "== segment smoke (seal → serve → post-start commit → merge under *:fail@%5, budget ${GRAFT_SEG_BUDGET_S:-15}s) =="
# The ISSUE 13 ingest→servable path as a bounded CI gate: seal a delta
# segment, serve it via impacted-list scoring, commit a SECOND segment
# AFTER server start and hot-swap it live (no restart — the acceptance
# bar), then background-merge the set — all under transient chaos.  The
# whole lifecycle must fit GRAFT_SEG_BUDGET_S ("servable in seconds").
t0=$(date +%s)
if ! env JAX_PLATFORMS=cpu \
    GRAFT_CHAOS='*:fail@%5' GRAFT_RETRY_MAX=4 GRAFT_BACKOFF_BASE_S=0.01 \
    SEG_SMOKE_DIR="$smoke_dir" \
    python - > "$smoke_dir/segments.log" 2>&1 <<'EOF'
import os
import numpy as np
from page_rank_and_tfidf_using_apache_spark_tpu import serving
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.serving import segments as sgm
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig

d = os.path.join(os.environ["SEG_SMOKE_DIR"], "segidx")
scfg = TfidfConfig(vocab_bits=8, prefetch=0, pipeline_depth=0)
chunks = [[f"tok{i} tok{i % 5} shared word" for i in range(j * 3, j * 3 + 3)]
          for j in range(4)]
out = run_tfidf_streaming(iter(chunks), scfg)
ref = sgm.seal_segment(d, out, scfg, doc_base=0)
sgm.commit_append(d, ref, scfg.config_hash())
srv = serving.TfidfServer(
    sgm.load_segment_set(d),
    serving.ServeConfig(top_k=3, scoring="impacted"),
).start()
s, _ = srv.query(["tok3"])
assert float(s[0]) > 0
# a segment committed AFTER server start, hot-swapped without restart
out2 = run_tfidf_streaming(iter([["freshterm post start doc"]]), scfg)
ref2 = sgm.seal_segment(d, out2, scfg, doc_base=out.n_docs)
sgm.commit_append(d, ref2, scfg.config_hash())
srv.refresh_segments(sgm.load_segment_set(d))
s2, i2 = srv.query(["freshterm"])
assert float(s2[0]) > 0 and int(i2[0]) == out.n_docs, (s2, i2)
# background compaction down to one segment, still serving the same doc
merger = sgm.SegmentMerger(d, scfg, max_segments=1)
while merger.merge_once():
    pass
assert len(sgm.latest_manifest(d).segments) == 1
srv.refresh_segments(sgm.load_segment_set(d))
s3, i3 = srv.query(["freshterm"])
assert int(i3[0]) == int(i2[0])
srv.stop()
print("segment smoke: OK — post-start commit served from segment "
      f"{ref2.name} (global doc {int(i2[0])}), merged to 1 segment")
EOF
then
    echo "FAIL: segment smoke; its output:" >&2
    cat "$smoke_dir/segments.log" >&2
    exit 1
fi
tail -1 "$smoke_dir/segments.log"
dt=$(( $(date +%s) - t0 ))
echo "segment smoke: ${dt}s"
if [ "$dt" -gt "${GRAFT_SEG_BUDGET_S:-15}" ]; then
    echo "FAIL: segment smoke exceeded its ${GRAFT_SEG_BUDGET_S:-15}s budget (${dt}s) — the ingest→servable path stopped being 'seconds'" >&2
    exit 1
fi

echo "== owned-strategy smoke (Zipf fixpoint under *:fail@%5, budget ${GRAFT_OWNED_BUDGET_S:-30}s) =="
# ISSUE 15: the owned-slices + sparse-boundary-exchange strategy as a
# bounded CI gate — a seeded Zipf graph runs a fixed-length fixpoint on
# a 4-device mesh under transient chaos, must match the single-chip
# ranks at 1e-9 (f64; fixed iterations, since the owned convergence
# gauge lags one step and a tolerance race would legitimately stop a
# different iteration), and the partition must publish a nonzero
# per-step comm footprint (the gauge the trace_diff comm gate
# regresses).
t0=$(date +%s)
if ! env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    GRAFT_CHAOS='*:fail@%5' GRAFT_RETRY_MAX=4 GRAFT_BACKOFF_BASE_S=0.01 \
    python - > "$smoke_dir/owned.log" 2>&1 <<'EOF'
import numpy as np
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_zipf
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
    run_pagerank_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder

g = synthetic_zipf(3000, 24000, seed=5)
cfg = PageRankConfig(iterations=40, dangling="redistribute",
                     init="uniform", dtype="float64")
base = run_pagerank(g, cfg)
m = MetricsRecorder()
res = run_pagerank_sharded(g, cfg, n_devices=4, strategy="owned", metrics=m)
assert np.abs(res.ranks - base.ranks).sum() <= 1e-9
assert res.iterations == 40
part = next(r for r in m.records if r.get("event") == "partition")
assert part["comm_bytes_per_step"] > 0, part
print("owned smoke: OK — 40-iteration fixpoint matched single-chip at "
      f"1e-9 under chaos, {part['comm_bytes_per_step']} comm B/step "
      "on 4 devices")
EOF
then
    echo "FAIL: owned-strategy smoke; its output:" >&2
    cat "$smoke_dir/owned.log" >&2
    exit 1
fi
tail -1 "$smoke_dir/owned.log"
dt=$(( $(date +%s) - t0 ))
echo "owned smoke: ${dt}s"
if [ "$dt" -gt "${GRAFT_OWNED_BUDGET_S:-30}" ]; then
    echo "FAIL: owned smoke exceeded its ${GRAFT_OWNED_BUDGET_S:-30}s budget (${dt}s)" >&2
    exit 1
fi

echo "== chaos gate (tier-1 under *:fail@%5 + device_lost mesh-shrink scenario) =="
# chaos.sh's second half runs the device_lost sharded scenario under
# XLA_FLAGS=--xla_force_host_platform_device_count=2: both sharded runners
# must survive losing logical device 1 via the elastic mesh-shrink rung.
tools/chaos.sh

echo "CI: all gates green"
