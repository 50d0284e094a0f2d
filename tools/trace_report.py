#!/usr/bin/env python3
"""Reconstruct a run's accounting from an obs trace file.

Reads the crash-safe JSONL trace the ``obs`` subsystem writes
(``<name>.<pid>.trace.jsonl``) and answers "where did the time go" — the
Spark-web-UI question — even for a run that was SIGKILLed mid-stream:

- per-phase wall-time **breakdown** (top-level spans on the main thread,
  grouped by name; incomplete spans are credited with their elapsed time
  up to the last event on record and flagged),
- the per-chunk **timeline** (``tfidf.chunk`` spans → chunk index, wall
  seconds, start offset),
- **retry / chaos / watchdog / degraded / exhausted tallies per site**
  (the resilience executor's event stream),
- the **last incomplete span** — the phase the process died inside,
- the run manifest (sibling ``.manifest.json``) and run-end summary when
  present.

Deliberately stdlib-only with no package imports: the bench parent (which
must never import jax) imports this module to turn child trace artifacts
into the BENCH record's ``extra.breakdown`` — no stderr scraping.

Usage::

    python tools/trace_report.py RUN.trace.jsonl [--json]
    python tools/trace_report.py TRACE_DIR [--json]   # stitch: group every
        # child run under its GRAFT_TRACE_PARENT id into one round tree
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any


def load_events(path: str) -> tuple[list[dict[str, Any]], int]:
    """Parse a JSONL trace; returns (events, bad_line_count).  A SIGKILL
    mid-write truncates at most the final line — skip unparseable lines
    rather than failing the whole post-mortem."""
    events: list[dict[str, Any]] = []
    bad = 0
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                evt = json.loads(line)
            except json.JSONDecodeError:
                bad += 1
                continue
            if isinstance(evt, dict) and "kind" in evt:
                events.append(evt)
            else:
                bad += 1
    return events, bad


def pair_spans(
    events: list[dict[str, Any]], last_t: float
) -> tuple[list[dict[str, Any]], list[dict[str, Any]]]:
    """Match span_begin/span_end into span records.

    Returns (complete, incomplete).  Incomplete spans (begin with no end —
    the process died inside them) get ``secs`` = elapsed up to the last
    event on record and ``complete: False``.
    """
    open_spans: dict[int, dict[str, Any]] = {}
    complete: list[dict[str, Any]] = []
    for evt in events:
        if evt["kind"] == "span_begin":
            open_spans[evt["span"]] = {
                "span": evt["span"],
                "parent": evt.get("parent"),
                "name": evt.get("name", "?"),
                "attrs": evt.get("attrs") or {},
                "thread": evt.get("thread"),
                "t0": evt["t"],
                "complete": True,
            }
        elif evt["kind"] == "span_end":
            rec = open_spans.pop(evt["span"], None)
            if rec is None:  # end without begin: trace started mid-run
                rec = {
                    "span": evt["span"],
                    "parent": evt.get("parent"),
                    "name": evt.get("name", "?"),
                    "attrs": evt.get("attrs") or {},
                    "thread": evt.get("thread"),
                    "t0": evt["t"] - evt.get("secs", 0.0),
                    "complete": True,
                }
            rec["secs"] = evt.get("secs", 0.0)
            rec["status"] = evt.get("status", "ok")
            complete.append(rec)
    incomplete = []
    for rec in open_spans.values():
        rec["complete"] = False
        rec["secs"] = max(last_t - rec["t0"], 0.0)
        rec["status"] = "incomplete"
        incomplete.append(rec)
    return complete, incomplete


def _tally(events: list[dict[str, Any]], kind: str) -> dict[str, int]:
    out: dict[str, int] = {}
    for evt in events:
        if evt["kind"] == kind:
            site = str(evt.get("site", evt.get("name", "?")))
            out[site] = out.get(site, 0) + 1
    return out


def _pct(sorted_xs: list[float], p: float) -> float | None:
    """Nearest-rank percentile over an ascending list (None when empty)."""
    if not sorted_xs:
        return None
    i = min(len(sorted_xs) - 1, max(0, -(-int(p * 100) * len(sorted_xs) // 100) - 1))
    return sorted_xs[i]


def report(path: str) -> dict[str, Any]:
    """Full accounting for one trace file, as a JSON-ready dict."""
    events, bad = load_events(path)
    if not events:
        return {"trace": path, "events": 0, "bad_lines": bad, "empty": True}
    t_first = events[0]["t"]
    t_last = max(e["t"] for e in events)
    run_start = next((e for e in events if e["kind"] == "run_start"), None)
    run_end = next((e for e in events if e["kind"] == "run_end"), None)
    # A sink_detached tombstone means the trace was truncated by a sink
    # write error, NOT by the process dying — keep the two separable.
    sink_lost = any(e["kind"] == "sink_detached" for e in events)
    t0 = run_start["t"] if run_start else t_first
    wall = (run_end["t"] if run_end else t_last) - t0

    spans, incomplete = pair_spans(events, t_last)
    all_spans = spans + incomplete

    # Breakdown: top-level (parentless) spans on the thread that owns the
    # run — concurrent worker-thread spans (the streaming tokenizer)
    # overlap the main timeline and would double-count wall time.
    main_thread = (run_start or events[0]).get("thread")
    breakdown: dict[str, float] = {}
    incomplete_phases: list[str] = []
    for rec in all_spans:
        if rec["parent"] is not None or rec.get("thread") != main_thread:
            continue
        breakdown[rec["name"]] = breakdown.get(rec["name"], 0.0) + rec["secs"]
        if not rec["complete"]:
            incomplete_phases.append(rec["name"])

    # Per-span-name aggregates (all threads, all depths).
    span_stats: dict[str, dict[str, float]] = {}
    for rec in all_spans:
        s = span_stats.setdefault(rec["name"], {"count": 0, "secs": 0.0})
        s["count"] += 1
        s["secs"] += rec["secs"]

    # Per-device timings (ROADMAP hardening (d)): the sharded ingest
    # publishes one ``device_timing`` event per super-chunk with each
    # device's shard-ready time, keyed by step — joined into the chunk
    # timeline below so a straggling device is visible per chunk.
    device_timings = {
        e.get("step"): e
        for e in events
        if e["kind"] == "device_timing" and e.get("step") is not None
    }

    chunks = sorted(
        (
            {
                "chunk": rec["attrs"].get("chunk"),
                "secs": rec["secs"],
                "t_rel": rec["t0"] - t0,
                "complete": rec["complete"],
                **(
                    {
                        "devices": device_timings[rec["attrs"]["step"]].get("devices"),
                        "per_device_secs": device_timings[rec["attrs"]["step"]].get("secs"),
                    }
                    if rec["name"] == "tfidf.super_chunk"
                    and rec["attrs"].get("step") in device_timings
                    else {}
                ),
            }
            for rec in all_spans
            if rec["name"] in ("tfidf.chunk", "tfidf.super_chunk")
            and "chunk" in rec["attrs"]
        ),
        key=lambda c: c["t_rel"],
    )

    # Elastic mesh-shrink transitions (resilience/elastic.py): one span per
    # degradation step, carrying old/new device counts and the ladder rung
    # taken — what makes a degraded bench round attributable from the
    # artifact alone ("why did throughput halve at +312s?" -> "8->4 shrink").
    mesh_shrinks = sorted(
        (
            {
                "site": rec["attrs"].get("site"),
                "ladder": rec["attrs"].get("ladder"),
                "devices_old": rec["attrs"].get("devices_old"),
                "devices_new": rec["attrs"].get("devices_new"),
                "t_rel": rec["t0"] - t0,
                "secs": rec["secs"],
                "complete": rec["complete"],
            }
            for rec in all_spans
            if rec["name"] == "mesh.shrink"
        ),
        key=lambda s: s["t_rel"],
    )
    shrink_sites: dict[str, int] = {}
    for s in mesh_shrinks:
        site = str(s["site"] or "?")
        shrink_sites[site] = shrink_sites.get(site, 0) + 1

    # Strategy decisions (ISSUE 9 satellite): auto_select_strategy and
    # plan_partition publish WHAT was chosen and the measured inputs that
    # drove the choice — "why did this run pick hybrid" is answerable
    # from the artifact alone.
    strategy = {
        "decisions": [
            {k: v for k, v in e.items() if k not in ("kind", "t", "thread")}
            for e in events
            if e["kind"] in ("strategy_decision", "auto_strategy")
        ],
        "plans": [
            {k: v for k, v in e.items() if k not in ("kind", "t", "thread")}
            for e in events
            if e["kind"] == "partition_plan"
        ],
    }
    if not strategy["decisions"] and not strategy["plans"]:
        strategy = None

    last_incomplete = None
    if incomplete:
        deepest = max(incomplete, key=lambda r: r["t0"])
        last_incomplete = {
            "name": deepest["name"],
            "span": deepest["span"],
            "attrs": deepest["attrs"],
            "elapsed_secs": deepest["secs"],
            "thread": deepest.get("thread"),
        }

    # Staged-ingest pipeline accounting (ISSUE 10): chunked_ingest
    # publishes one ``ingest_overlap`` event per run with the per-stage
    # wall seconds (tokenize / H2D staging / compute) and the
    # h2d_overlap_frac gauge — the fraction of H2D staging time spent
    # while chunk compute was in flight.  A traced process may hold
    # several ingest runs (the bench child runs serial + pipelined
    # passes); each is reported, in order.
    ingest_runs = [
        {k: v for k, v in e.items() if k not in ("kind", "t", "thread")}
        for e in events
        if e["kind"] == "ingest_overlap"
    ]

    # SLO record (ISSUE 11): the soak harness publishes ONE ``slo`` event
    # at scoring time — served p50/p99 under ingest load, error-budget
    # burn, time-to-recover, dropped/double-served.  The last one wins (a
    # trace normally holds exactly one).
    slo_events = [
        {k: v for k, v in e.items()
         if k not in ("kind", "t", "wall", "thread", "seq")}
        for e in events
        if e["kind"] == "slo"
    ]
    slo = slo_events[-1] if slo_events else None

    # Serving-path accounting (ISSUE 8): per-request ``serve_request``
    # events carry queue-wait and total latency; the serve.pad/dispatch/
    # pull spans give the phase split.  Present only for serve runs.
    serve_reqs = [e for e in events if e["kind"] == "serve_request"]
    serving = None
    if serve_reqs:
        lat = sorted(e.get("total_s", 0.0) for e in serve_reqs)
        qw = sorted(e.get("queue_wait_s", 0.0) for e in serve_reqs)
        serving = {
            "requests": len(serve_reqs),
            "cache_hits": sum(e.get("cache") == "hit" for e in serve_reqs),
            "errors": sum(1 for e in serve_reqs if e.get("error")),
            "latency_p50_s": _pct(lat, 0.50),
            "latency_p99_s": _pct(lat, 0.99),
            "queue_wait_p50_s": _pct(qw, 0.50),
            "queue_wait_p99_s": _pct(qw, 0.99),
            "phases": {
                name.split(".", 1)[1]: round(span_stats[name]["secs"], 4)
                for name in ("serve.pad", "serve.dispatch", "serve.pull")
                if name in span_stats
            },
        }

    # Serving-fabric accounting (ISSUE 17): the router process publishes
    # the fleet's lifecycle — spawns, health transitions, supervisor
    # respawns (with measured recovery), the committed generation-floor
    # timeline, rolling restarts, and a periodic per-replica stats fold
    # (the replicas' own numbers, read over /status).  Rendered as the
    # "fabric" section; tools/trace_diff.py regresses the fleet SLO
    # record between rounds.
    fab_events = [e for e in events
                  if str(e.get("kind", "")).startswith("fabric_")]
    fabric = None
    if fab_events:
        start = next((e for e in fab_events
                      if e["kind"] == "fabric_start"), None)
        stop_evt = next((e for e in reversed(fab_events)
                         if e["kind"] == "fabric_stop"), None)
        replica_stats: dict[Any, dict[str, Any]] = {}
        for e in fab_events:
            if e["kind"] == "fabric_replica_stats":
                replica_stats[e.get("replica")] = {
                    k: e.get(k)
                    for k in ("requests", "executions", "replays",
                              "p50_ms", "p99_ms", "generation", "floor")
                }
        for rid, st in replica_stats.items():
            st["qps"] = (round(st["requests"] / wall, 3)
                         if st.get("requests") and wall > 0 else None)
        fabric = {
            "replicas": start.get("replicas") if start else None,
            "spawns": sum(e["kind"] == "fabric_spawn" for e in fab_events),
            "kills": sum(e["kind"] == "fabric_kill" for e in fab_events),
            "suspects": sum(e["kind"] == "fabric_suspect"
                            for e in fab_events),
            "respawns": [
                {"replica": e.get("replica"), "pid": e.get("pid"),
                 "recovery_s": e.get("recovery_s"),
                 "t_rel": round(e["t"] - t0, 3)}
                for e in fab_events if e["kind"] == "fabric_respawn"
            ],
            "floor_timeline": [
                {"floor": e.get("floor"), "t_rel": round(e["t"] - t0, 3)}
                for e in fab_events if e["kind"] == "fabric_floor"
            ],
            "rolls": sum(e["kind"] == "fabric_rolled" for e in fab_events),
            # Drain-handoff forensics (ISSUE 20): rolls split by
            # mechanism (socket handoff vs the retry-carried fallback)
            # and the per-replica handoff phase timeline — spawn →
            # successor_ready → drain on the router side, the replica's
            # own drain_begin/drain_done interleaved when its trace is
            # folded in.  `totals.roll_retries` (from fabric_stop) is
            # the handoff acceptance gate: 0 when every roll handed off.
            "handoff_rolls": sum(
                e["kind"] == "fabric_rolled" and bool(e.get("handoff"))
                for e in fab_events),
            "retry_rolls": sum(
                e["kind"] == "fabric_rolled" and not e.get("handoff")
                for e in fab_events),
            "drain_timeline": sorted(
                [{"replica": e.get("replica"), "phase": e.get("phase"),
                  "pid": e.get("pid"), "t_rel": round(e["t"] - t0, 3)}
                 for e in fab_events if e["kind"] == "fabric_handoff"]
                + [{"replica": e.get("replica"), "phase": "drain_begin",
                    "pid": e.get("pid"), "t_rel": round(e["t"] - t0, 3)}
                   for e in fab_events
                   if e["kind"] == "fabric_drain_begin"]
                + [{"replica": e.get("replica"), "phase": "drain_done",
                    "drain_s": e.get("drain_s"),
                    "t_rel": round(e["t"] - t0, 3)}
                   for e in fab_events
                   if e["kind"] == "fabric_drain_done"],
                key=lambda row: row["t_rel"]),
            "replica_stats": replica_stats,
            "totals": (
                {k: v for k, v in stop_evt.items()
                 if k not in ("kind", "t", "wall", "thread", "seq")}
                if stop_evt else None
            ),
        }

    # Sharded-cache accounting (ISSUE 20): per-replica local/peer hit
    # rates folded from the router's periodic /status scrape, the
    # breaker transition timeline (cache_breaker events), and the peek
    # latency histogram from the run-end summary.  peer_hit_rate is
    # peer_hits over peek ATTEMPTS (hits + misses + timeouts) — skipped
    # open-breaker peeks never reached the wire and are not attempts.
    cache = None
    breaker_events = [e for e in events if e.get("kind") == "cache_breaker"]
    cache_stats: dict[Any, dict[str, Any]] = {}
    for e in events:
        if e.get("kind") == "fabric_replica_stats" and \
                e.get("peer_hits") is not None:
            hits = int(e.get("cache_hits") or 0)
            ph = int(e.get("peer_hits") or 0)
            pm = int(e.get("peer_misses") or 0)
            pt = int(e.get("peek_timeouts") or 0)
            reqs = int(e.get("requests") or 0)
            cache_stats[e.get("replica")] = {
                "requests": reqs,
                "local_hits": hits,
                "local_hit_rate": round(hits / reqs, 4) if reqs else None,
                "peer_hits": ph,
                "peer_misses": pm,
                "peek_timeouts": pt,
                "peer_hit_rate": (round(ph / (ph + pm + pt), 4)
                                  if ph + pm + pt else None),
                "fills": int(e.get("fills") or 0),
                "peer_stores": int(e.get("peer_stores") or 0),
                "breaker_open": e.get("breaker_open"),
            }
    if cache_stats or breaker_events:
        summary_h = ((run_end or {}).get("summary") or {}).get(
            "histograms") or {}
        cache = {
            "replica_stats": cache_stats,
            "peek_latency": summary_h.get("cache_peek_s"),
            "breaker_transitions": [
                {"replica": e.get("replica"), "peer": e.get("peer"),
                 "old": e.get("old"), "new": e.get("new"),
                 "t_rel": round(e["t"] - t0, 3)}
                for e in breaker_events
            ],
        }

    # Autoscaling timeline (ISSUE 19): the burn-rate autoscaler publishes
    # one ``autoscale`` event per ACTION (holds are silent) carrying the
    # measured inputs that drove it — burn rates, queue p99, offered
    # rate, fleet size before/after.  Flaps (direction reversals between
    # consecutive actions) are recomputed from the timeline so the
    # trace_diff gate never trusts a counter the process could misreport;
    # fed_scrape_error tallies ride along (scrape chaos forensics).
    as_events = [e for e in events if e["kind"] == "autoscale"]
    autoscale = None
    if as_events or any(e["kind"] == "autoscale_start" for e in events):
        timeline = []
        for e in as_events:
            row = {k: v for k, v in e.items()
                   if k not in ("kind", "t", "wall", "thread", "seq")}
            row["t_rel"] = round(e["t"] - t0, 3)
            timeline.append(row)
        autoscale = {
            "actions": len(as_events),
            "ups": sum(e.get("action") == "up" for e in as_events),
            "downs": sum(e.get("action") == "down" for e in as_events),
            "flaps": sum(
                1 for prev, cur in zip(as_events, as_events[1:])
                if prev.get("action") != cur.get("action")
            ),
            "errors": sum(e["kind"] == "autoscale_error" for e in events),
            "scrape_errors": sum(
                e["kind"] == "fed_scrape_error" for e in events
            ),
            "timeline": timeline,
        }

    manifest = None
    mpath = path.replace(".trace.jsonl", ".manifest.json")
    if mpath != path and os.path.exists(mpath):
        try:
            with open(mpath, "r", encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError):
            manifest = None

    return {
        "trace": path,
        "manifest": manifest,
        "trace_parent": (
            (run_start or {}).get("trace_parent")
            or (manifest or {}).get("trace_parent")
        ),
        "serving": serving,
        "slo": slo,
        "fabric": fabric,
        "cache": cache,
        "autoscale": autoscale,
        "events": len(events),
        "bad_lines": bad,
        "complete": run_end is not None,
        "status": (
            run_end.get("status")
            if run_end
            else ("trace-lost" if sink_lost else "killed")
        ),
        "wall_secs": wall,
        "breakdown": breakdown,
        "ingest": ingest_runs or None,
        "incomplete_phases": incomplete_phases,
        "spans": span_stats,
        "chunks": chunks,
        "retries": _tally(events, "retry"),
        "backoffs": _tally(events, "backoff"),
        "chaos": _tally(events, "chaos"),
        "watchdog": _tally(events, "watchdog"),
        "degraded": _tally(events, "degraded"),
        "exhausted": _tally(events, "exhausted"),
        "mesh_shrinks": mesh_shrinks,
        "shrinks": shrink_sites,
        "strategy": strategy,
        "checkpoints": sum(e["kind"] == "checkpoint_save" for e in events),
        "last_incomplete": last_incomplete,
        "summary": run_end.get("summary") if run_end else None,
    }


# Span names that wrap exactly one guarded host sync (a device->host pull
# or fence).  Their durations are the empirical distribution of healthy
# sync times — what the adaptive GRAFT_SYNC_DEADLINE_S knob (bench.py) is
# calibrated against.
SYNC_SPAN_NAMES = frozenset(
    {
        "tfidf.chunk",
        "tfidf.super_chunk",
        "tfidf.finalize",
        "pagerank.ckpt_pull",
        "pagerank.result_pull",
    }
)


def sync_p99(path: str, span_names: frozenset = SYNC_SPAN_NAMES) -> float | None:
    """p99 duration (seconds) over the completed sync-flavored spans in a
    trace, or None when the trace holds none.  bench.py feeds a PRIOR
    round's value into the next round's child sync deadline
    (``max(knob, 3 * p99)``), so the watchdog tracks the syncs' actually
    observed behavior instead of a guess."""
    events, _ = load_events(path)
    secs = sorted(
        e.get("secs", 0.0)
        for e in events
        if e["kind"] == "span_end" and e.get("name") in span_names
    )
    return _pct(secs, 0.99)


def stitch(root: str) -> dict[str, Any]:
    """Reassemble one trace TREE from a directory of per-process artifacts
    (ROADMAP hardening (c)): every ``*.trace.jsonl`` under ``root``
    (recursively) whose run adopted a ``GRAFT_TRACE_PARENT`` id is grouped
    under that id; runs without one group under ``"(unparented)"``.  The
    result is the whole-round accounting the bench parent could never see
    from any single child: per-child wall/status/breakdown plus the round
    totals, keyed by the id the parent exported."""
    import glob

    paths = sorted(
        glob.glob(os.path.join(root, "**", "*.trace.jsonl"), recursive=True),
        key=os.path.getmtime,
    )
    trees: dict[str, dict[str, Any]] = {}
    for p in paths:
        try:
            rep = report(p)
        except OSError:
            continue
        if rep.get("empty"):
            continue
        parent = rep.get("trace_parent") or "(unparented)"
        tree = trees.setdefault(
            parent, {"trace_parent": parent, "children": [],
                     "wall_secs": 0.0, "retries": 0, "checkpoints": 0}
        )
        man = rep.get("manifest") or {}
        tree["children"].append({
            "name": man.get("name") or os.path.basename(p).split(".")[0],
            "pid": man.get("pid"),
            "trace": p,
            "status": rep["status"],
            "wall_secs": round(rep["wall_secs"], 3),
            "breakdown": {k: round(v, 3) for k, v in rep["breakdown"].items()},
            "serving": rep.get("serving"),
            "slo": rep.get("slo"),
            "fabric": rep.get("fabric"),
            "cache": rep.get("cache"),
        })
        tree["wall_secs"] = round(tree["wall_secs"] + rep["wall_secs"], 3)
        tree["retries"] += sum(rep["retries"].values())
        tree["checkpoints"] += rep["checkpoints"]
    return {"root": root, "trees": sorted(
        trees.values(), key=lambda t: -len(t["children"])
    )}


def render_stitched(doc: dict[str, Any]) -> str:
    lines = [f"stitched trace root: {doc['root']}"]
    if not doc["trees"]:
        lines.append("  (no trace artifacts found)")
    for tree in doc["trees"]:
        lines.append(
            f"trace {tree['trace_parent']}: {len(tree['children'])} child "
            f"run(s), {tree['wall_secs']:.3f}s total wall, "
            f"{tree['retries']} retries, {tree['checkpoints']} checkpoints"
        )
        for ch in tree["children"]:
            top = sorted(ch["breakdown"].items(), key=lambda kv: -kv[1])[:3]
            phases = ", ".join(f"{k} {v:.2f}s" for k, v in top)
            lines.append(
                f"  {ch['name']:16s} pid={ch['pid']} {ch['status']:10s} "
                f"{ch['wall_secs']:9.3f}s  {phases}"
            )
            if ch.get("serving"):
                sv = ch["serving"]
                lines.append(
                    f"  {'':16s} serving: {sv['requests']} req, "
                    f"{sv['cache_hits']} hits, p50 "
                    f"{(sv['latency_p50_s'] or 0) * 1e3:.1f}ms p99 "
                    f"{(sv['latency_p99_s'] or 0) * 1e3:.1f}ms"
                )
            if ch.get("fabric"):
                fb = ch["fabric"]
                lines.append(
                    f"  {'':16s} fabric: {fb.get('replicas')} replica(s), "
                    f"{len(fb.get('respawns') or [])} respawn(s), "
                    f"{fb.get('rolls')} rolled"
                )
    return "\n".join(lines)


def render_human(rep: dict[str, Any]) -> str:
    if rep.get("empty"):
        return f"{rep['trace']}: empty trace ({rep['bad_lines']} bad line(s))"
    lines = [f"trace: {rep['trace']}"]
    man = rep.get("manifest")
    if man:
        lines.append(
            f"run: {man.get('name')} pid={man.get('pid')} "
            f"backend={man.get('backend')} git={man.get('git_sha')} "
            f"status={man.get('status')}"
        )
    lines.append(
        f"events: {rep['events']} ({rep['bad_lines']} bad), "
        f"wall {rep['wall_secs']:.3f}s, "
        + ("run completed" if rep["complete"] else "RUN DID NOT END (killed?)")
    )
    if rep["breakdown"]:
        lines.append("phase breakdown (top-level, main thread):")
        total = sum(rep["breakdown"].values())
        for name, secs in sorted(rep["breakdown"].items(), key=lambda kv: -kv[1]):
            mark = "  [incomplete]" if name in rep["incomplete_phases"] else ""
            pct = 100.0 * secs / rep["wall_secs"] if rep["wall_secs"] > 0 else 0.0
            lines.append(f"  {name:32s} {secs:10.3f}s {pct:5.1f}%{mark}")
        lines.append(f"  {'(phases total)':32s} {total:10.3f}s")
    if rep.get("ingest"):
        lines.append("ingest pipeline (staged: tokenize | h2d | compute):")
        for run in rep["ingest"]:
            lines.append(
                f"  {run.get('chunks', '?'):>4} chunk(s)  "
                f"tokenize {run.get('tokenize_secs', 0.0):8.3f}s  "
                f"h2d {run.get('h2d_secs', 0.0):8.3f}s  "
                f"compute {run.get('compute_secs', 0.0):8.3f}s  "
                f"h2d_overlap {100.0 * run.get('h2d_overlap_frac', 0.0):5.1f}%"
                f"  (prefetch={run.get('depth')}, "
                f"pipeline_depth={run.get('pipeline_depth')})"
            )
    if rep["chunks"]:
        done = [c for c in rep["chunks"] if c["complete"]]
        lines.append(
            f"chunks: {len(done)} complete of {len(rep['chunks'])} started"
        )
        worst = sorted(done, key=lambda c: -c["secs"])[:5]
        for c in worst:
            dev = ""
            if c.get("per_device_secs"):
                dev = "  devices [" + ", ".join(
                    f"{s:.4f}s" for s in c["per_device_secs"]
                ) + "]"
            lines.append(
                f"  chunk {c['chunk']}: {c['secs']:.4f}s (at +{c['t_rel']:.2f}s)"
                f"{dev}"
            )
    if rep.get("serving"):
        sv = rep["serving"]
        lines.append(
            f"serving: {sv['requests']} requests ({sv['cache_hits']} cache "
            f"hits, {sv['errors']} errors), latency p50 "
            f"{(sv['latency_p50_s'] or 0) * 1e3:.2f}ms / p99 "
            f"{(sv['latency_p99_s'] or 0) * 1e3:.2f}ms, queue-wait p50 "
            f"{(sv['queue_wait_p50_s'] or 0) * 1e3:.2f}ms"
        )
        if sv["phases"]:
            lines.append("  " + ", ".join(
                f"{k} {v:.3f}s" for k, v in sv["phases"].items()
            ))
    if rep.get("slo"):
        slo = rep["slo"]
        rec = slo.get("recovery") or {}
        budgets = slo.get("error_budget") or {}
        avail = budgets.get("availability") or {}
        lines.append(
            f"slo: {slo.get('requests')} requests at "
            f"{slo.get('qps')} qps over {slo.get('duration_s')}s — "
            f"served p50 {slo.get('served_p50_ms')}ms / "
            f"p99 {slo.get('served_p99_ms')}ms "
            f"(target {((slo.get('slo_targets') or {}).get('p99_ms'))}ms)"
        )
        lines.append(
            f"  error budget: {avail.get('bad', 0)} bad of "
            f"{avail.get('total', 0)} (consumed "
            f"{avail.get('consumed_frac')}x allowed, burn "
            f"{avail.get('burn_rate')}); dropped "
            f"{slo.get('dropped')}, double-served "
            f"{slo.get('double_served')}"
        )
        lines.append(
            f"  losses: {rec.get('losses_injected', 0)} injected, "
            f"time-to-recover "
            f"{rec.get('time_to_recover_s')}s; ingest "
            f"{((slo.get('ingest') or {}).get('chunks'))} chunks / "
            f"{((slo.get('ingest') or {}).get('rebuilds'))} rebuilds"
        )
    if rep.get("fabric"):
        fb = rep["fabric"]
        lines.append(
            f"fabric: {fb.get('replicas')} replica(s), {fb['spawns']} "
            f"spawn(s), {fb['kills']} kill(s), "
            f"{len(fb['respawns'])} respawn(s), {fb['rolls']} rolled, "
            f"{fb['suspects']} suspect transition(s)"
        )
        for rid in sorted(fb["replica_stats"], key=str):
            st = fb["replica_stats"][rid]
            lines.append(
                f"  replica {rid}: {st.get('requests')} req "
                f"({st.get('qps')} qps), p50 {st.get('p50_ms')}ms / "
                f"p99 {st.get('p99_ms')}ms, {st.get('replays')} replay(s), "
                f"gen {st.get('generation')} (floor {st.get('floor')})"
            )
        for r in fb["respawns"]:
            lines.append(
                f"  respawn: replica {r['replica']} at +{r['t_rel']}s, "
                f"recovered in {r['recovery_s']}s"
            )
        if fb["floor_timeline"]:
            lines.append("  floor timeline: " + " -> ".join(
                f"{f['floor']}@+{f['t_rel']}s" for f in fb["floor_timeline"]
            ))
        if fb.get("drain_timeline"):
            lines.append(
                f"  drain: {fb.get('handoff_rolls', 0)} handoff roll(s) / "
                f"{fb.get('retry_rolls', 0)} retry roll(s); timeline: "
                + " -> ".join(
                    f"r{d.get('replica')}:{d.get('phase')}@+{d['t_rel']}s"
                    for d in fb["drain_timeline"]
                )
            )
        if fb.get("totals"):
            t = fb["totals"]
            lines.append(
                f"  totals: {t.get('requests')} routed, "
                f"{t.get('delivered')} delivered, "
                f"{t.get('retries', 0)} retried "
                f"({t.get('roll_retries', 0)} during rolls), "
                f"{t.get('failed', 0)} dropped, "
                f"{t.get('double_served', 0)} double-served"
            )
    if rep.get("cache"):
        ca = rep["cache"]
        lines.append(
            f"cache: {len(ca['replica_stats'])} replica(s) reporting, "
            f"{len(ca['breaker_transitions'])} breaker transition(s)"
        )
        for rid in sorted(ca["replica_stats"], key=str):
            st = ca["replica_stats"][rid]
            lines.append(
                f"  replica {rid}: local hit rate "
                f"{st.get('local_hit_rate')}, peer hit rate "
                f"{st.get('peer_hit_rate')} ({st.get('peer_hits')} hit / "
                f"{st.get('peer_misses')} miss / "
                f"{st.get('peek_timeouts')} timeout), "
                f"{st.get('fills')} fill(s) out, "
                f"{st.get('peer_stores')} store(s) in, "
                f"{st.get('breaker_open')} breaker(s) open"
            )
        if ca.get("peek_latency"):
            lines.append(f"  peek latency: {ca['peek_latency']}")
        for b in ca["breaker_transitions"]:
            lines.append(
                f"  breaker: replica {b.get('replica')} -> peer "
                f"{b.get('peer')}: {b.get('old')} -> {b.get('new')} "
                f"at +{b['t_rel']}s"
            )
    if rep.get("autoscale"):
        a = rep["autoscale"]
        lines.append(
            f"autoscale: {a['actions']} action(s) ({a['ups']} up / "
            f"{a['downs']} down), {a['flaps']} flap(s), "
            f"{a['errors']} error(s), {a['scrape_errors']} scrape error(s)"
        )
        for d in a["timeline"]:
            inputs = ", ".join(
                f"{k}={d[k]}"
                for k in ("burn_availability", "burn_latency",
                          "queue_p99_ms", "rate_per_s")
                if d.get(k) is not None
            )
            lines.append(
                f"  {d.get('action')} at +{d.get('t_rel')}s "
                f"[{d.get('reason')}]: {d.get('replicas_before')}->"
                f"{d.get('replicas_after')} replica(s)"
                + (f" ({inputs})" if inputs else "")
            )
    for key in ("retries", "chaos", "watchdog", "degraded", "exhausted",
                "shrinks"):
        if rep.get(key):
            tally = ", ".join(f"{s}={n}" for s, n in sorted(rep[key].items()))
            lines.append(f"{key}: {tally}")
    for s in rep.get("mesh_shrinks", []):
        mark = "" if s["complete"] else "  [incomplete]"
        lines.append(
            f"mesh shrink: {s['devices_old']}->{s['devices_new']} "
            f"({s['ladder']}) at +{s['t_rel']:.2f}s, {s['secs']:.3f}s "
            f"rebuild [{s['site']}]{mark}"
        )
    if rep.get("strategy"):
        st = rep["strategy"]
        for d in st["decisions"]:
            chosen = d.get("chosen", "?")
            reason = d.get("reason", "")
            inputs = ", ".join(
                f"{k}={d[k]}"
                for k in ("devices", "nodes", "edges",
                          "replicated_state_bytes", "node_state_bytes",
                          "head_edge_frac")
                if k in d
            )
            lines.append(
                f"strategy: chose {chosen!r}"
                + (f" — {reason}" if reason else "")
                + (f" ({inputs})" if inputs else "")
            )
        for p in st["plans"]:
            lines.append(
                f"partition plan: {p.get('strategy')} d={p.get('devices')} "
                f"pad_frac={p.get('pad_frac')} block={p.get('block')} "
                f"e_dev={p.get('e_dev')}"
            )
    if rep["checkpoints"]:
        lines.append(f"checkpoints saved: {rep['checkpoints']}")
    if rep["last_incomplete"]:
        li = rep["last_incomplete"]
        lines.append(
            f"last incomplete span: {li['name']} {li['attrs'] or ''} "
            f"({li['elapsed_secs']:.3f}s elapsed, thread {li['thread']})"
        )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report", description=__doc__)
    ap.add_argument("trace", help="a <name>.<pid>.trace.jsonl file, or a "
                                  "directory to stitch (all children of one "
                                  "GRAFT_TRACE_PARENT id become one tree)")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    args = ap.parse_args(argv)
    if os.path.isdir(args.trace):
        doc = stitch(args.trace)
        print(json.dumps(doc, indent=2, default=str) if args.json
              else render_stitched(doc))
        return 0
    if not os.path.exists(args.trace):
        print(f"trace_report: no such file: {args.trace}", file=sys.stderr)
        return 2
    rep = report(args.trace)
    if args.json:
        print(json.dumps(rep, indent=2, default=str))
    else:
        print(render_human(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
