"""Run-telemetry tests (ISSUE 4): the obs/ span tracer + event bus +
crash-safe JSONL sinks + run manifests, and the trace-driven accounting
pipeline (tools/trace_report.py, bench.py ``extra.breakdown``).

Acceptance bars exercised here:

- a chaos-injected (``GRAFT_CHAOS=*:fail@%5``) streaming TF-IDF run
  SIGKILLed mid-stream leaves a parseable trace from which trace_report
  recovers per-chunk wall time, retry counts per site, and the last
  incomplete span;
- ``python bench.py`` on the CPU backend emits a BENCH record whose
  ``extra.breakdown`` phases sum to within 10% of the measured wall time,
  with the accounting read from the trace artifact (not stderr).
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    GRAFT_ENV_KNOBS,
    TfidfConfig,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
    MetricsRecorder,
    resolve_log_level,
)

REPO = Path(__file__).resolve().parents[1]


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def sink():
    s = obs.MemorySink()
    obs.bus().attach(s)
    yield s
    obs.bus().detach(s)


# ---------------------------------------------------------------- tracer


def test_span_nesting_and_status(sink):
    with obs.span("outer", k=1) as outer_id:
        with obs.span("inner") as inner_id:
            pass
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    ends = {e["name"]: e for e in sink.of_kind("span_end")}
    assert ends["inner"]["parent"] == outer_id
    assert ends["outer"]["parent"] is None
    assert ends["outer"]["attrs"] == {"k": 1}
    assert inner_id != outer_id
    assert ends["inner"]["secs"] >= 0
    assert ends["boom"]["status"] == "error:ValueError"
    # begin published before the body ran (crash evidence by construction)
    kinds = [e["kind"] for e in sink.events if e.get("name") == "inner"]
    assert kinds == ["span_begin", "span_end"]


def test_span_nesting_across_threads(sink):
    """Each thread keeps its own span stack: concurrent nests never steal
    each other's parent, and a fresh thread starts at top level even while
    the spawning thread holds an open span."""
    barrier = threading.Barrier(2)

    def work(tag: str):
        with obs.span(f"{tag}.root"):
            barrier.wait()  # both threads inside their roots at once
            with obs.span(f"{tag}.child"):
                barrier.wait()

    with obs.span("main.open"):  # must NOT become any thread's parent
        threads = [
            threading.Thread(target=work, args=(t,), name=t) for t in ("a", "b")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    ends = {e["name"]: e for e in sink.of_kind("span_end")}
    for tag in ("a", "b"):
        assert ends[f"{tag}.root"]["parent"] is None  # fresh thread = top level
        assert ends[f"{tag}.child"]["parent"] == ends[f"{tag}.root"]["span"]
        assert ends[f"{tag}.child"]["thread"] == tag


def test_explicit_cross_thread_parent(sink):
    """Cross-thread parentage is available by passing parent= explicitly
    (the prefetch pattern: worker spans attributed to the coordinator)."""
    with obs.span("coordinator") as cid:
        pass

    def worker():
        with obs.span("worker", parent=cid):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    end = [e for e in sink.of_kind("span_end") if e["name"] == "worker"][0]
    assert end["parent"] == cid


# ------------------------------------------------------------- event bus


def test_broken_sink_is_detached_not_fatal(sink):
    class Broken:
        def emit(self, event):
            raise RuntimeError("sink died")

    broken = Broken()
    obs.bus().attach(broken)
    obs.emit("ping")  # must not raise
    assert obs.bus().sink_count() >= 1
    obs.emit("pong")
    kinds = sink.kinds()
    assert "ping" in kinds and "pong" in kinds


def test_metrics_recorder_thread_safe_and_forwards(sink):
    m = MetricsRecorder()
    n_threads, per = 8, 200

    def pump(k):
        for i in range(per):
            m.record(event="x", thread=k, i=i)

    threads = [threading.Thread(target=pump, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(m.records) == n_threads * per
    assert len(sink.of_kind("metric")) >= n_threads * per


def test_resolve_log_level():
    import logging

    assert resolve_log_level(None) == logging.INFO
    assert resolve_log_level("debug") == logging.DEBUG
    assert resolve_log_level("WARNING") == logging.WARNING
    assert resolve_log_level("15") == 15
    assert resolve_log_level("bogus") == logging.INFO


def test_graft_log_level_knob_declared():
    assert "GRAFT_LOG_LEVEL" in GRAFT_ENV_KNOBS
    assert "GRAFT_TRACE_DIR" in GRAFT_ENV_KNOBS


# ----------------------------------------------------- chaos/retry events


def test_chaos_injected_retry_publishes_events(sink):
    pol = rx.RetryPolicy(max_retries=3, backoff_base_s=0.001)
    with chaos.inject("obs_t1:fail@1;obs_t1:fail@2"):
        out = rx.run_guarded(lambda: 42, site="obs_t1", policy=pol)
    assert out == 42
    chaos_evts = [e for e in sink.of_kind("chaos") if e["site"] == "obs_t1"]
    retry_evts = [e for e in sink.of_kind("retry") if e["site"] == "obs_t1"]
    backoffs = [e for e in sink.of_kind("backoff") if e["site"] == "obs_t1"]
    assert len(chaos_evts) == 2 and chaos_evts[0]["fault"] == "fail"
    assert len(retry_evts) == 2
    assert retry_evts[0]["attempt"] == 1 and "ChaosError" in retry_evts[0]["error"]
    assert len(backoffs) == 2 and all(b["secs"] > 0 for b in backoffs)


def test_exhausted_and_degraded_events(sink):
    pol = rx.RetryPolicy(max_retries=1, backoff_base_s=0.001)
    with chaos.inject("obs_t2:lost@1+"):
        out = rx.run_guarded(lambda: 1, site="obs_t2", policy=pol,
                             fallbacks=[("cpu", lambda _exc: "cpu")])
    assert out == "cpu"
    assert [e["site"] for e in sink.of_kind("degraded")] == ["obs_t2"]
    with chaos.inject("obs_t3:fail@1+"):
        with pytest.raises(Exception):
            rx.run_guarded(lambda: 1, site="obs_t3", policy=pol)
    exh = sink.of_kind("exhausted")
    assert exh and exh[-1]["site"] == "obs_t3" and exh[-1]["attempts"] == 2


def test_watchdog_event_on_deadline(sink):
    pol = rx.RetryPolicy(max_retries=1, backoff_base_s=0.001, deadline_s=0.1)
    with chaos.inject("obs_t4:hang@1:5"):
        out = rx.run_guarded(lambda: "ok", site="obs_t4", policy=pol)
    assert out == "ok"
    wd = [e for e in sink.of_kind("watchdog") if e["site"] == "obs_t4"]
    assert len(wd) == 1 and wd[0]["deadline_s"] == 0.1


# ------------------------------------------------------- run + manifest


def test_manifest_knob_snapshot(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_RETRY_MAX", "7")
    monkeypatch.setenv("GRAFT_CHAOS", "s:fail@1")
    monkeypatch.delenv("GRAFT_CKPT_KEEP", raising=False)
    run = obs.start_run("knobtest", trace_dir=str(tmp_path))
    try:
        with open(run.manifest_path) as f:
            man = json.load(f)
        assert set(man["knobs"]) == set(GRAFT_ENV_KNOBS)
        assert man["knobs"]["GRAFT_RETRY_MAX"] == "7"
        assert man["knobs"]["GRAFT_CHAOS"] == "s:fail@1"
        assert man["knobs"]["GRAFT_CKPT_KEEP"] is None
        assert man["status"] == "running" and man["pid"] == os.getpid()
        assert man["backend"] == "cpu"  # jax is imported in the test session
        assert man["device_count"] == 8  # the simulated test mesh
        assert "lint_clean" in man
    finally:
        obs.end_run()
    with open(run.manifest_path) as f:
        man = json.load(f)
    assert man["status"] == "ok"
    assert man["wall_secs"] > 0 and man["events"] >= 2
    assert "summary" in man


def test_run_counters_and_summary(tmp_path):
    with obs.run("aggtest", trace_dir=str(tmp_path)) as r:
        obs.counter("widgets")
        obs.counter("widgets", 2)
        obs.gauge("level", 0.5)
        for v in (1.0, 2.0, 3.0, 4.0):
            obs.histogram("lat", v)
    rep = _trace_report().report(r.trace_path)
    s = rep["summary"]
    assert s["counters"]["widgets"] == 3
    assert s["gauges"]["level"] == 0.5
    h = s["histograms"]["lat"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert abs(h["mean"] - 2.5) < 1e-9
    assert rep["complete"] and rep["status"] == "ok"


def test_run_supersede_and_error_status(tmp_path):
    r1 = obs.start_run("first", trace_dir=str(tmp_path))
    r2 = obs.start_run("second", trace_dir=str(tmp_path))  # supersedes r1
    obs.end_run()
    with open(r1.manifest_path) as f:
        assert json.load(f)["status"] == "superseded"
    with open(r2.manifest_path) as f:
        assert json.load(f)["status"] == "ok"
    with pytest.raises(RuntimeError):
        with obs.run("third", trace_dir=str(tmp_path)) as r3:
            raise RuntimeError("boom")
    with open(r3.manifest_path) as f:
        assert json.load(f)["status"] == "error:RuntimeError"


# ------------------------------------------- trace-driven accounting


def test_traced_streaming_run_report(tmp_path):
    """A healthy traced streaming run: breakdown covers the stream +
    finalize phases, the chunk timeline is complete, nothing dangling."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
        run_tfidf_streaming,
    )

    docs = [f"tok{i} tok{i % 5} shared word" for i in range(24)]
    chunks = [docs[i:i + 4] for i in range(0, len(docs), 4)]
    with obs.run("streamtest", trace_dir=str(tmp_path)) as r:
        run_tfidf_streaming(chunks, TfidfConfig(vocab_bits=8, prefetch=0))
    rep = _trace_report().report(r.trace_path)
    assert rep["complete"] and not rep["last_incomplete"]
    assert set(rep["breakdown"]) >= {"tfidf.stream", "tfidf.finalize"}
    assert [c["chunk"] for c in rep["chunks"]] == list(range(6))
    assert all(c["complete"] and c["secs"] >= 0 for c in rep["chunks"])
    assert rep["summary"]["counters"]["tfidf.chunks"] == 6
    # phases nest under the main thread's top level only — no double count
    assert sum(rep["breakdown"].values()) <= rep["wall_secs"] * 1.02 + 0.02


KILL_CHILD = """
import os, signal, sys

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig


def chunks():
    for i in range(40):
        if i == 12:
            os.kill(os.getpid(), signal.SIGKILL)  # die mid-stream, no cleanup
        yield [f"tok{j} tok{j % 5} shared word c{i}" for j in range(4)]


obs.start_run("killtest")
# fully serial (no tokenize or H2D run-ahead): the kill at chunk 12 must
# land with exactly chunks 0..11 drained, so the accounting pin is exact
run_tfidf_streaming(chunks(), TfidfConfig(vocab_bits=8, prefetch=0,
                                          pipeline_depth=0))
"""


def test_sigkilled_chaos_run_leaves_full_accounting(tmp_path):
    """ISSUE 4 acceptance: a chaos-injected (*:fail@%5) streaming TF-IDF
    run SIGKILLed mid-stream leaves a parseable JSONL trace from which
    trace_report recovers (a) per-chunk wall time for every completed
    chunk, (b) retry counts per site, (c) the last incomplete span — plus
    a manifest frozen at status "running" with the chaos knob on record."""
    script = tmp_path / "kill_child.py"
    script.write_text(textwrap.dedent(KILL_CHILD))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(REPO),  # the script runs from tmp_path
        GRAFT_TRACE_DIR=str(tmp_path),
        GRAFT_CHAOS="*:fail@%5",
        GRAFT_BACKOFF_BASE_S="0.001",
    )
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=240, env=env, cwd=REPO,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]

    traces = sorted(tmp_path.glob("killtest.*.trace.jsonl"))
    assert len(traces) == 1
    tr = _trace_report()
    events, bad = tr.load_events(str(traces[0]))
    assert events and bad <= 1  # at most the single SIGKILL-truncated line

    rep = tr.report(str(traces[0]))
    assert rep["complete"] is False and rep["status"] == "killed"
    # (a) per-chunk wall time for chunks 0..11 (the kill lands fetching #12)
    done = [c for c in rep["chunks"] if c["complete"]]
    assert [c["chunk"] for c in done] == list(range(12))
    assert all(c["secs"] > 0 for c in done)
    # (b) retry count per site: %5 chaos fired at guarded calls 5 and 10
    assert rep["chaos"].get("tfidf_chunk_sync", 0) >= 2
    assert rep["retries"].get("tfidf_chunk_sync", 0) >= 2
    # (c) the last incomplete span names the phase the process died inside
    # — since the staged pipeline (ISSUE 10) that is the ingest *stage*
    # the kill landed in (the source dies mid-tokenize), with the
    # enclosing tfidf.stream phase still on record as incomplete
    assert rep["last_incomplete"] is not None
    assert rep["last_incomplete"]["name"] == "ingest.tokenize"
    assert "tfidf.stream" in rep["incomplete_phases"]

    manifests = sorted(tmp_path.glob("killtest.*.manifest.json"))
    assert len(manifests) == 1
    man = json.loads(manifests[0].read_text())
    assert man["status"] == "running"  # SIGKILL: never finalized — evidence
    assert man["knobs"]["GRAFT_CHAOS"] == "*:fail@%5"


def test_trace_report_cli(tmp_path):
    with obs.run("clitest", trace_dir=str(tmp_path)) as r:
        with obs.span("phase.a"):
            pass
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         r.trace_path, "--json"],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["complete"] and "phase.a" in rep["breakdown"]
    human = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"), r.trace_path],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert human.returncode == 0 and "phase.a" in human.stdout


def test_sharded_per_device_timings_in_chunk_timeline(tmp_path):
    """ROADMAP hardening (d): the sharded ingest publishes one
    ``device_timing`` event per super-chunk; trace_report joins it into
    the chunk timeline, so a straggling device is attributable from the
    artifact alone."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        run_tfidf_sharded,
    )

    docs = [f"tok{i} tok{i % 5} shared word" for i in range(16)]
    chunks = [docs[i:i + 2] for i in range(0, len(docs), 2)]
    obs.start_run("shardtime", str(tmp_path))
    try:
        run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                          n_devices=4)
    finally:
        obs.end_run()
    trace = next(tmp_path.glob("shardtime.*.trace.jsonl"))
    rep = _trace_report().report(str(trace))
    timed = [c for c in rep["chunks"] if c.get("per_device_secs")]
    assert timed, rep["chunks"]
    for c in timed:
        assert c["devices"] == len(c["per_device_secs"]) == 4
        # waited in device order: the recorded times are non-decreasing
        assert c["per_device_secs"] == sorted(c["per_device_secs"])
        assert c["per_device_secs"][-1] >= 0


def test_stitch_groups_children_by_trace_parent(tmp_path, monkeypatch):
    """ROADMAP hardening (c): two child runs exporting the same
    GRAFT_TRACE_PARENT stitch into one tree; an unparented run stays
    outside it."""
    monkeypatch.setenv("GRAFT_TRACE_PARENT", "round-7")
    for name in ("child_a", "child_b"):
        with obs.run(name, trace_dir=str(tmp_path)):
            with obs.span("work"):
                pass
    monkeypatch.delenv("GRAFT_TRACE_PARENT")
    with obs.run("loner", trace_dir=str(tmp_path)):
        pass
    mod = _trace_report()
    doc = mod.stitch(str(tmp_path))
    by_parent = {t["trace_parent"]: t for t in doc["trees"]}
    assert {c["name"] for c in by_parent["round-7"]["children"]} == \
        {"child_a", "child_b"}
    assert {c["name"] for c in by_parent["(unparented)"]["children"]} == \
        {"loner"}
    # the stitched view is also reachable from the CLI (directory arg)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert proc.returncode == 0 and "round-7" in proc.stdout


# ---------------------------------------------------- bench integration


def _bench_module():
    spec = importlib.util.spec_from_file_location("bench_mod", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


BENCH_CPU_ENV = dict(
    JAX_PLATFORMS="cpu",
    BENCH_TFIDF_DOCS="256", BENCH_TFIDF_TOKENS_PER_DOC="30",
)


def test_bench_breakdown_sums_to_wall(tmp_path, monkeypatch):
    """ISSUE 4 acceptance, on the CPU through bench.py's own tfidf round
    (the whole bench refuses to run without a TPU): the extra.breakdown
    phases read from the child's trace artifact sum to within 10% of the
    measured wall time (the tfidf child's run span) — no stderr scraping
    on the accounting path."""
    monkeypatch.setenv("BENCH_TRACE_DIR", str(tmp_path / "traces"))
    bench = _bench_module()
    trace_dir = bench._round_trace_dir()
    # pid-scoped subdir: a persistent BENCH_TRACE_DIR never lets a previous
    # round's trace masquerade as this record's accounting
    assert Path(trace_dir).parent == tmp_path / "traces"
    env = dict(
        os.environ, **BENCH_CPU_ENV,
        BENCH_TFIDF_CHUNK_DOCS="64",
        BENCH_TFIDF_CKPT_DIR=str(tmp_path / "ck"),
        GRAFT_TRACE_DIR=trace_dir,
    )
    tfidf_out, record = bench._run_tfidf_child(env)
    assert tfidf_out is not None and record == {}
    extra = bench._tfidf_trace_extra(trace_dir, record)
    breakdown = extra["breakdown"]
    wall = extra["breakdown_wall_secs"]
    assert breakdown and wall > 0
    assert {"bench.batch_cold", "bench.stream_serial"} <= set(breakdown)
    total = sum(breakdown.values())
    assert abs(total - wall) / wall <= 0.10, (breakdown, wall)
    assert "partial" not in record
    # the artifacts themselves survive for post-mortems
    assert list(Path(trace_dir).glob("tfidf.*.trace.jsonl"))
    assert list(Path(trace_dir).glob("tfidf.*.manifest.json"))


@pytest.mark.parametrize("kind", ["degraded", "exhausted", None])
def test_bench_refuses_a_child_that_left_the_device(kind, tmp_path, monkeypatch):
    """A measurement child whose trace gained a degraded/exhausted event
    did not run on the chip: _run_child refuses it like a failed child.
    Events already in the trace before the child ran are not its own."""
    bench = _bench_module()
    trace = tmp_path / "pagerank.1.trace.jsonl"
    trace.write_text(json.dumps({"kind": "degraded", "site": "old"}) + "\n")

    def fake_run(*_a, **_kw):
        with open(trace, "a") as f:
            f.write(json.dumps({"kind": "span", "name": "x"}) + "\n")
            if kind:
                f.write(json.dumps({"kind": kind, "site": "pagerank_step",
                                    "ladder": "cpu"}) + "\n")
            f.write('{"kind": "torn')
        return subprocess.CompletedProcess([], 0, stdout='{"ips": 1.0}\n',
                                           stderr="")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench._run_child("impl=segment", 10,
                           {"GRAFT_TRACE_DIR": str(tmp_path)})
    assert out == (None if kind else {"ips": 1.0})


def test_bench_refuses_to_run_without_a_tpu():
    """bench.py measures only on the chip: with no TPU it exits non-zero,
    prints the probe's output and emits no record."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PROBE_TIMEOUT_S="90")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU found" in proc.stderr and "'backend': 'cpu'" in proc.stderr


# ------------------------------------------- compile and host-phase spans


def _span_ends(sink, name):
    return [e for e in sink.of_kind("span_end") if e["name"] == name]


def test_fresh_jit_publishes_compile_spans_inside_its_span(sink):
    """The first call of a fresh jit publishes jax.trace, jax.lower and
    jax.compile spans parented to the enclosing span and inside its wall
    interval; a second call publishes none."""
    import jax
    import jax.numpy as jnp

    obs.watch_compiles()
    step = jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)
    x = jnp.arange(16, dtype=jnp.float32)
    with obs.span("first") as first:
        step(x).block_until_ready()
    begin = next(e for e in sink.of_kind("span_begin") if e["span"] == first)
    end = _span_ends(sink, "first")[0]
    compiles = [e for e in sink.of_kind("span_end") if e["name"].startswith("jax.")]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {e["name"] for e in compiles}
    for e in compiles:
        if e["parent"] != first:
            continue  # a constant put before the span opened
        assert begin["wall"] <= e["begin_wall"] <= e["end_wall"] <= end["wall"]
        assert e["secs"] == pytest.approx(e["end_wall"] - e["begin_wall"])
    mine = [e for e in compiles if e["parent"] == first]
    assert {e["name"] for e in mine} == {"jax.trace", "jax.lower", "jax.compile"}
    assert all(e["attrs"]["fun"] for e in mine)
    assert {e["attrs"]["cache"] for e in mine if e["name"] == "jax.compile"} <= {
        "hit", "miss", "none"}

    n = len(sink.events)
    with obs.span("second") as second:
        step(x).block_until_ready()
    assert not [e for e in sink.events[n:] if e.get("name", "").startswith("jax.")]
    assert _span_ends(sink, "second")[0]["span"] == second


def test_watch_compiles_installs_one_listener(sink):
    """watch_compiles() is idempotent: called again it returns the
    installed watch, and one compile publishes one jax.compile span."""
    import jax
    import jax.numpy as jnp

    watch = obs.watch_compiles()
    assert obs.watch_compiles() is watch and obs.watch_compiles() is watch
    x = jnp.ones(5)
    with obs.span("once") as sid:
        jax.jit(lambda x: x * 7 - 2)(x).block_until_ready()
    mine = [e for e in _span_ends(sink, "jax.compile") if e["parent"] == sid]
    assert [e["attrs"]["fun"] for e in mine] == ["jit(<lambda>)"]


def test_record_publishes_a_closed_span(sink):
    with obs.span("outer") as outer:
        sid = obs.tracer().record("late", 100.0, 100.25, k=1)
    (late,) = _span_ends(sink, "late")
    assert late["span"] == sid != outer and late["parent"] == outer
    assert late["secs"] == pytest.approx(0.25) and late["attrs"] == {"k": 1}
    assert (late["begin_wall"], late["end_wall"]) == (100.0, 100.25)
    assert not [e for e in sink.of_kind("span_begin") if e.get("name") == "late"]
    # trace_report takes an end without a begin
    (rec,), _ = _trace_report().pair_spans([late], late["t"])
    assert rec["name"] == "late" and rec["parent"] == outer


OBS_ALONE = """
import sys, types
pkg = types.ModuleType("page_rank_and_tfidf_using_apache_spark_tpu")
pkg.__path__ = [sys.argv[1]]  # the package's own __init__ imports the models
sys.modules[pkg.__name__] = pkg
from page_rank_and_tfidf_using_apache_spark_tpu import obs
assert callable(obs.watch_compiles)
print("jax" in sys.modules)
"""


def test_importing_obs_alone_leaves_jax_out():
    proc = subprocess.run(
        [sys.executable, "-c", OBS_ALONE,
         str(REPO / "page_rank_and_tfidf_using_apache_spark_tpu")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


def test_run_pagerank_spans_put_dispatch_and_compile(sink):
    """A PageRank job puts its graph under pagerank.put_graph and compiles
    its fresh runner inside pagerank.dispatch."""
    from page_rank_and_tfidf_using_apache_spark_tpu.io import synthetic_powerlaw
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    graph = synthetic_powerlaw(200, 1200, seed=3)
    with obs.span("job") as job:
        run_pagerank(graph, PageRankConfig(iterations=4))
    (put,) = _span_ends(sink, "pagerank.put_graph")
    (dispatch,) = _span_ends(sink, "pagerank.dispatch")
    assert put["parent"] == job and dispatch["parent"] != job
    assert [r for r in sink.of_kind("metric") if r.get("event") == "put_graph"]
    inside = [e for e in _span_ends(sink, "jax.compile") if e["parent"] == dispatch["span"]]
    assert len(inside) == 1


def test_fixpoint_dispatch_span():
    """The shared single-chip fixpoint loop dispatches under
    ``<site_prefix>.dispatch``, one per segment."""
    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.hits import run_hits
    from page_rank_and_tfidf_using_apache_spark_tpu.io import synthetic_powerlaw
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import HitsConfig

    s = obs.MemorySink()
    obs.bus().attach(s)
    try:
        run_hits(synthetic_powerlaw(150, 900, seed=5), HitsConfig(iterations=6, tol=0.0))
    finally:
        obs.bus().detach(s)
    (segment,) = _span_ends(s, "hits.segment")
    (dispatch,) = _span_ends(s, "hits.dispatch")
    assert dispatch["parent"] == segment["span"]


def test_run_tfidf_pull_span_and_no_pipeline_record(sink):
    """A batch build pulls its output under tfidf.result_pull; the device
    span tfidf.pipeline is its only device timer (no ``pipeline`` record)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import run_tfidf

    out = run_tfidf([f"alpha beta w{i} w{i % 3}" for i in range(12)],
                    TfidfConfig(vocab_bits=8))
    (pull,) = _span_ends(sink, "tfidf.result_pull")
    (pipe,) = _span_ends(sink, "tfidf.pipeline")
    assert pull["parent"] == pipe["parent"] and pull["secs"] >= 0
    assert out.weight.size == out.term.size > 0
    assert not [r for r in out.metrics.records if r.get("event") == "pipeline"]
    assert [r["tokens"] for r in out.metrics.records if r.get("event") == "tokenize"] == [48]
