"""Resilience runtime tests (ISSUE 2): deterministic fault injection
(resilience/chaos.py) driving the retry/deadline executor
(resilience/executor.py) and the resumable execution paths end to end.

The acceptance bar: with GRAFT_CHAOS-style injection mid-run, PageRank
resumes from checkpoint and converges to the same ranks as an
uninterrupted run; streaming TF-IDF resume reprocesses ZERO completed
chunks (asserted via chunk-event counts); bench.py under a forced tfidf
timeout emits a ``"partial": true`` record with nonzero chunks completed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import (
    PageRankConfig,
    ResilienceExhausted,
    TfidfConfig,
)
from page_rank_and_tfidf_using_apache_spark_tpu.io import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.io.text import iter_corpus_chunks
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    resume_point,
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder

REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- chaos layer


def test_parse_plan_schedules():
    plan = chaos.parse_plan("a:fail@3; b:lost@2+ ; c:hang@%4:0.5")
    assert [i.kind for i in plan] == ["fail", "lost", "hang"]
    a, b, c = plan
    assert [a.matches("a", n) for n in (1, 2, 3, 4)] == [False, False, True, False]
    assert [b.matches("b", n) for n in (1, 2, 3)] == [False, True, True]
    assert [c.matches("c", n) for n in (3, 4, 8, 9)] == [False, True, True, False]
    assert c.param == 0.5
    assert not a.matches("other_site", 3)


def test_parse_plan_wildcard_site():
    (inj,) = chaos.parse_plan("*:fail@%2")
    assert inj.matches("anything", 2) and not inj.matches("anything", 3)


@pytest.mark.parametrize(
    "bad", ["nosep", "a:frob@1", "a:fail", "a:fail@0", "a:fail@x",
            "a:fail@%0", "a:fail@5++", "a:fail@%5+", "a:fail@+5"]
)
def test_parse_plan_rejects(bad):
    with pytest.raises(ValueError):
        chaos.parse_plan(bad)


def test_inject_overrides_env_and_counts(monkeypatch):
    monkeypatch.setenv("GRAFT_CHAOS", "s:lost@1")  # would fail immediately
    with chaos.inject("s:fail@2") as plan:
        chaos.on_call("s")  # call 1: no injection under the override
        with pytest.raises(chaos.ChaosError):
            chaos.on_call("s")  # call 2: injected transient
        assert plan.call_count("s") == 2
    # env plan active again after the context exits
    with pytest.raises(chaos.DeviceLostError):
        chaos.on_call("s")


# ---------------------------------------------------------------- executor


def test_backoff_deterministic_and_bounded():
    pol = rx.RetryPolicy(backoff_base_s=0.05, backoff_max_s=0.2)
    d1 = rx.backoff_delay("site", 1, pol)
    assert d1 == rx.backoff_delay("site", 1, pol)  # deterministic
    assert 0.05 <= d1 < 0.075
    assert rx.backoff_delay("site", 10, pol) == 0.2  # capped


def test_transient_classification():
    assert rx.is_transient(chaos.ChaosError("x"))
    assert rx.is_transient(rx.SyncDeadlineExceeded("x"))
    assert rx.is_transient(RuntimeError("RESOURCE_EXHAUSTED: queue full"))
    # a device out-of-memory carries the same status but fails again on
    # every retry
    assert not rx.is_transient(_hbm_oom())
    assert not rx.is_transient(_alloc_oom())
    assert not rx.is_transient(chaos.DeviceLostError("x"))
    assert not rx.is_transient(ValueError("shape mismatch"))


def test_run_guarded_retries_transients():
    calls = []
    pol = rx.RetryPolicy(max_retries=3, backoff_base_s=0.001)
    m = MetricsRecorder()
    with chaos.inject("t1:fail@1;t1:fail@2"):
        out = rx.run_guarded(lambda: calls.append(1) or 42, site="t1",
                             policy=pol, metrics=m)
    assert out == 42
    assert len(calls) == 1  # two injections happened BEFORE fn ran
    assert sum(r.get("event") == "retry" for r in m.records) == 2


def test_run_guarded_persistent_skips_retries_and_uses_fallback():
    pol = rx.RetryPolicy(max_retries=5, backoff_base_s=0.001)
    m = MetricsRecorder()
    with chaos.inject("t2:lost@1+") as plan:
        out = rx.run_guarded(lambda: 1, site="t2", policy=pol, metrics=m,
                             fallbacks=[("cpu", lambda _exc: "degraded")])
    assert out == "degraded"
    assert plan.call_count("t2") == 1  # no retry spent on a lost device
    assert any(r.get("event") == "degraded" for r in m.records)


def _compile_refusal():
    import jax

    return jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unaligned slice"
    )


def _hbm_oom():
    import jax

    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 18.52G of 15.75G hbm."
    )


def _alloc_oom():
    import jax

    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 4.00G. That was not possible. There are 1.20G free."
    )


NON_DEVICE_ERRORS = [
    pytest.param(lambda: ValueError("shape mismatch"), id="value_error"),
    pytest.param(_compile_refusal, id="compile_refusal"),
    pytest.param(_hbm_oom, id="hbm_oom_compile"),
    pytest.param(_alloc_oom, id="hbm_oom_alloc"),
]


@pytest.mark.parametrize("make_err", NON_DEVICE_ERRORS)
def test_run_guarded_non_device_error_skips_rungs(make_err):
    """Only a device loss or a transient that used up its retries walks
    the rungs: anything else re-raises unchanged, with no degraded or
    exhausted record and no rung called."""
    err = make_err()
    m = MetricsRecorder()
    rung_calls = []

    def boom():
        raise err

    with pytest.raises(type(err)) as ei:
        rx.run_guarded(
            boom, site="t_nondev", metrics=m,
            policy=rx.RetryPolicy(max_retries=3, backoff_base_s=0.001),
            fallbacks=[("cpu", lambda exc: rung_calls.append(exc) or "cpu")],
        )
    assert ei.value is err
    assert rung_calls == []
    assert not any(r.get("event") in ("degraded", "retry") for r in m.records)


@pytest.mark.parametrize("make_err", NON_DEVICE_ERRORS)
def test_run_pagerank_non_device_error_propagates(make_err, monkeypatch):
    """A runner the device cannot run (compile error, Mosaic refusal,
    shape error) fails the run: it is never re-lowered for the CPU."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    err = make_err()
    made = []

    def make_runner(n, cfg):
        made.append(cfg)

        def runner(*_args):
            raise err

        return runner

    monkeypatch.setattr(ops, "make_pagerank_runner", make_runner)
    m = MetricsRecorder()
    g = synthetic_powerlaw(50, 200, seed=3)
    with pytest.raises(type(err)) as ei:
        run_pagerank(g, PageRankConfig(iterations=5), metrics=m)
    assert ei.value is err
    assert len(made) == 1  # the CPU invoke's re-lowering never ran
    assert not any(r.get("event") in ("degraded", "exhausted") for r in m.records)


def test_run_guarded_exhausted_carries_checkpoint(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 7, {"x": np.arange(3)}, "h")
    pol = rx.RetryPolicy(max_retries=1, backoff_base_s=0.001)
    with chaos.inject("t3:fail@1+"):
        with pytest.raises(ResilienceExhausted) as ei:
            rx.run_guarded(lambda: 1, site="t3", policy=pol, checkpoint_dir=d)
    err = ei.value
    assert err.site == "t3" and err.attempts == 2
    assert err.last_checkpoint and err.last_checkpoint.endswith("ckpt_00000007.npz")
    assert isinstance(err.last_error, chaos.ChaosError)


def test_sync_deadline_watchdog_abandons_hung_call():
    pol = rx.RetryPolicy(max_retries=1, backoff_base_s=0.001, deadline_s=0.15)
    t0 = time.perf_counter()
    # call 1 hangs 5s inside the watched thread; the watchdog abandons it
    # and the retry (call 2, uninjected) succeeds.
    with chaos.inject("t4:hang@1:5"):
        out = rx.run_guarded(lambda: "ok", site="t4", policy=pol)
    assert out == "ok"
    assert time.perf_counter() - t0 < 2.0  # nowhere near the 5s hang


# -------------------------------------------------- checkpoint satellites


def test_latest_pointer_write_failure_leaks_no_tmp(tmp_path, monkeypatch):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, {"x": np.arange(2)}, "h")
    real_replace = os.replace

    def failing_replace(src, dst):
        if dst.endswith("LATEST"):
            raise OSError("disk full")
        return real_replace(src, dst)

    monkeypatch.setattr(ckpt.os, "replace", failing_replace)
    with pytest.raises(OSError):
        ckpt.save_checkpoint(d, 2, {"x": np.arange(2)}, "h")
    monkeypatch.setattr(ckpt.os, "replace", real_replace)
    assert not [n for n in os.listdir(d) if n.endswith(".tmp")]
    # the previous LATEST still resolves (old pointer, old payload intact)
    step, arrays, _ = ckpt.load_checkpoint(ckpt.latest_checkpoint(d), "h")
    assert step == 1


def test_gc_checkpoints_retention_keeps_latest(tmp_path):
    d = str(tmp_path)
    for s in range(6):
        ckpt.save_checkpoint(d, s, {"x": np.arange(2)}, "h", keep=0)
    deleted = ckpt.gc_checkpoints(d, keep=2)
    kept = sorted(n for n in os.listdir(d) if n.endswith(".npz"))
    assert kept == ["ckpt_00000004.npz", "ckpt_00000005.npz"]
    assert len(deleted) == 4
    assert ckpt.latest_checkpoint(d).endswith("ckpt_00000005.npz")
    with pytest.raises(ValueError):
        ckpt.gc_checkpoints(d, keep=0)


def test_save_checkpoint_default_retention(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFT_CKPT_KEEP", "3")
    d = str(tmp_path)
    for s in range(10):
        ckpt.save_checkpoint(d, s, {"x": np.arange(2)}, "h")
    assert sum(n.endswith(".npz") for n in os.listdir(d)) == 3


def test_peek_meta_reads_without_arrays(tmp_path):
    d = str(tmp_path)
    path = ckpt.save_checkpoint(d, 5, {"x": np.arange(4)}, "hash5",
                                extra={"n_docs": 9})
    meta = ckpt.peek_meta(path)
    assert meta["step"] == 5 and meta["config_hash"] == "hash5"
    assert meta["extra"] == {"n_docs": 9}


# -------------------------------------------------------- io chunk skipping


def test_iter_corpus_chunks_skip_prefix_keeps_indices():
    docs = [f"d{i}" for i in range(10)]
    plain = list(iter_corpus_chunks(iter(docs), 3))
    skipped = list(iter_corpus_chunks(iter(docs), 3, skip_chunks=2))
    assert len(skipped) == len(plain) == 4
    assert skipped[0] == [] and skipped[1] == []  # placeholders, no strings
    assert skipped[2:] == plain[2:]


def test_iter_corpus_chunks_rejects_rechunked_resume():
    """Resume bookkeeping is in chunk indices: skipping 2 chunks of 3 docs
    when the checkpoint ingested 8 means the chunking changed — refuse."""
    docs = [f"d{i}" for i in range(10)]
    ok = list(iter_corpus_chunks(iter(docs), 3, skip_chunks=2,
                                 expect_skipped_docs=6))
    assert ok[0] == [] and ok[2:] == [["d6", "d7", "d8"], ["d9"]]
    with pytest.raises(ValueError, match="chunking mismatch"):
        list(iter_corpus_chunks(iter(docs), 3, skip_chunks=2,
                                expect_skipped_docs=8))
    with pytest.raises(ValueError, match="corpus ended"):
        list(iter_corpus_chunks(iter(docs[:4]), 3, skip_chunks=4,
                                expect_skipped_docs=12))
    # A checkpoint covering a partial FINAL chunk is legitimate (crash after
    # ingest, during finalize): matching doc counts must not raise.
    tail = list(iter_corpus_chunks(iter(docs), 3, skip_chunks=4,
                                   expect_skipped_docs=10))
    assert tail == [[], [], [], []]


def test_streaming_resume_rejects_rechunked_corpus(tmp_path):
    """Model-side guard: feeding a resume run differently-sized real
    chunks (doc counts that cannot match the checkpoint) fails loudly
    instead of silently re-ingesting documents."""
    chunks = _chunks(6, docs_per_chunk=2)
    cfg = TfidfConfig(vocab_bits=10, prefetch=0, checkpoint_every=1,
                      checkpoint_dir=str(tmp_path / "ck"))
    run_tfidf_streaming(chunks[:4], cfg)  # "crash" after 4 chunks / 8 docs
    docs = [d for c in chunks for d in c]
    rechunked = [docs[i:i + 3] for i in range(0, len(docs), 3)]  # chunks of 3
    with pytest.raises(ValueError, match="chunking mismatch"):
        run_tfidf_streaming(rechunked, cfg, resume=True)


# ------------------------------------------- end-to-end recovery: PageRank


GRAPH_KW = dict(dangling="redistribute", init="uniform", dtype="float32")


def test_pagerank_transient_failure_recovers_identically():
    """(a) A transient dispatch failure mid-PageRank: the executor retries
    and the final ranks match an uninterrupted run to f32 tolerance."""
    g = synthetic_powerlaw(2000, 8000, seed=13)
    cfg = PageRankConfig(iterations=12, **GRAPH_KW)
    base = run_pagerank(g, cfg)
    m = MetricsRecorder()
    with chaos.inject("pagerank_step:fail@1"):
        res = run_pagerank(g, cfg, metrics=m)
    assert any(r.get("event") == "retry" for r in m.records)
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


def test_pagerank_device_loss_degrades_to_cpu():
    g = synthetic_powerlaw(500, 2000, seed=3)
    cfg = PageRankConfig(iterations=8, **GRAPH_KW)
    base = run_pagerank(g, cfg)
    m = MetricsRecorder()
    with chaos.inject("pagerank_step:lost@1+"):
        res = run_pagerank(g, cfg, metrics=m)
    assert any(r.get("event") == "degraded" for r in m.records)
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


@pytest.fixture
def fresh_health():
    from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic

    elastic.reset_health()
    yield
    elastic.reset_health()


@pytest.mark.parametrize(
    "site", ["pagerank_delta_sync", "pagerank_ckpt_pull",
             "pagerank_result_pull"],
)
def test_pagerank_single_chip_device_lost_at_pull_sites(tmp_path, site,
                                                        fresh_health):
    """ISSUE 9 carried-forward satellite: a single-chip device loss first
    surfacing at a checkpoint-pull-class site (the delta fetch, the
    checkpoint pull, the final result pull) used to dead-end — the CPU
    rung re-pulled the carry that died with the device.  Now those sites
    walk the same elastic salvage the sharded pull uses: acknowledge the
    loss, reload the newest snapshot, re-run only the uncommitted span on
    the CPU backend, and finish with ranks matching an uninterrupted run."""
    g = synthetic_powerlaw(800, 3200, seed=7)
    base = run_pagerank(g, PageRankConfig(iterations=12, **GRAPH_KW))
    cfg = PageRankConfig(iterations=12, checkpoint_every=4,
                         checkpoint_dir=str(tmp_path / "ck"), **GRAPH_KW)
    m = MetricsRecorder()
    with chaos.inject(f"{site}:device_lost@dev:0"):
        res = run_pagerank(g, cfg, metrics=m)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert degraded and degraded[0]["ladder"] == "cpu"
    assert "salvage_iter" in degraded[0]  # the elastic salvage, not the
    # legacy pull-the-dead-carry rung
    assert res.iterations == 12
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


def test_pagerank_single_chip_device_lost_without_checkpoint(fresh_health):
    """The salvage rung without any checkpoint dir: falls back to the
    init vector and re-runs the whole span on CPU — still converging to
    the uninterrupted ranks (nothing to salvage means recompute, not
    fail)."""
    g = synthetic_powerlaw(500, 2000, seed=3)
    cfg = PageRankConfig(iterations=8, **GRAPH_KW)
    base = run_pagerank(g, cfg)
    m = MetricsRecorder()
    with chaos.inject("pagerank_delta_sync:device_lost@dev:0"):
        res = run_pagerank(g, cfg, metrics=m)
    assert any(r.get("event") == "degraded" for r in m.records)
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


def test_pagerank_exhausted_resumes_from_checkpoint(tmp_path):
    """The full ladder: mid-run device loss with the CPU rung also failing
    -> ResilienceExhausted carrying the checkpoint -> a resume run (no
    chaos) converges to the uninterrupted ranks."""
    g = synthetic_powerlaw(800, 3200, seed=7)
    base = run_pagerank(g, PageRankConfig(iterations=12, **GRAPH_KW))

    ckdir = str(tmp_path / "ck")
    cfg = PageRankConfig(iterations=12, checkpoint_every=4,
                         checkpoint_dir=ckdir, **GRAPH_KW)
    m = MetricsRecorder()
    with chaos.inject("pagerank_step:lost@3+;pagerank_cpu_pull:lost@1+"):
        with pytest.raises(ResilienceExhausted) as ei:
            run_pagerank(g, cfg, metrics=m)
    # segments 1 and 2 completed -> checkpoint at iteration 8 survives
    assert ei.value.last_checkpoint is not None
    assert ckpt.peek_meta(ei.value.last_checkpoint)["step"] == 8

    m2 = MetricsRecorder()
    res = run_pagerank(g, cfg, metrics=m2, resume=True)
    resumed = [r for r in m2.records if r.get("event") == "resume"]
    assert resumed and resumed[0]["start_iter"] == 8
    assert res.iterations == 12
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


def test_pagerank_sharded_exhausted_then_resume(tmp_path, monkeypatch):
    """With the elastic mesh-shrink rung disabled (GRAFT_ELASTIC=0 — the
    operator off-switch), the sharded path keeps its pre-elastic
    contract: exhaustion surfaces the checkpoint, and a single-chip
    resume finishes to the same ranks.  (With elastic on, device loss is
    survived in-run instead — tests/test_elastic.py.)"""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        run_pagerank_sharded,
    )

    monkeypatch.setenv("GRAFT_ELASTIC", "0")
    g = synthetic_powerlaw(600, 2400, seed=11)
    base = run_pagerank(g, PageRankConfig(iterations=9, **GRAPH_KW))
    ckdir = str(tmp_path / "ck")
    cfg = PageRankConfig(iterations=9, checkpoint_every=3,
                         checkpoint_dir=ckdir, **GRAPH_KW)
    with chaos.inject("pagerank_step:lost@2+"):
        with pytest.raises(ResilienceExhausted) as ei:
            run_pagerank_sharded(g, cfg, n_devices=4)
    assert ei.value.last_checkpoint is not None
    res = run_pagerank(g, cfg, resume=True)  # degrade: finish single-chip
    np.testing.assert_allclose(res.ranks, base.ranks, atol=1e-6)


# ------------------------------------------- end-to-end recovery: TF-IDF


def _chunks(n_chunks: int, docs_per_chunk: int = 2) -> list[list[str]]:
    docs = [f"tok{i} tok{i % 5} shared word extra{i % 3}"
            for i in range(n_chunks * docs_per_chunk)]
    return [docs[i:i + docs_per_chunk]
            for i in range(0, len(docs), docs_per_chunk)]


def test_tfidf_chunk25_failure_resumes_with_zero_reprocessing(tmp_path):
    """(b) A chunk-25 failure in streaming TF-IDF: chunks 0-24 are not
    reprocessed (chunk-event counts prove it) and the resumed output
    matches the uninterrupted run."""
    chunks = _chunks(26)
    base_cfg = TfidfConfig(vocab_bits=10, prefetch=0)
    full = run_tfidf_streaming(chunks, base_cfg)

    cfg = TfidfConfig(vocab_bits=10, prefetch=0, checkpoint_every=1,
                      checkpoint_dir=str(tmp_path / "ck"))
    m1 = MetricsRecorder()
    with chaos.inject("tfidf_chunk_sync:lost@26"):  # the 26th drain = chunk 25
        with pytest.raises(ResilienceExhausted) as ei:
            run_tfidf_streaming(chunks, cfg, metrics=m1)
    done_before = [r["chunk"] for r in m1.records if r.get("event") == "chunk"]
    assert done_before == list(range(25))  # chunks 0-24 landed, then the kill
    assert ei.value.last_checkpoint is not None
    assert ckpt.peek_meta(ei.value.last_checkpoint)["step"] == 25
    assert resume_point(cfg) == 25

    m2 = MetricsRecorder()
    res = run_tfidf_streaming(chunks, cfg, metrics=m2, resume=True)
    done_after = [r["chunk"] for r in m2.records if r.get("event") == "chunk"]
    assert done_after == [25]  # ZERO completed chunks reprocessed
    assert res.n_docs == full.n_docs
    np.testing.assert_allclose(res.to_dense(), full.to_dense(), atol=1e-6)


def test_tfidf_transient_chunk_failures_are_invisible(tmp_path):
    chunks = _chunks(8)
    full = run_tfidf_streaming(chunks, TfidfConfig(vocab_bits=10, prefetch=0))
    m = MetricsRecorder()
    with chaos.inject("tfidf_chunk_sync:fail@%3"):
        res = run_tfidf_streaming(chunks, TfidfConfig(vocab_bits=10, prefetch=0),
                                  metrics=m)
    assert sum(r.get("event") == "retry" for r in m.records) >= 2
    np.testing.assert_allclose(res.to_dense(), full.to_dense(), atol=1e-6)


def test_tfidf_sharded_loss_then_resume(tmp_path, monkeypatch):
    """Same off-switch contract for sharded TF-IDF: no shrink rung, so a
    persistent loss exhausts with a resumable chunk checkpoint."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        run_tfidf_sharded,
    )

    monkeypatch.setenv("GRAFT_ELASTIC", "0")
    chunks = _chunks(12)
    base = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                             n_devices=4)
    cfg = TfidfConfig(vocab_bits=10, checkpoint_every=4,
                      checkpoint_dir=str(tmp_path / "ck"))
    with chaos.inject("tfidf_shard_sync:lost@2+"):
        with pytest.raises(ResilienceExhausted) as ei:
            run_tfidf_sharded(iter(chunks), cfg, n_devices=4)
    assert ei.value.last_checkpoint is not None
    res = run_tfidf_sharded(iter(chunks), cfg, n_devices=4, resume=True)
    assert res.n_docs == base.n_docs
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)


def test_tfidf_checkpoint_carries_throughput_accounting(tmp_path):
    cfg = TfidfConfig(vocab_bits=10, prefetch=0, checkpoint_every=2,
                      checkpoint_dir=str(tmp_path / "ck"))
    run_tfidf_streaming(_chunks(6), cfg)
    meta = ckpt.peek_meta(ckpt.latest_checkpoint(cfg.checkpoint_dir))
    assert meta["extra"]["n_docs"] == 12
    assert meta["extra"]["n_tokens"] > 0
    assert meta["extra"]["ingest_secs"] > 0


# ----------------------------------------------- bench.py partial record


def test_bench_forced_tfidf_timeout_emits_partial_record(tmp_path, monkeypatch):
    """Acceptance: bench.py's tfidf round under a forced timeout (chaos
    hangs every chunk drain from the 8th on; the child can never finish)
    emits a ``"partial": true`` record with nonzero chunks completed —
    instead of BENCH_r05's bare TIMEOUT log line and a discarded run.
    Driven on the CPU through the round's own function: the whole bench
    refuses to run without a TPU."""
    import importlib.util as ilu

    spec = ilu.spec_from_file_location("bench_mod", REPO / "bench.py")
    bench = ilu.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr(bench, "TFIDF_TIMEOUT_S", 30)
    monkeypatch.setenv("BENCH_TFIDF_RETRIES", "1")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        BENCH_TFIDF_DOCS="256", BENCH_TFIDF_TOKENS_PER_DOC="30",
        BENCH_TFIDF_CHUNK_DOCS="16",  # -> 16 streaming chunks
        BENCH_TFIDF_PACK_TOKENS="0",  # keep them 16: the cap-filling
        # re-pack would fold this tiny corpus into ONE chunk and the
        # hang below could never fire mid-stream
        BENCH_TFIDF_CKPT_EVERY="1",   # chunk-granular resume for this test
        BENCH_TFIDF_CKPT_DIR=str(tmp_path / "ck"),
        GRAFT_TRACE_DIR=str(tmp_path / "trace"),
        # every chunk drain from the 8th on hangs "forever": the child
        # checkpoints 7 chunks then wedges; the resume retry checkpoints 7
        # more from chunk 7 and wedges again
        GRAFT_CHAOS="tfidf_chunk_sync:hang@8+:600",
    )
    tfidf_out, tfidf = bench._run_tfidf_child(env)
    assert tfidf_out is None
    bench._tfidf_trace_extra(str(tmp_path / "trace"), tfidf)
    assert tfidf["partial"] is True
    assert tfidf["chunks_completed"] > 0
    assert tfidf["tokens_completed"] > 0
    assert tfidf["stream_tokens_per_sec_so_far"] > 0
    # the resume retry made it strictly past the first child's wedge point
    assert tfidf["chunks_completed"] >= 8
