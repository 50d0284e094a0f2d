"""Staged double-buffered ingest pipeline tests (ISSUE 10).

Covers the dataflow core's new pieces — :class:`Prefetched`'s poison/close
protocol, :func:`pack_doc_chunks`, :func:`overlap_fraction`, the staged
``chunked_ingest`` — and the acceptance bars: streaming TF-IDF byte-equal
to batch at every ``pipeline_depth``, chunk-kill resume with a
staged-but-uncommitted chunk in flight reprocessing zero committed
chunks, and chaos ``device_lost`` at ``ingest_h2d_put`` walking the
elastic rung on both the single-chip and 2-device sharded paths.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import ingest as dflow
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    resume_point,
    run_tfidf,
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import run_tfidf_sharded
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos, elastic
from page_rank_and_tfidf_using_apache_spark_tpu.resilience.executor import (
    ResilienceExhausted,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    IngestConfig,
    TfidfConfig,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder


@pytest.fixture
def fresh_health():
    elastic.reset_health()
    yield
    elastic.reset_health()


def _chunks(n_chunks: int, docs_per_chunk: int = 2) -> list[list[str]]:
    docs = [f"tok{i} tok{i % 5} shared word extra{i % 3}"
            for i in range(n_chunks * docs_per_chunk)]
    return [docs[i:i + docs_per_chunk]
            for i in range(0, len(docs), docs_per_chunk)]


# ------------------------------------------------ Prefetched protocol


def test_prefetched_producer_exception_keeps_traceback():
    """A producer exception re-raises on the consumer side WITH the
    original traceback — the producer frame must be visible (the ISSUE 10
    satellite: no more 'exception came from a queue' dead ends)."""

    def bad_source():
        yield 1
        raise ValueError("boom at item 2")

    it = dflow.prefetched(bad_source(), depth=2)
    assert next(it) == 1
    with pytest.raises(ValueError, match="boom at item 2") as ei:
        list(it)
    frames = []
    tb = ei.value.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert "bad_source" in frames  # the producer frame survived the queue


def test_prefetched_close_unblocks_full_queue_and_keeps_items():
    """close() must shut down a producer BLOCKED on a full queue promptly,
    and every produced-but-unconsumed item (including the one the producer
    had in hand) must survive into leftover() — zero loss."""
    produced: list[int] = []

    def source():
        for i in range(100):
            produced.append(i)
            yield i

    pf = dflow.Prefetched(source(), depth=2)
    assert next(pf) == 0
    time.sleep(0.1)  # let the producer fill the queue and block
    t0 = time.perf_counter()
    pf.close()
    assert time.perf_counter() - t0 < 2.0  # prompt, not a timeout crawl
    assert not pf.thread.is_alive()  # no leaked thread
    left = pf.leftover()
    # consumed [0]; everything else the producer pulled from the source
    # must be in leftover, in order
    assert left == produced[1:]
    assert len(left) >= 2  # queue depth + possibly the in-hand orphan


def test_prefetched_generator_abandonment_stops_producer():
    """Abandoning the legacy generator wrapper early (the chunk-kill
    resume path) must terminate the producer thread instead of leaking it
    blocked on a full queue."""
    before = threading.active_count()
    gen = dflow.prefetched(iter(range(1000)), depth=1)
    assert next(gen) == 0
    gen.close()  # abandon early
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_prefetched_end_to_end_order():
    assert list(dflow.prefetched(iter(range(50)), depth=3)) == list(range(50))


# ------------------------------------------------ pack_doc_chunks


def test_pack_doc_chunks_fills_target_and_preserves_order():
    docs = [f"w{i} " * (i % 7 + 1) for i in range(40)]  # 1..7 tokens each
    chunks = [docs[i:i + 3] for i in range(0, len(docs), 3)]
    packed = list(dflow.pack_doc_chunks(iter(chunks), target_tokens=20))
    # order preserved, nothing lost, documents never split
    assert [d for c in packed for d in c] == docs
    # every chunk except the last carries <= target but the NEXT doc
    # would have overflowed it (fills to within one document)
    for c in packed[:-1]:
        assert sum(dflow.estimate_tokens(d) for d in c) <= 20


def test_pack_doc_chunks_deterministic():
    docs = [f"a{i} b c" for i in range(30)]
    chunks = [docs[i:i + 4] for i in range(0, len(docs), 4)]
    p1 = list(dflow.pack_doc_chunks(iter(chunks), 10))
    p2 = list(dflow.pack_doc_chunks(iter(chunks), 10))
    assert p1 == p2


def test_pack_doc_chunks_oversized_doc_gets_own_chunk():
    docs = ["small doc", "x " * 200, "tiny"]
    packed = list(dflow.pack_doc_chunks(iter([docs]), 10))
    assert ["x " * 200] in [c for c in packed if len(c) == 1]
    assert [d for c in packed for d in c] == docs


# ------------------------------------------------ overlap_fraction


def test_overlap_fraction_math():
    # h2d [0,2] fully under compute [0,4] -> 1.0
    assert dflow.overlap_fraction([(0, 2)], [(0, 4)]) == pytest.approx(1.0)
    # h2d [3,5] half under compute [0,4] -> 0.5
    assert dflow.overlap_fraction([(3, 5)], [(0, 4)]) == pytest.approx(0.5)
    # disjoint -> 0.0; empty h2d -> 0.0
    assert dflow.overlap_fraction([(10, 12)], [(0, 4)]) == 0.0
    assert dflow.overlap_fraction([], [(0, 4)]) == 0.0
    # overlapping compute intervals must not double-count
    assert dflow.overlap_fraction(
        [(0, 4)], [(0, 2), (1, 3)]
    ) == pytest.approx(0.75)


def test_ingest_config_validation():
    assert IngestConfig().pipeline_depth == 2
    with pytest.raises(ValueError):
        IngestConfig(prefetch=-1)
    with pytest.raises(ValueError):
        IngestConfig(pipeline_depth=-1)
    assert TfidfConfig(prefetch=1, pipeline_depth=3).ingest() == IngestConfig(
        prefetch=1, pipeline_depth=3
    )


# ------------------------------------ byte-equality across pipeline depths


def test_streaming_byte_equal_to_batch_at_all_pipeline_depths():
    """ISSUE 10 acceptance: streaming output byte-equal to batch pinned at
    pipeline_depth in {0, 1, 2, 4} — only scheduling may change."""
    chunks = _chunks(10, docs_per_chunk=3)
    docs = [d for c in chunks for d in c]
    batch = run_tfidf(docs, TfidfConfig(vocab_bits=10)).to_dense()
    for depth in (0, 1, 2, 4):
        scfg = TfidfConfig(vocab_bits=10, chunk_tokens=64, prefetch=2,
                           pipeline_depth=depth)
        sw = run_tfidf_streaming(iter(chunks), scfg).to_dense()
        assert sw.tobytes() == batch.tobytes(), f"depth {depth}"


def test_streaming_byte_equal_with_packing():
    """Re-packing the source chunking (pack_target_tokens) changes chunk
    boundaries only — the output must stay byte-equal to batch."""
    chunks = _chunks(12, docs_per_chunk=1)
    docs = [d for c in chunks for d in c]
    batch = run_tfidf(docs, TfidfConfig(vocab_bits=10)).to_dense()
    m = MetricsRecorder()
    scfg = TfidfConfig(vocab_bits=10, chunk_tokens=64,
                       pack_target_tokens=30)
    out = run_tfidf_streaming(iter(chunks), scfg, metrics=m)
    assert out.to_dense().tobytes() == batch.tobytes()
    # packing really regrouped: fewer packed chunks than input chunks
    chunk_events = [r for r in m.records if r.get("event") == "chunk"]
    assert 0 < len(chunk_events) < 12


def test_ingest_overlap_record_published():
    m = MetricsRecorder()
    run_tfidf_streaming(iter(_chunks(6)), TfidfConfig(vocab_bits=10),
                        metrics=m)
    ov = [r for r in m.records if r.get("event") == "ingest_overlap"]
    assert len(ov) == 1
    rec = ov[0]
    assert set(rec) >= {"h2d_overlap_frac", "tokenize_secs", "h2d_secs",
                        "compute_secs", "chunks", "depth", "pipeline_depth"}
    assert rec["chunks"] == 6
    assert 0.0 <= rec["h2d_overlap_frac"] <= 1.0


# ------------------------------------------- resume with staged chunks


def test_chunk_kill_with_staged_inflight_resumes_zero_reprocessing(tmp_path):
    """A drain kill while later chunks are already STAGED (device_put
    issued, compute not committed) must leave a checkpoint at the last
    committed chunk; resume reprocesses zero committed chunks and matches
    the uninterrupted output."""
    chunks = _chunks(16)
    full = run_tfidf_streaming(iter(chunks), TfidfConfig(vocab_bits=10))

    cfg = TfidfConfig(vocab_bits=10, prefetch=2, pipeline_depth=2,
                      checkpoint_every=1,
                      checkpoint_dir=str(tmp_path / "ck"))
    m1 = MetricsRecorder()
    with chaos.inject("tfidf_chunk_sync:lost@9"):  # the 9th drain fails
        with pytest.raises(ResilienceExhausted) as ei:
            run_tfidf_streaming(iter(chunks), cfg, metrics=m1)
    assert ei.value.last_checkpoint is not None
    committed = resume_point(cfg)
    done_before = [r["chunk"] for r in m1.records if r.get("event") == "chunk"]
    # drained != committed: the failing drain happened INSIDE a commit
    # barrier, so some chunks drained after the last successful commit
    # (their DF lives only in the dead carry) — the checkpoint must hold
    # strictly committed state, never those
    assert committed == 6
    assert done_before == list(range(8))  # drains 0-7 landed, 8 was killed

    m2 = MetricsRecorder()
    res = run_tfidf_streaming(iter(chunks), cfg, metrics=m2, resume=True)
    done_after = [r["chunk"] for r in m2.records if r.get("event") == "chunk"]
    # resume replays exactly the uncommitted span: ZERO committed chunks
    # reprocessed (6 and 7 were drained but never committed, so their
    # replay is what keeps DF consistent)
    assert done_after == list(range(committed, 16))
    np.testing.assert_allclose(res.to_dense(), full.to_dense(), atol=1e-6)


# ----------------------------------- chaos at the H2D staging sites


def test_h2d_put_transient_faults_invisible():
    """Transient faults at ingest_h2d_put retry on the transfer thread
    and stay invisible to the caller."""
    chunks = _chunks(9)
    base = run_tfidf_streaming(iter(chunks), TfidfConfig(vocab_bits=10))
    m = MetricsRecorder()
    with chaos.inject("ingest_h2d_put:fail@%3"):
        res = run_tfidf_streaming(iter(chunks), TfidfConfig(vocab_bits=10),
                                  metrics=m)
    retries = [r for r in m.records if r.get("event") == "retry"
               and r.get("site") == dflow.H2D_PUT_SITE]
    assert len(retries) >= 2
    assert res.to_dense().tobytes() == base.to_dense().tobytes()


def test_single_chip_device_lost_at_h2d_put_walks_elastic_rung(
        fresh_health, tmp_path):
    """ISSUE 10 acceptance: chaos device_lost at ingest_h2d_put on the
    single-chip path walks the elastic rung (acknowledge + rollback to
    the last commit + CPU replay of retained host chunks) and matches the
    uninterrupted output — no ResilienceExhausted."""
    chunks = _chunks(12)
    base = run_tfidf_streaming(iter(chunks), TfidfConfig(vocab_bits=10))
    m = MetricsRecorder()
    cfg = TfidfConfig(vocab_bits=10, prefetch=2, pipeline_depth=2,
                      checkpoint_every=4,
                      checkpoint_dir=str(tmp_path / "ck"))
    with chaos.inject("ingest_h2d_put:device_lost@dev:0"):
        res = run_tfidf_streaming(iter(chunks), cfg, metrics=m)
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert len(degraded) == 1
    assert degraded[0]["ladder"] == "cpu"
    assert degraded[0]["site"] == dflow.H2D_PUT_SITE
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)


def test_single_chip_device_lost_mid_stream_rolls_back_to_commit(
        fresh_health, tmp_path):
    """The loss fires mid-stream with committed chunks behind it: the
    rollback must keep every committed chunk exactly once (no drops, no
    double counts) — byte-level equality of the dense matrix proves it."""
    chunks = _chunks(14)
    base = run_tfidf_streaming(iter(chunks), TfidfConfig(vocab_bits=10))
    m = MetricsRecorder()
    cfg = TfidfConfig(vocab_bits=10, prefetch=2, pipeline_depth=2,
                      checkpoint_every=3,
                      checkpoint_dir=str(tmp_path / "ck"))
    # dev schedule: fires on every ingest_h2d_put call until acknowledged;
    # delay the first injection past several commits by targeting a later
    # call — chunk 8's put is well past the chunk-6 checkpoint
    with chaos.inject("ingest_h2d_wait:device_lost@dev:0"):
        res = run_tfidf_streaming(iter(chunks), cfg, metrics=m)
    assert [r["ladder"] for r in m.records if r.get("event") == "degraded"] \
        == ["cpu"]
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)
    assert res.n_docs == base.n_docs


def test_sharded_device_lost_at_h2d_put_shrinks_mesh(fresh_health, tmp_path):
    """ISSUE 10 acceptance: chaos device_lost at ingest_h2d_put on a
    2-device sharded mesh walks the elastic mesh-shrink rung — the
    in-flight staged groups re-slice over the shrunk mesh from retained
    host corpora — and the output matches the uninterrupted run."""
    chunks = _chunks(12)
    base = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                             n_devices=2)
    elastic.reset_health()
    m = MetricsRecorder()
    obs.start_run("ingest_h2d_loss", str(tmp_path / "tr"))
    try:
        with chaos.inject("ingest_h2d_put:device_lost@dev:1"):
            res = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=10),
                                    n_devices=2, metrics=m)
    finally:
        obs.end_run()
    degraded = [r for r in m.records if r.get("event") == "degraded"]
    assert len(degraded) == 1
    assert (degraded[0]["devices_old"], degraded[0]["devices_new"]) == (2, 1)
    sc = [r for r in m.records if r.get("event") == "super_chunk"]
    assert sum(r["devices"] for r in sc) == 12  # every chunk exactly once
    np.testing.assert_allclose(res.to_dense(), base.to_dense(), atol=1e-6)


# --------------------------------------------- trace artifact rendering


def test_trace_report_renders_ingest_section(tmp_path):
    import importlib.util
    from pathlib import Path

    with obs.run("ingesttrace", trace_dir=str(tmp_path)):
        run_tfidf_streaming(iter(_chunks(4)), TfidfConfig(vocab_bits=10))
    trace = next(tmp_path.glob("ingesttrace.*.trace.jsonl"))
    spec = importlib.util.spec_from_file_location(
        "trace_report",
        Path(__file__).resolve().parents[1] / "tools" / "trace_report.py",
    )
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    rep = tr.report(str(trace))
    assert rep["ingest"] and len(rep["ingest"]) == 1
    assert rep["ingest"][0]["chunks"] == 4
    assert "h2d_overlap_frac" in rep["ingest"][0]
    human = tr.render_human(rep)
    assert "ingest pipeline" in human and "h2d_overlap" in human


def test_trace_diff_folds_overlapped_ingest_phases():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "trace_diff",
        Path(__file__).resolve().parents[1] / "tools" / "trace_diff.py",
    )
    td = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(td)
    # wall time moved from compute into overlapped h2d: NOT a regression
    old = {"ingest.compute": 10.0, "bench.warm": 1.0}
    new = {"ingest.compute": 6.0, "ingest.h2d": 4.0, "bench.warm": 1.0}
    rows = td.diff_breakdowns(old, new)
    combined = [r for r in rows if r["phase"] == "ingest.h2d+compute"]
    assert len(combined) == 1
    assert combined[0]["delta_secs"] == pytest.approx(0.0)
    assert not any(r["phase"] in ("ingest.h2d", "ingest.compute")
                   for r in rows)


# ---------------------------------------- review regressions (PR 10)


def test_wait_site_does_not_retry_iterator_failures():
    """A persistent stage failure whose message carries a transient
    marker (e.g. XLA 'UNAVAILABLE: socket closed') must NOT be
    retried at the ingest_h2d_wait site: the staged iterator is stateful,
    so a re-invoked next() would read _END off the finished Prefetched
    and silently truncate the stream (or skip the failed item inline).
    The cause must propagate to the caller/recovery point instead."""
    for depth in (0, 2):
        drained: list = []

        def stage(item):
            if item == 4:
                raise RuntimeError("UNAVAILABLE: socket closed")
            return item

        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            dflow.chunked_ingest(
                range(8), stage=stage, launch=lambda s: s,
                drain=drained.append, commit=lambda: None,
                depth=2, pipeline_depth=depth,
            )
        # the run did NOT complete as if successful, and what drained is
        # a contiguous prefix stopping before the casualty — nothing was
        # skipped past it (undrained items stay accounted for recovery)
        assert drained == list(range(len(drained))), (depth, drained)
        assert len(drained) <= 4, (depth, drained)


def test_wait_site_recovery_redelivers_after_marker_failure():
    """Same failure, with a recover hook: every unprocessed item
    (including the casualty) is re-delivered exactly once — no
    truncation, no double-processing."""
    fail = {"armed": True}
    drained: list = []
    seen: list = []

    def stage(item):
        if item == 4 and fail["armed"]:
            fail["armed"] = False
            raise RuntimeError("UNAVAILABLE: socket closed")
        return item

    def recover(exc, remaining, where):
        assert where == "stage"
        assert "UNAVAILABLE" in str(exc)
        seen.append(sorted(remaining))
        return seen[-1]

    dflow.chunked_ingest(
        iter(range(8)), stage=stage, launch=lambda s: s,
        drain=drained.append, commit=lambda: None,
        depth=2, pipeline_depth=2, recover=recover,
    )
    # the casualty was re-delivered (not skipped), and every item was
    # processed exactly once overall — no truncation, no double-drain
    assert len(seen) == 1 and 4 in seen[0]
    assert sorted(drained) == list(range(8))
    assert drained[:len(drained) - len(seen[0])] == \
        list(range(8 - len(seen[0])))


def test_wait_site_watchdog_never_drops_consumed_items(monkeypatch):
    """With GRAFT_SYNC_DEADLINE_S armed and a staging stage slower than
    the deadline, the wait site must NOT run under the watchdog: an
    abandoned attempt would still be blocked inside next() on the
    stateful staged iterator, and whatever item that zombie thread
    eventually consumed would vanish from the committed output (silently
    — the run 'succeeds' minus chunks).  The pull is a local thread
    handoff, so it runs inline; the device-facing put keeps its own
    deadline at ingest_h2d_put."""
    monkeypatch.setenv("GRAFT_SYNC_DEADLINE_S", "0.2")
    for depth in (0, 2):
        drained: list = []

        def stage(item):
            time.sleep(0.3)  # slower than the armed deadline
            return item

        dflow.chunked_ingest(
            range(6), stage=stage, launch=lambda s: s,
            drain=drained.append, commit=lambda: None,
            depth=2, pipeline_depth=depth,
        )
        assert drained == list(range(6)), (depth, drained)


def test_swept_source_exception_fails_recovery_replay():
    """A source exception the consumer never saw (it died on a drain
    fault first, and the teardown swept the parked exception out of the
    prefetch thread) must re-surface during the recovery replay at its
    stream position: the replayed run must NOT complete 'successfully'
    with a silently truncated corpus and the source error unread."""
    for pdepth in (0, 2):
        drained: list = []
        recovered: list = []

        def source():
            yield from range(4)
            raise ValueError("corrupt input past doc 3")

        armed = {"on": True}

        def drain(rec):
            if rec == 1 and armed["on"]:
                armed["on"] = False
                # let the producer run past the source fault so the
                # teardown sweeps it unread (the regression path); the
                # live-raise path is equivalent and also covered
                time.sleep(0.2)
                raise RuntimeError("persistent drain fault")
            drained.append(rec)

        def recover(exc, remaining, where):
            # mirrors production: recover handles the device-class
            # fault, anything else re-raises into the ladder
            recovered.append(type(exc).__name__)
            if isinstance(exc, ValueError):
                raise exc
            return remaining

        with pytest.raises(ValueError, match="corrupt input"):
            dflow.chunked_ingest(
                source(), stage=lambda it: it, launch=lambda s: s,
                drain=drain, commit=lambda: None,
                depth=2, pipeline_depth=pdepth, recover=recover,
            )
        # the drain fault recovered, then the swept source error failed
        # the replay (the run did NOT complete as if successful); what
        # drained is each real doc at most once, in stream order —
        # in-flight items at the moment the source error surfaced are
        # uncommitted work on a FAILED run, not silent drops
        assert recovered == ["RuntimeError", "ValueError"], (pdepth,
                                                             recovered)
        assert drained == sorted(set(drained)), (pdepth, drained)
        assert set(drained) <= {0, 1, 2, 3}, (pdepth, drained)


def test_estimate_tokens_matches_tokenizer_split_rule():
    """estimate_tokens must upper-bound the real tokenizer on
    punctuation/newline-heavy text (it splits on ALL non-alphanumerics,
    not whitespace), or pack_doc_chunks overfills chunks past the
    compiled cap and forces mid-stream recompiles."""
    from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio

    for doc in ("a,b,c,d", "x\ny\nz", "one two", "a--b__c", ""):
        assert dflow.estimate_tokens(doc) >= len(tio.tokenize(doc)), doc
    assert dflow.estimate_tokens("a,b,c,d") == 4
    # ngram=2 ~doubles the token count: the estimator must track it
    est2 = dflow.ngram_estimator(2)
    toks = tio.add_ngrams(tio.tokenize("a,b c;d"), 2)
    assert est2("a,b c;d") >= len(toks)
    assert dflow.ngram_estimator(1) is dflow.estimate_tokens


def test_packed_streaming_never_bumps_cap_on_punctuated_corpus():
    """End-to-end guard for the estimator: packing a punctuation-heavy
    corpus to a target at the chunk cap must not overflow it (no
    chunk_cap_bump recompiles mid-stream) and stays byte-equal."""
    docs = [",".join(f"tok{i}w{j}" for j in range(7)) for i in range(40)]
    chunks = [docs[i:i + 2] for i in range(0, len(docs), 2)]
    batch = run_tfidf(docs, TfidfConfig(vocab_bits=10)).to_dense()
    m = MetricsRecorder()
    scfg = TfidfConfig(vocab_bits=10, chunk_tokens=64,
                       pack_target_tokens=64)
    out = run_tfidf_streaming(iter(chunks), scfg, metrics=m)
    assert out.to_dense().tobytes() == batch.tobytes()
    assert not [r for r in m.records if r.get("event") == "chunk_cap_bump"]


def test_no_checkpoint_streaming_bounds_retained_chunks(monkeypatch):
    """With checkpointing off, retain_until_commit must not hold the
    whole corpus: a commit-only barrier every _RETAIN_COMMIT_EVERY chunks
    releases the retained host copies (and byte-equality holds across
    the extra barriers)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models import tfidf as mt

    monkeypatch.setattr(mt, "_RETAIN_COMMIT_EVERY", 4)
    chunks = _chunks(12)
    docs = [d for c in chunks for d in c]
    batch = run_tfidf(docs, TfidfConfig(vocab_bits=10)).to_dense()
    peak = {"n": 0}
    orig = dflow.chunked_ingest

    def spying(source, **kw):
        orig_drain = kw["drain"]
        retained = kw.get("retain_until_commit")
        assert retained is True
        # wrap commit to observe how many chunks were retained between
        # barriers via the drain counter
        count = {"n": 0}

        def drain(rec):
            count["n"] += 1
            orig_drain(rec)

        orig_commit = kw["commit"]

        def commit():
            peak["n"] = max(peak["n"], count["n"])
            count["n"] = 0
            orig_commit()

        kw["drain"], kw["commit"] = drain, commit
        return orig(source, **kw)

    monkeypatch.setattr(mt.dflow, "chunked_ingest", spying)
    out = run_tfidf_streaming(iter(chunks),
                              TfidfConfig(vocab_bits=10, prefetch=2,
                                          pipeline_depth=2))
    assert out.to_dense().tobytes() == batch.tobytes()
    # barriers fired mid-stream: no commit interval saw all 12 chunks
    assert 0 < peak["n"] <= 6
