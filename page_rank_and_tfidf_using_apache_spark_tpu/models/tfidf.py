"""TF-IDF model drivers: batch and streaming ingest.

Reference counterpart (SURVEY.md A6–A10, §3.2): the ``tfidf.py`` Spark
driver — tokenize/flatMap, TF and DF reduceByKey passes, IDF, join, save.
The batch path here is one device pipeline call; the streaming path
(BASELINE.json:11 "English Wikipedia ~6M docs, streaming ingest") feeds
fixed-shape token chunks through a once-compiled kernel, accumulating the
DF vector and doc count on device and spilling per-chunk TF counts to host,
then applies IDF in a second pass — the two-pass structure Spark gets from
its separate TF and DF shuffles, minus the shuffles.

Checkpointing (SURVEY.md §5.4): every ``checkpoint_every`` chunks the
accumulated ``(df, n_docs, chunk_index, tf-counts-so-far)`` state is
snapshotted atomically; resume skips already-ingested chunks.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import ingest as dflow
from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio
from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig, TfMode, ensure_dtype_support
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder, Timer

obs.watch_compiles()


@dataclasses.dataclass(frozen=True)
class TfidfOutput:
    """Host-side sparse TF-IDF matrix in COO form, sorted by (term, doc),
    plus the dense DF/IDF tables — the reference's saved A10 output."""

    n_docs: int
    vocab_bits: int
    doc: np.ndarray  # int32 [nnz]
    term: np.ndarray  # int32 [nnz]
    weight: np.ndarray  # f[nnz]
    df: np.ndarray  # f[vocab]
    idf: np.ndarray  # f[vocab]
    metrics: MetricsRecorder
    # Raw per-pair counts + per-doc lengths ride along so a second
    # weighting over the SAME postings (BM25 — dataflow/bm25.py) needs no
    # corpus re-pass.  None on outputs built before this field existed.
    count: np.ndarray | None = None  # f[nnz]
    doc_lengths: np.ndarray | None = None  # int32 [n_docs]

    @property
    def nnz(self) -> int:
        return int(self.doc.shape[0])

    def to_dense(self) -> np.ndarray:
        """[n_docs, vocab] dense matrix — tests/small corpora only."""
        out = np.zeros((self.n_docs, 1 << self.vocab_bits), dtype=self.weight.dtype)
        out[self.doc, self.term] = self.weight
        return out


def run_tfidf(
    docs: Sequence[str],
    cfg: TfidfConfig,
    *,
    metrics: MetricsRecorder | None = None,
    doc_names: Sequence[str] | None = None,
) -> TfidfOutput:
    """Batch TF-IDF: tokenize on host, one compiled device pipeline."""
    ensure_dtype_support(cfg.dtype)
    metrics = metrics or MetricsRecorder()
    # tokenize_corpus opens its own "io.tokenize" span — no wrapper here
    with Timer() as t_tok:
        corpus = tio.tokenize_corpus(
            docs,
            vocab_bits=cfg.vocab_bits,
            ngram=cfg.ngram,
            lowercase=cfg.lowercase,
            min_token_len=cfg.min_token_len,
            doc_names=doc_names,
        )
    metrics.record(event="tokenize", docs=corpus.n_docs, tokens=corpus.n_tokens, secs=t_tok.elapsed)

    with obs.span("tfidf.pipeline"):
        result = ops.tfidf_pipeline(
            jnp.asarray(corpus.doc_ids),
            jnp.asarray(corpus.term_ids),
            jnp.asarray(corpus.doc_lengths),
            n_docs=max(corpus.n_docs, 1),
            vocab=cfg.vocab_size,
            tf_mode=cfg.tf_mode,
            idf_mode=cfg.idf_mode,
            l2_normalize=cfg.l2_normalize,
        )
        rx.block_until_ready(result, site="tfidf_batch_sync", metrics=metrics)
    with obs.span("tfidf.result_pull"):
        n_pairs = int(result.n_pairs)
        return TfidfOutput(
            n_docs=corpus.n_docs,
            vocab_bits=cfg.vocab_bits,
            doc=np.asarray(result.doc[:n_pairs]),
            term=np.asarray(result.term[:n_pairs]),
            weight=np.asarray(result.weight[:n_pairs]),
            df=np.asarray(result.df),
            idf=np.asarray(result.idf),
            metrics=metrics,
            count=np.asarray(result.count[:n_pairs]),
            doc_lengths=np.asarray(corpus.doc_lengths),
        )


# The fixed-shape capacity policy moved into the dataflow core
# (dataflow/ingest.py) with the rest of the chunked-ingest machinery; the
# re-export keeps this module the policy's public address for the serving
# micro-batcher and the lint registry's shape matrices.
grow_chunk_cap = dflow.grow_chunk_cap


def stream_pad_plan(
    raw_token_counts: Sequence[int], cap: int = 0
) -> list[tuple[str, float]]:
    """Static padding-waste plan of the streaming ingest: run the raw
    per-chunk token counts through the REAL :func:`grow_chunk_cap` policy
    (no dispatch, no device) and return ``[("stream", pad_frac)]`` where
    ``pad_frac`` is the fraction of dispatched token slots that are padding
    across the whole stream.  This is the tier-3 pad_frac surface for the
    chunk-ingest entry points (analysis/cost.py), the TF-IDF counterpart of
    ``parallel.pagerank_sharded.plan_partition``."""
    import logging

    log = logging.getLogger("pr_tfidf_tpu")
    was_disabled = log.disabled
    log.disabled = True  # cap-bump log lines are production telemetry
    try:
        metrics = MetricsRecorder()
        total_raw = 0
        total_cap = 0
        for raw in raw_token_counts:
            cap, _ = grow_chunk_cap(raw, cap, metrics)
            total_raw += int(raw)
            total_cap += cap
    finally:
        log.disabled = was_disabled
    pad_frac = (total_cap - total_raw) / max(total_cap, 1)
    return [("stream", pad_frac)]


@dataclasses.dataclass
class IngestState:
    """Accumulated streaming-ingest state, shared by the streaming and
    sharded paths: exactly what a per-chunk checkpoint snapshots, so a
    killed run resumes at the first unprocessed chunk with zero rework.

    ``ingest_secs`` is cumulative wall time *as of the last checkpoint*,
    carried across resumes — it is what makes a partial run's tokens/sec a
    real, comparable metric (bench.py's ``"partial": true`` record).
    """

    df_total: np.ndarray
    chunk_index: int = 0  # chunks fully ingested (== next chunk to process)
    n_docs: int = 0
    n_tokens: int = 0
    ingest_secs: float = 0.0
    parts: list = dataclasses.field(default_factory=list)  # (doc, term, count)
    doc_length_parts: list = dataclasses.field(default_factory=list)


def resume_point(cfg: TfidfConfig) -> int:
    """Chunk index a ``resume=True`` run will start at (0 = from scratch)
    — cheap (reads only checkpoint metadata), so callers that can seek
    their corpus source may skip materializing the ingested prefix
    (io.text.iter_corpus_chunks ``skip_chunks=``)."""
    if not cfg.checkpoint_dir:
        return 0
    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    if latest is None:
        return 0
    return int(ckpt.peek_meta(latest)["step"])


def resume_ingest(cfg: TfidfConfig, metrics: MetricsRecorder) -> IngestState:
    """Load the latest ingest checkpoint (streaming and sharded paths share
    the format); a fresh zero state when no checkpoint exists."""
    if not cfg.checkpoint_dir:
        raise ValueError("resume=True requires checkpoint_dir")
    fresh = IngestState(df_total=np.zeros(cfg.vocab_size, cfg.dtype))
    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
    if latest is None:
        return fresh
    chunk_index, arrays, extra = ckpt.load_checkpoint(latest, cfg.config_hash())
    st = IngestState(
        df_total=arrays["df"],
        chunk_index=int(chunk_index),
        n_docs=int(extra["n_docs"]),
        n_tokens=int(extra.get("n_tokens", 0)),
        ingest_secs=float(extra.get("ingest_secs", 0.0)),
        parts=[(arrays["doc"], arrays["term"], arrays["count"])],
        doc_length_parts=[arrays["doc_lengths"]],
    )
    metrics.record(event="resume", path=latest, chunk=st.chunk_index, docs=st.n_docs)
    return st


def save_ingest_checkpoint(
    cfg: TfidfConfig, metrics: MetricsRecorder, st: IngestState,
    extra_meta: dict | None = None,
) -> None:
    """Snapshot accumulated ingest state, compacting the part lists in
    place so host memory stays flat across checkpoints.  ``extra_meta``
    rides along in the checkpoint metadata (the sharded path tags
    ``devices=N`` so a snapshot records which mesh shape wrote it); the
    payload itself is mesh-shape-independent — accumulated global DF and
    TF parts — so any device count can resume from it."""
    doc_a, term_a, count_a = (np.concatenate(x) for x in zip(*st.parts))
    st.parts = [(doc_a, term_a, count_a)]
    st.doc_length_parts = [np.concatenate(st.doc_length_parts)]
    path = ckpt.save_checkpoint(
        cfg.checkpoint_dir,
        st.chunk_index,
        {
            "df": st.df_total, "doc": doc_a, "term": term_a, "count": count_a,
            "doc_lengths": st.doc_length_parts[0],
        },
        cfg.config_hash(),
        extra={
            "n_docs": st.n_docs,
            "n_tokens": st.n_tokens,
            "ingest_secs": round(st.ingest_secs, 3),
            **(extra_meta or {}),
        },
    )
    metrics.record(event="checkpoint", path=path, chunk=st.chunk_index)


# Below this many accumulated pairs the numpy finalize wins (no dispatch /
# transfer overhead); above it the device path's fused elementwise math and
# segment reductions do (VERDICT r1 item 5).  Tests override to 0.
DEVICE_FINALIZE_MIN_NNZ = 1 << 20


def finalize_tfidf(
    st: IngestState,
    cfg: TfidfConfig,
    metrics: MetricsRecorder,
) -> TfidfOutput:
    """Second pass shared by the streaming and sharded ingest paths: IDF
    join + TF weighting + optional L2 normalize.  Small accumulations run in
    numpy; at scale the per-pair math and the per-doc L2 reduction run on
    device (ops.finalize_weights)."""
    dtype = cfg.dtype
    n_docs = st.n_docs
    df_total = st.df_total
    if not st.parts:
        z = np.zeros(0, np.int32)
        return TfidfOutput(0, cfg.vocab_bits, z, z, np.zeros(0, dtype),
                           df_total, np.zeros(cfg.vocab_size, dtype), metrics)

    doc_a = np.concatenate([p[0] for p in st.parts])
    term_a = np.concatenate([p[1] for p in st.parts])
    count_a = np.concatenate([p[2] for p in st.parts]).astype(dtype)
    doc_lengths = np.concatenate(st.doc_length_parts)

    with obs.span("tfidf.finalize", nnz=int(doc_a.shape[0])):
        idf = rx.device_get(
            ops.idf_vector(jnp.asarray(df_total), float(max(n_docs, 1)), cfg.idf_mode),
            site="tfidf_finalize_sync", metrics=metrics,
            checkpoint_dir=cfg.checkpoint_dir,
        )
        with Timer() as t_fin:
            if doc_a.shape[0] >= DEVICE_FINALIZE_MIN_NNZ:
                weight = rx.device_get(ops.finalize_weights(
                    jnp.asarray(doc_a), jnp.asarray(count_a),
                    jnp.asarray(doc_lengths), jnp.asarray(idf[term_a]),
                    n_docs=max(n_docs, 1), tf_mode=cfg.tf_mode,
                    l2_normalize=cfg.l2_normalize,
                ), site="tfidf_finalize_sync", metrics=metrics,
                   checkpoint_dir=cfg.checkpoint_dir)
                where = "device"
            else:
                if cfg.tf_mode is TfMode.RAW:
                    tf = count_a
                elif cfg.tf_mode is TfMode.FREQ:
                    tf = count_a / np.maximum(doc_lengths[doc_a].astype(dtype), 1.0)
                else:  # LOGNORM
                    tf = np.where(count_a > 0, 1.0 + np.log(np.maximum(count_a, 1.0)),
                                  0.0).astype(dtype)
                weight = tf * idf[term_a]
                if cfg.l2_normalize:
                    sq = np.zeros(n_docs, dtype)
                    np.add.at(sq, doc_a, weight * weight)
                    weight = weight / np.sqrt(np.maximum(sq, 1e-30))[doc_a]
                where = "host"
    metrics.record(event="finalize", where=where, nnz=int(doc_a.shape[0]),
                   secs=t_fin.elapsed)
    metrics.scalar("n_docs", n_docs)
    metrics.scalar("nnz", int(doc_a.shape[0]))
    return TfidfOutput(
        n_docs=n_docs, vocab_bits=cfg.vocab_bits,
        doc=doc_a, term=term_a, weight=weight.astype(dtype),
        df=df_total, idf=idf, metrics=metrics,
        count=count_a, doc_lengths=doc_lengths,
    )


def _pad_chunk(
    corpus: tio.TokenizedCorpus, cap: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    t = corpus.n_tokens
    doc_ids = np.zeros(cap, np.int32)
    term_ids = np.zeros(cap, np.int32)
    valid = np.zeros(cap, bool)
    doc_ids[:t] = corpus.doc_ids
    term_ids[:t] = corpus.term_ids
    valid[:t] = True
    return doc_ids, term_ids, valid


def _tokenized_chunks(
    doc_chunks: Iterable[Sequence[str]],
    cfg: TfidfConfig,
    start_chunk: int,
    n_docs0: int,
) -> Iterator[tuple[int, tio.TokenizedCorpus]]:
    """Tokenize chunks in order, assigning globally unique doc ids;
    skips the already-ingested prefix on resume.

    Resume bookkeeping is in chunk *indices*, so a caller re-chunking the
    corpus differently between runs would silently skip the wrong
    documents.  When the skipped prefix arrives as real chunks (not the
    empty placeholders of ``iter_corpus_chunks(skip_chunks=...)``, which
    validates on its own side), its document count must equal the
    checkpoint's ``n_docs`` — mismatch fails loudly.
    """
    n_docs = n_docs0
    skipped_docs = 0
    for i, docs in enumerate(doc_chunks):
        if i < start_chunk:
            skipped_docs += len(docs)
            if i == start_chunk - 1 and skipped_docs not in (0, n_docs0):
                raise ValueError(
                    f"resume chunking mismatch: the skipped prefix of "
                    f"{start_chunk} chunk(s) holds {skipped_docs} documents "
                    f"but the checkpoint ingested {n_docs0}; rerun with the "
                    "original chunking (e.g. the same --chunk-docs)"
                )
            continue  # already ingested before the resume point
        # tokenize_corpus opens its own "io.tokenize" span (also on the
        # prefetch thread) — no wrapper here
        corpus = tio.tokenize_corpus(
            docs,
            vocab_bits=cfg.vocab_bits,
            ngram=cfg.ngram,
            lowercase=cfg.lowercase,
            min_token_len=cfg.min_token_len,
            doc_id_offset=n_docs,
        )
        n_docs += corpus.n_docs
        yield i, corpus


# Commit-barrier interval (in chunks) for streaming runs WITHOUT
# checkpointing: bounds how many drained chunks' host copies
# retain_until_commit may hold (the elastic rung replays at most this
# span after a device loss).  With 2^18-token chunks this caps retention
# near 16M tokens of int32 pairs — flat host memory, rare drain bubbles.
_RETAIN_COMMIT_EVERY = 16


def run_tfidf_streaming(
    doc_chunks: Iterable[Sequence[str]],
    cfg: TfidfConfig,
    *,
    metrics: MetricsRecorder | None = None,
    resume: bool = False,
) -> TfidfOutput:
    """Streaming TF-IDF over an iterator of document chunks.

    Documents never span chunks, so per-chunk run-length DF increments add
    up to the exact global DF.  Chunk token arrays are padded to a fixed
    capacity (``cfg.chunk_tokens``, or the first chunk's size rounded up to
    a power of two) so the device kernel compiles once; an oversized chunk
    bumps the capacity with a logged recompile (SURVEY.md §7).

    The loop is a four-stage software pipeline (SURVEY.md §5.7, ISSUE 10):
    a background thread tokenizes up to ``cfg.prefetch`` chunks ahead; a
    **transfer thread** pads each chunk and issues its ``jax.device_put``
    (the H2D staging stage, chaos/retry site ``ingest_h2d_put``) holding
    at most ``cfg.pipeline_depth`` staged chunks of device memory — chunk
    N+1's transfer runs under chunk N's compute; the main thread
    dispatches the once-compiled kernel against pre-staged device buffers
    only and defers each chunk's host pull until ``cfg.prefetch`` launches
    are in flight.  ``prefetch=0, pipeline_depth=0`` is fully serial: no
    background threads and every chunk syncs before the next launches.
    ``cfg.pack_target_tokens > 0`` additionally re-packs the incoming
    chunking to fill the compiled capacity (padding, not scheduling, is
    most of the measured streaming-vs-batch gap).  Results are
    bit-identical at every depth — only scheduling changes.

    The DF accumulator is an **ingest carry**: a device-resident vector
    threaded through :func:`ops.tfidf.chunk_counts_carry` with its buffer
    donated, so XLA updates it in place every chunk and the host never
    pulls DF per chunk.  DF reaches the host only at *commit points* —
    checkpoint saves and finalize — behind the drain-before-commit
    barrier (``dataflow.fixpoint.commit_barrier``): a snapshot can only be
    written once every in-flight launch has drained, so it never contains
    DF contributions from chunks it does not record as ingested.

    Device loss anywhere in the pipeline (an H2D put on the transfer
    thread included — chaos site ``ingest_h2d_put``) walks the single-chip
    elastic rung: the loss is acknowledged, host state rolls back to the
    last commit point, and the pipeline replays the uncommitted span from
    the host copies it retained — the tokenized chunks — onto the CPU
    backend, byte-identically.  Committed chunks are never reprocessed.
    """
    ensure_dtype_support(cfg.dtype)
    metrics = metrics or MetricsRecorder()
    vocab = cfg.vocab_size
    dtype = cfg.dtype
    cap = cfg.chunk_tokens

    st = (resume_ingest(cfg, metrics) if resume
          else IngestState(df_total=np.zeros(vocab, dtype)))
    secs0 = st.ingest_secs
    run_started = time.perf_counter()
    last_ckpt = st.chunk_index
    # The device-resident DF carry (donated to every chunk dispatch; this
    # reference is always the LATEST carry, never a consumed one).
    df_dev = jnp.asarray(st.df_total)
    # None until a device loss: the elastic rung then pins every
    # subsequent put (and so every dispatch) to the CPU backend.
    target_dev = None

    if cfg.pack_target_tokens > 0:
        doc_chunks = dflow.pack_doc_chunks(
            doc_chunks, cfg.pack_target_tokens,
            estimate=dflow.ngram_estimator(cfg.ngram))
    source = _tokenized_chunks(doc_chunks, cfg, st.chunk_index, st.n_docs)

    # Rollback point for the elastic rung: what st looked like at the
    # last commit barrier.  Chunks drained after it have host TF parts
    # but their DF lives only in the (now dead) device carry — recovery
    # truncates them here and the pipeline replays their retained host
    # copies, so nothing is lost and nothing double-counts.
    committed: dict = {}

    def snap_commit() -> None:
        committed.update(
            parts=len(st.parts), dls=len(st.doc_length_parts),
            n_docs=st.n_docs, n_tokens=st.n_tokens, chunk=st.chunk_index,
        )

    snap_commit()

    def _put(arr):
        return (jax.device_put(arr, target_dev) if target_dev is not None
                else jax.device_put(arr))

    def stage_chunk(item):
        """H2D staging stage (transfer thread when pipeline_depth > 0):
        pad one tokenized chunk to the fixed capacity and issue its
        device transfers through the guarded staging site.  The item's
        host arrays stay retained by the pipeline until commit — the
        elastic rung re-stages from them."""
        nonlocal cap
        i, corpus = item
        cap, _ = grow_chunk_cap(corpus.n_tokens, cap, metrics, chunk=i)
        doc_ids, term_ids, valid = _pad_chunk(corpus, cap)
        d_doc, d_term, d_valid = dflow.staged_put(
            lambda: (_put(doc_ids), _put(term_ids), _put(valid)),
            metrics=metrics,
        )
        return (i, corpus, d_doc, d_term, d_valid)

    def launch(staged):
        """Dispatch the once-compiled kernel (async) against pre-staged
        device buffers only; the in-flight record carries what the drain
        needs to commit it."""
        nonlocal df_dev
        i, corpus, d_doc, d_term, d_valid = staged
        with Timer() as t:
            counts, df_dev = ops.chunk_counts_carry(
                d_doc, d_term, d_valid, df_dev, vocab=vocab,
            )  # async dispatch — no block here; df carry updated in place
        return (i, counts, corpus.doc_lengths,
                corpus.n_docs, corpus.n_tokens, t)

    def drain_one(rec):
        i, counts, doc_lengths, n_chunk_docs, n_tokens, t = rec
        with Timer() as t_sync, obs.span("tfidf.chunk", chunk=i):
            # Wait for this chunk's device results with ONE batched
            # device->host pull.  The old path paid five round-trips per
            # chunk (int(n_pairs) fence + three sliced np.asarray pulls +
            # the df pull) — at a ~76 ms host round-trip that serialized
            # the whole streaming path (VERDICT.md round 5).  Pulling the
            # padded arrays whole costs a few MB of extra bytes but only
            # one round-trip; the slice happens on host.  (The DF vector is
            # no longer part of this pull at all — it stays on device as
            # the donated ingest carry until a commit point.)  The pull
            # runs under the resilience executor: a transient failure or
            # blown sync deadline re-issues the transfer (device buffers
            # are still live); exhaustion surfaces ResilienceExhausted
            # carrying the last chunk checkpoint to resume from.
            h_doc, h_term, h_count, h_n_pairs = rx.device_get(
                (counts.doc, counts.term, counts.count, counts.n_pairs),
                site="tfidf_chunk_sync", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            )
            k = int(h_n_pairs)
            # .copy() so parts holds k-sized arrays, not views pinning the
            # whole cap-sized transfer buffer until finalize
            st.parts.append((h_doc[:k].copy(), h_term[:k].copy(), h_count[:k].copy()))
        st.doc_length_parts.append(doc_lengths)
        st.n_docs += n_chunk_docs
        st.n_tokens += n_tokens
        st.chunk_index = i + 1
        metrics.record(event="chunk", chunk=i, docs=st.n_docs, tokens=n_tokens,
                       pairs=k, dispatch_secs=round(t.elapsed, 6),
                       secs=t_sync.elapsed)
        obs.counter("tfidf.chunks")
        obs.histogram("tfidf.chunk_secs", t_sync.elapsed)

    def commit_df():
        # Pull the device DF carry into host state.  chunked_ingest calls
        # this only when no launch is in flight: the carry always reflects
        # every DISPATCHED chunk, so a mid-flight pull would commit DF for
        # chunks the state does not count as ingested.  Its own site (not
        # tfidf_chunk_sync): chaos schedules and retry tallies count
        # per-chunk drains, and a commit is not a chunk.
        with obs.span("tfidf.df_commit"):
            st.df_total = rx.device_get(
                df_dev, site="tfidf_df_commit", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            ).astype(dtype)
        snap_commit()

    def recover(exc, remaining, where):
        """Single-chip elastic rung for the staged pipeline: a
        device-attributed loss anywhere in it (H2D put on the transfer
        thread, dispatch, drain) is acknowledged, host state rolls back
        to the last commit point, the DF carry is rebuilt from committed
        host DF on the CPU backend, and the pipeline replays the
        uncommitted span from its retained host chunks (byte-identical
        order).  Anything else — elastic disabled, whole-backend faults
        with no device index — re-raises into the pre-existing ladder
        (ResilienceExhausted + checkpoint)."""
        nonlocal df_dev, target_dev
        lost = elastic.unwrap_device_loss(exc)
        idx = elastic.device_index(lost) if lost is not None else None
        if not elastic.enabled() or idx is None:
            raise exc
        elastic.health().mark_lost(idx)
        site = {"stage": dflow.H2D_PUT_SITE,
                "wait": dflow.H2D_WAIT_SITE}.get(where, "tfidf_chunk_sync")
        rerun = st.chunk_index - committed["chunk"]
        obs.emit("degraded", site=site, ladder="cpu",
                 salvage_chunk=committed["chunk"], rerun_chunks=rerun,
                 error=f"{type(exc).__name__}: {exc}"[:200])
        obs.counter("degraded")
        metrics.record(event="degraded", site=site, ladder="cpu",
                       salvage_chunk=committed["chunk"], rerun_chunks=rerun)
        with obs.span("tfidf.cpu_salvage", at_chunk=committed["chunk"],
                      rerun_chunks=rerun):
            del st.parts[committed["parts"]:]
            del st.doc_length_parts[committed["dls"]:]
            st.n_docs = committed["n_docs"]
            st.n_tokens = committed["n_tokens"]
            st.chunk_index = committed["chunk"]
            target_dev = jax.devices("cpu")[0]
            df_dev = jax.device_put(st.df_total, target_dev)
        return remaining

    def checkpoint_due() -> bool:
        if cfg.checkpoint_every > 0 and cfg.checkpoint_dir:
            return st.chunk_index - last_ckpt >= cfg.checkpoint_every
        # Checkpointing off: retain_until_commit would otherwise hold
        # every drained chunk's host copy until the single end-of-stream
        # commit — a second full-corpus copy.  A commit-only barrier (DF
        # pull + rollback-point re-snap, no snapshot file) every K chunks
        # keeps host memory flat at the cost of one pipeline drain per K.
        return st.chunk_index - last_ckpt >= _RETAIN_COMMIT_EVERY

    def save_ckpt():
        nonlocal last_ckpt
        last_ckpt = st.chunk_index
        if not (cfg.checkpoint_every > 0 and cfg.checkpoint_dir):
            return  # retention-bounding barrier: commit already ran
        st.ingest_secs = secs0 + (time.perf_counter() - run_started)
        save_ingest_checkpoint(cfg, metrics, st)
        # the save compacts st.parts in place — re-snap the rollback
        # point so its list indices match the compacted layout
        snap_commit()

    # The host pipeline — staged H2D double-buffering, bounded in-flight
    # launches, drain-before-commit checkpoints, background source
    # prefetch, elastic recovery — is the dataflow core's chunked_ingest
    # primitive; this driver only supplies the TF-IDF closures (and keeps
    # its guarded sites/spans byte-identical to the pre-port path).
    with obs.span("tfidf.stream", resume_chunk=st.chunk_index):
        dflow.chunked_ingest(
            source,
            stage=stage_chunk,
            launch=launch,
            drain=drain_one,
            commit=commit_df,
            ingest=cfg.ingest(),
            checkpoint_due=checkpoint_due,
            save_checkpoint=save_ckpt,
            recover=recover,
            retain_until_commit=True,
            metrics=metrics,
        )

    return finalize_tfidf(st, cfg, metrics)
