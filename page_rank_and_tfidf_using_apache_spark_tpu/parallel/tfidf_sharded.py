"""Multi-chip TF-IDF: data-parallel chunk ingest with psum'd DF and a
replicated IDF broadcast.

Reference counterpart (SURVEY.md §2.2 R1–R3, BASELINE.json:11): Spark
splits the corpus into partitions, shuffles ((term, doc), 1) records for the
TF and DF passes, and torrent-broadcasts small tables.  Here each device
ingests its own fixed-shape token chunk (documents never span chunks, so
per-chunk run-length DF increments are exact), one ``psum`` over the mesh
combines the per-device DF vectors — the DF `reduceByKey` — and the
resulting IDF vector is *replicated* across chips, which is BASELINE.json:5's
"IDF broadcast across chips" realized as a sharding annotation instead of a
torrent protocol.

Shapes: a "super-chunk" is [D, cap] token arrays, one row per device;
compile happens once per (D, cap).

Since ISSUE 10 the host loop IS ``dataflow.ingest.chunked_ingest`` — the
same staged pipeline as single-chip streaming: a tokenize thread feeds
super-chunk groups, a transfer thread issues the **sharded puts** for
group N+1 (chaos/retry site ``ingest_h2d_put``) while group N computes,
holding at most ``cfg.pipeline_depth`` staged groups of device memory,
and the drain is the one guarded batched pull per super-chunk.  Device
loss anywhere in the pipeline reaches the single recovery point: the
committed ingest state is checkpointed, the mesh is rebuilt over the
survivors (``elastic.plan_shrink``), and the pipeline **re-slices the
in-flight staged groups over the shrunk mesh** by regrouping the host
corpora it retained — committed chunks are never reprocessed, and a
second loss inside the replay simply re-enters the same recovery point
(4 → 2 → 1 chaos-tested).
"""

from __future__ import annotations

import time
from typing import Iterable, Iterator, Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import ingest as dflow
from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    IngestState,
    TfidfOutput,
    _tokenized_chunks,
    finalize_tfidf,
    grow_chunk_cap,
    resume_ingest,
    save_ingest_checkpoint,
)
from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import collectives as coll
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
    DATA_AXIS,
    make_mesh,
    rebuild_mesh,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfidfConfig, ensure_dtype_support
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder


def _publish_device_timings(arr, step: int) -> None:
    """Per-device shard-ready timings for the trace chunk timeline
    (ROADMAP hardening (d)): fence each device's shard of the tiny
    ``n_pairs`` vector and record when it became ready, measured from the
    call.  Shards are waited in device order, so entry ``i`` is an upper
    bound for a device that finished while an earlier one still ran — the
    straggler (the max) is exact, which is what load-balance debugging
    needs.  Best-effort telemetry: any fault here is left for the guarded
    batched pull that follows.  Runs ONLY under an active traced run —
    untraced ingest keeps the single batched pull as its only sync (each
    per-shard fence is a real host round-trip, and with no run the event
    would be discarded anyway)."""
    if obs.current_run() is None:
        return
    try:
        t0 = time.perf_counter()
        secs = []
        for s in arr.addressable_shards:
            s.data.block_until_ready()  # graftlint: disable=unguarded-host-sync,host-sync-in-loop (per-shard fence for telemetry only; the guarded batched pull right after owns retry/deadline/degradation)
            secs.append(round(time.perf_counter() - t0, 6))
        obs.emit("device_timing", site="tfidf_super_chunk", step=step,
                 devices=len(secs), secs=secs)
    except Exception:  # noqa: BLE001 — never let telemetry kill ingest
        pass


def make_sharded_counts_kernel(mesh: Mesh, vocab: int):
    """Compile: [D, cap] tokens → per-device counts + globally-psum'd DF."""
    axis = mesh.axis_names[0]

    def kernel(doc_ids, term_ids, valid):
        counts = ops.count_pairs(doc_ids[0], term_ids[0], token_valid=valid[0])
        df_local = ops.document_frequency(counts, vocab)
        df = coll.psum(df_local, axis)  # the DF reduceByKey, on ICI
        # re-add the device axis so out_specs can shard along it
        return (counts.doc[None], counts.term[None], counts.count[None],
                counts.n_pairs[None], counts.valid[None]), df

    esh = P(axis, None)
    return jax.jit(
        shard_map(
            kernel,
            mesh=mesh,
            in_specs=(esh, esh, esh),
            out_specs=(
                (esh, esh, esh, P(axis), esh),
                P(),  # DF replicated — the IDF broadcast target
            ),
            check_vma=False,
        )
    )


def run_tfidf_sharded(
    doc_chunks: Iterable[Sequence[str]],
    cfg: TfidfConfig,
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    metrics: MetricsRecorder | None = None,
    resume: bool = False,
) -> TfidfOutput:
    """Sharded counterpart of models.tfidf.run_tfidf_streaming: consumes the
    same chunk iterator, ingesting D chunks per device step.  Checkpointing
    shares the streaming path's format (``cfg.checkpoint_every`` counts input
    *chunks*, not super-chunks, so a config moved between the two paths
    checkpoints at the same cadence) and ``resume=True`` skips the
    already-ingested prefix of the iterator."""
    ensure_dtype_support(cfg.dtype)
    metrics = metrics or MetricsRecorder()
    if mesh is None:
        mesh = make_mesh(n_devices, DATA_AXIS)
    d = int(mesh.devices.size)
    axis = mesh.axis_names[0]
    vocab = cfg.vocab_size
    dtype = cfg.dtype

    cap = cfg.chunk_tokens
    kernel = make_sharded_counts_kernel(mesh, vocab)
    esh = NamedSharding(mesh, P(axis, None))

    st = (resume_ingest(cfg, metrics) if resume
          else IngestState(df_total=np.zeros(vocab, dtype)))
    last_ckpt = st.chunk_index
    secs0 = st.ingest_secs
    run_started = time.perf_counter()
    step = 0

    if cfg.pack_target_tokens > 0:
        doc_chunks = dflow.pack_doc_chunks(
            doc_chunks, cfg.pack_target_tokens,
            estimate=dflow.ngram_estimator(cfg.ngram))
    chunk_source = _tokenized_chunks(doc_chunks, cfg, st.chunk_index,
                                     st.n_docs)

    def grouped(src: Iterator) -> Iterator[list[tio.TokenizedCorpus]]:
        # one pipeline item = one super-chunk group of <= d corpora; ``d``
        # is read per group, so after a shrink the tail arrives pre-sized
        # (in-flight old-width groups are regrouped by ``recover`` below)
        buf: list[tio.TokenizedCorpus] = []
        for _, corpus in src:
            buf.append(corpus)
            if len(buf) >= d:
                yield buf
                buf = []
        if buf:
            yield buf

    def stage_group(group: list[tio.TokenizedCorpus]):
        """H2D staging stage (transfer thread): build the [D, cap] host
        arrays for one super-chunk and issue the sharded puts through the
        guarded staging site.  The group's corpora stay retained by the
        pipeline until the drain commits them, so the recovery point can
        re-slice them over a rebuilt mesh.  The staged record carries the
        group along — the drain commits per input chunk."""
        nonlocal cap
        need = max(c.n_tokens for c in group)
        cap, _ = grow_chunk_cap(need, cap, metrics)
        doc_ids = np.zeros((d, cap), np.int32)
        term_ids = np.zeros((d, cap), np.int32)
        valid = np.zeros((d, cap), bool)
        for i, c in enumerate(group):
            doc_ids[i, : c.n_tokens] = c.doc_ids
            term_ids[i, : c.n_tokens] = c.term_ids
            valid[i, : c.n_tokens] = True
        dev = dflow.staged_put(
            lambda: (jax.device_put(doc_ids, esh),
                     jax.device_put(term_ids, esh),
                     jax.device_put(valid, esh)),
            metrics=metrics,
        )
        return (group, dev)

    def launch_group(staged):
        nonlocal step
        group, (d_doc, d_term, d_valid) = staged
        t0 = time.perf_counter()
        (c_doc, c_term, c_cnt, c_np, _c_valid), df = kernel(
            d_doc, d_term, d_valid
        )  # async dispatch — the pull waits in the drain
        rec = (group, step, c_doc, c_term, c_cnt, c_np, df, t0)
        step += 1
        return rec

    def drain_group(rec) -> None:
        group, step_i, c_doc, c_term, c_cnt, c_np, df, t0 = rec
        with obs.span("tfidf.super_chunk", step=step_i,
                      chunk=st.chunk_index):
            # per-device shard-ready times onto the bus BEFORE the batched
            # pull, so the trace's chunk timeline can attribute a slow
            # super-chunk to the straggling device (hardening (d))
            _publish_device_timings(c_np, step_i)
            # One batched device->host pull: a single round-trip per
            # super-chunk instead of a fence plus four separate transfers
            # (each paying a host round-trip).  Guarded: a transient failure
            # re-issues the pull against the live buffers; persistent
            # faults walk the ladder and surface to the pipeline's
            # recovery point (mesh shrink + re-slice of retained groups).
            h_doc, h_term, h_cnt, n_pairs, h_df = rx.device_get(
                (c_doc, c_term, c_cnt, c_np, df),
                site="tfidf_shard_sync", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            )
        st.df_total = st.df_total + h_df.astype(dtype)
        n_pairs = np.asarray(n_pairs).ravel()
        for i, c in enumerate(group):
            k = int(n_pairs[i])
            # .copy() so parts holds k-sized arrays, not views pinning the
            # whole (d, cap) transfer buffer until finalize
            st.parts.append(
                (h_doc[i, :k].copy(), h_term[i, :k].copy(),
                 h_cnt[i, :k].copy())
            )
            st.doc_length_parts.append(c.doc_lengths)
        st.n_docs += int(sum(c.n_docs for c in group))
        st.chunk_index += len(group)
        st.n_tokens += int(sum(c.n_tokens for c in group))
        metrics.record(
            event="super_chunk", step=step_i, devices=len(group),
            docs=st.n_docs, tokens=int(sum(c.n_tokens for c in group)),
            secs=time.perf_counter() - t0,
        )

    def checkpoint_due() -> bool:
        if not (cfg.checkpoint_every > 0 and cfg.checkpoint_dir):
            return False
        return st.chunk_index - last_ckpt >= cfg.checkpoint_every

    def save_ckpt() -> None:
        nonlocal last_ckpt
        st.ingest_secs = secs0 + (time.perf_counter() - run_started)
        save_ingest_checkpoint(cfg, metrics, st, extra_meta={"devices": d})
        last_ckpt = st.chunk_index

    def regrouped(remaining: Iterator) -> Iterator[list]:
        # re-slice: flatten whatever group widths the dying mesh left in
        # flight and regroup to the CURRENT mesh width (``grouped`` reads
        # ``d`` per group — a second shrink inside the replay re-sizes
        # again)
        return grouped((None, c) for group in remaining for c in group)

    def recover(exc, remaining, where):
        """Mesh-shrink recovery point: on device loss anywhere in the
        pipeline (H2D put, dispatch, drain), checkpoint the committed
        ingest state, rebuild the mesh/kernel over the survivors, and
        re-slice the in-flight staged groups (retained as host corpora by
        the pipeline) over the shrunk mesh.  Committed chunks are
        untouched — zero reprocessing, same guarantee as the resume path.
        A further loss inside the replay re-enters here (the stacked-loss
        re-entry the elastic ladder requires)."""
        nonlocal mesh, d, esh, kernel, last_ckpt
        # Salvage committed work FIRST: whatever happens next (shrink or
        # re-raise into the legacy ladder), the chunks already committed
        # must survive as a snapshot.  The old loop had this for free —
        # its periodic save ran before the next drain could fail; the
        # pipeline's drain-before-commit barrier can order a failing
        # drain ahead of a due checkpoint.
        saved = None
        if cfg.checkpoint_dir and st.parts:
            st.ingest_secs = secs0 + (time.perf_counter() - run_started)
            save_ingest_checkpoint(cfg, metrics, st,
                                   extra_meta={"devices": d})
            last_ckpt = st.chunk_index
            saved = ckpt.latest_checkpoint(cfg.checkpoint_dir)

        def reraise():
            # an exhausted ladder raised before the salvage above existed
            # must still hand the caller the freshest snapshot
            if (saved is not None
                    and isinstance(exc, rx.ResilienceExhausted)
                    and exc.last_checkpoint is None):
                raise rx.ResilienceExhausted(
                    exc.site, exc.attempts, exc.last_error, saved
                ) from exc
            raise exc

        lost = elastic.unwrap_device_loss(exc)
        if not elastic.enabled() or lost is None:
            reraise()
        idx = elastic.device_index(lost)
        if idx is not None:
            elastic.health().mark_lost(idx)
        plan = elastic.plan_shrink(list(mesh.devices.flat))
        if plan is None:
            reraise()
        site = {"stage": dflow.H2D_PUT_SITE,
                "wait": dflow.H2D_WAIT_SITE}.get(where, "tfidf_shard_sync")
        with elastic.publish_shrink(site, plan, lost, metrics):
            # keep the dying mesh's axis name: a caller-provided mesh may
            # not be named DATA_AXIS, and esh below is built from ``axis``
            mesh = rebuild_mesh(plan.devices, axis)
            d = plan.new_count
            esh = NamedSharding(mesh, P(axis, None))
            kernel = make_sharded_counts_kernel(mesh, vocab)
        return regrouped(remaining)

    with obs.span("tfidf.shard_stream", devices=d,
                  resume_chunk=st.chunk_index):
        dflow.chunked_ingest(
            grouped(chunk_source),
            stage=stage_group,
            launch=launch_group,
            drain=drain_group,
            commit=lambda: None,  # the drain's pull IS the commit: DF is
            # psum'd and pulled per super-chunk, nothing stays on device
            ingest=cfg.ingest(),
            checkpoint_due=checkpoint_due,
            save_checkpoint=save_ckpt,
            recover=recover,
            metrics=metrics,
        )

    return finalize_tfidf(st, cfg, metrics)
