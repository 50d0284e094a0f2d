"""PageRank model driver: orchestration, checkpointing, metrics.

Reference counterpart (SURVEY.md A1/A4/A5): the ``pagerank.py`` driver —
``main(argv)`` building the graph, running the ``for i in range(iters)``
loop, collecting ranks.  Here the driver's only jobs are host-side: move the
graph to device once, launch the compiled loop, periodically snapshot state,
and emit structured per-segment metrics (SURVEY.md §5.5).  The numeric loop
itself is ops/pagerank.py, compiled to a single XLA program.

Checkpointing (SURVEY.md §5.3/§5.4): with ``checkpoint_every = k`` the run
executes in k-iteration compiled segments with an atomic snapshot of
``(ranks, iteration, config_hash)`` between segments — recovery is
restart-from-snapshot (there is no lineage to replay on TPU), exercised by
the kill/resume fault-injection test.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import fixpoint as dflow
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import Graph
from page_rank_and_tfidf_using_apache_spark_tpu.models import driver
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils import config
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder, Timer

obs.watch_compiles()


def put_graph_for(graph: Graph, cfg: PageRankConfig) -> ops.DeviceGraph:
    """``ops.put_graph`` with whatever static layout ``cfg.spmv_impl``
    needs (dense hybrid head rows, sort-shuffle buckets) built from the
    config's layout knobs.  Layout impls never read the raw edge arrays
    (the layout duplicates every edge), so their device copy is skipped."""
    layout = ops.layout_for_impl(cfg.spmv_impl)
    return ops.put_graph(
        graph, cfg.dtype,
        layout=layout,
        head_coverage=cfg.head_coverage,
        head_row_width=cfg.head_row_width,
        bucket_width=cfg.shuffle_bucket_width,
        keep_edge_arrays=layout is None,
    )


@dataclasses.dataclass(frozen=True)
class PageRankResult:
    ranks: np.ndarray  # f[n_nodes], aligned with graph's compacted ids
    iterations: int  # iterations actually executed
    l1_delta: float  # L1 delta of the final iteration
    metrics: MetricsRecorder


def run_pagerank(
    graph: Graph,
    cfg: PageRankConfig,
    *,
    metrics: MetricsRecorder | None = None,
    resume: bool = False,
) -> PageRankResult:
    """Run PageRank per ``cfg`` on the default device (single-chip path;
    the sharded multi-chip path is parallel/pagerank_sharded.py)."""
    config.ensure_dtype_support(cfg.dtype)
    metrics = metrics or MetricsRecorder()
    n = graph.n_nodes
    if n == 0:
        return PageRankResult(np.zeros(0, cfg.dtype), 0, 0.0, metrics)
    cfg = driver.resolve_personalize(graph, cfg)

    # Layout and both puts; a resume reads its checkpoint between the puts.
    # The ranks are allocated after the layout: allocated before it, the
    # layout ran ~4 ms slower on a TPU v5e host.
    with obs.span("pagerank.put_graph"):
        # The one-time host layout build (degree sort / head split / bucket
        # padding for the hybrid and sort_shuffle impls) is amortized over
        # the whole run — record it so bench.py can prove that claim.
        with Timer() as t_put:
            dg = put_graph_for(graph, cfg)
        # which reduction the segment SpMV lowers; None for other impls
        metrics.record(event="put_graph", spmv_impl=cfg.spmv_impl,
                       segment_reduce=(ops.segment_reduce(dg)
                                       if cfg.spmv_impl == "segment" else None),
                       gather_chunks=(ops.gather_chunks(dg.src.shape)
                                      if cfg.spmv_impl in ops.EDGE_GATHER_IMPLS
                                      else None),
                       preprocess_secs=t_put.elapsed)
        e = jax.device_put(ops.restart_vector(n, cfg))
        ranks = np.asarray(ops.init_ranks(n, cfg))
        start_iter = driver.resume_from_checkpoint(cfg, metrics, ranks, n=n) if resume else 0
        ranks_dev = jax.device_put(ranks.astype(cfg.dtype))

    make = ops.make_spark_exact_runner if cfg.spark_exact else ops.make_pagerank_runner

    def invoke(runner, rd):
        # Async dispatch consumes (donates) the rank carry ``rd`` — so the
        # scalar sync below must NOT surface transient failures to the
        # outer pagerank_step guard, whose retry would re-dispatch into
        # the consumed buffer.  The fetch gets its own guarded site: a
        # transient blip re-pulls the scalar against the still-live OUTPUT
        # buffers, which is always safe.  The first call of a fresh runner
        # traces, lowers and compiles inside the dispatch span.
        with obs.span("pagerank.dispatch"):
            rd, iters, delta = runner(dg, rd, e)
        with obs.span("pagerank.delta_sync"):
            delta = float(rx.device_get(
                delta, site="pagerank_delta_sync", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            ))  # scalar fetch is the only reliable device sync
        return rd, iters, delta

    def make_cpu_invoke(seg_cfg):
        """Degradation-ladder rung (resilience/executor.py): re-lower the
        segment for the CPU backend and run it there.  The graph is re-put
        from host state — the device copy may be gone with the device —
        and the live ranks are pulled through the guarded executor (the
        pull itself can hang on a lost device)."""
        runner = make(n, seg_cfg)

        def cpu_invoke(rd):
            with obs.span("pagerank.cpu_degrade"):
                cpu = jax.devices("cpu")[0]
                with jax.default_device(cpu):
                    dg_cpu = put_graph_for(graph, cfg)
                    e_cpu = jax.device_put(
                        rx.device_get(e, site="pagerank_cpu_pull"), cpu
                    )
                    rd_cpu = jax.device_put(
                        rx.device_get(rd, site="pagerank_cpu_pull"), cpu
                    )
                    out, iters, delta = runner(dg_cpu, rd_cpu, e_cpu)
                    delta = float(delta)
            return out, iters, delta

        return cpu_invoke

    def extract_np(rd):
        with obs.span("pagerank.ckpt_pull"):
            return rx.device_get(
                rd, site="pagerank_ckpt_pull", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            )

    def init_state() -> np.ndarray:
        return np.asarray(ops.init_ranks(n, cfg))

    def cpu_exec(seg_cfg, ranks_g: np.ndarray):
        """Re-lower on the CPU backend from HOST state (graph re-put, no
        read of any dead device buffer) and run ``seg_cfg.iterations``."""
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            dg_cpu = put_graph_for(graph, cfg)
            e_cpu = jax.device_put(np.asarray(ops.restart_vector(n, cfg)), cpu)
            rd_cpu = jax.device_put(ranks_g.astype(cfg.dtype), cpu)
            runner = make(n, seg_cfg)
            rd2, iters, delta = runner(dg_cpu, rd_cpu, e_cpu)
            return rd2, int(iters), float(delta), dg_cpu, e_cpu

    def cpu_salvage_exec(rerun_cfg, ranks_g: np.ndarray):
        """dataflow.fixpoint.make_cpu_salvage contract: CPU re-lowering +
        rerun from host state, returning the replacement invoke."""
        rd2, iters, delta, dg_cpu, e_cpu = cpu_exec(rerun_cfg, ranks_g)

        def cpu_invoke2(runner, rd):
            rd, iters, delta = runner(dg_cpu, rd, e_cpu)
            with obs.span("pagerank.delta_sync"):
                delta = float(rx.device_get(
                    delta, site="pagerank_delta_sync", metrics=metrics,
                    checkpoint_dir=cfg.checkpoint_dir,
                ))
            return rd, iters, delta

        return rd2, iters, delta, cpu_invoke2

    # The single-chip elastic salvage rung (carried-forward ISSUE 9
    # satellite): a device-attributed loss first surfacing at the delta
    # sync, checkpoint pull or result pull used to dead-end — the CPU
    # rung re-*pulled* the dead/donated carry and failed with it.  The
    # rung is the SHARED dataflow one: salvage newest snapshot, rerun the
    # uncommitted span on the CPU backend, swap the loop onto CPU
    # execution.  Whole-backend faults keep the legacy cpu rung.
    elastic_salvage = dflow.make_cpu_salvage(
        cfg, metrics, site_prefix="pagerank",
        init_state=init_state, cpu_exec=cpu_salvage_exec,
        make_runner=lambda c: make(n, c), extract_np=extract_np,
    )

    ranks_dev, done, last_delta = driver.run_segments(
        cfg, metrics, ranks_dev, start_iter,
        make_runner=lambda seg_cfg: make(n, seg_cfg),
        invoke=invoke,
        extract_np=extract_np,
        segments_allowed=not cfg.spark_exact,
        make_cpu_invoke=make_cpu_invoke,
        elastic_rebuild=elastic_salvage,
    )

    with obs.span("pagerank.result_pull"):
        # Device loss first surfacing at the RESULT pull walks the same
        # shared salvage rung (checkpoint → CPU re-run of the uncommitted
        # span → pull from the CPU buffers).
        ranks_np = rx.device_get(
            ranks_dev, site="pagerank_result_pull", metrics=metrics,
            checkpoint_dir=cfg.checkpoint_dir,
            fallbacks=[(None, dflow.make_pull_salvage(
                cfg, metrics, site_prefix="pagerank",
                init_state=init_state, cpu_exec=cpu_salvage_exec,
                get_done=lambda: done,
            ))],
        )
    return PageRankResult(
        ranks=ranks_np, iterations=done, l1_delta=last_delta, metrics=metrics
    )
