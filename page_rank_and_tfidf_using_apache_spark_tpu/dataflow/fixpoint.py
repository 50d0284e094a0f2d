"""``fixpoint`` — the dataflow core's iterate-to-convergence primitive.

Two halves, mirroring Spark's split between an RDD program and the driver
that schedules it:

- :func:`iterate` is the **in-jit combinator**: one ``lax.scan`` /
  ``lax.while_loop`` skeleton carrying ``(state, delta, iters)``, shared
  by every fixpoint workload (single-chip and sharded PageRank, batched
  personalized PageRank, HITS, connected components).  Before the
  dataflow port each runner re-implemented this loop privately; a
  convergence fix now lands once.
- :func:`run_segments` is the **host driver**: run the compiled loop in
  checkpoint-sized segments with the resilience ladder (retry → elastic
  mesh shrink / CPU re-lowering → ``ResilienceExhausted`` + checkpoint)
  and the obs spans attached ONCE, underneath every workload.  This is
  the code that moved here from ``models/driver.py`` (which still
  re-exports it): the Spark counterpart is the DAGScheduler driving an
  iterative job, and the reason it lives in ``dataflow/`` is the ISSUE 9
  marginal-cost claim — a new fixpoint workload gets checkpointing,
  elastic degradation and tracing by *calling* this, not by copying it.

``run_segments`` is workload-agnostic: ``cfg`` is duck-typed (any frozen
config with ``iterations`` / ``tol`` / ``checkpoint_every`` /
``checkpoint_dir`` / ``config_hash()``), and ``site_prefix`` names the
guarded sites and spans (``pagerank`` for the ported runners, ``hits`` /
``cc`` / ``ppr`` for the new workloads) so traces and chaos plans stay
per-workload addressable.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, NamedTuple

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder, Timer


def commit_barrier(
    drain_all: Callable[[], None],
    commit: Callable[[], None],
    save_checkpoint: Callable[[], None] | None = None,
) -> None:
    """The drain-before-commit barrier of the staged ingest pipeline
    (ISSUE 10): every in-flight launch drains, THEN device carry state is
    pulled, THEN (optionally) the snapshot is written — so a checkpoint
    can never hold carry contributions from chunks it does not record as
    ingested, no matter how deep the H2D staging / in-flight windows run.

    Lives here rather than in ``dataflow/ingest.py`` because it is the
    ingest counterpart of the fixpoint checkpoint discipline above (a
    segment must complete before its snapshot): one module owns "what a
    commit point means" for both dataflow driver shapes.  The span makes
    barrier stalls attributable in traces — time spent here is pipeline
    drain, not compute."""
    with obs.span("ingest.commit_barrier"):
        drain_all()
        commit()
        if save_checkpoint is not None:
            save_checkpoint()


def default_delta(new, old):
    """L1 distance between successive carries — PageRank's convergence
    gauge, and a sane default for any single-array fixpoint."""
    import jax.numpy as jnp

    return jnp.sum(jnp.abs(new - old))


def iterate(
    step: Callable,
    carry0,
    *,
    iterations: int,
    tol: float = 0.0,
    delta_fn: Callable = default_delta,
):
    """The dataflow ``iterate`` primitive (Spark's driver ``for`` loop over
    a cached RDD, fused into ONE XLA program — zero host round-trips
    between iterations).

    Runs ``step(carry) -> carry`` to a fixpoint inside the enclosing jit:
    ``lax.scan`` for fixed ``iterations`` (tol == 0), ``lax.while_loop``
    carrying the delta for tolerance runs.  ``delta_fn(new, old)`` is the
    convergence gauge (scalar; compared against ``tol``).  Returns
    ``(carry, iters_done, last_delta)``; with ``iterations == 0`` the
    delta is ``inf`` (nothing measured).

    Must be called under ``jax.jit`` (the runner owns donation of the
    carry buffer — see ``ops.pagerank.make_pagerank_runner``).
    """
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(carry0)
    delta_dtype = leaves[0].dtype if leaves else jnp.float32
    if not jnp.issubdtype(delta_dtype, jnp.floating):
        # integer carries (label propagation) still need a float delta
        # slot: the while_loop init is inf, and delta_fn must return this
        # dtype (components uses a changed-label count cast to f32)
        delta_dtype = jnp.float32

    if tol > 0.0:
        def cond(state):
            _, delta, it = state
            return jnp.logical_and(delta > tol, it < iterations)

        def body(state):
            carry, _, it = state
            new = step(carry)
            return new, delta_fn(new, carry), it + 1

        init = (carry0, jnp.array(jnp.inf, delta_dtype),
                jnp.array(0, jnp.int32))
        carry, delta, it = jax.lax.while_loop(cond, body, init)
        return carry, it, delta

    def body(carry, _):
        new = step(carry)
        return new, delta_fn(new, carry)

    carry, deltas = jax.lax.scan(body, carry0, None, length=iterations)
    last = deltas[-1] if iterations > 0 else jnp.array(jnp.inf, delta_dtype)
    return carry, jnp.array(iterations, jnp.int32), last


def checkpoint_salvage(cfg, init_state: Callable[[], np.ndarray]):
    """``(at_iter, state_np)`` from the newest checkpoint, else
    ``(0, init_state())`` — what a device-loss rung restarts the
    uncommitted span from (the live carry died with the device)."""
    if cfg.checkpoint_dir:
        latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
        if latest is not None:
            step, arrays, _ = ckpt.load_checkpoint(latest, cfg.config_hash())
            return int(step), arrays["ranks"]
    return 0, init_state()


def make_cpu_salvage(
    cfg,
    metrics: MetricsRecorder,
    *,
    site_prefix: str,
    init_state: Callable[[], np.ndarray],
    cpu_exec: Callable,
    make_runner: Callable,
    extract_np: Callable,
):
    """The single-chip elastic salvage rung, built ONCE here for every
    fixpoint workload (the sharded counterpart lives in
    parallel/pagerank_sharded.py): a *device-attributed* loss — including
    one first surfacing at a delta-sync or checkpoint-pull site, where
    the donated carry is already dead — is acknowledged in the health
    registry, the newest snapshot (else the init vector) is salvaged, and
    the uncommitted span re-runs on the CPU backend from HOST state.
    Whole-backend faults (no device index) raise through to the legacy
    cpu rung / exhausted path, preserving the pre-existing ladder.

    ``cpu_exec(rerun_cfg, state_np) -> (state_dev, iters, delta,
    invoke)``: re-lower and run on CPU, returning the replacement
    ``invoke`` every subsequent segment uses.  Plug the result into
    :func:`run_segments`'s ``elastic_rebuild`` parameter.
    """

    def rebuild(exc, rd, done, seg_cfg):
        lost = elastic.unwrap_device_loss(exc)
        idx = elastic.device_index(lost) if lost is not None else None
        if not elastic.enabled() or idx is None:
            raise exc
        elastic.health().mark_lost(idx)
        at_iter, state = checkpoint_salvage(cfg, init_state)
        todo = done - at_iter + seg_cfg.iterations
        obs.emit("degraded", site=f"{site_prefix}_step", ladder="cpu",
                 salvage_iter=at_iter, rerun_iters=todo,
                 error=f"{type(exc).__name__}: {exc}"[:200])
        obs.counter("degraded")
        metrics.record(event="degraded", site=f"{site_prefix}_step",
                       ladder="cpu", salvage_iter=at_iter, rerun_iters=todo)
        with obs.span(f"{site_prefix}.cpu_salvage", at_iter=at_iter,
                      todo=todo):
            rerun_cfg = dataclasses.replace(
                seg_cfg, iterations=todo, checkpoint_every=0,
                checkpoint_dir=None,
            )
            rd2, iters, delta, invoke2 = cpu_exec(rerun_cfg, state)
        return ElasticResult(
            rd2, at_iter + int(iters) - done, float(delta),
            make_runner, invoke2, extract_np, {"backend": "cpu"},
        )

    return rebuild


def make_pull_salvage(
    cfg,
    metrics: MetricsRecorder,
    *,
    site_prefix: str,
    init_state: Callable[[], np.ndarray],
    cpu_exec: Callable,
    get_done: Callable[[], int],
):
    """The RESULT-pull counterpart of :func:`make_cpu_salvage`, shared by
    every single-chip fixpoint (and models/pagerank.py): a
    device-attributed loss first surfacing at ``{site_prefix}_result_pull``
    — no segment dispatch left to catch it — acknowledges the loss,
    salvages the newest snapshot, re-runs the uncommitted span on the CPU
    backend and pulls from the CPU buffers (the loss is acknowledged, so
    chaos cannot re-fire at the same site).  Returns a ``fallbacks`` rung
    for the final ``rx.device_get``."""

    def pull_salvage(exc):
        lost = elastic.unwrap_device_loss(exc)
        idx = elastic.device_index(lost) if lost is not None else None
        if not elastic.enabled() or idx is None:
            raise exc
        elastic.health().mark_lost(idx)
        at_iter, state = checkpoint_salvage(cfg, init_state)
        done = int(get_done())
        todo = done - at_iter
        site = f"{site_prefix}_result_pull"
        obs.emit("degraded", site=site, ladder="cpu",
                 salvage_iter=at_iter, rerun_iters=todo,
                 error=f"{type(exc).__name__}: {exc}"[:200])
        obs.counter("degraded")
        metrics.record(event="degraded", site=site, ladder="cpu",
                       salvage_iter=at_iter, rerun_iters=todo)
        with obs.span(f"{site_prefix}.cpu_salvage", at_iter=at_iter,
                      todo=todo):
            dtype = init_state().dtype
            if todo <= 0:
                return np.asarray(state).astype(dtype)
            rerun_cfg = dataclasses.replace(
                cfg, iterations=todo, checkpoint_every=0, checkpoint_dir=None
            )
            rd2, _iters, _delta, _invoke = cpu_exec(rerun_cfg, state)
            return rx.device_get(
                rd2, site=site, metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            )

    return pull_salvage


def run_single_chip_fixpoint(
    cfg,
    metrics: MetricsRecorder,
    *,
    site_prefix: str,
    init_state: Callable[[], np.ndarray],
    make_runner: Callable,
    build_operands: Callable[[], tuple],
    call: Callable,
):
    """The whole single-chip host driver for a fixpoint workload, shared
    wiring in one place (PPR / HITS / connected components run through
    this; models/pagerank.py keeps its own driver for resume +
    spark_exact): guarded delta-sync fetch (own site, so a transient
    failure never re-dispatches into the donated carry), checkpoint-pull
    and result-pull sites, the CPU re-lowering rung, the elastic salvage
    rung (:func:`make_cpu_salvage`), and the segment loop.

    - ``build_operands()`` builds the non-carry device operands (graph
      layout, teleport matrix, ...) from HOST state for the *current*
      default device — called once up front and again inside the CPU
      rungs, so recovery never reads a dead device buffer;
    - ``call(runner, operands, carry)`` invokes the compiled runner with
      the workload's argument order, returning ``(carry, iters, delta)``
      un-synced.

    Returns ``(state_np, iterations, last_delta)``.
    """
    import jax

    state0 = init_state()
    state_dtype = state0.dtype
    with Timer() as t_put:
        operands = build_operands()
    metrics.record(event="put_graph", preprocess_secs=t_put.elapsed)
    state_dev = jax.device_put(state0)

    def make_invoke(ops_tuple):
        def invoke(runner, rd):
            with obs.span(f"{site_prefix}.dispatch"):
                rd, iters, delta = call(runner, ops_tuple, rd)
            with obs.span(f"{site_prefix}.delta_sync"):
                delta = float(rx.device_get(
                    delta, site=f"{site_prefix}_delta_sync", metrics=metrics,
                    checkpoint_dir=cfg.checkpoint_dir,
                ))
            return rd, iters, delta

        return invoke

    def extract_np(rd):
        with obs.span(f"{site_prefix}.ckpt_pull"):
            return rx.device_get(
                rd, site=f"{site_prefix}_ckpt_pull", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
            )

    def make_cpu_invoke(seg_cfg):
        runner = make_runner(seg_cfg)

        def cpu_invoke(rd):
            with obs.span(f"{site_prefix}.cpu_degrade"):
                cpu = jax.devices("cpu")[0]
                with jax.default_device(cpu):
                    cpu_ops = build_operands()
                    rd_cpu = jax.device_put(rx.device_get(
                        rd, site=f"{site_prefix}_cpu_pull"
                    ), cpu)
                    out, iters, delta = call(runner, cpu_ops, rd_cpu)
                    delta = float(delta)
            return out, iters, delta

        return cpu_invoke

    def cpu_salvage_exec(rerun_cfg, state_np):
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            cpu_ops = build_operands()
            rd_cpu = jax.device_put(
                np.asarray(state_np).astype(state_dtype), cpu
            )
            runner = make_runner(rerun_cfg)
            rd2, iters, delta = call(runner, cpu_ops, rd_cpu)
            delta = float(delta)
        return rd2, int(iters), delta, make_invoke(cpu_ops)

    state_dev, done, last_delta = run_segments(
        cfg, metrics, state_dev, 0,
        make_runner=make_runner,
        invoke=make_invoke(operands),
        extract_np=extract_np,
        make_cpu_invoke=make_cpu_invoke,
        elastic_rebuild=make_cpu_salvage(
            cfg, metrics, site_prefix=site_prefix, init_state=init_state,
            cpu_exec=cpu_salvage_exec, make_runner=make_runner,
            extract_np=extract_np,
        ),
        site_prefix=site_prefix,
    )
    with obs.span(f"{site_prefix}.result_pull"):
        state_np = rx.device_get(
            state_dev, site=f"{site_prefix}_result_pull", metrics=metrics,
            checkpoint_dir=cfg.checkpoint_dir,
            fallbacks=[(None, make_pull_salvage(
                cfg, metrics, site_prefix=site_prefix,
                init_state=init_state, cpu_exec=cpu_salvage_exec,
                get_done=lambda: done,
            ))],
        )
    return state_np, done, last_delta


class ElasticResult(NamedTuple):
    """What an elastic shrink handler returns after it rebuilt the mesh
    and ran the failed segment on the survivors: the segment outputs plus
    the replacement callables every *subsequent* segment must use."""

    ranks_dev: object
    iters: int  # effective NEW iterations relative to the pre-failure count
    delta: float
    make_runner: Callable
    invoke: Callable
    extract_np: Callable
    metrics_extra: dict  # merged into per-segment metrics (e.g. devices=N)


def run_segments(
    cfg,
    metrics: MetricsRecorder,
    ranks_dev,
    start_iter: int,
    *,
    make_runner: Callable,
    invoke: Callable,
    extract_np: Callable[[object], np.ndarray],
    segments_allowed: bool = True,
    extra_metrics: dict | None = None,
    make_cpu_invoke: Callable | None = None,
    elastic_rebuild: Callable | None = None,
    site_prefix: str = "pagerank",
):
    """Run ``cfg.iterations`` in checkpoint-sized compiled segments.

    - ``make_runner(seg_cfg)`` compiles the loop for one segment length;
      called at most twice (body segments + tail) thanks to caching here.
    - ``invoke(runner, ranks_dev)`` executes and returns
      ``(ranks_dev, iters_done, delta)`` with a completed host sync.
    - ``extract_np(ranks_dev)`` yields the checkpointable state array.
    - ``make_cpu_invoke(seg_cfg)``, when given, builds the degradation-
      ladder rung: a ``ranks_dev -> (ranks_dev, iters, delta)`` callable
      re-lowered for the CPU backend, run when on-device retries are
      exhausted or the device is lost.
    - ``elastic_rebuild(exc, ranks_dev, done, seg_cfg)``, when given, is
      the mesh-shrink rung for sharded runners (and the single-chip
      checkpoint-salvage rung — models/pagerank.py): on device loss it
      salvages the current state, rebuilds over the survivors,
      repartitions, runs the failed segment there, and returns an
      :class:`ElasticResult` whose callables replace this loop's (the
      runner cache is dropped — every compiled program was welded to the
      dead mesh).  It raises when it does not apply (not a device loss,
      elastic disabled, nothing survives), passing the ladder on.

    Each segment dispatch runs under the resilience executor: transient
    failures retry with backoff (the runner is functional, so re-invoking
    with the same ranks cannot double-apply iterations), persistent ones
    walk the rungs above, and exhaustion raises ``ResilienceExhausted``
    carrying the latest checkpoint under ``cfg.checkpoint_dir``.  The
    single-chip runners *donate* their rank carry (ops/pagerank.py), so
    ``invoke`` must never let a post-dispatch sync failure reach this
    site's retry (which would re-dispatch into the consumed buffer):
    models/pagerank.py fetches the delta through its own guarded site
    (``pagerank_delta_sync``) whose retries re-pull against live OUTPUT
    buffers, and an exhausted inner fetch is non-transient here — it
    walks the rungs, and a rung that cannot read the consumed carry
    raises onward until ``ResilienceExhausted`` hands the caller the
    latest checkpoint.  This site's own transient failures (chaos fires
    at attempt start, before dispatch) still retry with the carry
    intact.

    A device loss surfacing inside the CHECKPOINT pull (the ISSUE 9
    carried-forward gap: the live carry died with the device, so
    ``extract_np`` cannot read it) walks the same ``elastic_rebuild``
    rung with a zero-iteration segment: the rung salvages the newest
    snapshot, rebuilds, re-runs only the uncommitted span, and the
    checkpoint is then written from the rebuilt state.

    Checkpoints are tagged with the segment's ``extra_metrics`` (the
    sharded runners put ``devices=N`` there), so a snapshot records which
    mesh shape wrote it — while staying readable across shrinks, because
    the payload is always the logical ``n`` ranks.

    Returns ``(ranks_dev, done, last_delta)``.
    """
    segment = (
        cfg.checkpoint_every
        if (cfg.checkpoint_every > 0 and cfg.tol == 0.0 and segments_allowed)
        else cfg.iterations - start_iter
    )
    # GRAFT_SYNC_DEADLINE_S guards *host syncs*, whose healthy duration is
    # bounded; a compiled segment's legitimate runtime scales with its
    # iteration count, so inheriting the sync deadline here would kill
    # healthy long segments.  The dispatch site gets its own knob
    # (GRAFT_STEP_DEADLINE_S, default 0 = no watchdog).
    policy = dataclasses.replace(
        rx.RetryPolicy.from_env(),
        deadline_s=float(os.environ.get("GRAFT_STEP_DEADLINE_S", 0.0)),
    )
    runners: dict[int, Callable] = {}
    cpu_invokes: dict[int, Callable] = {}
    done = start_iter
    last_delta = float("inf")

    def adopt(res: ElasticResult) -> None:
        # swap this loop onto the rebuilt execution context
        nonlocal make_runner, invoke, extract_np, extra_metrics
        make_runner, invoke, extract_np = (
            res.make_runner, res.invoke, res.extract_np
        )
        extra_metrics = {**(extra_metrics or {}), **res.metrics_extra}
        runners.clear()  # every cached program targeted the old mesh
        cpu_invokes.clear()

    while done < cfg.iterations:
        todo = min(segment, cfg.iterations - done)
        seg_cfg = dataclasses.replace(
            cfg, iterations=todo, checkpoint_every=0, checkpoint_dir=None
        )
        if todo not in runners:
            runners[todo] = make_runner(seg_cfg)
        rungs: list = []
        if elastic_rebuild is not None:
            def elastic_rung(exc, seg_cfg=seg_cfg, rd=ranks_dev):
                # salvage + shrink + rerun happen in the handler; here we
                # only swap this loop onto the rebuilt execution context
                res: ElasticResult = elastic_rebuild(exc, rd, done, seg_cfg)
                adopt(res)
                return res.ranks_dev, res.iters, res.delta

            rungs.append((None, elastic_rung))
        if make_cpu_invoke is not None:
            def cpu_rung(_exc, todo=todo, seg_cfg=seg_cfg, rd=ranks_dev):
                if todo not in cpu_invokes:
                    cpu_invokes[todo] = make_cpu_invoke(seg_cfg)
                return cpu_invokes[todo](rd)

            rungs.append(("cpu", cpu_rung))
        with Timer() as t, obs.span(f"{site_prefix}.segment",
                                    start=done, todo=todo):
            ranks_dev, iters, delta = rx.run_guarded(
                lambda r=runners[todo], rd=ranks_dev: invoke(r, rd),
                site=f"{site_prefix}_step", policy=policy, metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir, fallbacks=rungs,
            )
        done += int(iters)
        last_delta = float(delta)
        obs.histogram(f"{site_prefix}.segment_secs", t.elapsed)
        metrics.record(
            iter=done,
            l1_delta=last_delta,
            secs=t.elapsed,
            iters_per_sec=int(iters) / t.elapsed if t.elapsed > 0 else float("inf"),
            **(extra_metrics or {}),
        )
        if cfg.checkpoint_every > 0 and cfg.checkpoint_dir and done < cfg.iterations:
            with obs.span(f"{site_prefix}.checkpoint", iter=done):
                try:
                    payload = extract_np(ranks_dev)
                except Exception as exc:
                    # Device loss first surfacing at the checkpoint pull
                    # (ISSUE 9 carried-forward gap): the live carry is
                    # gone, so walk the same elastic salvage rung the
                    # segment dispatch uses — zero-iteration segment: the
                    # rung re-runs only the uncommitted span from the
                    # newest snapshot — and snapshot the rebuilt state.
                    if (elastic_rebuild is None
                            or elastic.unwrap_device_loss(exc) is None):
                        raise
                    res = elastic_rebuild(
                        exc, ranks_dev,
                        done, dataclasses.replace(seg_cfg, iterations=0),
                    )
                    adopt(res)
                    ranks_dev = res.ranks_dev
                    done += int(res.iters)  # 0 when salvage was exact
                    payload = extract_np(ranks_dev)
                path = ckpt.save_checkpoint(
                    cfg.checkpoint_dir, done,
                    {"ranks": payload}, cfg.config_hash(),
                    extra=dict(extra_metrics or {}),
                )
            metrics.record(event="checkpoint", path=path, iter=done)
        if cfg.tol > 0.0:
            # the while_loop runner handled tolerance in-program; one
            # segment is the whole run
            break

    metrics.scalar("iterations", done)
    metrics.scalar("l1_delta", last_delta)
    return ranks_dev, done, last_delta
