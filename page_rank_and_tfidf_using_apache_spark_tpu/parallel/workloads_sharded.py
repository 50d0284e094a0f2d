"""Sharded graph workloads on owned slices (ISSUE 15 satellite): HITS and
connected components reuse the ``owned`` partition machinery through the
dataflow layer; batched personalized PageRank shards its QUERY axis.

HITS and CC both pull along BOTH edge directions (a reverse combine the
dst-sorted layout cannot serve), so each builds TWO boundary-exchange
layouts over ONE shared node ownership: the forward layout on the graph
itself and the reverse layout on the transposed graph under the SAME tail
bounds (``ops.boundary.plan_owned(bounds=...)``) — every node's state
lives in exactly one owned slice, and each direction exchanges only its
own cut.  Neither workload peels a hub head (``max_head=0``): CC's
combine is ``min`` (no psum can serve a replicated head) and HITS's
normalization already costs two ``pmax`` per step, so the heads would buy
nothing — per-step collectives are the two boundary butterflies plus the
norm/convergence reductions, all O(boundary), never O(n).

PPR is different: the graph is small enough to replicate (it is the
single-chip workload's operand), and the SCALE axis is the query batch —
so ``run_ppr_sharded`` shards the ``[B, n]`` teleport/rank matrices along
the mesh's data axis and runs the UNCHANGED ``dataflow.ppr`` batch
runner under GSPMD (the registered ``dataflow_ppr_batch`` contract covers
the program; sharding is an input property, not a new program).

Equivalence bars (tests/test_owned.py): HITS hubs/authorities and CC
labels match their single-chip oracles at 1e-6 (CC exactly); PPR matches
the single-chip batch runner at 1e-9 in f64.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import (
    components as cc,
)
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import fixpoint as dataflow
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import hits as hits_mod
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import ppr as ppr_mod
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.partition import (
    OwnedArray,
)
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import Graph
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import (
    put_graph_for,
)
from page_rank_and_tfidf_using_apache_spark_tpu.ops import boundary as ob
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import collectives as coll
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
    DATA_AXIS,
    NODES_AXIS,
    make_mesh,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    ComponentsConfig,
    HitsConfig,
    PageRankConfig,
    ensure_dtype_support,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
    MetricsRecorder,
    Timer,
)


def transpose_graph(graph: Graph) -> Graph:
    """The reversed edge set as a dst-sorted :class:`Graph` over the SAME
    compacted node ids — the reverse-direction pull of HITS/CC becomes a
    forward pull on this view.  (``from_edges`` would re-compact ids and
    could drop edgeless nodes; this keeps the node space aligned.)"""
    order = np.lexsort((graph.dst, graph.src))  # new (dst, src) = (src, dst)
    return Graph(
        n_nodes=graph.n_nodes,
        src=graph.dst[order].astype(np.int32),
        dst=graph.src[order].astype(np.int32),
        out_degree=np.bincount(
            graph.dst, minlength=graph.n_nodes
        ).astype(np.int32),
        node_ids=graph.node_ids,
        weight=graph.weight[order] if graph.weight is not None else None,
    )


def build_owned_pair(
    graph: Graph, n_devices: int, dtype: str
) -> tuple[ob.OwnedShard, ob.OwnedShard]:
    """(forward, reverse) owned shards over ONE shared node ownership:
    the forward plan picks the tail bounds (headless — see module
    docstring), the reverse plan inherits them on the transposed graph."""
    tg = transpose_graph(graph)
    fwd_plan = ob.plan_owned(graph, n_devices, max_head=0)
    rev_plan = ob.plan_owned(
        tg, n_devices, max_head=0,
        head_ids=fwd_plan.head_ids, bounds=fwd_plan.bounds,
    )
    return (ob.build_owned_shard(graph, fwd_plan, dtype),
            ob.build_owned_shard(tg, rev_plan, dtype))


def _edge_args(shard: ob.OwnedShard):
    """The per-direction device operands of a headless owned exchange."""
    return (shard.tail_src_idx, shard.tail_dst, shard.tail_w, shard.out_idx)


def _device_put_pair(sf: ob.OwnedShard, sr: ob.OwnedShard, mesh: Mesh):
    esh = NamedSharding(mesh, P(mesh.axis_names[0], None))
    return tuple(
        jax.device_put(a, esh) for a in (*_edge_args(sf), *_edge_args(sr))
    )


# ------------------------------------------------------------------- HITS


def make_hits_sharded_runner(sf: ob.OwnedShard, sr: ob.OwnedShard,
                             cfg: HitsConfig, mesh: Mesh):
    """Compile the owned HITS fixpoint: ``run((hub, auth), fwd..., rev...)
    -> ((hub, auth), iters, delta)`` — per step, one boundary butterfly
    per direction, one ``pmax`` per normalization, and the convergence
    psum; every collective O(boundary)/O(1), never O(n)."""
    axis = mesh.axis_names[0]
    block = sf.block

    def step(ha, fsrc, fdst, fw, fout, rsrc, rdst, rw, rout):
        hub, auth = ha
        bt = coll.butterfly_all_gather(
            ob.pack_boundary(hub, fout[0]), axis
        )
        lk = ob.boundary_lookup(hub, bt, jnp.zeros(sf.h_pad, hub.dtype))
        auth_raw = jax.ops.segment_sum(
            lk[fsrc[0]] * fw[0], fdst[0],
            num_segments=block, indices_are_sorted=True,
        )
        amax = coll.pmax(jnp.max(auth_raw), axis)
        auth_n = auth_raw / jnp.maximum(amax, 1e-30)
        bt2 = coll.butterfly_all_gather(
            ob.pack_boundary(auth_n, rout[0]), axis
        )
        lk2 = ob.boundary_lookup(auth_n, bt2, jnp.zeros(sr.h_pad, hub.dtype))
        hub_raw = jax.ops.segment_sum(
            lk2[rsrc[0]] * rw[0], rdst[0],
            num_segments=block, indices_are_sorted=True,
        )
        hmax = coll.pmax(jnp.max(hub_raw), axis)
        hub_n = hub_raw / jnp.maximum(hmax, 1e-30)
        return (hub_n, auth_n)

    def loop(ha0, *arrays):
        return dataflow.iterate(
            lambda ha: step(ha, *arrays), ha0,
            iterations=cfg.iterations, tol=cfg.tol,
            delta_fn=lambda new, old: coll.psum(
                jnp.sum(jnp.abs(new[0] - old[0])), axis
            ),
        )

    e = P(axis, None)
    state = (P(axis), P(axis))
    mapped = shard_map(
        loop, mesh=mesh,
        in_specs=(state, e, e, e, e, e, e, e, e),
        out_specs=(state, P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def run_hits_sharded(
    graph: Graph,
    cfg: HitsConfig = HitsConfig(),
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    metrics: MetricsRecorder | None = None,
) -> hits_mod.HitsResult:
    """Sharded counterpart of ``dataflow.hits.run_hits`` on owned slices —
    same networkx-parity iteration, hubs/authorities each held only by
    their owner, pinned against the single-chip oracle at 1e-6."""
    ensure_dtype_support(cfg.dtype)
    metrics = metrics or MetricsRecorder()
    if mesh is None:
        mesh = make_mesh(n_devices, NODES_AXIS)
    d = int(mesh.devices.size)
    n = graph.n_nodes
    if n == 0:
        z = np.zeros(0, cfg.dtype)
        return hits_mod.HitsResult(z, z, 0, 0.0, metrics)

    with Timer() as t_part:
        sf, sr = build_owned_pair(graph, d, cfg.dtype)
        dev = _device_put_pair(sf, sr, mesh)
    metrics.record(event="partition", strategy="owned", workload="hits",
                   devices=d, block=sf.block,
                   pad_frac=round(
                       (d * sf.e_dev - graph.n_edges)
                       / max(d * sf.e_dev, 1), 4),
                   secs=t_part.elapsed)
    tail_sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    layout = OwnedArray.from_shard(
        sf, tail_sharding=tail_sh, head_sharding=NamedSharding(mesh, P())
    )
    init = np.full(n, 1.0 / n, cfg.dtype)
    hub0 = layout.put(init, cfg.dtype)
    auth0 = layout.put(init, cfg.dtype)

    runner = make_hits_sharded_runner(sf, sr, cfg, mesh)
    with obs.span("hits.sharded", devices=d, n=n):
        (hub_d, auth_d), iters, delta = runner((hub0.tail, auth0.tail), *dev)
        delta = float(delta)  # scalar fetch is the only reliable device sync
        with obs.span("hits.result_pull"):
            hubs = layout.with_value(hub_d, hub0.head).pull(
                site="hits_result_pull", metrics=metrics,
            )
            auths = layout.with_value(auth_d, auth0.head).pull(
                site="hits_result_pull", metrics=metrics,
            )
    hs, as_ = float(hubs.sum()), float(auths.sum())
    hubs = hubs / hs if hs > 0 else hubs
    auths = auths / as_ if as_ > 0 else auths
    metrics.scalar("iterations", int(iters))
    return hits_mod.HitsResult(hubs=hubs, authorities=auths,
                               iterations=int(iters), l1_delta=delta,
                               metrics=metrics)


# ------------------------------------------------- connected components


def make_components_sharded_runner(sf: ob.OwnedShard, sr: ob.OwnedShard,
                                   cfg: ComponentsConfig, mesh: Mesh):
    """Compile the owned min-label fixpoint: both directions' boundary
    labels arrive through the butterflies, the combine is a sorted
    ``segment_min`` per direction, and the changed-label count converges
    through one psum — the padding sentinel is the int32 max, so pads are
    ``min``-neutral by value instead of by mask."""
    import jax.ops  # noqa: F401  (segment_min lives under jax.ops)

    axis = mesh.axis_names[0]
    block = sf.block
    big = jnp.iinfo(jnp.int32).max

    def step(labels, fsrc, fdst, rsrc, rdst, fout, rout):
        bt = coll.butterfly_all_gather(
            ob.pack_boundary(labels, fout[0]), axis
        )
        lk = ob.boundary_lookup(
            labels, bt, jnp.full(sf.h_pad, big, labels.dtype), fill=big
        )
        incoming = jax.ops.segment_min(
            lk[fsrc[0]], fdst[0],
            num_segments=block, indices_are_sorted=True,
        )
        bt2 = coll.butterfly_all_gather(
            ob.pack_boundary(labels, rout[0]), axis
        )
        lk2 = ob.boundary_lookup(
            labels, bt2, jnp.full(sr.h_pad, big, labels.dtype), fill=big
        )
        outgoing = jax.ops.segment_min(
            lk2[rsrc[0]], rdst[0],
            num_segments=block, indices_are_sorted=True,
        )
        return jnp.minimum(labels, jnp.minimum(incoming, outgoing))

    def loop(labels0, *arrays):
        return dataflow.iterate(
            lambda lab: step(lab, *arrays), labels0,
            iterations=cfg.iterations, tol=cfg.tol,
            delta_fn=lambda new, old: coll.psum(
                jnp.sum((new != old).astype(jnp.float32)), axis
            ),
        )

    e = P(axis, None)
    mapped = shard_map(
        loop, mesh=mesh,
        in_specs=(P(axis), e, e, e, e, e, e),
        out_specs=(P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def run_components_sharded(
    graph: Graph,
    cfg: ComponentsConfig = ComponentsConfig(),
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    metrics: MetricsRecorder | None = None,
) -> cc.ComponentsResult:
    """Sharded counterpart of ``dataflow.components.run_components`` on
    owned label slices — labels match the single-chip run EXACTLY (min is
    order-free), so the oracle pin is equality, not a tolerance."""
    metrics = metrics or MetricsRecorder()
    if mesh is None:
        mesh = make_mesh(n_devices, NODES_AXIS)
    d = int(mesh.devices.size)
    n = graph.n_nodes
    if n == 0:
        return cc.ComponentsResult(np.zeros(0, np.int32), 0, 0, metrics)

    with Timer() as t_part:
        sf, sr = build_owned_pair(graph, d, "float32")
        dev = _device_put_pair(sf, sr, mesh)
    metrics.record(event="partition", strategy="owned", workload="cc",
                   devices=d, block=sf.block, secs=t_part.elapsed)
    tail_sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    layout = OwnedArray.from_shard(
        sf, tail_sharding=tail_sh, head_sharding=NamedSharding(mesh, P())
    )
    lab0 = layout.put(np.arange(n, dtype=np.int32), np.int32)

    # the min-combine reads labels, never edge weights: drop the weight
    # coefficient arrays from the operand tuple
    fsrc, fdst, _fw, fout = dev[0], dev[1], dev[2], dev[3]
    rsrc, rdst, _rw, rout = dev[4], dev[5], dev[6], dev[7]
    runner = make_components_sharded_runner(sf, sr, cfg, mesh)
    with obs.span("cc.sharded", devices=d, n=n):
        lab_d, iters, changed = runner(
            lab0.tail, fsrc, fdst, rsrc, rdst, fout, rout
        )
        changed = float(changed)  # scalar fetch syncs the dispatch
        with obs.span("cc.result_pull"):
            labels = layout.with_value(lab_d, lab0.head).pull(
                site="cc_result_pull", metrics=metrics,
            )
    converged = changed <= cfg.tol
    if not converged:
        metrics.record(event="cc_not_converged", iterations=int(iters),
                       still_changing=int(changed))
    n_components = int(np.unique(labels).shape[0])
    metrics.scalar("n_components", n_components)
    return cc.ComponentsResult(labels=labels.astype(np.int32),
                               n_components=n_components,
                               iterations=int(iters), metrics=metrics,
                               converged=converged)


# --------------------------------------------- PPR: sharded query axis


def run_ppr_sharded(
    graph: Graph,
    cfg: PageRankConfig,
    queries,
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    metrics: MetricsRecorder | None = None,
) -> ppr_mod.PprBatchResult:
    """Batched personalized PageRank with the QUERY axis sharded: the
    ``[B, n]`` teleport matrix and rank carry split across the mesh's
    data axis (B padded to a device multiple by repeating the last
    query), the graph operands replicated, and the UNCHANGED
    ``dataflow.ppr`` batch runner partitioned by GSPMD — queries are
    embarrassingly parallel, so the only cross-chip traffic is the
    worst-query convergence max."""
    ensure_dtype_support(cfg.dtype)
    if cfg.personalize is not None:
        raise ValueError("run_ppr_sharded takes queries=, not cfg.personalize")
    if not queries:
        raise ValueError("need at least one personalization query")
    metrics = metrics or MetricsRecorder()
    if mesh is None:
        mesh = make_mesh(n_devices, DATA_AXIS)
    axis = mesh.axis_names[0]
    d = int(mesh.devices.size)
    n = graph.n_nodes
    b = len(queries)
    b_pad = -(-b // d) * d
    queries_p = list(queries) + [queries[-1]] * (b_pad - b)
    metrics.record(event="ppr_sharded", queries=b, batch_pad=b_pad,
                   devices=d, nodes=n)

    batch_sh = NamedSharding(mesh, P(axis, None))
    repl = NamedSharding(mesh, P())
    e_dev = jax.device_put(
        ppr_mod.restart_batch(graph, cfg, queries_p).astype(cfg.dtype),
        batch_sh,
    )
    dg = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, repl), put_graph_for(graph, cfg)
    )
    ranks0 = jax.device_put(
        np.broadcast_to(
            np.asarray(ppr_mod.ops.init_ranks(n, cfg)), (b_pad, n)
        ).copy(),
        batch_sh,
    )
    runner = ppr_mod.make_ppr_batch_runner(n, cfg)
    with obs.span("ppr.sharded", devices=d, queries=b):
        rd, iters, delta = runner(dg, ranks0, e_dev)
        delta = float(delta)  # scalar fetch syncs the dispatch
        with obs.span("ppr.result_pull"):
            ranks = rx.device_get(
                rd, site="ppr_result_pull", metrics=metrics,
            )
    return ppr_mod.PprBatchResult(ranks=np.asarray(ranks)[:b],
                                  iterations=int(iters), l1_delta=delta,
                                  metrics=metrics)
