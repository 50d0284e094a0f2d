"""Multi-chip PageRank: the graph partitioned once over a mesh, every
iteration one ``jit`` + ``shard_map`` program with its collectives inside.

Reference counterpart (SURVEY.md §2.2 R1/R2, BASELINE.json:9): Spark's
hash-partitioned RDDs and the shuffle that re-co-partitions
``links.join(ranks)`` every iteration.  Here the graph is partitioned
**once** on host, laid out per device, and every iteration's cross-chip
combine is XLA collectives over ICI inside the loop body: no host round
trip between iterations, no repartitioning.

Two layouts, chosen by :func:`auto_select_strategy` from what it can
observe (replicated state against per-chip memory, the mesh size):

- ``hybrid`` (replicated ranks): every chip holds the whole rank vector.
  The dst-sorted arcs are dealt in equal contiguous slices (a node whose
  in-arcs span devices has a segment on each), and each slice carries its
  CSR pointers, so each shard's segment sum is the one-chip ``segment``
  path's scan, with no scatter.  When the graph is unweighted and the
  power-law head of :func:`ops.plan_hybrid_head` covers at least a quarter
  of the arcs (:func:`replicated_head`), the head's in-arcs are cut out
  as fixed-width dense rows (ops.pagerank.HybridLayout, *Sparse
  Allreduce*'s dense-head/sparse-tail split, PAPERS.md), dealt to devices
  in equal row blocks and summed row by row; otherwise the head is empty
  and the edge weights ride in the tail's ``valid`` coefficients.  Each
  chip's full-size partial combines in ONE ``psum`` a step; dangling mass
  and the convergence delta need no collective.
- ``owned`` (rank slices): each shard owns only its tail block's rank
  slice, a small combined-degree hub head is the one replicated
  mini-state, and cross-shard reads move through fixed-width boundary
  buffers (a log2(d)-round ``ppermute`` butterfly) plus one [H_pad+2]
  ``psum`` that also carries the dangling mass and the one-step-lagged
  delta (``ops/boundary.py`` has the anatomy).  State per chip is
  O(n/d + H): the layout for a graph whose replicated state does not fit.
  The butterfly needs a power-of-two mesh.

Both reduce each shard's arcs through the sorted segment path
(``spmv_impl='segment'``, the only impl the runner accepts).
:class:`ShardedPageRank` keeps the partition, the device arrays and the
compiled runners resident across jobs.  ``spark_exact`` mode is
single-chip-only (it exists for parity testing, not scale) — requesting
it sharded raises.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import fixpoint as dataflow
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.partition import (
    OwnedArray,
)
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import Graph
from page_rank_and_tfidf_using_apache_spark_tpu.models import driver
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import PageRankResult
from page_rank_and_tfidf_using_apache_spark_tpu.ops import boundary as ob
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import collectives as coll
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
    NODES_AXIS,
    make_mesh,
    rebuild_mesh,
)
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import elastic
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    TUNABLE_DEFAULTS,
    DanglingMode,
    PageRankConfig,
    RankInit,
    ensure_dtype_support,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder, Timer


# Stand-in per-device budget for the CPU backend, which has no device
# memory limit to read; on a TPU the chip's own limit is used.
CPU_HBM_BYTES = 8 << 30


def device_hbm_bytes() -> int:
    """Per-device memory budget of the default backend: the TPU's
    ``bytes_limit``, else :data:`CPU_HBM_BYTES`."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return int(dev.memory_stats()["bytes_limit"])
    return CPU_HBM_BYTES


def replicated_state_bytes(
    n_nodes: int, n_edges: int, n_devices: int, dtype: str = "float32"
) -> int:
    """The per-chip footprint of the replicated-rank layout: ~6 node
    vectors live at once (ranks, new ranks, contribs, inv_outdeg,
    dangling, e) plus this chip's edge slice (src/dst int32 + the
    coefficient mask).  One model shared by :func:`auto_select_strategy`
    and the replicated-wall assertions in bench.py/__graft_entry__.py —
    the selector and the acceptance harnesses must not drift apart."""
    item = np.dtype(dtype).itemsize
    node_state = 6 * n_nodes * item
    edge_state = int(n_edges / max(n_devices, 1) * (8 + item))
    return int(node_state + edge_state)


def auto_select_strategy(
    graph: Graph,
    n_devices: int,
    *,
    dtype: str = "float32",
    hbm_bytes: int | None = None,
) -> str:
    """Pick a layout by per-chip memory footprint: ``hybrid`` while the
    replicated node state plus this chip's edge slice fits in half the
    per-device memory (``hbm_bytes``, default :func:`device_hbm_bytes`),
    else ``owned`` (1/D node state, sparse boundary exchange), whose
    butterfly needs a power-of-two mesh: on any other mesh a graph that
    does not fit replicated raises ``ValueError``."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    replicated = replicated_state_bytes(
        graph.n_nodes, graph.n_edges, n_devices, dtype
    )
    # ONE strategy_decision event carries the measured inputs, so
    # trace_report can show why a run picked its layout.  No-op outside
    # a traced run.
    inputs = dict(
        devices=n_devices,
        nodes=graph.n_nodes, edges=graph.n_edges,
        replicated_state_bytes=replicated,
        hbm_bytes=int(hbm_bytes),
    )
    fits = replicated <= hbm_bytes / 2
    if not fits and (n_devices < 1 or n_devices & (n_devices - 1)):
        raise ValueError(
            f"replicated state of {replicated} B a chip exceeds half of "
            f"{int(hbm_bytes)} B, and the owned layout needs a power-of-two "
            f"mesh, not {n_devices} devices"
        )
    chosen = "hybrid" if fits else "owned"
    obs.emit("strategy_decision", chosen=chosen,
             reason=f"replicated node state {'fits in' if fits else 'exceeds'}"
                    " half the per-chip HBM budget", **inputs)
    return chosen


def replicated_head(
    graph: Graph,
    *,
    coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    row_width: int = TUNABLE_DEFAULTS["head_row_width"],
) -> tuple[np.ndarray, int]:
    """The dense head of the replicated layout: ``(head ids, row width)``
    of :func:`ops.plan_hybrid_head` when the graph is unweighted and that
    head covers at least a quarter of the arcs (the dense rows then carry
    the hot in-degree mass), else an empty head (a weighted graph's
    weights ride in the tail's coefficients, which dense rows lack)."""
    indeg = np.diff(graph.csr_indptr())
    head_ids, w = ops.plan_hybrid_head(
        indeg, graph.n_edges, coverage=coverage, row_width=row_width
    )
    head_edges = int(indeg[head_ids].sum()) if head_ids.size else 0
    if graph.weight is None and head_ids.size and head_edges >= graph.n_edges // 4:
        return head_ids, w
    return head_ids[:0], w


class PartitionPlan(NamedTuple):
    """Pure *planning* output of a layout: padded sizes and the
    padding-waste fraction, computed without materializing a single
    per-device array (and without any device dispatch).

    This is the introspection surface the graftlint tier-3 pad_frac
    analyzer gates on (``analysis/cost.py``): ``partition_graph`` builds its
    arrays FROM this plan, so the static number the linter budgets is — by
    construction, not by convention — the same ``pad_frac`` a real
    multichip run logs in its ``partition`` event."""

    strategy: str
    n: int  # real node count
    n_pad: int  # D * block
    block: int  # nodes per device block
    e_dev: int  # tail edge slots per device (padded width)
    pad_frac: float  # fraction of padded edge slots (load-imbalance gauge)
    # 'hybrid' only: (head node count, dense row width, total dense rows,
    # dense rows per device) — the head side of the slot accounting; a
    # count of 0 is the empty head
    head: tuple[int, int, int, int] | None = None
    # 'owned' only: the full boundary-exchange plan (ops.boundary.OwnedPlan
    # — head set, tail bounds, boundary sets, pad + comm accounting);
    # partition_graph materializes exactly it
    owned: ob.OwnedPlan | None = None
    # Array entries each device sends per iteration under this plan (the
    # static per-step comm footprint — ICI bytes = entries * itemsize);
    # published with the partition event and gauged by _ShardedExec so
    # trace_diff can regress it across rounds.
    comm_entries_per_step: int | None = None


def _psum_entries(d: int, n_pad: int) -> int:
    """Per-step comm footprint of the replicated layout, in array entries
    sent per device per iteration: one ring-scheduled [n_pad] psum (~2
    passes).  ``owned`` moves only its padded boundary buffers plus the
    head psum (:func:`ops.boundary.comm_entries_per_step`)."""
    return 2 * n_pad * (d - 1) // d if d > 1 else 0


def _publish_plan(plan: PartitionPlan, n_devices: int) -> PartitionPlan:
    """Log the chosen partition plan (strategy + the numbers that drove
    it) as ONE obs event, so a trace explains the layout a run executed
    with (trace_report's strategy section).  No-op outside a traced run —
    the tier-3 lint calls plan_partition freely."""
    ow = plan.owned
    plan = plan._replace(
        comm_entries_per_step=(
            ow.comm_entries_per_step() if ow is not None
            else _psum_entries(n_devices, plan.n_pad)
        )
    )
    obs.emit(
        "partition_plan", strategy=plan.strategy, devices=n_devices,
        n=plan.n, n_pad=plan.n_pad, block=plan.block, e_dev=plan.e_dev,
        pad_frac=round(float(plan.pad_frac), 6),
        head=(list(plan.head) if plan.head is not None else None),
        comm_entries_per_step=plan.comm_entries_per_step,
        **(
            dict(
                owned_head=ow.h, owned_h_pad=ow.h_pad, owned_b_pad=ow.b_pad,
                boundary_total=int(ow.boundary_counts.sum()),
                boundary_pad_frac=round(float(ow.boundary_pad_frac), 6),
            )
            if ow is not None else {}
        ),
    )
    return plan


def _size_bucket(x: int) -> int:
    """``x`` rounded up to a multiple of ``2**(x.bit_length() - 11)``: at
    most 1/1024 more.  The replicated layout pads its per-device widths
    to it, so graphs a few vertices and arcs apart (two draws of one
    generator) get one set of shapes, hence one compiled program and one
    cache entry, and an edge slice of a million or more comes in whole
    1024-element tiles: the TPU compiler lowers the sharded step into a
    different program at some slice lengths just short of a tile (PERF.md
    section 6)."""
    q = 1 << max(0, int(x).bit_length() - 11)
    return -(-int(x) // q) * q


def plan_partition(
    graph: Graph,
    n_devices: int,
    *,
    strategy: str = "hybrid",
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
    owned_max_head: int = TUNABLE_DEFAULTS["owned_max_head"],
) -> PartitionPlan:
    """Plan a partition without building it: padded widths and
    ``pad_frac`` only — O(E) host work, no per-device arrays, no device
    traffic.  ``partition_graph`` materializes exactly this plan."""
    d = n_devices
    n = graph.n_nodes
    e = graph.n_edges

    if strategy == "owned":
        # The whole boundary-exchange plan lives in ops.boundary (head
        # set, min-max tail bounds, per-owner boundary sets, pad + comm
        # accounting); this wrapper only adapts it to the PartitionPlan
        # introspection surface the tier-3 pad gauge budgets.
        op = ob.plan_owned(graph, d, coverage=head_coverage,
                           max_head=owned_max_head)
        return _publish_plan(
            PartitionPlan(strategy, n, op.n_pad, op.block, op.e_dev,
                          op.pad_frac, owned=op),
            d,
        )
    if strategy != "hybrid":
        raise ValueError(
            f"unknown shard strategy {strategy!r}; use 'hybrid' or 'owned'"
        )

    # Replicated-state layout: head rows and tail edges both split at
    # row/edge granularity, so the only padding is the dense rows'
    # sentinel slots plus two ceil remainders.  pad_frac counts ALL
    # dispatched slots (head row slots + tail edge slots) against the
    # real edge count.
    block = _size_bucket(max(1, math.ceil(n / d)))
    indeg = np.diff(graph.csr_indptr())
    head_ids, w = replicated_head(
        graph, coverage=head_coverage, row_width=head_row_width
    )
    head_deg = indeg[head_ids]
    rows = int((-(-head_deg // w)).sum()) if head_ids.size else 0
    rows_dev = _size_bucket(math.ceil(rows / d)) if rows else 0
    e_tail = e - int(head_deg.sum())
    e_dev = _size_bucket(max(1, math.ceil(e_tail / d)))
    slots = d * (e_dev + rows_dev * w)
    pad_frac = (slots - e) / max(slots, 1)
    return _publish_plan(
        PartitionPlan(strategy, n, block * d, block, e_dev, pad_frac,
                      head=(int(head_ids.size), int(w), rows, rows_dev)),
        d,
    )


class ShardedGraph(NamedTuple):
    """Host-side partitioned graph layout, ready for device_put.

    Global node ids are padded slots (the pad sits at the end, so the
    logical ranks are the first ``n``).  ``valid`` masks the per-device
    padding and carries the edge weight of a weighted graph.
    """

    strategy: str
    n: int  # real node count
    n_pad: int  # D * block
    block: int  # nodes per device block
    src: np.ndarray  # int32 [D, E_dev]
    dst: np.ndarray  # int32 [D, E_dev], sorted per device
    valid: np.ndarray  # f [D, E_dev]
    inv_outdeg: np.ndarray  # f [n_pad]
    dangling: np.ndarray  # f [n_pad] (padding rows are NOT dangling: 0)
    pad_frac: float  # fraction of padded edge slots (load-imbalance gauge)
    local_indptr: np.ndarray  # int32 [D, n_pad+1]: per-device CSR row
    # pointers into that device's (sorted) tail slice — the scatter-free
    # segment sum's pointers (host memory cost D*n_pad ints; sharded on
    # device); (D, 1) zeros under 'owned'
    # 'hybrid': this device's slice of the dense head rows.  Sentinel
    # source id n_pad reads the zero slot of the step's extended weight
    # vector; all-sentinel padding rows (the whole of an empty head)
    # add 0.0 to node 0.
    head_src: np.ndarray | None = None  # int32 [D, R_dev, W]
    head_node: np.ndarray | None = None  # int32 [D, R_dev] global dst ids
    # 'owned' only: the materialized boundary-exchange layout (every
    # per-device array + the owned/replicated state vectors); the fields
    # above hold placeholder shapes for that strategy
    owned: ob.OwnedShard | None = None


def partition_graph(
    graph: Graph,
    n_devices: int,
    *,
    strategy: str = "hybrid",
    dtype: str = "float32",
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
    owned_max_head: int = TUNABLE_DEFAULTS["owned_max_head"],
) -> ShardedGraph:
    """Partition once on host (the reference partitions on every shuffle).

    Under ``hybrid`` each device's tail slice comes with its CSR pointers
    (``local_indptr``, D node-sized int32 arrays): each shard's segment
    sum reduces through them with no scatter.  The head decision
    (:func:`replicated_head`), the padded widths and ``pad_frac`` come
    from :func:`plan_partition` — the static plan the tier-3 cost linter
    budgets is the one this function materializes.

    A weighted graph plans an empty head, so every arc is in the tail and
    the ``valid`` slots carry its WEIGHT instead of 1.0 (padding stays 0):
    the per-edge product the step already computes becomes the weighted
    SpMV; ``inv_outdeg`` normalizes by out-strength.  The ``owned``
    layout threads weights through its own coefficient arrays."""
    plan = plan_partition(graph, n_devices, strategy=strategy,
                          head_coverage=head_coverage,
                          head_row_width=head_row_width,
                          owned_max_head=owned_max_head)
    d = n_devices
    n = graph.n_nodes
    block, n_pad, e_dev, pad_frac = (
        plan.block, plan.n_pad, plan.e_dev, plan.pad_frac
    )

    if strategy == "owned":
        shard = ob.build_owned_shard(graph, plan.owned, dtype)
        ph = np.zeros((d, 1), np.int32)  # legacy-field placeholders
        return ShardedGraph(
            strategy, n, plan.n_pad, plan.block,
            src=ph, dst=ph, valid=np.zeros((d, 1), dtype),
            inv_outdeg=shard.inv_tail, dangling=shard.dang_tail,
            pad_frac=pad_frac, local_indptr=ph, owned=shard,
        )

    head_k, w, rows, rows_dev = plan.head
    # head rows: remap the single-chip sentinel n -> n_pad (the zero slot
    # of the sharded step's extended weight vector); an empty head is one
    # all-sentinel placeholder row per device
    head_src = np.full((d, max(rows_dev, 1), max(w, 1)), n_pad, np.int32)
    head_node = np.zeros((d, max(rows_dev, 1)), np.int32)
    if head_k:
        # Materialize exactly the planned head/tail split: the global
        # hybrid layout (same plan_hybrid_head policy as the single-chip
        # impl), its dense rows dealt to devices in equal contiguous row
        # blocks.
        hl = ops.build_hybrid_layout(
            graph, coverage=head_coverage, row_width=head_row_width
        )
        assert hl.head_src.shape == (rows, w)  # plan IS the layout
        hnode_g = hl.head_ids[hl.head_row_node].astype(np.int32)
        for i in range(d):
            lo, hi = min(i * rows_dev, rows), min((i + 1) * rows_dev, rows)
            rows_i = head_src[i, : hi - lo, :w]
            rows_i[...] = hl.head_src[lo:hi]
            rows_i[rows_i == n] = n_pad
            head_node[i, : hi - lo] = hnode_g[lo:hi]
        tail_src, tail_dst, tail_ip = hl.tail_src, hl.tail_dst, hl.tail_indptr
    else:
        tail_src, tail_dst, tail_ip = graph.src, graph.dst, graph.csr_indptr()
    # tail: equal contiguous slices of the dst-sorted tail edge array
    # (pad src=0 dst=n_pad-1 masked by valid, which keeps each slice
    # sorted)
    e_tail = tail_src.shape[0]
    cap_t = e_dev * d
    src = np.zeros(cap_t, np.int32)
    dst = np.full(cap_t, n_pad - 1, np.int32)
    valid = np.zeros(cap_t, dtype)
    src[:e_tail] = tail_src
    dst[:e_tail] = tail_dst
    valid[:e_tail] = 1.0 if graph.weight is None else graph.weight
    inv = np.zeros(n_pad, dtype)
    inv[:n] = graph.inv_out_strength(dtype)
    dangling = np.zeros(n_pad, dtype)
    dangling[:n] = (graph.out_degree == 0).astype(dtype)
    local_indptr = _slice_indptr(tail_ip, n_pad, e_dev, d)
    return ShardedGraph(
        strategy, n, n_pad, block,
        src.reshape(d, e_dev), dst.reshape(d, e_dev),
        valid.reshape(d, e_dev), inv, dangling, pad_frac, local_indptr,
        head_src=head_src, head_node=head_node,
    )


def _slice_indptr(indptr: np.ndarray, n_pad: int, e_dev: int, d: int) -> np.ndarray:
    """Per-device CSR pointers of a dst-sorted edge array dealt to ``d``
    devices in equal contiguous slices of ``e_dev``: each slice's pointers
    are the global ones (``indptr``, padded to ``n_pad`` nodes) shifted by
    the slice start and clamped to the slice, so a node whose edges span
    devices has a segment on each, and padding slots past the last edge
    fall outside every segment (they are zero-valued anyway)."""
    n = indptr.shape[0] - 1
    g_ip = np.concatenate([indptr, np.full(n_pad - n, indptr[-1], np.int64)])
    offsets = (np.arange(d, dtype=np.int64) * e_dev)[:, None]
    return np.clip(g_ip[None, :] - offsets, 0, e_dev).astype(np.int32)


def _to_padded(sg: ShardedGraph, global_vec: np.ndarray, dtype: str) -> np.ndarray:
    out = np.zeros(sg.n_pad, dtype)
    out[: sg.n] = global_vec
    return out


def make_sharded_runner(sg: ShardedGraph, cfg: PageRankConfig, mesh: Mesh):
    """Compile the sharded iteration loop.

    Returns ``run(device_arrays...) -> (ranks [n_pad], iters, delta)`` with
    ranks replicated (``hybrid``) or the owned carry (``owned``) on exit.
    The program is ``jit_sharded_pagerank`` (``jit_sharded_pagerank_owned``
    under ``owned``) in a device trace.
    """
    if cfg.spark_exact:
        raise NotImplementedError(
            "spark_exact is a single-chip parity mode; run it without a mesh"
        )
    if cfg.spmv_impl != "segment":
        raise NotImplementedError(
            f"spmv_impl={cfg.spmv_impl!r} is not wired into the sharded "
            "runner; each shard reduces through the sorted segment path: "
            "use 'segment' with --mesh"
        )
    axis = mesh.axis_names[0]
    damping = cfg.damping
    total_mass = float(sg.n) if cfg.init is RankInit.ONE else 1.0
    redistribute = cfg.dangling is DanglingMode.REDISTRIBUTE
    n_pad, block = sg.n_pad, sg.block
    edge_spec = P(axis, None)

    if sg.strategy == "owned":
        # Owned slices + sparse boundary exchange (module docstring +
        # ops/boundary.py).  Per step and per device, the ONLY
        # collectives are the log2(d) ppermute rounds of the boundary
        # butterfly and ONE [H_pad+2] psum combining the head partials —
        # whose two spare slots also carry the dangling-mass partial and
        # the PREVIOUS step's local tail delta, so neither needs a psum of
        # its own.  The global convergence gauge therefore lags one
        # iteration (a tolerance run does at most one extra step; ranks
        # are exact either way), which is the price of the
        # log2(d)-ppermute + 1-psum collective budget the registry
        # enforces.  The rank carry is a 4-tuple
        # (tail [n_pad] sharded, head [h_pad] replicated,
        #  dslot [d] sharded, gdelta [] replicated) and is DONATED.
        shard = sg.owned
        h_pad, d_ax = shard.h_pad, shard.d
        inv_d = 1.0 / d_ax  # d is pow2: exact in binary fp

        def step(carry, tsrc, tdst, tw, hsrc, hslot, hw, out_idx,
                 inv_t, dang_t, inv_h, dang_h, e_t, e_h):
            tail, head, dslot, _gd = carry
            wt = tail * inv_t  # [block] local weighted ranks
            wh = head * inv_h  # [h_pad] replicated weighted head
            btable = coll.butterfly_all_gather(
                ob.pack_boundary(wt, out_idx[0]), axis
            )  # [d*b_pad]: every shard's outgoing boundary values
            lookup = ob.boundary_lookup(wt, btable, wh)
            tail_contrib = ops.sorted_segment_sum(
                ops.gather_rows(lookup, tsrc[0]) * tw[0], tdst[0], block
            )
            buf = ops.sorted_segment_sum(
                ops.gather_rows(lookup, hsrc[0]) * hw[0], hslot[0], h_pad + 2
            )
            if redistribute:
                # head part is replicated: each device contributes 1/d of
                # it so the psum restores exactly one copy (d pow2 ⇒ the
                # scale round-trips exactly)
                buf = buf.at[h_pad].add(
                    jnp.sum(tail * dang_t) + jnp.sum(head * dang_h) * inv_d
                )
            buf = buf.at[h_pad + 1].add(dslot[0])
            buf = coll.psum(buf, axis)  # THE one psum of the step
            head_contrib = buf[:h_pad]
            gdelta_prev = buf[h_pad + 1]
            if redistribute:
                dmass = buf[h_pad]
                tail_contrib = tail_contrib + dmass * e_t
                head_contrib = head_contrib + dmass * e_h
            new_tail = (1.0 - damping) * total_mass * e_t + damping * tail_contrib
            new_head = (1.0 - damping) * total_mass * e_h + damping * head_contrib
            new_dslot = (
                jnp.sum(jnp.abs(new_tail - tail))
                + jnp.sum(jnp.abs(new_head - head)) * inv_d
            )[None]
            return new_tail, new_head, new_dslot, gdelta_prev

        def sharded_pagerank_owned(carry0, *arrays):
            return dataflow.iterate(
                lambda c: step(c, *arrays), carry0,
                iterations=cfg.iterations, tol=cfg.tol,
                delta_fn=lambda new, old: new[3],
            )

        state_spec = (P(axis), P(), P(axis), P())
        mapped = shard_map(
            sharded_pagerank_owned,
            mesh=mesh,
            in_specs=(state_spec,
                      edge_spec, edge_spec, edge_spec,  # tail edges
                      edge_spec, edge_spec, edge_spec,  # head edges
                      edge_spec,                        # out_idx
                      P(axis), P(axis), P(), P(),       # inv/dang tail+head
                      P(axis), P()),                    # e_tail, e_head
            out_specs=(state_spec, P(), P()),
            check_vma=False,
        )
        # the owned carry is donated: per-chip state is the strategy's
        # whole point, so XLA must reuse the slice buffers in place
        # (DONATED_CALLEES row 'owned_runner'; tier-3 verifies aliasing)
        return jax.jit(mapped, donate_argnums=(0,))

    # Replicated ranks: this device's dense head rows reduce row by row
    # (no scatter for the hot in-degree mass), its tail slice through the
    # sorted segment path's scan, both into the same full-size partial —
    # ONE psum combines everything across chips.  An empty head
    # materializes one all-sentinel placeholder row per device: skip the
    # dense path entirely then, not just when the padded shape is empty
    # (it never is).
    has_head = bool((np.asarray(sg.head_src) != sg.n_pad).any())

    def step(ranks, src, dst, valid, ip, hsrc, hnode, inv, dang, e):
        weighted = ranks * inv
        per_edge = ops.gather_rows(weighted, src[0]) * valid[0]
        partial = ops.sorted_segment_sum(per_edge, dst[0], n_pad,
                                         indptr=ip[0])
        if has_head:
            w_ext = jnp.concatenate(
                [weighted, jnp.zeros(1, weighted.dtype)]
            )
            row_sums = ops.hybrid_rowsum(ops.gather_rows(w_ext, hsrc[0]))
            partial = partial.at[hnode[0]].add(row_sums)
        contribs = coll.psum(partial, axis)  # the reduceByKey, on ICI
        if redistribute:
            contribs = contribs + jnp.sum(ranks * dang) * e
        return (1.0 - damping) * total_mass * e + damping * contribs

    def sharded_pagerank(ranks0, *arrays):
        # one scan/while skeleton for every fixpoint in the repo: the
        # dataflow core's iterate combinator (dataflow/fixpoint.py); the
        # ranks are replicated, so the local delta is the global one
        return dataflow.iterate(
            lambda ranks: step(ranks, *arrays), ranks0,
            iterations=cfg.iterations, tol=cfg.tol,
            delta_fn=lambda new, old: jnp.sum(jnp.abs(new - old)),
        )

    mapped = shard_map(
        sharded_pagerank,
        mesh=mesh,
        in_specs=(P(), edge_spec, edge_spec, edge_spec, edge_spec,
                  P(axis, None, None), edge_spec, P(), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def sharded_graph_layout(sg: ShardedGraph, mesh: Mesh) -> tuple:
    """The runner's graph operands, in argument order, each paired with
    its sharding on ``mesh`` — what :class:`_ShardedExec` puts, and what a
    compile for a described (not attached) mesh turns into shapes."""
    axis = mesh.axis_names[0]
    esh = NamedSharding(mesh, P(axis, None))
    rsh = NamedSharding(mesh, P())
    if sg.strategy == "owned":
        shard = sg.owned
        tsh = NamedSharding(mesh, P(axis))
        return (
            (shard.tail_src_idx, esh),
            (shard.tail_dst, esh),
            (shard.tail_w, esh),
            (shard.head_src_idx, esh),
            (shard.head_slot, esh),
            (shard.head_w, esh),
            (shard.out_idx, esh),
            (shard.inv_tail, tsh),
            (shard.dang_tail, tsh),
            (shard.inv_head, rsh),
            (shard.dang_head, rsh),
        )
    # node-state vectors are replicated: the step reads the full vectors
    return (
        (sg.src, esh),
        (sg.dst, esh),
        (sg.valid, esh),
        (sg.local_indptr, esh),
        (sg.head_src, NamedSharding(mesh, P(axis, None, None))),
        (sg.head_node, esh),
        (sg.inv_outdeg, rsh),
        (sg.dangling, rsh),
    )


def shard_segment_reduce(sg: ShardedGraph) -> str:
    """Which reduction each shard's sorted segment sum lowers under this
    partition: ``"scan"`` (the slice's CSR pointers and more than one
    512-edge row: no scatter) or ``"scatter"``.  ``owned`` builds no
    pointers."""
    if sg.strategy == "owned":
        return "scatter"
    return ops.segment_reduce_for(sg.dst.shape[1], has_indptr=True)


def shard_gather_chunks(sg: ShardedGraph) -> dict[str, int]:
    """How many chunks each shard's per-edge rank-table gathers run in
    (:func:`ops.gather_chunks`): the tail's and the head's, 1 where the
    gather is whole."""
    tail, head = ((sg.owned.tail_src_idx, sg.owned.head_src_idx)
                  if sg.strategy == "owned" else (sg.src, sg.head_src))
    return {
        "tail": ops.gather_chunks(tail.shape[1:]),
        "head": ops.gather_chunks(head.shape[1:]),
    }


class _ShardedExec:
    """Everything welded to ONE mesh: the partition, the device-resident
    graph arrays, the state sharding, and the callables run_segments
    drives.  The elastic rung survives device loss by building a fresh
    instance over the surviving mesh — nothing here is mutated."""

    def __init__(self, graph: Graph, cfg: PageRankConfig, mesh: Mesh,
                 strategy: str, metrics: MetricsRecorder):
        self.mesh = mesh
        self.d = int(mesh.devices.size)
        self._runners: dict = {}  # segment config -> jitted runner
        with Timer() as t_part:
            self.sg = partition_graph(
                graph, self.d, strategy=strategy, dtype=cfg.dtype,
                head_coverage=cfg.head_coverage,
                head_row_width=cfg.head_row_width,
                owned_max_head=cfg.owned_max_head,
            )
            with Timer() as t_put:
                # fenced so that put_secs times the transfer, not its enqueue
                self.dev = jax.block_until_ready(  # graftlint: disable=unguarded-host-sync (a fence on this build's own host-to-device put, no computation; the first guarded step syncs anyway)
                    tuple(jax.device_put(a, sh)
                          for a, sh in sharded_graph_layout(self.sg, mesh)))
        # the static per-step exchange footprint: ICI bytes each device
        # sends per iteration under this partition (the sublinearity gauge
        # the MULTICHIP scale sweep + trace_diff comm gate consume)
        item = np.dtype(cfg.dtype).itemsize
        owned, sh = self.sg.strategy == "owned", self.sg.owned
        entries = (ob.comm_entries_per_step(self.d, sh.b_pad, sh.h_pad) if owned
                   else _psum_entries(self.d, self.sg.n_pad))
        self.comm_bytes_per_step = int(entries * item)
        obs.gauge("pagerank.comm_bytes_per_step", self.comm_bytes_per_step)
        metrics.record(
            event="partition", strategy=strategy, devices=self.d,
            block=self.sg.block, edges_per_device=int(
                self.sg.owned.e_dev + self.sg.owned.he_dev
                if owned else self.sg.src.shape[1]
            ),
            pad_frac=round(self.sg.pad_frac, 4), secs=t_part.elapsed,
            put_secs=t_put.elapsed,
            comm_bytes_per_step=self.comm_bytes_per_step,
            segment_reduce=shard_segment_reduce(self.sg),
            gather_chunks=shard_gather_chunks(self.sg),
        )
        axis = mesh.axis_names[0]
        self._cfg = cfg
        self._metrics = metrics
        if owned:
            # owned-slice state: a (tail sharded, head replicated) pair
            # behind the dataflow OwnedArray view, plus the lagged-delta
            # carry slots put_ranks adds
            shard = self.sg.owned
            self._tail_sh = NamedSharding(mesh, P(axis))
            self._repl_sh = NamedSharding(mesh, P())
            self.olayout = OwnedArray.from_shard(
                shard, tail_sharding=self._tail_sh,
                head_sharding=self._repl_sh,
            )
            e_t, e_h = ob.split_global(
                shard, ops.restart_vector(self.sg.n, cfg), cfg.dtype
            )
            self.e_vec = (jax.device_put(e_t, self._tail_sh),
                          jax.device_put(e_h, self._repl_sh))
            return
        # replicated ranks, padded at the end: the logical ranks are the
        # first n slots
        self.state_sharding = NamedSharding(mesh, P())
        self.e_vec = (jax.device_put(
            _to_padded(self.sg, ops.restart_vector(self.sg.n, cfg), cfg.dtype),
            self.state_sharding),)

    def make_runner(self, seg_cfg: PageRankConfig):
        """The jitted runner of one segment config, built once: a later
        job reuses it, and with it its compiled program."""
        if seg_cfg not in self._runners:
            self._runners[seg_cfg] = make_sharded_runner(self.sg, seg_cfg, self.mesh)
        return self._runners[seg_cfg]

    def args(self, rd) -> tuple:
        """The runner's arguments around the rank state ``rd``."""
        return (rd, *self.dev, *self.e_vec)

    def invoke(self, runner, rd):
        # a fresh runner traces, lowers and compiles inside the dispatch span
        with obs.span("pagerank.dispatch"):
            rd, iters, delta = runner(*self.args(rd))
        with obs.span("pagerank.delta_sync"):
            if self.sg.strategy == "owned":
                # The owned carry is DONATED: the delta fetch gets its own
                # guarded site so a transient sync failure re-pulls the live
                # OUTPUT scalar instead of letting the segment site's retry
                # re-dispatch into the consumed carry (models/pagerank.py's
                # pagerank_delta_sync discipline).
                delta = rx.device_get(
                    delta, site="pagerank_delta_sync", metrics=self._metrics,
                    checkpoint_dir=self._cfg.checkpoint_dir,
                )
            delta = float(delta)  # scalar fetch is the only reliable device sync
        return rd, iters, delta

    def put_ranks(self, ranks_g: np.ndarray):
        """Global [n] ranks -> padded, sharded device state."""
        if self.sg.strategy == "owned":
            arr = self.olayout.put(ranks_g, self._cfg.dtype)
            # lagged-delta slots start at +inf so the gauge cannot read
            # "converged" before the first real global delta arrives
            dslot = jax.device_put(
                np.full(self.d, np.inf, self._cfg.dtype), self._tail_sh
            )
            gdelta = jax.device_put(
                np.asarray(np.inf, self._cfg.dtype), self._repl_sh
            )
            return (arr.tail, arr.head, dslot, gdelta)
        return jax.device_put(_to_padded(self.sg, ranks_g, self._cfg.dtype),
                              self.state_sharding)

    def pull_view(self, rd):
        """The part of the rank state that crosses D2H: owned state is a
        (tail, head, dslot, gdelta) carry whose delta slots are scratch."""
        return (rd[0], rd[1]) if self.sg.strategy == "owned" else rd

    def unpad(self, ranks_np) -> np.ndarray:
        """Pulled rank state (:meth:`pull_view`) -> global [n] ranks."""
        if self.sg.strategy == "owned":
            return ob.merge_global(self.sg.owned, ranks_np[0], ranks_np[1])
        return ranks_np[: self.sg.n]

    def extract_np(self, rd) -> np.ndarray:
        """Padded device state -> global [n] ranks (checkpoint payload)."""
        with obs.span("pagerank.ckpt_pull"):
            return self.unpad(rx.device_get(
                self.pull_view(rd), site="pagerank_ckpt_pull",
                metrics=self._metrics,
                checkpoint_dir=self._cfg.checkpoint_dir,
            ))


def _acknowledge_loss(exc: BaseException) -> None:
    """Mark the device a loss names as lost in the health registry."""
    idx = elastic.device_index(exc)
    if idx is not None:
        elastic.health().mark_lost(idx)


def _latest_ranks(cfg: PageRankConfig) -> tuple[int, np.ndarray] | None:
    """The newest checkpoint's (iteration, global ranks), or None."""
    latest = (ckpt.latest_checkpoint(cfg.checkpoint_dir)
              if cfg.checkpoint_dir else None)
    if latest is None:
        return None
    step, arrays, _ = ckpt.load_checkpoint(latest, cfg.config_hash())
    return int(step), arrays["ranks"]


def _shrink_and_rerun(graph: Graph, cfg: PageRankConfig, strategy: str,
                      metrics: MetricsRecorder, old: _ShardedExec,
                      exc: BaseException, site: str, ranks_g: np.ndarray,
                      seg_cfg: PageRankConfig):
    """Rebuild the layout over the survivors of ``old``'s mesh, put
    ``ranks_g`` there and run ``seg_cfg`` on it (nothing when it has no
    iterations): ``(exec, ranks, iterations, delta)``.  A LOOP, because a
    second device can die while the rerun itself is in flight: the rerun
    runs as one chaos-hooked attempt with no exhaustion of its own, and a
    further loss re-enters this ladder — re-plan from the already-shrunk
    mesh — instead of surfacing as ResilienceExhausted."""
    devices = list(old.mesh.devices.flat)
    axis = old.mesh.axis_names[0]
    while True:
        plan = elastic.plan_shrink(devices)
        if plan is None:
            raise exc
        with elastic.publish_shrink(site, plan, exc, metrics):
            # keep the dying mesh's axis name: a caller-provided mesh
            # may not be named NODES_AXIS, and the runner/shardings
            # are built from whatever the mesh declares
            new_mesh = rebuild_mesh(plan.devices, axis)
            new = _ShardedExec(graph, cfg, new_mesh, strategy, metrics)
            rd = new.put_ranks(ranks_g)
        if seg_cfg.iterations <= 0:
            return new, rd, 0, None
        try:
            rd, iters, delta = rx.attempt_once(
                lambda n=new, r=rd: n.invoke(n.make_runner(seg_cfg), r),
                site="pagerank_elastic_rerun",
            )
            return new, rd, iters, delta
        except Exception as exc2:  # noqa: BLE001 — re-entry filter below
            lost = elastic.unwrap_device_loss(exc2)
            if lost is None:
                raise
            _acknowledge_loss(lost)
            exc = lost
            devices = list(new_mesh.devices.flat)


def _make_elastic_rebuild(graph: Graph, cfg: PageRankConfig, strategy: str,
                          metrics: MetricsRecorder, exec_box: dict):
    """The mesh-shrink rung for run_segments (driver.ElasticResult
    contract): salvage the global ranks, checkpoint them, rebuild the mesh
    over the surviving devices (the layout is planned again for the new
    count), and rerun the failed segment with zero recomputed *committed*
    iterations."""

    def rebuild(exc, ranks_dev, done, seg_cfg):
        if not elastic.enabled() or not elastic.is_device_loss(exc):
            raise exc
        _acknowledge_loss(exc)
        old = exec_box["exec"]
        # (1) salvage state at the last committed iteration: live buffers
        # first (survivor shards are usually intact), else the newest
        # checkpoint — both carry the logical [n] ranks, so they read the
        # same across mesh shapes.  A FURTHER device loss surfacing inside
        # the salvage pull itself is acknowledged and the pull retried —
        # each lap must mark a NEW device, so a genuinely dead pull falls
        # through to the checkpoint after at most one lap per lost device.
        while True:
            try:
                ranks_g, at_iter = old.extract_np(ranks_dev), done
                break
            except Exception as exc_s:
                lost_s = elastic.unwrap_device_loss(exc_s)
                idx_s = (elastic.device_index(lost_s)
                         if lost_s is not None else None)
                if idx_s is not None and elastic.health().mark_lost(idx_s):
                    exc = lost_s  # the newest loss is what the shrink blames
                    continue
                saved = _latest_ranks(cfg)
                if saved is None:
                    raise exc
                at_iter, ranks_g = saved
                break
        if cfg.checkpoint_dir:
            ckpt.save_checkpoint(
                cfg.checkpoint_dir, at_iter, {"ranks": ranks_g},
                cfg.config_hash(), extra={"devices": old.d},
            )
        # (2)-(4) shrink / rebuild / rerun; committed iterations
        # (< at_iter) are never recomputed on any lap
        new, rd2, iters, delta = _shrink_and_rerun(
            graph, cfg, strategy, metrics, old, exc, "pagerank_step", ranks_g,
            dataclasses.replace(seg_cfg,
                                iterations=done - at_iter + seg_cfg.iterations),
        )
        exec_box["exec"] = new
        return driver.ElasticResult(
            rd2, at_iter + int(iters) - done, delta, new.make_runner, new.invoke,
            new.extract_np, {"devices": new.d},
        )

    return rebuild


class ShardedPageRank:
    """A graph partitioned and resident on a mesh, with its runners: built
    once, then any number of PageRank jobs run on it.

    Building resolves ``strategy="auto"`` (:func:`auto_select_strategy`,
    by per-chip memory and mesh size), partitions the graph on the
    host, puts its arrays on the mesh and publishes the ``partition``
    record.  :meth:`run` is one job: start ranks put, ``cfg.iterations``
    steps through ``driver.run_segments``, ranks pulled and un-padded.  A
    later job partitions, puts and compiles nothing: the runner of each
    segment length is built once (:meth:`compile` builds them ahead of the
    first job).  A device lost in a job rebuilds the layout over the
    survivors (the elastic rung), and later jobs run on that mesh."""

    def __init__(
        self,
        graph: Graph,
        cfg: PageRankConfig,
        *,
        n_devices: int | None = None,
        mesh: Mesh | None = None,
        strategy: str = "auto",
        metrics: MetricsRecorder | None = None,
    ):
        ensure_dtype_support(cfg.dtype)
        self.metrics = metrics or MetricsRecorder()
        if mesh is None:
            mesh = make_mesh(n_devices, NODES_AXIS)
        d = mesh.devices.size
        self.graph = graph
        if strategy == "auto" and graph.n_nodes:
            strategy = auto_select_strategy(graph, d, dtype=cfg.dtype)
            self.metrics.record(event="auto_strategy", chosen=strategy, devices=d)
        self.strategy = strategy
        self.cfg = driver.resolve_personalize(graph, cfg)
        # the elastic rungs swap in the exec rebuilt over the survivors
        self._box = {"exec": (
            _ShardedExec(graph, self.cfg, mesh, strategy, self.metrics)
            if graph.n_nodes else None
        )}

    @property
    def exec(self) -> _ShardedExec:
        return self._box["exec"]

    def compile(self) -> None:
        """Compile the programs of a job from the first iteration, without
        running one: each segment's runner is lowered and compiled against
        start ranks put on the mesh."""
        cfg = self.cfg
        if self.exec is None:
            return
        # the segment lengths driver.run_segments runs a fresh job in
        seg = (cfg.checkpoint_every
               if cfg.checkpoint_every > 0 and cfg.tol == 0.0 else cfg.iterations)
        lengths, done = set(), 0
        while done < cfg.iterations:
            lengths.add(min(seg, cfg.iterations - done))
            done += min(seg, cfg.iterations - done)
        rd = self.exec.put_ranks(ops.init_ranks(self.exec.sg.n, cfg))
        for todo in lengths:
            seg_cfg = dataclasses.replace(
                cfg, iterations=todo, checkpoint_every=0, checkpoint_dir=None
            )
            self.exec.make_runner(seg_cfg).lower(*self.exec.args(rd)).compile()

    def run(self, *, resume: bool = False) -> PageRankResult:
        """One job on the resident graph; its records go to ``metrics``."""
        cfg, metrics, strategy = self.cfg, self.metrics, self.strategy
        if self.exec is None:
            return PageRankResult(np.zeros(0, cfg.dtype), 0, 0.0, metrics)
        with obs.span("pagerank.sharded_job", strategy=strategy):
            result = self._run(resume)
        exec_ = self.exec
        metrics.record(event="sharded_job", strategy=strategy,
                       iterations=result.iterations, devices=exec_.d,
                       comm_bytes_per_step=exec_.comm_bytes_per_step)
        return result

    def _run(self, resume: bool) -> PageRankResult:
        cfg, metrics, strategy = self.cfg, self.metrics, self.strategy
        graph, exec_box = self.graph, self._box
        exec_ = exec_box["exec"]
        mesh, d = exec_.mesh, exec_.d
        ranks_g = ops.init_ranks(exec_.sg.n, cfg)
        start_iter = (
            driver.resume_from_checkpoint(cfg, metrics, ranks_g, n=exec_.sg.n)
            if resume else 0
        )
        ranks_dev = exec_.put_ranks(ranks_g)

        # No make_cpu_invoke here: the compiled program is welded to the mesh
        # (collectives over its axis), so there is no single-device re-lowering
        # of the SAME program to degrade to.  The elastic rung is the sharded
        # degradation path: rebuild over survivors down to a 1-device mesh
        # (which the CPU backend can host when the accelerator pool is gone).
        ranks_dev, done, last_delta = driver.run_segments(
            cfg, metrics, ranks_dev, start_iter,
            make_runner=exec_.make_runner,
            invoke=exec_.invoke,
            extract_np=exec_.extract_np,
            extra_metrics={"devices": d},
            elastic_rebuild=_make_elastic_rebuild(
                graph, cfg, strategy, metrics, exec_box
            ),
        )
        # Device loss FIRST surfacing at the result pull (no segment dispatch
        # left to catch it) used to exhaust the ladder; this rung routes the
        # pull through the same elastic shrink: salvage the newest checkpoint
        # (the live buffers died with the device), rebuild over the survivors,
        # re-run the uncommitted iterations there, and pull from the rebuilt
        # mesh.  The rung swaps exec_box so the un-padding below matches
        # the layout the returned padded ranks were produced in.
        def pull_rebuild(exc):
            if not elastic.enabled() or not elastic.is_device_loss(exc):
                raise exc
            _acknowledge_loss(exc)
            old = exec_box["exec"]
            at_iter, ranks_g = (_latest_ranks(cfg)
                                or (0, ops.init_ranks(old.sg.n, cfg)))
            new, rd2, _, _ = _shrink_and_rerun(
                graph, cfg, strategy, metrics, old, exc, "pagerank_result_pull",
                ranks_g, dataclasses.replace(
                    cfg, iterations=done - at_iter, checkpoint_every=0,
                    checkpoint_dir=None,
                ),
            )
            exec_box["exec"] = new
            # same site: chaos's device_lost is gated on the health registry,
            # so the acknowledged loss cannot re-fire here
            with obs.span("pagerank.result_pull_rebuilt"):
                return rx.device_get(
                    new.pull_view(rd2),
                    site="pagerank_result_pull", metrics=metrics,
                    checkpoint_dir=cfg.checkpoint_dir,
                )

        with obs.span("pagerank.result_pull"):
            pull_view = exec_.pull_view(ranks_dev)
            ranks_np = rx.device_get(
                pull_view, site="pagerank_result_pull", metrics=metrics,
                checkpoint_dir=cfg.checkpoint_dir,
                fallbacks=[(None, pull_rebuild)],
            )
        exec_ = exec_box["exec"]  # a rebuild rung may have swapped it
        # Where the final ranks live, and each mesh device's memory in use
        # while they do: a layout that lands everything on one chip shows up.
        placed = pull_view[0] if strategy == "owned" else pull_view
        metrics.record(
            event="ranks_placement", strategy=strategy,
            devices=len(placed.sharding.device_set),
            bytes_in_use={
                str(dev.id): (dev.memory_stats() or {}).get("bytes_in_use")
                for dev in mesh.devices.flat
            },
        )
        return PageRankResult(
            ranks=exec_.unpad(ranks_np), iterations=done,
            l1_delta=last_delta, metrics=metrics,
        )


def run_pagerank_sharded(
    graph: Graph,
    cfg: PageRankConfig,
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    strategy: str = "auto",
    metrics: MetricsRecorder | None = None,
    resume: bool = False,
) -> PageRankResult:
    """Sharded counterpart of models.pagerank.run_pagerank — same semantics
    flags, same checkpoint segments, ranks bit-comparable across device
    counts up to float reduction order (chip-count invariance is pinned by
    tests/test_parallel.py): a :class:`ShardedPageRank` built for one job.

    Device loss no longer aborts the run: the elastic rung (resilience/
    elastic.py) shrinks the mesh onto the surviving devices, repartitions,
    and resumes — falling through to ``ResilienceExhausted`` + checkpoint
    only when nothing survives or ``GRAFT_ELASTIC=0``."""
    return ShardedPageRank(
        graph, cfg, n_devices=n_devices, mesh=mesh, strategy=strategy,
        metrics=metrics,
    ).run(resume=resume)
