"""Multi-chip PageRank: sharded CSR SpMV with XLA collectives.

Reference counterpart (SURVEY.md §2.2 R1/R2, BASELINE.json:9): Spark's
hash-partitioned RDDs and the shuffle that re-co-partitions
``links.join(ranks)`` every iteration.  Here the graph is partitioned
**once** on host, laid out per device, and every iteration's cross-chip
combine is a single XLA collective over ICI — no repartitioning ever
happens because the partition is static and the collective does the moving.

Two sharding strategies (SURVEY.md §7 "power-law load imbalance" is why
both exist):

- ``edges`` (default): each device owns an equal *contiguous slice of the
  dst-sorted edge array* — perfectly balanced FLOPs even on power-law
  graphs (a celebrity node's in-edges simply span devices).  The rank
  vector is replicated; each device segment-sums its slice into a full-size
  partial and one ``psum`` combines partials (the `reduceByKey`).
  Dangling mass needs no collective (replicated state).
- ``nodes``: each device owns a *block of nodes* (rank shard + that block's
  in-edges) — memory scales 1/D, the layout for graphs whose node state
  outgrows one chip's HBM (soc-LiveJournal1 config, BASELINE.json:9).
  Per iteration: ``all_gather`` the degree-weighted rank blocks, local
  segment_sum into the block, ``psum`` only for the dangling-mass scalar.
- ``nodes_balanced``: same memory layout and iteration as ``nodes``, but the
  node-block boundaries are chosen at equal *in-edge* splits instead of
  equal node counts, so a power-law degree distribution (one celebrity node
  next to millions of leaves) no longer concentrates most of the SpMV work
  on one chip.  Node ids are relabeled into a padded per-device space on
  host (``node_map``); the device program is identical to ``nodes``.  The
  padded block is uniform (= the max device's node count), so per-device
  node counts are capped at 2x the equal-node block — memory stays within
  2x of ``nodes`` instead of degrading toward n*d on hub-heavy graphs.
- ``src`` / ``src_ring``: the *push* layout (SURVEY.md §2.3 "all-to-all"
  row, §5.8 edge-cut exchange).  Device i owns source block i — rank shard
  plus its nodes' out-edges — so the per-edge gather reads only the local
  1/D-sized rank block (never a gathered [n_pad] vector), each device
  segment-sums a full per-destination partial, and one **reduce-scatter**
  combines and re-shards it in a single collective: half the bytes of the
  ``edges`` psum, and immune to hub *in*-degree imbalance (edges follow
  their source; out-degree is the bounded axis of web graphs).
  ``src_ring`` runs the identical exchange as an explicit ``ppermute``
  ring (collectives.ring_reduce_scatter) — the hand-scheduled hop-by-hop
  form whose equality with psum_scatter tests pin.
- ``hybrid``: the degree-aware power-law layout (*Sparse Allreduce*'s
  dense-head/sparse-tail split, PAPERS.md).  Replicated rank vector like
  ``edges``; the high-in-degree head's edges live as fixed-width dense
  rows (ops.pagerank.HybridLayout) split evenly across devices and
  reduced on the MXU, the long tail as equal contiguous dst-sorted edge
  slices; each device's full-size partial combines in the same single
  ``psum``.  Because BOTH sides split at edge/row granularity — a hub's
  dense rows simply span devices — the power-law in-degree imbalance that
  pads ``nodes``/``nodes_balanced`` to 0.6 cannot occur: the plan-level
  ``pad_frac`` stays at the ceil-remainder level of ``edges`` plus the
  head rows' sentinel slots.
- ``owned``: the break-the-replicated-state-wall layout (ISSUE 15;
  *Sparse Allreduce*'s hub-peeled sparse exchange over DrJAX-style native
  collectives — see ``ops/boundary.py`` for the full anatomy).  Each
  shard owns ONLY its tail block's rank slice; a small combined-degree
  hub head is the one replicated mini-state (its contributions combine
  in ONE [H_pad+2] ``psum`` that also carries the dangling mass and the
  one-step-lagged global delta — so per step the ONLY collectives are
  the log₂(d) ``ppermute`` rounds of the boundary butterfly plus that
  single psum); every other cross-shard read moves through fixed-width
  padded boundary buffers holding just the cut-crossing entries.  State
  per chip is O(n/d + H), comm per step is O(boundary + H) — both
  sublinear in n on power-law graphs, which is what lets 10-100x
  web-Google node counts run at all.
- ``auto``: picks by memory footprint and degree shape — ``hybrid`` when
  the replicated node state fits per-chip HBM and the graph has a
  dense-worthy power-law head, ``edges`` when it fits but has no head,
  ``owned`` beyond (replicated-state-doesn't-fit is the trigger; see
  :func:`auto_select_strategy`).

Both run the whole iteration loop inside one ``jit`` + ``shard_map``
program: collectives are compiled into the loop body, so there are zero
host round-trips between iterations, same as the single-chip path.  Every
strategy but ``owned`` gives each shard the CSR pointers of its edge slice,
so its segment sum is the one-chip ``segment`` path's scan, with no
scatter.  :class:`ShardedPageRank` keeps the partition, the device arrays
and the compiled runners resident across jobs.

``spark_exact`` mode is single-chip-only (it exists for parity testing, not
scale) — requesting it sharded raises.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import fixpoint as dataflow
from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.partition import (
    PartitionedArray,
)
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
    """The per-chip footprint of a REPLICATED-rank strategy: ~6 node
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
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
) -> str:
    """Pick a shard strategy by per-chip memory footprint.

    ``edges`` replicates every node-sized vector on every chip (no memory
    scaling — the round-1 gap for soc-LiveJournal1-sized graphs), so once
    the replicated node state plus this chip's edge slice stops fitting in
    half the per-device memory (``hbm_bytes``, default
    :func:`device_hbm_bytes`), switch to ``owned``: 1/D node state with a
    sparse boundary exchange (``nodes_balanced`` on a non-pow2 mesh).
    """
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    replicated = replicated_state_bytes(
        graph.n_nodes, graph.n_edges, n_devices, dtype
    )
    # Every exit publishes ONE strategy_decision event carrying the
    # measured inputs, so trace_report can show WHY a run picked its
    # strategy (ISSUE 9 satellite) — today the choice was invisible in
    # traces.  No-op outside a traced run.
    inputs = dict(
        devices=n_devices,
        nodes=graph.n_nodes, edges=graph.n_edges,
        replicated_state_bytes=replicated,
        hbm_bytes=int(hbm_bytes),
    )
    if replicated > hbm_bytes / 2:
        # Replicated state does not fit: owned slices + sparse boundary
        # exchange (ISSUE 15) — O(n/d + H) state per chip where the older
        # nodes_balanced layout still all_gathers O(n) bytes per step.
        # The owned butterfly needs a power-of-two mesh (the same shapes
        # the elastic shrink chain rebuilds at); a non-pow2 count keeps
        # the legacy memory-scaling layout.
        pow2 = n_devices >= 1 and n_devices & (n_devices - 1) == 0
        obs.emit("strategy_decision",
                 chosen="owned" if pow2 else "nodes_balanced",
                 reason="replicated node state exceeds half the per-chip "
                        "HBM budget", **inputs)
        return "owned" if pow2 else "nodes_balanced"
    # Replicated state fits — prefer the degree-aware hybrid layout when
    # the graph has a dense-worthy power-law head covering a meaningful
    # fraction of the edges (the dense MXU rows then carry the hot
    # in-degree mass scatter-free); plain ``edges`` otherwise.  A
    # weighted graph never picks hybrid: its sharded form has no
    # weighted dense rows (partition_graph would refuse).
    indeg = np.diff(graph.csr_indptr())
    # evaluate the head at the SAME knobs the partition will materialize
    # with — plan_hybrid_head's planner/builder agreement contract
    head_ids, _w = ops.plan_hybrid_head(
        indeg, graph.n_edges, coverage=head_coverage,
        row_width=head_row_width,
    )
    head_edges = int(indeg[head_ids].sum()) if head_ids.size else 0
    inputs.update(head_nodes=int(head_ids.size), head_edges=head_edges,
                  head_edge_frac=round(head_edges / max(graph.n_edges, 1), 4))
    if (head_ids.size and head_edges >= graph.n_edges // 4
            and graph.weight is None):
        obs.emit("strategy_decision", chosen="hybrid",
                 reason="replicated state fits and the power-law head "
                        "covers >=25% of edges", **inputs)
        return "hybrid"
    obs.emit("strategy_decision", chosen="edges",
             reason="replicated state fits; no dense-worthy degree head",
             **inputs)
    return "edges"


class PartitionPlan(NamedTuple):
    """Pure *planning* output of a shard strategy: split boundaries, padded
    sizes and the padding-waste fraction, computed without materializing a
    single per-device array (and without any device dispatch).

    This is the introspection surface the graftlint tier-3 pad_frac
    analyzer gates on (``analysis/cost.py``): ``partition_graph`` builds its
    arrays FROM this plan, so the static number the linter budgets is — by
    construction, not by convention — the same ``pad_frac`` a real
    multichip run logs in its ``partition`` event (cross-checked against
    MULTICHIP_r05.json by tests/test_cost_lint.py)."""

    strategy: str
    n: int  # real node count
    n_pad: int  # D * block
    block: int  # nodes per device block
    e_dev: int  # edge slots per device (padded width; tail-only for hybrid)
    pad_frac: float  # fraction of padded edge slots (load-imbalance gauge)
    bounds_nodes: np.ndarray | None = None  # [D+1] node-block boundaries
    ebounds: np.ndarray | None = None  # [D+1] edge-range boundaries (nodes*)
    per: np.ndarray | None = None  # [D] real edges per device ('src*')
    # 'hybrid' only: (head node count, dense row width, total dense rows,
    # dense rows per device) — the head side of the slot accounting
    head: tuple[int, int, int, int] | None = None
    # 'owned' only: the full boundary-exchange plan (ops.boundary.OwnedPlan
    # — head set, tail bounds, boundary sets, pad + comm accounting);
    # partition_graph materializes exactly it
    owned: ob.OwnedPlan | None = None
    # Array entries each device sends per iteration under this plan (the
    # static per-step comm footprint — ICI bytes = entries * itemsize);
    # published with the partition event and gauged by _ShardedExec so
    # trace_diff can regress it across rounds (ISSUE 15 satellite).
    comm_entries_per_step: int | None = None



def _comm_entries(strategy: str, d: int, n_pad: int, block: int,
                  owned_plan: "ob.OwnedPlan | None" = None) -> int:
    """Static per-step comm footprint of a partition plan, in array
    entries sent per device per iteration (ring-scheduled collectives:
    allreduce ~2 passes, gather/scatter ~1).  The replicated strategies
    move O(n_pad) per step; ``owned`` moves only the padded boundary
    buffers plus the head psum — the sublinearity the MULTICHIP scale
    sweep measures."""
    if d <= 1:
        return 0
    if strategy == "owned":
        assert owned_plan is not None
        return owned_plan.comm_entries_per_step()
    if strategy in ("edges", "hybrid"):  # dense [n_pad] psum
        return 2 * n_pad * (d - 1) // d
    # nodes*/src*: all_gather / reduce-scatter of the block axis, plus
    # two scalar psums (dangling mass + delta)
    return (d - 1) * block + 4


def _publish_plan(plan: PartitionPlan, n_devices: int) -> PartitionPlan:
    """Log the chosen partition plan (strategy + the numbers that drove
    it) as ONE obs event, so a trace explains the layout a run executed
    with (ISSUE 9 satellite: trace_report's strategy section).  No-op
    outside a traced run — the tier-3 lint calls plan_partition freely."""
    plan = plan._replace(
        comm_entries_per_step=_comm_entries(
            plan.strategy, n_devices, plan.n_pad, plan.block, plan.owned
        )
    )
    ow = plan.owned
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
    most 1/1024 more.  The replicated layouts pad their per-device widths
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
    strategy: str = "edges",
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
    owned_max_head: int = TUNABLE_DEFAULTS["owned_max_head"],
) -> PartitionPlan:
    """Plan a partition without building it: boundaries, padded widths and
    ``pad_frac`` only — O(E) host work, no per-device arrays, no device
    traffic.  ``partition_graph`` materializes exactly this plan."""
    if strategy not in ("edges", "nodes", "nodes_balanced", "src", "src_ring",
                        "hybrid", "owned"):
        raise ValueError(f"unknown shard strategy {strategy!r}")
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

    if strategy == "hybrid":
        # Replicated-state layout: head rows and tail edges both split at
        # row/edge granularity, so the only padding is the dense rows'
        # sentinel slots plus two ceil remainders.  pad_frac counts ALL
        # dispatched slots (head row slots + tail edge slots) against the
        # real edge count — comparable with the other strategies' gauge.
        block = _size_bucket(max(1, math.ceil(n / d)))
        indeg = np.diff(graph.csr_indptr())
        head_ids, w = ops.plan_hybrid_head(
            indeg, e, coverage=head_coverage, row_width=head_row_width
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

    if strategy in ("src", "src_ring"):
        block = max(1, math.ceil(n / d))
        n_pad = block * d
        per = np.bincount(graph.src // block, minlength=d)
        e_dev = max(1, int(per.max()))
        pad_frac = (d * e_dev - e) / max(d * e_dev, 1)
        return _publish_plan(
            PartitionPlan(strategy, n, n_pad, block, e_dev, pad_frac,
                          per=per),
            d,
        )

    if strategy == "edges":
        block = _size_bucket(max(1, math.ceil(n / d)))
        e_dev = _size_bucket(max(1, math.ceil(e / d)))
        cap = e_dev * d
        pad_frac = (cap - e) / max(cap, 1)
        return _publish_plan(
            PartitionPlan(strategy, n, block * d, block, e_dev, pad_frac), d
        )

    if strategy == "nodes":
        block = max(1, math.ceil(n / d))
        bounds_nodes = np.minimum(np.arange(0, d + 1) * block, n)
    else:  # nodes_balanced
        # OPTIMAL min-max contiguous split (binary search over the padded
        # width + greedy max-fill feasibility), with per-device node count
        # capped at 2x the equal-node block: the uniform padded block is
        # the max device's node count, so an uncapped edge-balanced split
        # of a hub-heavy graph would push n_pad toward n*d and forfeit the
        # 1/D memory scaling this layout exists for.  The previous greedy
        # target-then-clamp scan planned up to 3x more padding than the
        # optimum on hub-heavy graphs (MULTICHIP_r05 measured 0.61 at 8
        # devices where the optimum is 0.47, and 0.45 at 4 where it is
        # 0.12); the node-granularity floor — a single hub's in-edge run
        # cannot split across devices in this layout — is what remains
        # (the 'hybrid' strategy exists to go below it).
        cap = 2 * max(1, math.ceil(n / d))
        indptr = graph.csr_indptr()

        def fill(width: int) -> np.ndarray | None:
            """Greedy max-fill at the given padded width; None = the n
            nodes do not fit on d devices at this width."""
            bounds = np.zeros(d + 1, np.int64)
            b = 0
            for i in range(d):
                hi = int(np.searchsorted(
                    indptr, indptr[b] + width, side="right")) - 1
                hi = min(max(hi, b), b + cap, n)
                bounds[i + 1] = hi
                b = hi
            return bounds if b >= n else None

        lo_w = max(1, math.ceil(e / d))
        hi_w = max(e, 1)
        bounds_nodes = fill(hi_w)
        assert bounds_nodes is not None  # d * cap >= 2n always covers n
        while lo_w < hi_w:
            mid = (lo_w + hi_w) // 2
            bm = fill(mid)
            if bm is None:
                lo_w = mid + 1
            else:
                hi_w, bounds_nodes = mid, bm
        block = max(1, int(np.diff(bounds_nodes).max()))
    ebounds = np.searchsorted(graph.dst, bounds_nodes)
    e_dev = max(1, int(np.diff(ebounds).max()))
    pad_frac = (d * e_dev - e) / max(d * e_dev, 1)
    return _publish_plan(
        PartitionPlan(strategy, n, block * d, block, e_dev, pad_frac,
                      bounds_nodes=bounds_nodes, ebounds=ebounds),
        d,
    )


class ShardedGraph(NamedTuple):
    """Host-side partitioned graph layout, ready for device_put.

    ``src`` is always global node ids; ``dst`` is block-local under the
    ``nodes`` strategy and global under ``edges``.  ``valid`` masks the
    per-device padding (power-law blocks pad unevenly under ``nodes``).
    """

    strategy: str
    n: int  # real node count
    n_pad: int  # D * block
    block: int  # nodes per device block
    src: np.ndarray  # int32 [D, E_dev]
    dst: np.ndarray  # int32 [D, E_dev]
    valid: np.ndarray  # f [D, E_dev]
    inv_outdeg: np.ndarray  # f [n_pad]
    dangling: np.ndarray  # f [n_pad] (padding rows are NOT dangling: 0)
    pad_frac: float  # fraction of padded edge slots (load-imbalance gauge)
    node_map: np.ndarray  # int64 [n]: global node id → padded slot
    # (identity-into-prefix for 'edges'/'nodes'; a relabeling under
    # 'nodes_balanced' where device blocks have unequal node counts)
    local_indptr: np.ndarray  # int32 [D, S+1]: per-device CSR row
    # pointers into that device's (sorted) edge slice — the tail slice
    # under 'hybrid' — S = n_pad under 'edges'/'hybrid'/'src*', block
    # under node strategies: the scatter-free segment sum's pointers, and
    # the monotone-diff ones of spmv_impl='cumsum' (host memory cost D*S
    # ints; sharded on device); (D, 1) zeros where not built
    # 'hybrid' only: this device's slice of the dense head rows.  Sentinel
    # source id n_pad reads the zero slot of the step's extended weight
    # vector; all-sentinel padding rows scatter 0.0 into node 0.
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
    strategy: str = "edges",
    dtype: str = "float32",
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
    owned_max_head: int = TUNABLE_DEFAULTS["owned_max_head"],
) -> ShardedGraph:
    """Partition once on host (the reference partitions on every shuffle).

    Every strategy but ``owned`` builds per-device CSR pointers
    (``local_indptr``): each shard's segment sum reduces through them with
    no scatter, and the 'cumsum' impls difference prefix sums at them.
    Under 'edges', 'hybrid' and 'src*' they cost D node-sized int32 arrays.

    All split boundaries, padded widths and ``pad_frac`` come from
    :func:`plan_partition` — the static plan the tier-3 cost linter
    budgets is the one this function materializes.

    A weighted graph rides for free in every edge-mask strategy: the
    ``valid`` mask slots carry the edge WEIGHT instead of 1.0 (padding
    stays 0), so the per-edge product the step already computes becomes
    the weighted SpMV; ``inv_outdeg`` normalizes by out-strength.  The
    ``owned`` layout threads weights through its own coefficient arrays.
    Only ``hybrid`` refuses weights sharded (its dense head rows are
    weightless by construction — use another strategy or single-chip
    hybrid)."""
    plan = plan_partition(graph, n_devices, strategy=strategy,
                          head_coverage=head_coverage,
                          head_row_width=head_row_width,
                          owned_max_head=owned_max_head)
    d = n_devices
    n = graph.n_nodes
    e = graph.n_edges
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
            pad_frac=pad_frac, node_map=np.arange(n, dtype=np.int64),
            local_indptr=ph, owned=shard,
        )

    weighted = graph.weight is not None
    if weighted and strategy == "hybrid":
        raise NotImplementedError(
            "sharded strategy 'hybrid' has no weighted-edge form (the "
            "dense head rows carry no weight matrix); use 'owned', "
            "'edges' or a node strategy for weighted graphs"
        )
    # the per-edge coefficient the valid mask carries: weight or 1.0
    ew = graph.weight if weighted else None

    inv_g = graph.inv_out_strength(dtype)
    dang_g = (graph.out_degree == 0).astype(dtype)

    if strategy == "hybrid":
        # Materialize exactly the planned head/tail split: the global
        # hybrid layout (same plan_hybrid_head policy as the single-chip
        # impl), its dense rows dealt to devices in equal contiguous row
        # blocks, the tail as equal contiguous dst-sorted edge slices.
        hl = ops.build_hybrid_layout(
            graph, coverage=head_coverage, row_width=head_row_width
        )
        head_k, w, rows, rows_dev = plan.head
        assert hl.head_src.shape == (rows, w)  # plan IS the layout
        # head rows: remap the single-chip sentinel n -> n_pad (the zero
        # slot of the sharded step's extended weight vector)
        hnode_g = hl.head_ids[hl.head_row_node].astype(np.int32)
        head_src = np.full((d, max(rows_dev, 1), max(w, 1)), n_pad, np.int32)
        head_node = np.zeros((d, max(rows_dev, 1)), np.int32)
        for i in range(d):
            lo, hi = min(i * rows_dev, rows), min((i + 1) * rows_dev, rows)
            rows_i = head_src[i, : hi - lo, :w]
            rows_i[...] = hl.head_src[lo:hi]
            rows_i[rows_i == n] = n_pad
            head_node[i, : hi - lo] = hnode_g[lo:hi]
        # tail: equal contiguous slices of the tail edge array, 'edges'
        # style (pad src=0 dst=n_pad-1 masked by valid)
        e_tail = hl.tail_src.shape[0]
        cap_t = e_dev * d
        src = np.zeros(cap_t, np.int32)
        dst = np.full(cap_t, n_pad - 1, np.int32)
        valid = np.zeros(cap_t, dtype)
        src[:e_tail] = hl.tail_src
        dst[:e_tail] = hl.tail_dst
        valid[:e_tail] = 1.0
        inv = np.zeros(n_pad, dtype)
        inv[:n] = inv_g
        dangling = np.zeros(n_pad, dtype)
        dangling[:n] = dang_g
        local_indptr = _slice_indptr(hl.tail_indptr, n_pad, e_dev, d)
        return ShardedGraph(
            strategy, n, n_pad, block,
            src.reshape(d, e_dev), dst.reshape(d, e_dev),
            valid.reshape(d, e_dev), inv, dangling, pad_frac,
            np.arange(n, dtype=np.int64), local_indptr,
            head_src=head_src, head_node=head_node,
        )

    if strategy in ("src", "src_ring"):
        # Push layout: device i owns SOURCE block [i*block, (i+1)*block) —
        # its rank shard and its nodes' out-edges.  Contributions are
        # computed from the local rank block alone (the per-edge gather
        # reads a 1/D-sized table), each device segment-sums its edges into
        # a full [n_pad] per-destination partial, and one reduce-scatter
        # (psum_scatter, or the explicit ppermute ring under 'src_ring')
        # both combines and re-shards it.  Hub-heavy *in*-degree (the
        # power-law axis of web graphs) cannot imbalance this layout: edges
        # follow their source, and out-degree is the bounded one.
        owner = graph.src // block
        order = np.lexsort((graph.dst, owner))  # by device, then dst-sorted
        src_o = graph.src[order]
        dst_o = graph.dst[order]
        ew_o = ew[order] if weighted else None
        per = plan.per
        starts = np.concatenate([[0], np.cumsum(per)])
        src_l = np.zeros((d, e_dev), np.int32)
        dst2 = np.full((d, e_dev), n_pad - 1, np.int32)  # pad keeps dst sorted
        valid = np.zeros((d, e_dev), dtype)
        for i in range(d):
            lo, hi = starts[i], starts[i + 1]
            k = hi - lo
            src_l[i, :k] = src_o[lo:hi] - i * block  # block-local sources
            dst2[i, :k] = dst_o[lo:hi]
            valid[i, :k] = ew_o[lo:hi] if weighted else 1.0
        inv = np.zeros(n_pad, dtype)
        inv[:n] = inv_g
        dangling = np.zeros(n_pad, dtype)
        dangling[:n] = dang_g
        # Per-device CSR pointers over the full padded destination space:
        # each device's slice is dst-sorted, so its pointers are one
        # searchsorted over its own slice.
        local_indptr = np.empty((d, n_pad + 1), np.int32)
        for i in range(d):
            k = int(per[i])
            local_indptr[i] = np.searchsorted(
                dst2[i, :k], np.arange(n_pad + 1)
            ).astype(np.int32)
        return ShardedGraph(strategy, n, n_pad, block, src_l, dst2, valid,
                            inv, dangling, pad_frac,
                            np.arange(n, dtype=np.int64), local_indptr)

    if strategy == "edges":
        cap = e_dev * d
        src = np.full(cap, 0, np.int32)
        dst = np.full(cap, n_pad - 1, np.int32)  # keeps dst sorted per slice tail
        valid = np.zeros(cap, dtype)
        src[:e] = graph.src
        dst[:e] = graph.dst
        valid[:e] = ew if weighted else 1.0
        inv = np.zeros(n_pad, dtype)
        inv[:n] = inv_g
        dangling = np.zeros(n_pad, dtype)
        dangling[:n] = dang_g
        dst2 = dst.reshape(d, e_dev)
        local_indptr = _slice_indptr(graph.csr_indptr(), n_pad, e_dev, d)
        return ShardedGraph(strategy, n, n_pad, block,
                            src.reshape(d, e_dev), dst2,
                            valid.reshape(d, e_dev), inv, dangling, pad_frac,
                            np.arange(n, dtype=np.int64), local_indptr)

    # Node-sharded strategies: device i owns global nodes [b_i, b_{i+1})
    # (their rank shard and their in-edges, which are contiguous in the
    # dst-sorted edge array).  'nodes' picks equal-node boundaries; padding
    # each device's edge slice to the max then bears the full power-law
    # imbalance.  'nodes_balanced' picks boundaries at equal-EDGE splits
    # (node-granular, capped at 2x the equal-node block — see
    # plan_partition), evening out SpMV work instead.
    bounds_nodes = plan.bounds_nodes

    # global node id → padded slot (device i's nodes at [i*block, ...))
    node_map = np.empty(n, np.int64)
    for i in range(d):
        lo, hi = bounds_nodes[i], bounds_nodes[i + 1]
        node_map[lo:hi] = i * block + np.arange(hi - lo)

    ebounds = plan.ebounds
    src = np.zeros((d, e_dev), np.int32)
    dst_local = np.full((d, e_dev), block - 1, np.int32)
    valid = np.zeros((d, e_dev), dtype)
    src_mapped = node_map[graph.src].astype(np.int32)
    for i in range(d):
        lo, hi = ebounds[i], ebounds[i + 1]
        k = hi - lo
        src[i, :k] = src_mapped[lo:hi]
        dst_local[i, :k] = graph.dst[lo:hi] - bounds_nodes[i]
        valid[i, :k] = ew[lo:hi] if weighted else 1.0
    inv = np.zeros(n_pad, dtype)
    inv[node_map] = inv_g
    dangling = np.zeros(n_pad, dtype)
    dangling[node_map] = dang_g
    # Device i's edges are global rows [ebounds[i], ebounds[i+1]) — its CSR
    # pointers are the global ones for its node range, re-based to the
    # slice; padding node slots repeat the last pointer (empty segments)
    # and padding edge slots fall outside every segment.
    g_ip = graph.csr_indptr()
    local_indptr = np.empty((d, block + 1), np.int32)
    for i in range(d):
        lo_n, hi_n = bounds_nodes[i], bounds_nodes[i + 1]
        seg = (g_ip[lo_n : hi_n + 1] - ebounds[i]).astype(np.int32)
        local_indptr[i, : seg.size] = seg
        local_indptr[i, seg.size :] = seg[-1] if seg.size else 0
    return ShardedGraph(strategy, n, n_pad, block, src, dst_local, valid,
                        inv, dangling, pad_frac, node_map, local_indptr)


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
    out[sg.node_map] = global_vec
    return out


def _restart_padded(sg: ShardedGraph, cfg: PageRankConfig) -> np.ndarray:
    return _to_padded(sg, ops.restart_vector(sg.n, cfg), cfg.dtype)


def make_sharded_runner(sg: ShardedGraph, cfg: PageRankConfig, mesh: Mesh):
    """Compile the sharded iteration loop.

    Returns ``run(device_arrays...) -> (ranks [n_pad], iters, delta)`` with
    ranks replicated (``edges``) or node-sharded (``nodes``) on exit.  The
    program is ``jit_sharded_pagerank`` (``jit_sharded_pagerank_owned``
    under ``owned``) in a device trace.
    """
    if cfg.spark_exact:
        raise NotImplementedError(
            "spark_exact is a single-chip parity mode; run it without a mesh"
        )
    if cfg.spmv_impl not in ("segment", "cumsum", "cumsum_mxu"):
        raise NotImplementedError(
            f"spmv_impl={cfg.spmv_impl!r} is not wired into the sharded "
            "runner; use 'segment', 'cumsum' or 'cumsum_mxu' with --mesh"
        )
    if sg.strategy == "owned" and cfg.spmv_impl != "segment":
        raise NotImplementedError(
            "the owned strategy reduces its tail through the sorted "
            "segment path; use spmv_impl='segment'"
        )
    axis = mesh.axis_names[0]
    damping = cfg.damping
    total_mass = float(sg.n) if cfg.init is RankInit.ONE else 1.0
    redistribute = cfg.dangling is DanglingMode.REDISTRIBUTE
    n_pad, block = sg.n_pad, sg.block

    if sg.strategy == "owned":
        # Owned slices + sparse boundary exchange (ISSUE 15; module
        # docstring + ops/boundary.py).  Per step and per device, the ONLY
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

        edge_spec = P(axis, None)
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

    def local_reduce(per_edge, dst_row, ip_row, num_segments):
        """Per-device `reduceByKey` over its sorted edge slice: the shared
        scatter-free monotone-diff skeleton under 'cumsum'/'cumsum_mxu',
        the sorted segment sum otherwise."""
        if cfg.spmv_impl == "cumsum":
            return ops.cumsum_diff_spmv(per_edge, ip_row)
        if cfg.spmv_impl == "cumsum_mxu":
            return ops.cumsum_diff_spmv(per_edge, ip_row,
                                        cumsum_fn=ops.cumsum_blocked)
        return ops.sorted_segment_sum(per_edge, dst_row, num_segments,
                                      indptr=ip_row)

    head_specs: tuple = ()
    if sg.strategy == "edges":
        # state: replicated full rank vector; one psum per iteration.
        def step(ranks, src, dst, valid, ip, inv, dang, e):
            weighted = ranks * inv
            per_edge = ops.gather_rows(weighted, src[0]) * valid[0]
            partial = local_reduce(per_edge, dst[0], ip[0], n_pad)
            contribs = coll.psum(partial, axis)  # the reduceByKey, on ICI
            if redistribute:
                contribs = contribs + jnp.sum(ranks * dang) * e
            return (1.0 - damping) * total_mass * e + damping * contribs

        state_spec = P()  # replicated ranks
        vec_spec = P()  # inv/dangling/e replicated (step reads the full vectors)
        local_delta = lambda new, old: jnp.sum(jnp.abs(new - old))
    elif sg.strategy == "hybrid":
        # Degree-aware power-law layout: replicated ranks like 'edges';
        # this device's dense head rows reduce on the MXU (one matvec, no
        # scatter for the hot in-degree mass), its tail slice through the
        # sorted segment path, both into the same full-size partial — ONE
        # psum combines everything across chips.
        # a headless graph (uniform degrees) materializes one all-sentinel
        # placeholder row per device — skip the dense path entirely then,
        # not just when the padded shape is empty (it never is)
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
            contribs = coll.psum(partial, axis)
            if redistribute:
                contribs = contribs + jnp.sum(ranks * dang) * e
            return (1.0 - damping) * total_mass * e + damping * contribs

        head_specs = (P(axis, None, None), P(axis, None))
        state_spec = P()
        vec_spec = P()
        local_delta = lambda new, old: jnp.sum(jnp.abs(new - old))
    elif sg.strategy in ("src", "src_ring"):
        # Push layout: gather from the LOCAL rank block only, segment-sum
        # into a full per-destination partial, then one reduce-scatter both
        # combines across chips and keeps only this device's block — half
        # the bytes of the 'edges' psum (no re-broadcast leg), and unlike
        # 'nodes' the per-edge gather never touches a gathered [n_pad]
        # vector.  'src_ring' runs the same exchange as an explicit
        # ppermute ring (SURVEY.md §2.3 edge-cut row; §5.8).
        exchange = (coll.ring_reduce_scatter if sg.strategy == "src_ring"
                    else coll.reduce_scatter)

        def step(ranks_b, src, dst, valid, ip, inv_b, dang_b, e_b):
            weighted_b = ranks_b * inv_b  # [block], local
            per_edge = ops.gather_rows(weighted_b, src[0]) * valid[0]
            partial = local_reduce(per_edge, dst[0], ip[0], n_pad)
            contrib_b = exchange(partial, axis)  # [block]
            if redistribute:
                dmass = coll.psum(jnp.sum(ranks_b * dang_b), axis)
                contrib_b = contrib_b + dmass * e_b
            return (1.0 - damping) * total_mass * e_b + damping * contrib_b

        state_spec = P(axis)
        vec_spec = P(axis)
        local_delta = lambda new, old: coll.psum(jnp.sum(jnp.abs(new - old)), axis)
    else:
        # state: [block] rank shard per device; inv/dangling/e are likewise
        # node-sharded (per-chip HBM holds only 1/D of every [n_pad] vector,
        # which is the whole point of this strategy); all_gather the
        # degree-weighted ranks, psum only the dangling-mass scalar.
        def step(ranks_b, src, dst_local, valid, ip, inv_b, dang_b, e_b):
            weighted_full = coll.all_gather(ranks_b * inv_b, axis)
            per_edge = ops.gather_rows(weighted_full, src[0]) * valid[0]
            contrib_b = local_reduce(per_edge, dst_local[0], ip[0], block)
            if redistribute:
                dmass = coll.psum(jnp.sum(ranks_b * dang_b), axis)
                contrib_b = contrib_b + dmass * e_b
            return (1.0 - damping) * total_mass * e_b + damping * contrib_b

        state_spec = P(axis)
        vec_spec = P(axis)
        local_delta = lambda new, old: coll.psum(jnp.sum(jnp.abs(new - old)), axis)

    def sharded_pagerank(ranks0, *arrays):
        # one scan/while skeleton for every fixpoint in the repo: the
        # dataflow core's iterate combinator (dataflow/fixpoint.py), with
        # this strategy's collective delta as the convergence gauge
        return dataflow.iterate(
            lambda ranks: step(ranks, *arrays), ranks0,
            iterations=cfg.iterations, tol=cfg.tol, delta_fn=local_delta,
        )

    edge_spec = P(axis, None)
    mapped = shard_map(
        sharded_pagerank,
        mesh=mesh,
        in_specs=(state_spec, edge_spec, edge_spec, edge_spec, edge_spec,
                  *head_specs, vec_spec, vec_spec, vec_spec),
        out_specs=(state_spec, P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def sharded_graph_layout(sg: ShardedGraph, mesh: Mesh) -> tuple:
    """The runner's graph operands, in argument order, each paired with
    its sharding on ``mesh`` — what :func:`device_put_sharded_graph`
    places, and what a compile for a described (not attached) mesh turns
    into shapes."""
    axis = mesh.axis_names[0]
    esh = NamedSharding(mesh, P(axis, None))
    if sg.strategy == "owned":
        shard = sg.owned
        tsh = NamedSharding(mesh, P(axis))
        rsh = NamedSharding(mesh, P())
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
    # Node-state vectors follow the strategy: replicated under ``edges`` /
    # ``hybrid`` (the step reads the full vectors), node-sharded under
    # ``nodes`` (1/D per-chip HBM — the strategy's reason to exist).
    replicated_state = sg.strategy in ("edges", "hybrid")
    vsh = NamedSharding(mesh, P() if replicated_state else P(axis))
    out = [
        (sg.src, esh),
        (sg.dst, esh),
        (sg.valid, esh),
        (sg.local_indptr, esh),
    ]
    if sg.strategy == "hybrid":
        out.append((sg.head_src, NamedSharding(mesh, P(axis, None, None))))
        out.append((sg.head_node, esh))
    out.append((sg.inv_outdeg, vsh))
    out.append((sg.dangling, vsh))
    return tuple(out)


def device_put_sharded_graph(sg: ShardedGraph, mesh: Mesh):
    return tuple(jax.device_put(a, sh) for a, sh in sharded_graph_layout(sg, mesh))


def shard_segment_reduce(sg: ShardedGraph, spmv_impl: str) -> str | None:
    """Which reduction each shard's sorted segment sum lowers under this
    partition: ``"scan"`` (the slice's CSR pointers and more than one
    512-edge row: no scatter), ``"scatter"``, or None where
    ``spmv_impl`` reduces by prefix sums.  ``owned`` builds no pointers."""
    if spmv_impl != "segment":
        return None
    if sg.strategy == "owned":
        return "scatter"
    return ops.segment_reduce_for(sg.dst.shape[1], has_indptr=True)


def shard_gather_chunks(sg: ShardedGraph) -> dict[str, int]:
    """How many chunks each shard's per-edge rank-table gathers run in
    (:func:`ops.gather_chunks`): the tail's and the head's, 1 where the
    gather is whole or the strategy has no head."""
    if sg.strategy == "owned":
        tail, head = sg.owned.tail_src_idx, sg.owned.head_src_idx
    else:
        tail, head = sg.src, sg.head_src
    return {
        "tail": ops.gather_chunks(tail.shape[1:]),
        "head": 1 if head is None else ops.gather_chunks(head.shape[1:]),
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
                    device_put_sharded_graph(self.sg, mesh))
        # the static per-step exchange footprint: ICI bytes each device
        # sends per iteration under this partition (the sublinearity gauge
        # the MULTICHIP scale sweep + trace_diff comm gate consume)
        item = np.dtype(cfg.dtype).itemsize
        if self.sg.strategy == "owned":
            sh = self.sg.owned
            entries = ob.comm_entries_per_step(self.d, sh.b_pad, sh.h_pad)
        else:
            entries = _comm_entries(
                self.sg.strategy, self.d, self.sg.n_pad, self.sg.block
            )
        self.comm_bytes_per_step = int(entries * item)
        obs.gauge("pagerank.comm_bytes_per_step", self.comm_bytes_per_step)
        metrics.record(
            event="partition", strategy=strategy, devices=self.d,
            block=self.sg.block, edges_per_device=int(
                self.sg.owned.e_dev + self.sg.owned.he_dev
                if self.sg.strategy == "owned" else self.sg.src.shape[1]
            ),
            pad_frac=round(self.sg.pad_frac, 4), secs=t_part.elapsed,
            put_secs=t_put.elapsed,
            comm_bytes_per_step=self.comm_bytes_per_step,
            segment_reduce=shard_segment_reduce(self.sg, cfg.spmv_impl),
            gather_chunks=shard_gather_chunks(self.sg),
        )
        axis = mesh.axis_names[0]
        self._cfg = cfg
        self._metrics = metrics
        if self.sg.strategy == "owned":
            # owned-slice state: a (tail sharded, head replicated) pair
            # behind the dataflow OwnedArray view, plus the lagged-delta
            # carry slots put_ranks adds
            shard = self.sg.owned
            self._tail_sh = NamedSharding(mesh, P(axis))
            self._repl_sh = NamedSharding(mesh, P())
            self.state_sharding = self._tail_sh
            self.olayout = OwnedArray.from_shard(
                shard, tail_sharding=self._tail_sh,
                head_sharding=self._repl_sh,
            )
            e_t, e_h = ob.split_global(
                shard, ops.restart_vector(self.sg.n, cfg), cfg.dtype
            )
            self.e_vec = (jax.device_put(e_t, self._tail_sh),
                          jax.device_put(e_h, self._repl_sh))
            self.layout = None
            return
        self.state_sharding = (
            NamedSharding(mesh, P())
            if self.sg.strategy in ("edges", "hybrid")
            else NamedSharding(mesh, P(axis))
        )
        self.e_vec = jax.device_put(_restart_padded(self.sg, cfg),
                                    self.state_sharding)
        # the dataflow partitioned-collection view of the rank state: one
        # logical [n] array behind the padded/relabeled device layout
        self.layout = PartitionedArray.from_plan(
            self.sg.n, self.sg.n_pad, self.sg.node_map, self.state_sharding
        )

    def make_runner(self, seg_cfg: PageRankConfig):
        """The jitted runner of one segment config, built once: a later
        job reuses it, and with it its compiled program."""
        if seg_cfg not in self._runners:
            self._runners[seg_cfg] = make_sharded_runner(self.sg, seg_cfg, self.mesh)
        return self._runners[seg_cfg]

    def args(self, rd) -> tuple:
        """The runner's arguments around the rank state ``rd``."""
        if self.sg.strategy == "owned":
            return (rd, *self.dev, *self.e_vec)
        return (rd, *self.dev, self.e_vec)

    def invoke(self, runner, rd):
        if self.sg.strategy == "owned":
            # The owned carry is DONATED: the delta fetch gets its own
            # guarded site so a transient sync failure re-pulls the live
            # OUTPUT scalar instead of letting the segment site's retry
            # re-dispatch into the consumed carry (models/pagerank.py's
            # pagerank_delta_sync discipline).
            owned_runner = runner
            with obs.span("pagerank.dispatch"):
                rd, iters, delta = owned_runner(rd, *self.dev, *self.e_vec)
            with obs.span("pagerank.delta_sync"):
                delta = float(rx.device_get(
                    delta, site="pagerank_delta_sync",
                    metrics=self._metrics,
                    checkpoint_dir=self._cfg.checkpoint_dir,
                ))
            return rd, iters, delta
        # a fresh runner traces, lowers and compiles inside the dispatch span
        with obs.span("pagerank.dispatch"):
            rd, iters, delta = runner(*self.args(rd))
        with obs.span("pagerank.delta_sync"):
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
        return self.layout.put(ranks_g, self._cfg.dtype).value

    def extract_np(self, rd) -> np.ndarray:
        """Padded device state -> global [n] ranks (checkpoint payload)."""
        with obs.span("pagerank.ckpt_pull"):
            if self.sg.strategy == "owned":
                return self.olayout.with_value(rd[0], rd[1]).pull(
                    site="pagerank_ckpt_pull", metrics=self._metrics,
                    checkpoint_dir=self._cfg.checkpoint_dir,
                )
            return self.layout.with_value(rd).pull(
                site="pagerank_ckpt_pull", metrics=self._metrics,
                checkpoint_dir=self._cfg.checkpoint_dir,
            )


def _make_elastic_rebuild(graph: Graph, cfg: PageRankConfig, strategy: str,
                          metrics: MetricsRecorder, exec_box: dict):
    """The mesh-shrink rung for run_segments (driver.ElasticResult
    contract): salvage the global ranks, checkpoint them, rebuild the mesh
    over the surviving devices (the ``nodes_balanced`` planner re-balances
    its edge splits for the new count), and rerun the failed segment with
    zero recomputed *committed* iterations."""

    def rebuild(exc, ranks_dev, done, seg_cfg):
        if not elastic.enabled() or not elastic.is_device_loss(exc):
            raise exc
        idx = elastic.device_index(exc)
        if idx is not None:
            elastic.health().mark_lost(idx)
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
                latest = (ckpt.latest_checkpoint(cfg.checkpoint_dir)
                          if cfg.checkpoint_dir else None)
                if latest is None:
                    raise exc
                step, arrays, _ = ckpt.load_checkpoint(
                    latest, cfg.config_hash()
                )
                ranks_g, at_iter = arrays["ranks"], int(step)
                break
        if cfg.checkpoint_dir:
            ckpt.save_checkpoint(
                cfg.checkpoint_dir, at_iter, {"ranks": ranks_g},
                cfg.config_hash(), extra={"devices": old.d},
            )
        # (2)-(4) shrink / rebuild / rerun — as a LOOP, because a second
        # device can die while the rerun itself is in flight (the elastic
        # gap, ISSUE 8): the rerun runs as one chaos-hooked attempt with
        # no exhaustion of its own, and a further loss re-enters this
        # ladder — re-plan from the already-shrunk mesh — instead of
        # surfacing as ResilienceExhausted.  Committed iterations
        # (< at_iter) are never recomputed on any lap.
        devices = list(old.mesh.devices.flat)
        axis = old.mesh.axis_names[0]
        todo2 = done - at_iter + seg_cfg.iterations
        seg_cfg2 = dataclasses.replace(seg_cfg, iterations=todo2)
        while True:
            plan = elastic.plan_shrink(devices)
            if plan is None:
                raise exc
            with elastic.publish_shrink("pagerank_step", plan, exc, metrics):
                # keep the dying mesh's axis name: a caller-provided mesh
                # may not be named NODES_AXIS, and the runner/shardings
                # are built from whatever the mesh declares
                new_mesh = rebuild_mesh(plan.devices, axis)
                # repartition for the survivors
                new = _ShardedExec(graph, cfg, new_mesh, strategy, metrics)
                rd2 = new.put_ranks(ranks_g)
            try:
                rd2, iters, delta = rx.attempt_once(
                    lambda n=new, r=rd2, c=seg_cfg2: n.invoke(
                        n.make_runner(c), r
                    ),
                    site="pagerank_elastic_rerun",
                )
                break
            except Exception as exc2:  # noqa: BLE001 — re-entry filter below
                lost = elastic.unwrap_device_loss(exc2)
                if lost is None:
                    raise
                idx2 = elastic.device_index(lost)
                if idx2 is not None:
                    elastic.health().mark_lost(idx2)
                exc = lost
                devices = list(new_mesh.devices.flat)
        exec_box["exec"] = new
        effective = at_iter + int(iters) - done
        return driver.ElasticResult(
            rd2, effective, delta, new.make_runner, new.invoke,
            new.extract_np, {"devices": new.d},
        )

    return rebuild


class ShardedPageRank:
    """A graph partitioned and resident on a mesh, with its runners: built
    once, then any number of PageRank jobs run on it.

    Building resolves ``strategy="auto"`` (:func:`auto_select_strategy`,
    by per-chip memory and degree shape), partitions the graph on the
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
        strategy: str = "edges",
        metrics: MetricsRecorder | None = None,
    ):
        ensure_dtype_support(cfg.dtype)
        self.metrics = metrics or MetricsRecorder()
        if mesh is None:
            mesh = make_mesh(n_devices, NODES_AXIS)
        d = mesh.devices.size
        self.graph = graph
        if strategy == "auto" and graph.n_nodes:
            strategy = auto_select_strategy(
                graph, d, dtype=cfg.dtype,
                head_coverage=cfg.head_coverage,
                head_row_width=cfg.head_row_width,
            )
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
        # mesh.  The rung swaps exec_box so the node_map below matches the
        # layout the returned padded ranks were produced in.
        def pull_rebuild(exc):
            if not elastic.enabled() or not elastic.is_device_loss(exc):
                raise exc
            idx = elastic.device_index(exc)
            if idx is not None:
                elastic.health().mark_lost(idx)
            old = exec_box["exec"]
            at_iter, ranks_g = 0, ops.init_ranks(old.sg.n, cfg)
            if cfg.checkpoint_dir:
                latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
                if latest is not None:
                    step, arrays, _ = ckpt.load_checkpoint(latest, cfg.config_hash())
                    at_iter, ranks_g = int(step), arrays["ranks"]
            devices = list(old.mesh.devices.flat)
            axis = old.mesh.axis_names[0]
            todo = done - at_iter
            seg_cfg = dataclasses.replace(
                cfg, iterations=todo, checkpoint_every=0, checkpoint_dir=None
            )
            # loop for the same reason as the segment rung: a second loss
            # during the re-run of the uncommitted span re-enters the ladder
            # (re-plan from the shrunk mesh) instead of exhausting
            while True:
                plan = elastic.plan_shrink(devices)
                if plan is None:
                    raise exc
                with elastic.publish_shrink(
                    "pagerank_result_pull", plan, exc, metrics
                ):
                    new_mesh = rebuild_mesh(plan.devices, axis)
                    new = _ShardedExec(graph, cfg, new_mesh, strategy, metrics)
                    rd2 = new.put_ranks(ranks_g)
                if todo <= 0:
                    break
                try:
                    rd2, _, _ = rx.attempt_once(
                        lambda n=new, r=rd2: n.invoke(n.make_runner(seg_cfg), r),
                        site="pagerank_elastic_rerun",
                    )
                    break
                except Exception as exc2:  # noqa: BLE001 — re-entry filter below
                    lost = elastic.unwrap_device_loss(exc2)
                    if lost is None:
                        raise
                    idx2 = elastic.device_index(lost)
                    if idx2 is not None:
                        elastic.health().mark_lost(idx2)
                    exc = lost
                    devices = list(new_mesh.devices.flat)
            exec_box["exec"] = new
            # same site: chaos's device_lost is gated on the health registry,
            # so the acknowledged loss cannot re-fire here
            with obs.span("pagerank.result_pull_rebuilt"):
                return rx.device_get(
                    (rd2[0], rd2[1]) if strategy == "owned" else rd2,
                    site="pagerank_result_pull", metrics=metrics,
                    checkpoint_dir=cfg.checkpoint_dir,
                )

        with obs.span("pagerank.result_pull"):
            # owned state is a (tail, head, dslot, gdelta) carry: only the
            # two rank components cross D2H — the delta slots are scratch
            pull_view = (
                (ranks_dev[0], ranks_dev[1]) if strategy == "owned"
                else ranks_dev
            )
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
        if strategy == "owned":
            ranks_final = ob.merge_global(
                exec_.sg.owned, ranks_np[0], ranks_np[1]
            )
        else:
            ranks_final = ranks_np[exec_.sg.node_map]
        return PageRankResult(
            ranks=ranks_final, iterations=done,
            l1_delta=last_delta, metrics=metrics,
        )


def run_pagerank_sharded(
    graph: Graph,
    cfg: PageRankConfig,
    *,
    n_devices: int | None = None,
    mesh: Mesh | None = None,
    strategy: str = "edges",
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
