"""PageRank numeric core: one XLA program per iteration loop.

Reference counterpart (SURVEY.md §3.1, BASELINE.json:5): the per-iteration
Spark chain ``links.join(ranks).flatMap(computeContribs).reduceByKey(add)
.mapValues(0.15 + 0.85*r)`` — two shuffle stages per iteration, scheduled by
the DAGScheduler, executed as per-record iterator chains.

TPU-native design: the whole iteration is one sparse matvec plus an axpy —
``contribs = Aᵀ · (ranks / outdeg)``; ``ranks' = base + d·(contribs [+
dangling])`` — expressed as a gather + ``segment_sum`` over destination-
sorted edges (the `reduceByKey` becomes a contiguous segmented reduction the
MXU/VPU pipeline, not a shuffle), and the *entire loop* lives inside one
``jit``-compiled ``lax.scan`` / ``lax.while_loop``: zero host round-trips
between iterations, XLA fuses the damping/axpy/delta into the reduction's
epilogue.

Semantics flags (SURVEY.md §3.1 dangling-node caveat):
- ``dangling=drop``        mass at out-degree-0 nodes vanishes (canonical
                           Spark example behavior).
- ``dangling=redistribute`` dangling mass re-spread over the restart
                           distribution (textbook/networkx behavior; keeps
                           ``sum(ranks)`` invariant).
- ``spark_exact``          additionally reproduces the example's shrinking
                           key-set: nodes that receive no contribution drop
                           out of the rank table entirely (rank 0, and they
                           stop contributing even if they have out-links).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.fixpoint import iterate
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import Graph
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    TUNABLE_DEFAULTS,
    DanglingMode,
    PageRankConfig,
    RankInit,
)


class HybridLayout(NamedTuple):
    """Static degree-aware head/tail split of the dst-sorted edge array
    (*Sparse Allreduce*'s dense-head/sparse-tail decomposition of a
    power-law degree distribution, blocked for the MXU per *RankMap*).

    The **head** is the top-k in-degree destinations covering roughly
    ``coverage`` of all edges (every one with in-degree >= the row width,
    so a dense row is never mostly padding): each head node's in-edges are
    chunked into fixed-width rows of ``head_src``, whose per-iteration
    reduction is a single ``[R, W] @ [W]`` matvec on the MXU — the hot,
    scatter-heavy rows of the power-law distribution stop touching the
    scatter path entirely.  The **tail** keeps the sorted-segment layout.
    Sentinel source id ``n`` points at the zero slot of the extended
    weight vector, so padding needs no mask."""

    head_ids: jax.Array  # int32 [H] head node ids (in-degree descending)
    head_src: jax.Array  # int32 [R, W] per-row edge sources (sentinel n)
    head_row_node: jax.Array  # int32 [R] row -> head slot, non-decreasing
    tail_src: jax.Array  # int32 [Et]
    tail_dst: jax.Array  # int32 [Et], non-decreasing
    tail_indptr: jax.Array  # int32 [N+1] CSR pointers over the tail edges
    head_w: jax.Array | None = None  # f [R, W] edge weights (0 at sentinels)
    tail_w: jax.Array | None = None  # f [Et] edge weights


class ShuffleLayout(NamedTuple):
    """Sort-based static-shuffle layout: the dst-sorted edge array padded
    so every destination's run occupies whole fixed-width buckets.  The
    per-iteration reduction is then a pure ``reshape -> reduce`` over the
    bucket matrix plus a bucket-granular (B× smaller) sorted segment-sum —
    no edge-granular scatter or prefix scan survives on the contribution
    side.  Sentinel source id ``n`` reads the zero slot of the extended
    weight vector."""

    bucket_src: jax.Array  # int32 [NB, B] per-bucket edge sources
    bucket_node: jax.Array  # int32 [NB] bucket -> dst node, non-decreasing
    bucket_w: jax.Array | None = None  # f [NB, B] edge weights (0 at pads)


class DeviceGraph(NamedTuple):
    """Device-resident graph state (the reference's ``links.cache()`` —
    SURVEY.md A3: built once, reused across all iterations)."""

    src: jax.Array  # int32 [E], edge sources, dst-sorted order
    dst: jax.Array  # int32 [E], non-decreasing
    inv_outdeg: jax.Array  # f[N], 1/out_degree — 1/out_STRENGTH on a
    # weighted graph — (0 at dangling nodes)
    dangling: jax.Array  # f[N], 1.0 where out_degree == 0
    has_outlinks: jax.Array  # f[N], 1.0 where out_degree > 0
    indptr: jax.Array | None = None  # int32 [N+1], CSR row pointers into dst
    hybrid: HybridLayout | None = None  # spmv_impl='hybrid' static layout
    shuffle: ShuffleLayout | None = None  # spmv_impl='sort_shuffle' layout
    # Per-edge weights in dst-sorted order (weighted PageRank, ISSUE 15):
    # the SpMV contribution becomes ``w(u,v) * rank[u] / strength[u]`` —
    # networkx ``pagerank(weight=)`` semantics.  None = unweighted.
    edge_weight: jax.Array | None = None


def _pow2_floor(x: int) -> int:
    return 1 << max(int(x).bit_length() - 1, 0)


def plan_hybrid_head(
    in_degree: np.ndarray,
    n_edges: int,
    *,
    coverage: float = 0.5,
    row_width: int = 128,
) -> tuple[np.ndarray, int]:
    """Head-membership policy shared by the single-chip layout builder and
    the sharded partition *planner* (parallel/pagerank_sharded.py) — the
    two must agree or the linted plan is not the materialized one.

    Returns ``(head_order, W)``: node ids in in-degree-descending order
    truncated to the head, and the effective row width.  The head is the
    smallest top-k covering ``coverage`` of all edges, where every member
    has in-degree >= W (a lower-degree node would make its dense row
    mostly padding — those stay on the tail path).  W adapts downward to
    the largest power of two <= the max in-degree so small graphs still
    exercise the dense path."""
    if n_edges == 0 or in_degree.size == 0:
        return np.zeros(0, np.int64), max(8, row_width)
    w = max(8, min(row_width, _pow2_floor(int(in_degree.max()))))
    order = np.argsort(-in_degree, kind="stable")
    deg_sorted = in_degree[order]
    k_deg = int(np.searchsorted(-deg_sorted, -w, side="right"))
    if k_deg == 0:
        return np.zeros(0, np.int64), w
    cum = np.cumsum(deg_sorted[:k_deg], dtype=np.int64)
    k_cov = int(np.searchsorted(cum, coverage * n_edges, side="left")) + 1
    k = min(k_deg, k_cov)
    return order[:k].astype(np.int64), w


class HybridHostLayout(NamedTuple):
    """Numpy form of :class:`HybridLayout` plus its padding accounting —
    built once on host at ``put_graph`` time (the amortized
    ``spmv_preprocess_secs`` bench.py records)."""

    head_ids: np.ndarray
    head_src: np.ndarray
    head_row_node: np.ndarray
    tail_src: np.ndarray
    tail_dst: np.ndarray
    tail_indptr: np.ndarray
    head_edges: int
    pad_slots: int  # sentinel slots in the dense rows
    head_w: np.ndarray | None = None  # [R, W] weights (0 at sentinels)
    tail_w: np.ndarray | None = None  # [Et] weights


def build_hybrid_layout(
    graph: Graph, *, coverage: float = 0.5, row_width: int = 128
) -> HybridHostLayout:
    """One-time host pass: degree sort -> head/tail split -> dense row
    blocking.  O(E) after the cached csr_indptr; fully vectorized."""
    n = graph.n_nodes
    ip = graph.csr_indptr()
    indeg = np.diff(ip)
    head_ids, w = plan_hybrid_head(
        indeg, graph.n_edges, coverage=coverage, row_width=row_width
    )
    in_head = np.zeros(n + 1, bool)
    in_head[head_ids] = True

    # dense head rows: each head node's in-edge run chunked into whole
    # rows of width w, the last row padded with the sentinel id n.  A run
    # fills a contiguous stretch of the row-major rows, so each is one
    # slice copy (no per-edge index arrays: at a billion edges those are
    # several GB each).
    deg = indeg[head_ids] if head_ids.size else np.zeros(0, np.int64)
    rows_per = -(-deg // w)
    r = int(rows_per.sum())
    head_src = np.full((r, w), n, np.int32)
    weighted = graph.weight is not None
    head_w = np.zeros((r, w), np.float64) if weighted else None  # graftlint: disable=dtype-drift (host staging; cast to the run dtype at put_graph)
    head_row_node = np.repeat(
        np.arange(head_ids.size, dtype=np.int64), rows_per
    ).astype(np.int32)
    if head_ids.size:
        slot_start = np.concatenate([[0], np.cumsum(rows_per)[:-1]]) * w
        flat_src = head_src.reshape(-1)
        flat_w = head_w.reshape(-1) if weighted else None
        for at, lo, k in zip(slot_start.tolist(), ip[head_ids].tolist(), deg.tolist()):
            flat_src[at:at + k] = graph.src[lo:lo + k]
            if weighted:
                flat_w[at:at + k] = graph.weight[lo:lo + k]

    keep = ~in_head[graph.dst]
    tail_src = graph.src[keep].astype(np.int32)
    tail_dst = graph.dst[keep].astype(np.int32)
    tail_indptr = np.searchsorted(tail_dst, np.arange(n + 1, dtype=np.int32)).astype(np.int32)
    head_edges = int(graph.n_edges - tail_src.size)
    return HybridHostLayout(
        head_ids=head_ids.astype(np.int32),
        head_src=head_src,
        head_row_node=head_row_node,
        tail_src=tail_src,
        tail_dst=tail_dst,
        tail_indptr=tail_indptr,
        head_edges=head_edges,
        pad_slots=r * w - head_edges,
        head_w=head_w,
        tail_w=graph.weight[keep] if weighted else None,
    )


def build_shuffle_layout(
    graph: Graph, *,
    bucket_width: int = TUNABLE_DEFAULTS["shuffle_bucket_width"],
) -> tuple[
    np.ndarray, np.ndarray, np.ndarray | None
]:
    """One-time host pass for the sort-based static shuffle: pad every
    destination's (already dst-sorted) edge run to whole buckets of width
    ``bucket_width``.  Returns ``(bucket_src [NB, B], bucket_node [NB],
    bucket_w [NB, B] | None)`` — fully vectorized, no per-node python
    loop; ``bucket_w`` carries per-edge weights (0 at pad slots) for a
    weighted graph."""
    n, e, b = graph.n_nodes, graph.n_edges, bucket_width
    ip = graph.csr_indptr()
    indeg = np.diff(ip)
    buckets_per = -(-indeg // b)
    nb = int(buckets_per.sum())
    bucket_src = np.full((nb, b), n, np.int32)
    bucket_w = (
        np.zeros((nb, b), np.float64)  # graftlint: disable=dtype-drift (host staging; cast to the run dtype at put_graph)
        if graph.weight is not None else None
    )
    bucket_node = np.repeat(
        np.arange(n, dtype=np.int64), buckets_per
    ).astype(np.int32)
    if e:
        # per-edge (row, col) inside its node's bucket block
        offs = np.arange(e, dtype=np.int64) - np.repeat(ip[:-1], indeg)
        bucket_start = np.concatenate([[0], np.cumsum(buckets_per)])
        row = np.repeat(bucket_start[:-1], indeg) + offs // b
        bucket_src[row, offs % b] = graph.src
        if bucket_w is not None:
            bucket_w[row, offs % b] = graph.weight
    return bucket_src, bucket_node, bucket_w


def put_graph(
    graph: Graph,
    dtype: str = "float32",
    *,
    layout: str | None = None,
    head_coverage: float = TUNABLE_DEFAULTS["head_coverage"],
    head_row_width: int = TUNABLE_DEFAULTS["head_row_width"],
    bucket_width: int = TUNABLE_DEFAULTS["shuffle_bucket_width"],
    keep_edge_arrays: bool = True,
) -> DeviceGraph:
    """Host Graph → device arrays (one host→device transfer per run).

    ``layout`` additionally builds the static SpMV layout an impl needs:
    ``"hybrid"`` (degree-aware dense head + segment tail) or
    ``"sort_shuffle"`` (fixed-width dst buckets).  See
    :func:`layout_for_impl` for the impl -> layout mapping.

    ``keep_edge_arrays=False`` skips the raw ``src``/``dst``/``indptr``
    device upload (zero-length placeholders instead): the layout impls
    never read them, and at bench scale they are ~3E dead int32 on HBM
    plus transfer time — only valid when the caller commits to a
    layout-backed impl (models.pagerank.put_graph_for does)."""
    # Weighted graphs normalize by out-STRENGTH (Σ outgoing weights —
    # networkx stochastic_graph semantics); unweighted by out-degree.
    # Dangling is out_degree == 0 under both (weights are positive).
    inv = graph.inv_out_strength(dtype)
    if not keep_edge_arrays and layout is None:
        raise ValueError("keep_edge_arrays=False requires a static layout")
    src_h = graph.src if keep_edge_arrays else np.zeros(0, np.int32)
    dst_h = graph.dst if keep_edge_arrays else np.zeros(0, np.int32)
    indptr = (
        graph.csr_indptr().astype(np.int32)
        if keep_edge_arrays else np.zeros(0, np.int32)
    )
    weighted = graph.weight is not None
    edge_weight = (
        jnp.asarray(graph.weight.astype(dtype))
        if weighted and keep_edge_arrays else None
    )
    hybrid = None
    shuffle = None
    if layout == "hybrid":
        hl = build_hybrid_layout(
            graph, coverage=head_coverage, row_width=head_row_width
        )
        hybrid = HybridLayout(
            head_ids=jnp.asarray(hl.head_ids),
            head_src=jnp.asarray(hl.head_src),
            head_row_node=jnp.asarray(hl.head_row_node),
            tail_src=jnp.asarray(hl.tail_src),
            tail_dst=jnp.asarray(hl.tail_dst),
            tail_indptr=jnp.asarray(hl.tail_indptr),
            head_w=(jnp.asarray(hl.head_w.astype(dtype))
                    if hl.head_w is not None else None),
            tail_w=(jnp.asarray(hl.tail_w.astype(dtype))
                    if hl.tail_w is not None else None),
        )
    elif layout == "sort_shuffle":
        bucket_src, bucket_node, bucket_w = build_shuffle_layout(
            graph, bucket_width=bucket_width
        )
        shuffle = ShuffleLayout(
            bucket_src=jnp.asarray(bucket_src),
            bucket_node=jnp.asarray(bucket_node),
            bucket_w=(jnp.asarray(bucket_w.astype(dtype))
                      if bucket_w is not None else None),
        )
    elif layout is not None:
        raise ValueError(f"unknown graph layout {layout!r}")
    return DeviceGraph(
        src=jnp.asarray(src_h),
        dst=jnp.asarray(dst_h),
        inv_outdeg=jnp.asarray(inv),
        dangling=jnp.asarray((graph.out_degree == 0).astype(dtype)),
        has_outlinks=jnp.asarray((graph.out_degree > 0).astype(dtype)),
        indptr=jnp.asarray(indptr),
        hybrid=hybrid,
        shuffle=shuffle,
        edge_weight=edge_weight,
    )


def layout_for_impl(impl: str) -> str | None:
    """Which static layout ``put_graph`` must build for an spmv impl."""
    return {"hybrid": "hybrid", "sort_shuffle": "sort_shuffle"}.get(impl)


def restart_vector(n: int, cfg: PageRankConfig) -> np.ndarray:
    """The teleport distribution e: uniform for standard PageRank, an
    indicator over the source set for personalized PageRank
    (BASELINE.json:10; SURVEY.md §3.4)."""
    dtype = cfg.dtype
    if cfg.personalize is None:
        return np.full(n, 1.0 / n, dtype=dtype)
    e = np.zeros(n, dtype=dtype)
    idx = np.asarray(cfg.personalize, dtype=np.int64)
    if idx.size == 0:
        raise ValueError("personalize must name at least one node")
    if (idx < 0).any() or (idx >= n).any():
        raise ValueError(f"personalize node ids out of range [0, {n})")
    # np.add.at so duplicate ids accumulate — e must always sum to 1.
    np.add.at(e, idx, 1.0 / idx.size)
    return e


def init_ranks(n: int, cfg: PageRankConfig) -> np.ndarray:
    if cfg.init is RankInit.ONE:
        return np.ones(n, dtype=cfg.dtype)
    return np.full(n, 1.0 / n, dtype=cfg.dtype)


# Most indices one gather reads at once.  XLA stages a gather's table in a
# TPU's VMEM only while the gather reads up to ~100 M indices; beyond that
# the table stays in HBM.  On a TPU v5e, 131 M random indices into a 17 M
# entry f32 table cost 14.6 ns an element in one gather, 8.6 in chunks of
# 16 M, 7.2 in chunks of 8 M and of 4 M, 6.7 in chunks of 2 M; 5.1 M
# indices into a 0.9 M-entry table cost 6.7 whole.  The cap sits where the
# rate first levels off, above the gathers that already run at the chunked
# rate whole.
GATHER_CAP = 1 << 23


def gather_chunks(shape: tuple[int, ...]) -> int:
    """How many chunks of whole leading rows :func:`gather_rows` splits an
    index array of ``shape`` into: 1 up to :data:`GATHER_CAP` indices, else
    as few as keep each chunk within the cap (or one row, where a row alone
    exceeds it)."""
    row = math.prod(shape[1:])
    if math.prod(shape) <= GATHER_CAP:
        return 1
    return -(-shape[0] // max(1, GATHER_CAP // row))


def gather_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a 1-D table, split along ``idx``'s leading axis
    into :func:`gather_chunks` chunks under one loop where ``idx`` holds
    more than :data:`GATHER_CAP` indices, so that XLA can keep the table in
    VMEM.  The chunks are equal; the last one ends at the leading axis's
    end and may overlap its predecessor, which it overwrites with the same
    values.  At or below the cap this is exactly ``table[idx]``, with no
    loop."""
    k = gather_chunks(idx.shape)
    if k == 1:
        return table[idx]
    lead = idx.shape[0]
    size = -(-lead // k)
    rest = (0,) * (idx.ndim - 1)

    def chunk(i, out):
        start = jnp.minimum(i * size, lead - size)
        part = jax.lax.dynamic_slice(idx, (start, *rest), (size, *idx.shape[1:]))
        return jax.lax.dynamic_update_slice(out, table[part], (start, *rest))

    return jax.lax.fori_loop(0, k, chunk, jnp.zeros(idx.shape, table.dtype))


# The raw-edge impls whose SpMV gathers through :func:`_edge_values`.
EDGE_GATHER_IMPLS = ("segment", "cumsum", "cumsum_mxu")


def _edge_values(dg: DeviceGraph, weighted_ranks: jax.Array) -> jax.Array:
    """Per-edge contribution ``weighted_ranks[src] (* w(src, dst))`` — the
    one place the optional edge-weight multiply lives for the raw-edge
    impls (:data:`EDGE_GATHER_IMPLS` share it)."""
    per_edge = gather_rows(weighted_ranks, dg.src)
    if dg.edge_weight is not None:
        per_edge = per_edge * dg.edge_weight
    return per_edge


# Row width of sorted_segment_sum's in-row scan: the longest run of values
# summed as a tree of log2(_SEGMENT_ROW) adds before runs join across rows.
_SEGMENT_ROW = 512


def sorted_segment_sum(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    indptr: jax.Array | None = None,
) -> jax.Array:
    """``jax.ops.segment_sum`` over ascending ``segment_ids``, accurate in
    f32 however long a segment runs.

    A scatter-add sums each segment sequentially into one accumulator, so
    a hub's rounding error grows with its in-degree: at web-Google shape
    (one node with 780,937 in-edges) the plain scatter left PageRank L1
    4.0e-3 from a float64 reference after 20 iterations, on a v5e and on
    the CPU alike.  Here each ``_SEGMENT_ROW``-wide row of ``values`` is
    first summed per run of equal ids by a segmented Hillis-Steele scan
    (elementwise shifts, no gather or scatter).

    With ``indptr``, the CSR pointers of ``segment_ids`` (``[num_segments
    + 1]``), and more than one row of values, no scatter is left: the runs
    are joined across rows and each segment's total is read at its last
    position (:func:`_sum_runs_at_ends`).  Without them one scatter-add
    takes each run's total from its last position: one term per row a
    segment touches, but a scatter over every position."""
    if indptr is not None and indptr.shape != (num_segments + 1,):
        raise ValueError(
            f"indptr has shape {indptr.shape}, want ({num_segments + 1},)"
        )
    if values.shape[0] <= _SEGMENT_ROW:  # one row: a scatter of at most 512
        return jax.ops.segment_sum(
            values, segment_ids, num_segments=num_segments,
            indices_are_sorted=True,
        )
    if indptr is not None:
        return _sum_runs_at_ends(values, segment_ids, indptr)
    v, ids = _row_scan(values, segment_ids)
    run_end = jnp.concatenate(
        [ids[:, 1:] != ids[:, :-1], jnp.ones((ids.shape[0], 1), bool)], axis=1
    )
    return jax.ops.segment_sum(
        jnp.where(run_end, v, 0).ravel(), ids.ravel(),
        num_segments=num_segments, indices_are_sorted=True,
    )


def _row_scan(
    values: jax.Array, segment_ids: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """``(v, ids)``: both padded to ``[R, _SEGMENT_ROW]`` rows (the last id
    repeated, the values 0), ``v`` summed within each row by
    :func:`_segmented_scan`."""
    row = _SEGMENT_ROW
    pad = (-values.shape[0]) % row
    ids = jnp.pad(segment_ids, (0, pad), mode="edge").reshape(-1, row)
    return _segmented_scan(jnp.pad(values, (0, pad)).reshape(-1, row), ids), ids


def _segmented_scan(v: jax.Array, ids: jax.Array) -> jax.Array:
    """Inclusive sums along each row of ``v [R, W]`` over runs of equal
    ``ids`` (ascending along the row): a Hillis-Steele scan of log2(W)
    elementwise steps, so a run of any length sums as a tree."""

    def shift(x, d, fill):
        return jnp.pad(x[:, :-d], ((0, 0), (d, 0)), constant_values=fill)

    # after the step at distance d, v[i] sums the (up to 2d) values ending
    # at i that share its id: ids are sorted, so equal ids d apart bound a
    # run of equal ids, and unequal ones mean the run starts after i - d
    d = 1
    while d < v.shape[1]:
        v = v + jnp.where(shift(ids, d, -1) == ids, shift(v, d, 0), 0)
        d *= 2
    return v


# A jit of its own, so that a runner built for each job reuses its trace
# and lowering instead of lowering the scans' steps again: on a TPU v5e
# host that cut the runner's lowering from ~360 to ~80 ms a job.
@jax.jit
def _sum_runs_at_ends(
    values: jax.Array, segment_ids: jax.Array, indptr: jax.Array
) -> jax.Array:
    """:func:`sorted_segment_sum` through the CSR pointers, scatter-free.

    After :func:`_row_scan`, ``v[r, j]`` sums ``j``'s run within row ``r``
    up to ``j``.  The rows before ``r`` that hold row ``r``'s first id all
    end in it, and no other row does: so the carry into row ``r`` sums the
    run of row tails that ends at row ``r-1``, where row ``r-1`` ends in
    that id.  The same segmented scan over the ``R`` tails, keyed by each
    row's last id, sums those runs as a tree, however many rows a hub
    spans.  Each segment's total is then read at its last position."""
    v, ids = _row_scan(values, segment_ids)
    first, last = ids[:, 0], ids[:, -1]
    through = _segmented_scan(v[None, :, -1], last[None])[0]
    carry = jnp.concatenate([  # carry[r] into row r
        jnp.zeros(1, v.dtype),
        jnp.where(last[:-1] == first[1:], through[:-1], 0),
    ])
    # add each row's carry to its first run, then read every segment's
    # total at its last position: one gather of num_segments positions
    v = v + jnp.where(ids == first[:, None], carry[:, None], 0)
    start, end = indptr[:-1], indptr[1:]
    return jnp.where(end > start, v.ravel()[jnp.maximum(end - 1, 0)], 0)


def spmv_segment(dg: DeviceGraph, weighted_ranks: jax.Array, n: int) -> jax.Array:
    """contribs[v] = Σ_{(u,v)∈E} w(u,v)·weighted_ranks[u] via the sorted
    segment sum — the `reduceByKey(add)` of BASELINE.json:5 as one
    segmented reduction (w ≡ 1 unweighted).  ``dg.indptr``, where the
    graph has it, makes that reduction scatter-free (:func:`segment_reduce`)."""
    return sorted_segment_sum(
        _edge_values(dg, weighted_ranks), dg.dst, n, indptr=dg.indptr
    )


def segment_reduce(dg: DeviceGraph) -> str:
    """Which reduction :func:`spmv_segment` lowers for ``dg``: ``"scan"``
    (scatter-free) where the graph has CSR pointers and more than one row
    of edges, else ``"scatter"``."""
    return segment_reduce_for(dg.dst.shape[0], dg.indptr is not None)


def segment_reduce_for(n_values: int, has_indptr: bool) -> str:
    """Which reduction :func:`sorted_segment_sum` lowers for ``n_values``
    values, with or without their CSR pointers."""
    if not has_indptr or n_values <= _SEGMENT_ROW:
        return "scatter"
    return "scan"


def spmv_bcoo(dg: DeviceGraph, weighted_ranks: jax.Array, n: int) -> jax.Array:
    """Same contraction through jax.experimental.sparse.BCOO (the
    BASELINE.json:5 prescription) — kept as a benchmarked alternative."""
    from jax.experimental import sparse

    data = (
        dg.edge_weight if dg.edge_weight is not None
        else jnp.ones_like(weighted_ranks, shape=dg.src.shape)
    )
    mat = sparse.BCOO(
        (data, jnp.stack([dg.dst, dg.src], axis=1)),
        shape=(n, n),
        indices_sorted=True,
        unique_indices=True,
    )
    return mat @ weighted_ranks


def cumsum_diff_spmv(per_edge, indptr, cumsum_fn=jnp.cumsum) -> jax.Array:
    """Shared prefix-sum segmented-reduction skeleton: ``out[v] =
    cumsum(per_edge)[indptr[v+1]] - cumsum(per_edge)[indptr[v]]``, exploiting
    a sorted-segment invariant to replace the scatter-add with a cumsum
    plus two *monotone* gathers.  ``cumsum_fn`` is the prefix-sum primitive
    (``jnp.cumsum`` for the XLA variant, the Pallas carry kernel for
    spmv_impl='pallas'); accuracy analysis on :func:`spmv_cumsum`."""
    c0 = jnp.concatenate([jnp.zeros(1, per_edge.dtype), cumsum_fn(per_edge)])
    return c0[indptr[1:]] - c0[indptr[:-1]]


def cumsum_blocked(x: jax.Array, block: int = 128) -> jax.Array:
    """Inclusive prefix sum as MXU work instead of XLA's reduce-window.

    ``jnp.cumsum`` over millions of elements lowers to an O(E·log E)
    reduce-window chain on TPU; here the E-length scan becomes one
    ``[M, B] @ [B, B]`` upper-triangular matmul on the systolic array
    (row-wise inclusive cumsum of an ``[M, B]`` reshape) plus a B×-smaller
    recursive carry — ~2 HBM passes and trivial MXU FLOPs (E·B).  Error is
    the blocked-summation order, no worse than the sequential scan's.
    """
    n = x.shape[0]
    if n <= 4 * block:
        return jnp.cumsum(x)
    m = -(-n // block)
    xp = jnp.concatenate([x, jnp.zeros(m * block - n, x.dtype)]).reshape(m, block)
    # T[k, j] = 1 for k <= j: row-cumsum via one MXU matmul.  HIGHEST
    # precision keeps f32 inputs f32 on TPU (default would round through
    # bf16, breaking the "same accuracy class as the sequential scan"
    # contract); the FLOPs are trivial either way.
    tri = jnp.triu(jnp.ones((block, block), x.dtype))
    rows = jnp.matmul(xp, tri, precision=jax.lax.Precision.HIGHEST)
    row_tot = rows[:, -1]
    carry = cumsum_blocked(row_tot, block) - row_tot  # exclusive row carry
    return (rows + carry[:, None]).reshape(-1)[:n]


def spmv_cumsum(dg: DeviceGraph, weighted_ranks: jax.Array, n: int) -> jax.Array:
    """Prefix-sum SpMV through ``jnp.cumsum`` — measured 1.5x faster per
    PageRank iteration than ``segment_sum`` at web-Google scale on TPU v5e,
    where XLA's scatter path is the bottleneck.  Accuracy cost in float32:
    the prefix sum accumulates to the full vector mass before differencing,
    so per-SpMV L1 error is ~2e-4 relative (vs ~1e-5 for segment_sum);
    parity tests run it in float64 where both are exact to 1e-12.
    """
    if dg.indptr is None:
        raise ValueError("spmv_impl='cumsum' needs DeviceGraph.indptr (use put_graph)")
    return cumsum_diff_spmv(_edge_values(dg, weighted_ranks), dg.indptr)


def spmv_cumsum_mxu(dg: DeviceGraph, weighted_ranks: jax.Array, n: int) -> jax.Array:
    """The prefix-sum SpMV with the MXU-blocked cumsum (:func:`cumsum_blocked`)
    as the scan primitive — same accuracy class as spmv_cumsum."""
    if dg.indptr is None:
        raise ValueError("spmv_impl='cumsum_mxu' needs DeviceGraph.indptr (use put_graph)")
    return cumsum_diff_spmv(_edge_values(dg, weighted_ranks), dg.indptr,
                            cumsum_fn=cumsum_blocked)


def hybrid_rowsum(rows: jax.Array) -> jax.Array:
    """Dense-head row reduction: ``[R, W] -> [R]`` as one MXU matvec
    against a ones vector (the RankMap-style blocked contraction).  On a
    TPU the Pallas kernel streams the row matrix through VMEM in one HBM
    pass; elsewhere a plain row sum (the interpreter at bench scale would
    be pointless), which adds in one order whether or not XLA fuses the
    gather that made the rows into it: the CPU's dot does not.  The branch
    is picked for the platform the program is lowered for, so a compile for
    a described chip gets the kernel too."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pallas_kernels as pk

    return jax.lax.platform_dependent(
        rows, tpu=pk.rowsum_pallas, default=lambda r: r.sum(axis=1)
    )


def spmv_hybrid(dg: DeviceGraph, weighted_ranks: jax.Array, n: int) -> jax.Array:
    """Degree-aware hybrid SpMV: the high-in-degree head as a dense
    ``[R, W]`` gather + MXU row reduction (zero scatter traffic for the
    power-law hot rows), the long tail through the scatter-free
    prefix-sum/monotone-diff path over its own CSR pointers, combined
    with one scatter-add of H head totals.

    Accuracy class: the head rows sum in fixed blocked order (segment
    class — each node accumulates within its own rows only); the tail
    inherits the prefix-sum class of :func:`spmv_cumsum`, but over only
    the tail's mass — roughly half the accumulated error of the full
    cumsum impl at the default 0.5 head coverage."""
    hl = dg.hybrid
    if hl is None:
        raise ValueError("spmv_impl='hybrid' needs put_graph(layout='hybrid')")
    if hl.tail_src.shape[0]:
        per_tail = weighted_ranks[hl.tail_src]
        if hl.tail_w is not None:
            per_tail = per_tail * hl.tail_w
        contribs = cumsum_diff_spmv(per_tail, hl.tail_indptr)
    else:
        contribs = jnp.zeros(n, weighted_ranks.dtype)
    h = hl.head_ids.shape[0]
    if h:
        w_ext = jnp.concatenate(
            [weighted_ranks, jnp.zeros(1, weighted_ranks.dtype)]
        )
        rows = w_ext[hl.head_src]
        if hl.head_w is not None:
            rows = rows * hl.head_w  # sentinel slots carry weight 0
        row_sums = hybrid_rowsum(rows)
        head = jax.ops.segment_sum(
            row_sums, hl.head_row_node, num_segments=h, indices_are_sorted=True
        )
        contribs = contribs.at[hl.head_ids].add(head)
    return contribs


def spmv_sort_shuffle(
    dg: DeviceGraph, weighted_ranks: jax.Array, n: int
) -> jax.Array:
    """Sort-based static-shuffle SpMV: with every destination's edge run
    padded to whole fixed-width buckets at ``put_graph`` time, the
    per-iteration contribution side is a pure ``reshape -> reduce`` over
    the bucket matrix plus a bucket-granular sorted segment-sum — the
    edge-granular scatter/prefix machinery shrinks by the bucket width."""
    sl = dg.shuffle
    if sl is None:
        raise ValueError(
            "spmv_impl='sort_shuffle' needs put_graph(layout='sort_shuffle')"
        )
    if sl.bucket_src.shape[0] == 0:
        return jnp.zeros(n, weighted_ranks.dtype)
    w_ext = jnp.concatenate(
        [weighted_ranks, jnp.zeros(1, weighted_ranks.dtype)]
    )
    vals = w_ext[sl.bucket_src]
    if sl.bucket_w is not None:
        vals = vals * sl.bucket_w  # pad slots carry weight 0
    bucket_sums = vals.sum(axis=1)
    return jax.ops.segment_sum(
        bucket_sums, sl.bucket_node, num_segments=n, indices_are_sorted=True
    )


def spmv(dg: DeviceGraph, weighted: jax.Array, n: int, impl: str) -> jax.Array:
    """The one SpMV dispatch point: route a weighted gather+combine
    through the impl the graph's static layout was built for.  This is
    the ``dataflow.graph_combine`` shuffle backend — every fixpoint
    workload (PageRank, personalized PageRank, HITS) shares these tuned
    impls instead of owning scatter strategy privately."""
    if impl == "segment":
        return spmv_segment(dg, weighted, n)
    if impl == "bcoo":
        return spmv_bcoo(dg, weighted, n)
    if impl == "cumsum":
        return spmv_cumsum(dg, weighted, n)
    if impl == "cumsum_mxu":
        return spmv_cumsum_mxu(dg, weighted, n)
    if impl == "hybrid":
        return spmv_hybrid(dg, weighted, n)
    if impl == "sort_shuffle":
        return spmv_sort_shuffle(dg, weighted, n)
    if impl == "pallas":
        from page_rank_and_tfidf_using_apache_spark_tpu.ops import pallas_kernels as pk

        if dg.indptr is None:
            raise ValueError("spmv_impl='pallas' needs DeviceGraph.indptr (use put_graph)")
        # Mosaic compiles only for a TPU; everywhere else (CPU tests,
        # simulated meshes) the same kernel runs under the interpreter.
        def run(interpret, w):
            return pk.spmv_pallas(dg.src, dg.indptr, w, n=n,
                                  edge_weight=dg.edge_weight, interpret=interpret)

        return jax.lax.platform_dependent(
            weighted,
            tpu=functools.partial(run, False),
            default=functools.partial(run, True),
        )
    raise ValueError(f"unknown spmv impl {impl!r}")


def pagerank_step(
    ranks: jax.Array,
    dg: DeviceGraph,
    e: jax.Array,
    *,
    n: int,
    damping: float,
    dangling: DanglingMode,
    total_mass: float,
    impl: str = "segment",
) -> jax.Array:
    """One power-iteration step.

    ``total_mass`` is the invariant rank-vector sum: ``n`` under the Spark
    init=ONE convention (uniform restart term is then the familiar constant
    0.15), ``1.0`` under the textbook init=UNIFORM convention (restart term
    (1-d)/n).  The restart distribution ``e`` always sums to 1; both the
    restart and the redistributed dangling mass are spread according to it,
    so under dangling=redistribute ``sum(ranks) == total_mass`` is exactly
    preserved every step.
    """
    weighted = ranks * dg.inv_outdeg
    contribs = spmv(dg, weighted, n, impl)
    if dangling is DanglingMode.REDISTRIBUTE:
        # lost mass re-enters through the restart distribution e; on a
        # sharded mesh this sum is the lax.psum of BASELINE.json:5.
        dangling_mass = jnp.sum(ranks * dg.dangling)
        contribs = contribs + dangling_mass * e
    base = (1.0 - damping) * total_mass * e
    return base + damping * contribs


class SparkExactState(NamedTuple):
    """Carry for exact canonical-Spark-example emulation: the rank table's
    key set shrinks to nodes that received contributions (SURVEY.md §3.1)."""

    ranks: jax.Array  # f[N]; value only meaningful where present == 1
    present: jax.Array  # f[N]; 1.0 if node currently in the rank table


def spark_exact_step(
    state: SparkExactState, dg: DeviceGraph, *, n: int, damping: float, impl: str = "segment"
) -> SparkExactState:
    weighted = state.ranks * state.present * dg.inv_outdeg
    contribs = spmv(dg, weighted, n, impl)
    # A node re-enters the table iff some present source with out-links
    # points at it (join emits ≥1 record for it).
    received = spmv(dg, state.present * dg.has_outlinks, n, impl)
    present = (received > 0).astype(state.ranks.dtype)
    ranks = present * ((1.0 - damping) + damping * contribs)
    return SparkExactState(ranks=ranks, present=present)


def make_pagerank_runner(n: int, cfg: PageRankConfig):
    """Compile the full iteration loop into one XLA program.

    Returns ``run(dg, ranks0, e) -> (ranks, iters_done, final_delta)``.
    Fixed-iteration runs use ``lax.scan`` (XLA unrolls the loop body once and
    reuses it); tolerance runs use ``lax.while_loop`` carrying the L1 delta.
    The Python-side driver loop of the reference (SURVEY.md §3.1 🔥 outer
    loop) disappears entirely — there are no host round-trips between
    iterations.

    ``ranks0`` is **donated** (``donate_argnums=(1,)``): the carry is dead
    the moment the loop starts, so XLA reuses its buffer for the output
    ranks instead of holding two node-sized vectors live across the whole
    loop.  The input array is consumed — callers that re-invoke a runner
    must re-``device_put`` a fresh carry (the segment driver threads each
    segment's output into the next, so it never reuses one; bench.py re-puts
    per timing rep).  The tier-3 donation verifier (analysis/cost.py) holds
    this contract against the lowered computation's input/output aliasing.

    The loop skeleton is the dataflow core's :func:`dataflow.fixpoint
    .iterate` combinator — one scan/while implementation shared with the
    sharded runner and every new fixpoint workload.
    """
    damping = cfg.damping
    impl = cfg.spmv_impl
    dangling = cfg.dangling
    total_mass = float(n) if cfg.init is RankInit.ONE else 1.0

    def step_fn(ranks: jax.Array, dg: DeviceGraph, e: jax.Array) -> jax.Array:
        return pagerank_step(
            ranks, dg, e,
            n=n, damping=damping, dangling=dangling,
            total_mass=total_mass, impl=impl,
        )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(dg: DeviceGraph, ranks0: jax.Array, e: jax.Array):
        return iterate(
            lambda ranks: step_fn(ranks, dg, e), ranks0,
            iterations=cfg.iterations, tol=cfg.tol,
        )

    return run


def make_spark_exact_runner(n: int, cfg: PageRankConfig):
    """Runner for spark_exact mode (always fixed iterations, like the
    reference's ``for i in range(iters)`` driver loop).  ``ranks0`` is
    donated, same contract as :func:`make_pagerank_runner`."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(dg: DeviceGraph, ranks0: jax.Array, e: jax.Array):
        del e  # spark_exact is never personalized
        state0 = SparkExactState(ranks=ranks0, present=dg.has_outlinks)
        state, iters, last = iterate(
            lambda s: spark_exact_step(
                s, dg, n=n, damping=cfg.damping, impl=cfg.spmv_impl
            ),
            state0,
            iterations=cfg.iterations,
            delta_fn=lambda new, old: jnp.sum(jnp.abs(new.ranks - old.ranks)),
        )
        return state.ranks, iters, last

    return run
