"""Host-side graph ingest: SNAP edge lists → device-ready edge arrays.

Reference counterpart (SURVEY.md §2.1 A2/A3): the Spark chain
``sc.textFile(edges).map(parse).distinct().groupByKey().cache()`` — a text
parse followed by a dedup shuffle and an adjacency-list build kept hot
across iterations.  TPU-native design: parse once on host into flat numpy
arrays, dedup with one vectorized sort, and keep the graph device-resident
as **destination-sorted edge arrays** (a CSC-by-destination layout): the
per-iteration `reduceByKey` then becomes a `segment_sum` over contiguous
destination segments, which is the layout XLA tiles best.

SNAP format: ``#``-prefixed comment header lines, whitespace-separated
integer ``src dst`` pairs (BASELINE.json:7,9 name SNAP web-Google and
soc-LiveJournal1).
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """A directed graph in destination-sorted edge-array form.

    Node ids are compacted to ``[0, n_nodes)``; ``node_ids[i]`` maps row
    ``i`` back to the original id from the input file (identity when the
    input was already compact).

    Invariants: ``dst`` is non-decreasing; ``(src, dst)`` pairs are unique
    (the reference's ``distinct()``); ``out_degree[v] == #edges with
    src == v``; dangling nodes are exactly ``out_degree == 0``.
    """

    n_nodes: int
    src: np.ndarray  # int32 [n_edges], sorted by (dst, src)
    dst: np.ndarray  # int32 [n_edges], non-decreasing
    out_degree: np.ndarray  # int32 [n_nodes]
    node_ids: np.ndarray  # original ids, [n_nodes]
    # Optional per-edge weights aligned with src/dst (same (dst, src)
    # order).  None = unweighted.  Weights are strictly positive (enforced
    # by from_edges): a node's dangling status then stays "no out-edges"
    # under both conventions, and the weighted out-STRENGTH normalizer
    # (networkx ``pagerank(weight=)`` semantics) is always finite.
    weight: np.ndarray | None = None

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def out_strength(self) -> np.ndarray:
        """float64 [n_nodes] sum of outgoing edge weights (== out_degree
        for an unweighted graph); the normalizer of the weighted SpMV.
        Cached like csr_indptr."""
        cached = getattr(self, "_out_strength", None)
        if cached is None:
            if self.weight is None:
                cached = self.out_degree.astype(np.float64)  # graftlint: disable=dtype-drift (host-side normalizer staging; cast to the run dtype at put_graph)
            else:
                cached = np.bincount(
                    self.src, weights=self.weight, minlength=self.n_nodes
                )
            object.__setattr__(self, "_out_strength", cached)
        return cached

    def inv_out_strength(self, dtype) -> np.ndarray:
        """``1 / out_strength`` (0 at dangling nodes), divided in float64
        and cast to ``dtype`` AFTER — THE one implementation every graph
        consumer shares (put_graph, partition_graph, build_owned_shard):
        the 1e-9 f64 chip-count-invariance pins depend on all of them
        normalizing bit-identically."""
        s = self.out_strength()
        with np.errstate(divide="ignore"):
            return np.where(
                s > 0, 1.0 / np.where(s > 0, s, 1.0), 0.0
            ).astype(dtype)

    @property
    def dangling_mask(self) -> np.ndarray:
        return self.out_degree == 0

    def csr_indptr(self) -> np.ndarray:
        """int64 [n_nodes+1] CSR row pointers into the dst-sorted edge array
        (cached: every consumer — device graph build, shard partitioning,
        Pallas window metadata — shares one host pass)."""
        cached = getattr(self, "_indptr", None)
        if cached is None:
            # keys of dst's own dtype: int64 keys would copy dst to int64
            keys = np.arange(self.n_nodes + 1, dtype=self.dst.dtype)
            cached = np.searchsorted(self.dst, keys).astype(np.int64)
            object.__setattr__(self, "_indptr", cached)
        return cached

    def __repr__(self) -> str:  # keep pytest output readable
        return f"Graph(n_nodes={self.n_nodes}, n_edges={self.n_edges})"


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    *,
    weight: np.ndarray | None = None,
    dedup: bool = True,
    drop_self_loops: bool = False,
    compact_ids: bool = True,
) -> Graph:
    """Build a :class:`Graph` from raw (src, dst) id arrays.

    ``dedup=True`` reproduces the reference's ``distinct()``; self-loops are
    kept by default (``distinct()`` does not remove them).  ``weight`` (all
    entries > 0) rides along per edge; duplicate (src, dst) pairs SUM their
    weights under dedup (the parallel-edge collapse networkx applies when a
    multigraph is read as a weighted digraph).
    """
    src = np.asarray(src).ravel()
    dst = np.asarray(dst).ravel()
    if src.shape != dst.shape:
        raise ValueError(f"src/dst shape mismatch: {src.shape} vs {dst.shape}")
    if weight is not None:
        weight = np.asarray(weight, np.float64).ravel()  # graftlint: disable=dtype-drift (host-side edge weights; cast to the run dtype at put_graph/partition_graph)
        if weight.shape != src.shape:
            raise ValueError(
                f"weight shape {weight.shape} != edge shape {src.shape}"
            )
        if weight.size and not (weight > 0).all():
            raise ValueError("edge weights must be strictly positive")
    if drop_self_loops:
        keep = src != dst
        src, dst = src[keep], dst[keep]
        if weight is not None:
            weight = weight[keep]

    if compact_ids:
        node_ids, inverse = np.unique(np.concatenate([src, dst]), return_inverse=True)
        src = inverse[: src.shape[0]]
        dst = inverse[src.shape[0] :]
        n = int(node_ids.shape[0])
    else:
        n = int(max(src.max(initial=-1), dst.max(initial=-1)) + 1) if src.size else 0
        if n > (1 << 31):
            raise ValueError(
                f"compact_ids=False with max id {n - 1}: the O(n) rank/degree "
                "vectors would not fit; use compact_ids=True"
            )
        node_ids = np.arange(n, dtype=np.int64)

    src = src.astype(np.int64)
    dst = dst.astype(np.int64)
    # Sort (dst major, src minor) — both the dedup order and the final
    # destination-sorted layout every SpMV impl relies on.  The native C++
    # radix sort wins by several x at soc-LiveJournal1 scale; the numpy
    # lexsort fallback is bit-identical (unlike a dst*n+src composite key,
    # neither can overflow for large raw ids under compact_ids=False).
    from page_rank_and_tfidf_using_apache_spark_tpu.utils import native

    sorted_pair = (
        native.sort_dedup_edges(src, dst, dedup=dedup)
        if src.size and n <= (1 << 31) and weight is None else None
    )
    if sorted_pair is not None:
        src, dst = sorted_pair
    else:
        order = np.lexsort((src, dst))
        src, dst = src[order], dst[order]
        if weight is not None:
            weight = weight[order]
        if dedup and src.size:
            keep = np.empty(src.shape, dtype=bool)
            keep[0] = True
            keep[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
            if weight is not None:
                # duplicate (src, dst) pairs collapse to one edge carrying
                # the SUM of their weights (groups are contiguous after the
                # lexsort, so one reduceat covers them all)
                weight = np.add.reduceat(weight, np.flatnonzero(keep))
            src, dst = src[keep], dst[keep]

    out_degree = np.bincount(src, minlength=n).astype(np.int32)
    return Graph(
        n_nodes=n,
        src=src.astype(np.int32),
        dst=dst.astype(np.int32),
        out_degree=out_degree,
        node_ids=node_ids,
        weight=weight,
    )


_ARC_CHUNK = 1 << 24  # arcs checked and counted at a time (bincount copies to int64)


def from_sorted_arcs(src: np.ndarray, dst: np.ndarray, n_nodes: int) -> Graph:
    """A :class:`Graph` over arcs that already hold its invariants: int32
    ids in ``[0, n_nodes)``, sorted by ``(dst, src)`` with no duplicate,
    as a loader that sorts and dedups at the source (a Graph500
    generator) hands them over.  No sort and no copy of the arcs: one
    threaded pass in chunks checks the order and the ids and counts the
    out-degrees, which is what a billion arcs can afford.  ``node_ids`` is
    the identity.  Raises ``ValueError`` where an invariant fails."""
    src, dst = np.asarray(src), np.asarray(dst)
    if src.dtype != np.int32 or dst.dtype != np.int32 or src.ndim != 1 \
            or src.shape != dst.shape:
        raise ValueError("arcs must be two 1-D int32 arrays of one length")
    e = src.size

    def part(bounds: tuple[int, int]) -> np.ndarray:
        counts = np.zeros(n_nodes, np.int64)
        for lo in range(bounds[0], bounds[1], _ARC_CHUNK):
            hi = min(lo + _ARC_CHUNK, bounds[1])
            s, d = src[lo:hi], dst[lo:hi]
            if min(s.min(), d.min()) < 0 or max(s.max(), d.max()) >= n_nodes:
                raise ValueError(f"arc ids outside [0, {n_nodes})")
            b = max(lo, 1)  # each arc against the one before it, across chunks
            ds, dd = src[b:hi], dst[b:hi]
            ps, pd = src[b - 1:hi - 1], dst[b - 1:hi - 1]
            if not ((dd > pd) | ((dd == pd) & (ds > ps))).all():
                raise ValueError("arcs are not sorted by (dst, src) without duplicates")
            counts += np.bincount(s, minlength=n_nodes)
        return counts

    workers = max(1, min(os.cpu_count() or 1, 16, -(-e // _ARC_CHUNK)))
    cuts = np.linspace(0, e, workers + 1).astype(np.int64).tolist()
    out_degree = np.zeros(n_nodes, np.int64)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for counts in pool.map(part, zip(cuts[:-1], cuts[1:])):
            out_degree += counts
    return Graph(
        n_nodes=n_nodes,
        src=src,
        dst=dst,
        out_degree=out_degree.astype(np.int32),
        node_ids=np.arange(n_nodes, dtype=np.int64),
    )


def parse_snap_text(text: str | bytes, **kwargs) -> Graph:
    """Parse SNAP edge-list text (``#`` comments, whitespace-separated int
    pairs). Vectorized: one pass to strip comments, one ``split`` for all
    tokens."""
    if isinstance(text, bytes):
        text = text.decode("utf-8", errors="replace")
    data_lines = [ln for ln in text.splitlines() if ln and not ln.lstrip().startswith("#")]
    if not data_lines:
        return from_edges(np.empty(0, np.int64), np.empty(0, np.int64), **kwargs)
    flat = " ".join(data_lines).split()
    arr = np.array(flat, dtype=np.int64)
    if arr.size % 2 != 0:
        raise ValueError(f"edge list has odd token count {arr.size}; not (src, dst) pairs")
    pairs = arr.reshape(-1, 2)
    return from_edges(pairs[:, 0], pairs[:, 1], **kwargs)


def load_snap(path: str, **kwargs) -> Graph:
    """Load a SNAP-format edge-list file.

    Uses the native C++ parser (utils/native.py) when available — the pure
    python tokenize of a 69M-edge soc-LiveJournal1 file is the kind of host
    bottleneck SURVEY.md §7 flags — falling back to the numpy path.
    """
    from page_rank_and_tfidf_using_apache_spark_tpu.utils import native

    pairs = native.parse_edge_file(path)
    if pairs is not None:
        return from_edges(pairs[:, 0], pairs[:, 1], **kwargs)
    with open(path, "rb") as f:
        return parse_snap_text(f.read(), **kwargs)


def save_ranks(path: str, graph: Graph, ranks: np.ndarray, *, top_k: int | None = None) -> None:
    """Write ``<original_node_id>\\t<rank>`` lines, highest rank first —
    the reference's ``saveAsTextFile`` of collected ranks (SURVEY.md A5)."""
    order = np.argsort(-ranks, kind="stable")
    if top_k is not None:
        order = order[:top_k]
    with open(path, "w") as f:
        for i in order:
            f.write(f"{graph.node_ids[i]}\t{ranks[i]:.10g}\n")


def synthetic_powerlaw(
    n_nodes: int,
    n_edges: int,
    *,
    seed: int = 0,
    zipf_a: float = 1.5,
) -> Graph:
    """Synthetic graph with a power-law in-degree distribution.

    Stand-in for the SNAP datasets (not mounted in this environment —
    BASELINE.md); matches their shape class: heavy-tailed degrees, dangling
    nodes, duplicate edges before dedup.  Sources uniform, destinations
    Zipf-distributed over a random permutation so "celebrity" nodes exist —
    the load-imbalance stressor SURVEY.md §7 calls out for sharded SpMV.
    """
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, size=n_edges, dtype=np.int64)
    # Zipf over ranks, clipped to [0, n_nodes), then scattered via a random
    # permutation so hot nodes are not all small ids.
    z = rng.zipf(zipf_a, size=n_edges) - 1
    z = np.minimum(z, n_nodes - 1)
    perm = rng.permutation(n_nodes)
    dst = perm[z]
    return from_edges(src, dst)


def synthetic_zipf(
    n_nodes: int,
    n_edges: int,
    *,
    seed: int = 0,
    exponent: float = 1.5,
    src_exponent: float | None = None,
) -> Graph:
    """Seeded Zipf graph hitting its TARGET counts exactly: exactly
    ``n_nodes`` nodes and exactly ``n_edges`` unique edges (ISSUE 15
    satellite; :func:`synthetic_powerlaw` only aims near them — dedup
    shrinks its edge count by a seed-dependent few percent, which makes
    cross-scale comparisons like the owned-strategy comm-bytes sweep
    noisy).  Destinations are Zipf(``exponent``) over a random
    permutation, so hub IN-degree follows the power law the sharded
    planners are stressed by; sources are uniform by default, or
    Zipf(``src_exponent``) over an independent permutation — the
    both-axes power law real web graphs have (SNAP web-Google's
    out-degree is as heavy-tailed as its in-degree), and the shape class
    under which the owned strategy's boundary is hub-dominated: distinct
    sources drawn from a Zipf(a) grow ~n^(1/a), so cut-crossing entries —
    and with them per-step comm bytes — are SUBLINEAR in node count (the
    MULTICHIP scale sweep measures exactly this exponent).

    Top-up rounds oversample until the deduped pool reaches the target,
    then a seeded uniform subsample trims to it — trimming uniformly
    preserves the degree distribution's shape.
    """
    if n_nodes < 2:
        raise ValueError(f"synthetic_zipf needs n_nodes >= 2, got {n_nodes}")
    if n_edges < 2:
        raise ValueError(f"synthetic_zipf needs n_edges >= 2, got {n_edges}")
    if n_edges > n_nodes * (n_nodes - 1):
        raise ValueError(
            f"target {n_edges} edges exceeds the simple-digraph capacity "
            f"of {n_nodes} nodes"
        )
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_nodes)
    perm_s = rng.permutation(n_nodes) if src_exponent is not None else None
    # Hub SOURCES (the top source ranks) link uniformly; only tail
    # sources link preferentially (Zipf destinations).  A directory hub
    # links broadly, a niche page links into the popular head — and
    # without the split, the (hub src × hub dst) pair mass makes i.i.d.
    # unique-edge sampling collide so hard the top-up loop crawls at 10x
    # scale (its distinct-pair capacity saturates).
    src_hub_ranks = 1024
    # Pin ids 0 and n_nodes-1 so the node COUNT is exact without id
    # compaction renumbering anything (dedup may drop the duplicates).
    keys = {np.int64(0) * n_nodes + (n_nodes - 1),
            np.int64(n_nodes - 1) * n_nodes + 0}
    pool = np.fromiter(keys, np.int64)
    accept = 1.0  # unique yield of the previous round, sizes the next
    while pool.size < n_edges:
        want = max(n_edges - pool.size, 1024)
        batch = int(min(want / max(accept, 0.05) * 1.25, 4 * n_edges)) + 64
        z = np.minimum(rng.zipf(exponent, size=batch) - 1, n_nodes - 1)
        dst = perm[z]
        if perm_s is None:
            src = rng.integers(0, n_nodes, size=batch, dtype=np.int64)
        else:
            zs = np.minimum(rng.zipf(src_exponent, size=batch) - 1,
                            n_nodes - 1)
            src = perm_s[zs]
            hub = zs < src_hub_ranks
            dst[hub] = rng.integers(0, n_nodes, size=int(hub.sum()),
                                    dtype=np.int64)
        before = pool.size
        pool = np.unique(np.concatenate([pool, src * n_nodes + dst]))
        accept = max((pool.size - before) / batch, 0.01)
    if pool.size > n_edges:
        # keep the two pinned endpoint edges; trim the rest uniformly
        pinned = np.isin(pool, np.fromiter(keys, np.int64))
        rest = np.flatnonzero(~pinned)
        take = rng.choice(rest, n_edges - int(pinned.sum()), replace=False)
        pool = np.concatenate([pool[pinned], pool[take]])
    src = pool // n_nodes
    dst = pool % n_nodes
    g = from_edges(src, dst, dedup=False, compact_ids=False)
    assert g.n_nodes == n_nodes and g.n_edges == n_edges
    return g
