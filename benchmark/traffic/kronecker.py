"""Seeded Graph500 Kronecker graph, as LDBC Graphalytics builds its
``graph500-<scale>`` datasets.

The Graph500 generator (graph500.org specification, the Kronecker
generator of its reference code): ``edge_factor * 2**scale`` edges, each
placed by ``scale`` independent draws of one quadrant of the adjacency
matrix with probabilities ``initiator = (A, B, C, D)``; vertex ids are then
permuted at random.  Graphalytics takes the edges as undirected, drops
self-loops and duplicates and removes the vertices left without an edge.
PageRank reads each undirected edge both ways, so the result is the
symmetric arc list: int32 ``(src, dst)`` sorted by ``(dst, src)``, ids
compacted to ``[0, n)`` in the order of the permuted ids.

The edges are drawn in fixed chunks, each from its own stream spawned from
the seed, so the arcs are the same whatever the number of threads; the
chunks, the sort of the arcs by destination range and the dedup run on a
thread pool (numpy releases the interpreter lock in each of them).
Imports nothing of the program.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 22  # edges drawn per stream
BUCKET_BITS = 8  # the arcs are sorted in 2**8 ranges of destination ids


def _pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(max_workers=os.cpu_count() or 1)


def _draw(seq: np.random.SeedSequence, m: int, scale: int, cum: np.ndarray,
          perm: np.ndarray) -> np.ndarray:
    """``m`` edges as arc keys ``dst << scale | src`` in both directions,
    self-loops dropped, sorted."""
    rng = np.random.default_rng(seq)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    bit = np.empty(m, np.int64)
    for level in range(scale):
        x = rng.random(m, dtype=np.float32)
        lower = x >= cum[1]  # C or D: the lower half of the matrix
        right = (x >= cum[0]) != lower  # B, or
        right |= x >= cum[2]  # D: the right half
        np.left_shift(lower, level, out=bit, casting="unsafe")
        u |= bit
        np.left_shift(right, level, out=bit, casting="unsafe")
        v |= bit
    u = perm[u]
    v = perm[v]
    keep = u != v
    u, v = u[keep], v[keep]
    keys = np.concatenate([(v << scale) | u, (u << scale) | v])
    keys.sort()
    return keys


def graph500(scale: int, edge_factor: int, initiator, seed: int
             ) -> tuple[np.ndarray, np.ndarray, int]:
    """``(src, dst, n)``: the symmetric arcs of the Graphalytics graph500
    dataset at ``scale``, drawn from ``seed``."""
    a, b, c, d = (float(p) for p in initiator)
    if abs(a + b + c + d - 1.0) > 1e-9 or min(a, b, c, d) < 0:
        raise ValueError(f"initiator {initiator} is not a distribution")
    if not 1 <= scale <= 30:
        raise ValueError(f"scale {scale} outside 1..30")
    n_ids = 1 << scale
    m = edge_factor * n_ids
    cum = np.array([a, a + b, a + b + c], np.float32)
    root = np.random.SeedSequence(int(seed))
    perm_seq, *chunk_seqs = root.spawn(1 + -(-m // CHUNK))
    perm = np.random.default_rng(perm_seq).permutation(n_ids)
    sizes = [min(CHUNK, m - i * CHUNK) for i in range(len(chunk_seqs))]

    shift = scale + max(scale - BUCKET_BITS, 0)  # key bits below a bucket
    n_buckets = 1 << min(BUCKET_BITS, scale)
    edges_at = (np.arange(n_buckets + 1, dtype=np.int64) << shift)
    with _pool() as pool:
        chunks = list(pool.map(lambda a: _draw(a[0], a[1], scale, cum, perm),
                               zip(chunk_seqs, sizes)))
        cuts = [np.searchsorted(k, edges_at) for k in chunks]

        def bucket(i: int) -> np.ndarray:
            keys = np.concatenate([k[c[i]:c[i + 1]] for k, c in zip(chunks, cuts)])
            keys.sort(kind="stable")  # merges the chunks' sorted runs
            first = np.ones(keys.size, bool)
            first[1:] = keys[1:] != keys[:-1]
            return keys[first]

        buckets = list(pool.map(bucket, range(n_buckets)))
        del chunks
        # ids with an arc: every arc's destination (the arcs are symmetric)
        present = np.zeros(n_ids, bool)

        def mark(keys: np.ndarray) -> None:
            present[keys >> scale] = True

        list(pool.map(mark, buckets))
        new_id = (np.cumsum(present) - 1).astype(np.int32)
        starts = np.concatenate([[0], np.cumsum([k.size for k in buckets])])
        src = np.empty(int(starts[-1]), np.int32)
        dst = np.empty(int(starts[-1]), np.int32)
        mask = np.int64(n_ids - 1)

        def relabel(i: int) -> None:
            keys = buckets[i]
            src[starts[i]:starts[i + 1]] = new_id[keys & mask]
            dst[starts[i]:starts[i + 1]] = new_id[keys >> scale]

        list(pool.map(relabel, range(n_buckets)))
    return src, dst, int(present.sum())
