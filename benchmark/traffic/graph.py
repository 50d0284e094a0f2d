"""Seeded web graph with exact node and edge counts and fitted degrees.

A configuration model: the in- and out-degree sequences are fixed by the
configuration (a power law of the stated exponent, shifted so that its
largest degree and its sum are the stated ones), and the seed only assigns
them to nodes and pairs the edge stubs.  So every seed gives the same
degrees, the same amount of work and the same hub sizes, in another
layout.  A share of the nodes dangle (no out-edge); every node has at least
one edge, so the node count is exact without id compaction.

Stubs are paired by a seeded shuffle; pairs that repeat an edge or loop on
a node are paired again among themselves, and the few that still collide
are placed by degree-preserving swaps with accepted edges.  Imports
nothing of the program.
"""

from __future__ import annotations

import numpy as np


def degree_sequence(n: int, total: int, dmax: int, exponent: float,
                    floor: int = 0) -> np.ndarray:
    """Descending integer degrees of ``n`` nodes summing to ``total``,
    largest ``dmax``: ``dmax * (i0 / (i + i0)) ** (1 / (exponent - 1))``
    for rank ``i``, whose tail is a power law of density exponent
    ``exponent``; ``i0`` is solved so the sum is ``total``, and rounding
    keeps the sum exact (largest remainders go up)."""
    if not floor * n <= total <= dmax * n or dmax > total:
        raise ValueError(f"no degree sequence of {n} nodes, sum {total}, max {dmax}")
    alpha = 1.0 / (exponent - 1.0)
    i = np.arange(n, dtype=np.float64)

    def weights(log_i0: float) -> np.ndarray:
        i0 = np.exp(log_i0)
        return dmax * (i0 / (i + i0)) ** alpha

    lo, hi = -20.0, 60.0  # the sum rises with i0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if weights(mid).sum() < total else (lo, mid)
    w = weights(hi)
    d = np.maximum(np.floor(w), floor).astype(np.int64)
    short = total - int(d.sum())
    if short < 0:
        raise ValueError(f"floor {floor} leaves no room for sum {total}")
    d[np.argsort(d - w, kind="stable")[:short]] += 1
    return d


class _EdgeSet:
    """Membership of edge keys: a sorted base array and small changes."""

    def __init__(self, base: np.ndarray):
        self.base = base
        self.added: set = set()
        self.gone: set = set()

    def __contains__(self, key: int) -> bool:
        if key in self.added:
            return True
        i = int(np.searchsorted(self.base, key))
        return i < self.base.size and int(self.base[i]) == key and key not in self.gone

    def has_any(self, keys: np.ndarray) -> np.ndarray:
        if self.added or self.gone:
            return np.array([k in self for k in keys.tolist()], bool)
        i = np.minimum(np.searchsorted(self.base, keys), self.base.size - 1)
        return self.base[i] == keys

    def keys(self) -> np.ndarray:
        keep = self.base
        if self.gone:
            keep = keep[~np.isin(keep, np.fromiter(self.gone, np.int64))]
        return np.sort(np.concatenate([keep, np.fromiter(self.added, np.int64)]))


def _swap_in(edges: _EdgeSet, src: np.ndarray, dst: np.ndarray, n: int,
             rng: np.random.Generator) -> None:
    """Place the stub pairs ``(src, dst)`` that still collide by swapping
    each with a random accepted edge ``(x, y)`` into ``(s, y)`` and ``(x,
    d)``: every degree stays as it was."""
    for s, d in zip(src.tolist(), dst.tolist()):
        while True:
            j = int(edges.base[rng.integers(edges.base.size)])
            if j not in edges:
                continue
            x, y = divmod(j, n)
            a, b = s * n + y, x * n + d
            if s != y and x != d and a != b and a not in edges and b not in edges:
                edges.gone.add(j)
                edges.added.update((a, b))
                break


def web_edges(n_nodes: int, n_edges: int, *, seed: int, in_exponent: float, in_max: int,
              out_exponent: float, out_max: int,
              dangling_share: float) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` int64 arrays of exactly ``n_edges`` unique directed
    edges without self-loops over exactly ``n_nodes`` nodes, in ascending
    ``src * n + dst`` order, with the configuration's degree sequences."""
    n_dangling = int(round(dangling_share * n_nodes))
    din = degree_sequence(n_nodes, n_edges, in_max, in_exponent)
    dout = degree_sequence(n_nodes - n_dangling, n_edges, out_max, out_exponent, floor=1)
    rng = np.random.default_rng(seed)
    by_in = rng.permutation(n_nodes)  # node of in-degree rank k
    linked = by_in[din > 0]
    if linked.size < n_dangling:
        raise ValueError("more dangling nodes than nodes with an in-edge")
    dangling = np.zeros(n_nodes, bool)
    dangling[rng.choice(linked, n_dangling, replace=False)] = True
    by_out = rng.permutation(np.flatnonzero(~dangling))  # node of out-degree rank k
    src = np.repeat(by_out, dout)
    dst = rng.permutation(np.repeat(by_in, din))
    k = src * n_nodes + dst
    base, first = np.unique(k, return_index=True)
    ok = np.zeros(k.size, bool)
    ok[first] = True
    ok &= src != dst
    edges = _EdgeSet(base[(base // n_nodes) != (base % n_nodes)])
    src, dst = src[~ok], rng.permutation(dst[~ok])
    for _ in range(16):  # pair the colliding stubs again among themselves
        if src.size == 0:
            break
        k = src * n_nodes + dst
        _, first = np.unique(k, return_index=True)
        ok = np.zeros(k.size, bool)
        ok[first] = True
        ok &= (src != dst) & ~edges.has_any(k)
        edges.added.update(k[ok].tolist())
        src, dst = src[~ok], rng.permutation(dst[~ok])
    _swap_in(edges, src, dst, n_nodes, rng)
    keys = edges.keys()
    return keys // n_nodes, keys % n_nodes
