"""Plain PageRank power iteration for graphs of a billion arcs.

The semantics of ``reference/pagerank.py`` (uniform start and teleport,
dangling mass spread uniformly, ``iterations`` steps, float64 unless
``dtype`` says otherwise, ranks rounded to ``store`` after every step
where given), computed as row-blocked ``scipy.sparse`` CSR products on a
thread pool: the arcs must come sorted by ``(dst, src)``, and each block is
a contiguous range of destinations with about the same number of arcs.
scipy releases the interpreter lock in the product, so the blocks run in
parallel, and each sums its rows in arc order as the single CSR product
does.  Imports nothing of the program.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

BLOCKS_PER_THREAD = 4
COUNT_PARTS = 8  # threads that count ids, each into an array of its own
COUNT_CHUNK = 1 << 24  # ids counted at a time (np.bincount copies them to int64)


def count_ids(ids: np.ndarray, n: int, pool: ThreadPoolExecutor) -> np.ndarray:
    """int64 occurrences of each id in ``[0, n)``, counted in parallel
    with bounded memory."""

    def part(a: np.ndarray) -> np.ndarray:
        out = np.zeros(n, np.int64)
        for lo in range(0, a.size, COUNT_CHUNK):
            out += np.bincount(a[lo:lo + COUNT_CHUNK], minlength=n)
        return out

    total = np.zeros(n, np.int64)
    for c in pool.map(part, np.array_split(ids, COUNT_PARTS)):
        total += c
    return total


class Matrix:
    """The ``[n, n]`` 0/1 matrix ``A[dst, src]`` of the arcs, as row blocks."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int,
                 dtype=np.float64, threads: int | None = None):
        if src.size > 1 and (dst[1:] < dst[:-1]).any():
            raise ValueError("arcs are not sorted by destination")
        self.n = n
        self.dtype = np.dtype(dtype)
        self.threads = threads or os.cpu_count() or 1
        with ThreadPoolExecutor(max_workers=self.threads) as pool:
            # keys of dst's own dtype: int64 keys would copy dst to int64
            indptr = np.concatenate(list(pool.map(
                lambda keys: np.searchsorted(dst, keys),
                np.array_split(np.arange(n + 1, dtype=dst.dtype), self.threads))))
            self.out_degree = count_ids(src, n, pool).astype(np.float64)
        k = max(1, min(n, self.threads * BLOCKS_PER_THREAD))
        cuts = np.searchsorted(indptr, np.linspace(0, src.size, k + 1)[1:-1])
        rows = np.unique(np.concatenate([[0], cuts, [n]]))
        idx = src.astype(np.int32, copy=False)
        ones = np.ones(int(np.diff(indptr[rows]).max(initial=0)), self.dtype)
        self.blocks = []
        for lo, hi in zip(rows[:-1], rows[1:]):
            a, b = int(indptr[lo]), int(indptr[hi])
            ptr = (indptr[lo:hi + 1] - a).astype(np.int32)
            block = sp.csr_matrix((ones[: b - a], idx[a:b], ptr), shape=(int(hi - lo), n))
            # views, not the copies scipy makes of a small part of a large array
            block.indices, block.data = idx[a:b], ones[: b - a]
            self.blocks.append((int(lo), int(hi), block))

    def matvec(self, x: np.ndarray, pool: ThreadPoolExecutor) -> np.ndarray:
        out = np.empty(self.n, self.dtype)

        def block(b) -> None:
            lo, hi, a = b
            out[lo:hi] = a @ x

        list(pool.map(block, self.blocks))
        return out


def pagerank(src: np.ndarray, dst: np.ndarray, n: int, iterations: int,
             damping: float, dtype=np.float64, store=None,
             matrix: Matrix | None = None) -> np.ndarray:
    """Ranks after ``iterations`` steps, as float64; ``matrix`` reuses the
    blocks of an earlier call on the same arcs and dtype."""
    a = matrix or Matrix(src, dst, n, dtype)
    dtype = a.dtype.type
    outdeg = a.out_degree
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1.0), 0.0).astype(dtype)
    dangling = outdeg == 0

    def held(x):
        return x if store is None else x.astype(store).astype(dtype)

    r = held(np.full(n, 1.0 / n, dtype))
    with ThreadPoolExecutor(max_workers=a.threads) as pool:
        for _ in range(iterations):
            r = held((dtype(1.0 - damping) / n + dtype(damping) * (
                a.matvec(r * inv, pool) + r[dangling].sum(dtype=dtype) / n)).astype(dtype))
    return r.astype(np.float64)
