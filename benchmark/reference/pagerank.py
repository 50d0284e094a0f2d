"""Plain PageRank power iteration, the reference for the PageRank cells.

A copy of ``chip_smoke.pagerank_reference`` (PR 21) that works from raw
edge arrays: uniform start and teleport, dangling mass spread uniformly,
``iterations`` steps.  In float64 or float32 it is a scipy CSR product
accumulating in that dtype; with ``store`` the rank vector is rounded to
``store`` after every step, as a program holding its ranks in that type
would.  In any other dtype every intermediate is rounded to that dtype and
the edge sums accumulate in it one edge at a time.  The controls (the same
iteration in a lower precision) need both.  Imports nothing of the
program.
"""

from __future__ import annotations

import numpy as np


def pagerank(src: np.ndarray, dst: np.ndarray, n: int, iterations: int,
             damping: float, dtype=np.float64, store=None) -> np.ndarray:
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1.0), 0.0)
    dangling = outdeg == 0
    if np.dtype(dtype) in (np.float64, np.float32):
        import scipy.sparse as sp

        def held(x):
            return x if store is None else x.astype(store).astype(dtype)

        a = sp.csr_matrix((np.ones(src.shape[0], dtype), (dst, src)), shape=(n, n))
        inv = inv.astype(dtype)
        r = held(np.full(n, 1.0 / n, dtype))
        for _ in range(iterations):
            r = held((dtype(1.0 - damping) / n + dtype(damping) * (
                a @ (r * inv) + r[dangling].sum(dtype=dtype) / n)).astype(dtype))
        return r.astype(np.float64)
    inv = inv.astype(dtype)
    base = np.asarray((1.0 - damping) / n, dtype)
    d = np.asarray(damping, dtype)
    r = np.full(n, 1.0 / n, dtype)
    for _ in range(iterations):
        w = (r * inv).astype(dtype)
        contrib = np.zeros(n, dtype)
        np.add.at(contrib, dst, w[src])
        mass = np.zeros(1, dtype)
        np.add.at(mass, np.zeros(int(dangling.sum()), np.int64), r[dangling])
        spread = (mass[0] / np.asarray(n, dtype)).astype(dtype)
        r = (base + d * (contrib + spread).astype(dtype)).astype(dtype)
    return r.astype(np.float64)
