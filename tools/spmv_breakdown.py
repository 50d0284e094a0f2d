"""Honest per-component SpMV timing (VERDICT r1 item 2).

Times each stage of the PageRank SpMV pipeline at web-Google scale and emits
ONE JSON object mapping component -> ms/op, naming the dominant stage.  This
table decides where kernel-engineering effort goes (NOTES.md perf ideas).

Method (NOTES.md's measurement protocol):

- run each variant R times inside ONE jit via ``lax.fori_loop``, with a value
  dependency chaining iterations (prevents DCE and cross-rep overlap);
- fence by fetching a scalar to host;
- per-op time = (T(fn_R) - T(fn_0)) / R, which subtracts compile-cache lookup,
  dispatch, and host<->device RTT.

Usage: python tools/spmv_breakdown.py [--nodes N] [--edges E] [--reps R]
                                      [--only key,key] [--out breakdown.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=875_000)
    ap.add_argument("--edges", type=int, default=5_100_000)
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated table keys to time (default all)")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON table to this path")
    ap.add_argument("--force", action="store_true",
                    help="allow overwriting a TPU-measured --out artifact "
                         "with a non-TPU run (utils/artifacts.py guard)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import DanglingMode

    from page_rank_and_tfidf_using_apache_spark_tpu.utils import artifacts

    backend = jax.default_backend()
    try:
        # fail FAST, before minutes of measurement, if the write would
        # downgrade a TPU-stamped artifact
        artifacts.check_overwrite(args.out, backend, force=args.force)
    except artifacts.ProvenanceError as exc:
        print(f"REFUSED: {exc}", file=sys.stderr)
        return 3
    reps = args.reps
    g = synthetic_powerlaw(args.nodes, args.edges, seed=args.seed)
    n, n_edges = g.n_nodes, g.n_edges
    dg = ops.put_graph(g, "float32")
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.random(n).astype(np.float32))
    pe = jnp.asarray(rng.random(n_edges).astype(np.float32))
    print(f"backend={backend} n={n} E={n_edges} reps={reps}",
          file=sys.stderr, flush=True)

    def timed(name, make_body, *arrays):
        """make_body(x, *rest) -> array; first arg is the chained carry."""

        def run_n(r):
            @jax.jit
            def f(x0, *rest):
                def body(i, x):
                    out = make_body(x, *rest)
                    # min(|out|) >= 0 always, so minimum(., 0) is exactly 0
                    # and the carry never drifts — but the reduction touches
                    # every element, so the rep chain depends on the WHOLE
                    # result and XLA cannot DCE the measured work.  (The old
                    # out.ravel()[0] consumed one element — XLA sliced the
                    # rest away, the "cumsum_blocked_E: 0.0" artifact — and
                    # went negative on monotone_diff's signed data, drifting
                    # the carry.)
                    keep = jnp.minimum(jnp.abs(out).min(), 0.0)
                    return x + keep.astype(x.dtype)

                return lax.fori_loop(0, r, body, x0)

            return f

        f0, fr = run_n(0), run_n(reps)
        for f in (f0, fr):
            float(f(*arrays).ravel()[0])  # compile both programs
        t0 = time.perf_counter()
        float(f0(*arrays).ravel()[0])
        base = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(fr(*arrays).ravel()[0])
        full = time.perf_counter() - t0
        ms = max((full - base) / reps * 1e3, 0.0)
        print(f"{name:32s} {ms:8.3f} ms  (rtt {base * 1e3:.0f} ms)",
              file=sys.stderr, flush=True)
        return ms

    table: dict[str, float] = {}
    want = set(args.only.split(",")) if args.only else None

    def add(key, name, make_body, *arrays):
        if want is None or key in want:
            table[key] = timed(name, make_body, *arrays)

    src_sorted = jnp.asarray(np.sort(np.asarray(dg.src)))

    add("gather_w_src", "gather w[src] [E]", lambda x, s: x[s], w, dg.src)
    add("gather_w_src_sorted",
        "gather w[sorted(src)] [E]", lambda x, s: x[s], w, src_sorted)
    add("cumsum_E", "cumsum [E]", lambda x: jnp.cumsum(x), pe)
    add("cumsum_blocked_E",
        "cumsum_blocked [E] (MXU)", lambda x: ops.cumsum_blocked(x), pe)
    add("segment_sum_E_to_N",
        "segment_sum [E->N]",
        lambda x, d: jax.ops.segment_sum(
            x, d, num_segments=n, indices_are_sorted=True),
        pe, dg.dst)
    # the real diff stage gathers from the (E+1)-length cumsum output with
    # indptr values up to E — shape must match or the access pattern lies
    ce = jnp.asarray(rng.random(n_edges + 1).astype(np.float32))
    add("monotone_diff_N",
        "diff c[indptr] [N]",
        lambda c, ip: c[ip[1:]] - c[ip[:-1]], ce, dg.indptr)
    add("spmv_cumsum", "spmv cumsum", lambda x: ops.spmv_cumsum(dg, x, n), w)
    add("spmv_cumsum_mxu",
        "spmv cumsum_mxu", lambda x: ops.spmv_cumsum_mxu(dg, x, n), w)
    add("spmv_segment",
        "spmv segment", lambda x: ops.spmv_segment(dg, x, n), w)
    # the segment impl before its in-row scan: the gather, then one plain
    # scatter-add (fast, but a hub's f32 sum drifts)
    add("spmv_segment_one_level",
        "spmv segment (plain scatter-add)",
        lambda x: jax.ops.segment_sum(
            x[dg.src], dg.dst, num_segments=n, indices_are_sorted=True),
        w)
    # degree-aware hybrid + sort-based static shuffle (ISSUE 7): the
    # static layouts build once on host (amortized; bench.py records the
    # cost as spmv_preprocess_secs), the per-iteration kernels race here
    dg_h = ops.put_graph(g, "float32", layout="hybrid")
    dg_s = ops.put_graph(g, "float32", layout="sort_shuffle")
    hl = dg_h.hybrid
    if hl.head_ids.shape[0]:
        add("hybrid_head_rowsum",
            "hybrid head gather+rowsum [R,W]",
            lambda x: ops.hybrid_rowsum(
                jnp.concatenate([x, jnp.zeros(1, x.dtype)])[hl.head_src]
            ),
            w)
    add("spmv_hybrid",
        "spmv hybrid (dense head + tail)",
        lambda x: ops.spmv_hybrid(dg_h, x, n), w)
    add("spmv_sort_shuffle",
        "spmv sort_shuffle (bucket reduce)",
        lambda x: ops.spmv_sort_shuffle(dg_s, x, n), w)
    add("full_step_cumsum",
        "full step (cumsum)",
        lambda x: ops.pagerank_step(
            x, dg, jnp.full(n, 1.0 / n, jnp.float32), n=n, damping=0.85,
            dangling=DanglingMode.REDISTRIBUTE, total_mass=1.0, impl="cumsum"),
        w)

    # Stage tables are per-path: the deployed cumsum impl runs gather ->
    # cumsum -> monotone diff; the segment impl runs gather -> segment_sum.
    # The old table maxed over the union, so the named "dominant" stage
    # could come from a path the winning impl never executes (VERDICT r5).
    cumsum_path = ("gather_w_src", "cumsum_E", "monotone_diff_N")
    segment_path = ("gather_w_src", "segment_sum_E_to_N")
    payload = {
        "n_nodes": n,
        "n_edges": n_edges,
        "reps": reps,
        "ms_per_op": {k: round(v, 4) for k, v in table.items()},
    }
    # dominant stage of the deployed (cumsum) path, plus the alternative
    # path's, so kernel effort aims at the right stage (when --only timed
    # the whole path)
    for key, path in (("dominant_component", cumsum_path),
                      ("dominant_component_segment_path", segment_path)):
        if all(k in table for k in path):
            payload[key] = max(path, key=lambda k: table[k])
    print(json.dumps({"backend": backend, **payload}))  # stdout regardless
    try:
        artifacts.write_artifact(args.out, payload, backend=backend,
                                 force=args.force)
    except artifacts.ProvenanceError as exc:  # raced stamp change
        print(f"REFUSED: {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
