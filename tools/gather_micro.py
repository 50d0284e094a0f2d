"""Mosaic dynamic lane/sublane-gather throughput probe (decides the SpMV
kernel design).

The SpMV breakdown (tools/spmv_breakdown.py, breakdown_tpu.json) shows the
whole PageRank step is dominated by XLA's gather/scatter (~150M gathers/s,
<1% of v5e HBM bandwidth).  Mosaic's only dynamic gathers are
``take_along_axis(x, idx, axis)`` with ``idx.shape == x.shape`` lowering to
``tpu.dynamic_gather`` on lanes (axis=1) or sublanes (axis=0).  Findings
this probe encodes (TPU v5e, jax 0.9.0):

- (1, W) single-row shapes do not lower at all (gather canonicalizes to an
  unsupported pattern);
- (8, W) shapes lower for any W via jax.export, but the Mosaic BACKEND
  compiler crashes ("please report a bug", apply-vector-layout) for W
  beyond a modest tile count — jax.export is NOT a sufficient proxy; the
  real width ceiling must be probed on-chip, which this script does by
  compiling each width before timing it;
- the usable-width ceiling and the ns/gather curve decide the SpMV design
  (table-chunk bucketing vs in-kernel local reductions).

Timing follows the NOTES.md protocol: reps chained inside one jit via
``lax.fori_loop`` (value dependency defeats DCE/overlap), scalar fetch as
the fence, 0-rep baseline subtracted.

Usage: python tools/gather_micro.py [--reps 8] [--out gather_micro.json]
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
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--target-gathers", type=int, default=4_400_000,
                    help="~gathers per rep (web-Google edge count scale)")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="allow overwriting a TPU-measured --out artifact "
                         "with a non-TPU run (utils/artifacts.py guard)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from page_rank_and_tfidf_using_apache_spark_tpu.utils import artifacts

    reps = args.reps
    rng = np.random.default_rng(0)
    backend = jax.default_backend()
    print(f"backend={backend} reps={reps}", file=sys.stderr, flush=True)
    try:
        # fail FAST, before minutes of measurement, if the write would
        # downgrade a TPU-stamped artifact
        artifacts.check_overwrite(args.out, backend, force=args.force)
    except artifacts.ProvenanceError as exc:
        print(f"REFUSED: {exc}", file=sys.stderr)
        return 3

    def make_runner(width, steps, axis, broadcast):
        rows = 8
        x_rows = 1 if broadcast else rows

        def kernel(x_ref, idx_ref, o_ref):
            x = x_ref[:]
            if broadcast:
                x = jnp.broadcast_to(x, (rows, width))
            o_ref[:] = jnp.take_along_axis(x, idx_ref[:], axis=axis)

        io_spec = pl.BlockSpec((rows, width), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)

        def call(x, idx):
            return pl.pallas_call(
                kernel,
                grid=(steps,),
                in_specs=[
                    pl.BlockSpec((x_rows, width), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM),
                    io_spec,
                ],
                out_specs=io_spec,
                out_shape=jax.ShapeDtypeStruct((rows * steps, width), x.dtype),
                interpret=args.interpret,
            )(x, idx)

        return call

    def timed(name, width, steps, axis=1, broadcast=False):
        """Effective ns/gather via the chained fori_loop protocol; returns a
        record with {'compile_ok': False} if Mosaic rejects the shape."""
        rows = 8
        x_rows = 1 if broadcast else rows
        hi = rows if axis == 0 else width
        x = jnp.asarray(rng.random((x_rows, width)).astype(np.float32))
        idx = jnp.asarray(
            rng.integers(0, hi, (rows * steps, width)).astype(np.int32))
        call = make_runner(width, steps, axis, broadcast)

        def run_n(r):
            @jax.jit
            def f(x0, ix):
                def body(i, acc):
                    out = call(acc, ix)
                    # Reduce over the WHOLE kernel output: min(|out|) is
                    # >= 0 so the minimum with 0 keeps the carry unchanged,
                    # while the value dependency covers every gathered
                    # element — XLA cannot DCE the pallas_call.  (The old
                    # out[0, 0] consumption produced the physically
                    # impossible 0.0 ns/gather "bcast_w128" artifact.)
                    return acc + jnp.minimum(jnp.abs(out).min(), 0.0)

                return lax.fori_loop(0, r, body, x0)

            return f

        f0, fr = run_n(0), run_n(reps)
        try:
            for f in (f0, fr):
                float(f(x, idx)[0, 0])  # compile
        except Exception as exc:  # Mosaic backend rejection — record it
            msg = str(exc).splitlines()[0][:120] if str(exc) else repr(exc)[:120]
            print(f"{name:34s} COMPILE FAIL: {msg}", file=sys.stderr,
                  flush=True)
            return {"compile_ok": False, "error": msg}
        t0 = time.perf_counter()
        float(f0(x, idx)[0, 0])
        base = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(fr(x, idx)[0, 0])
        full = time.perf_counter() - t0
        per_rep = max((full - base) / reps, 1e-9)
        n_g = rows * steps * width
        ns = per_rep / n_g * 1e9
        print(f"{name:34s} {per_rep * 1e3:9.3f} ms/rep  {n_g / 1e6:6.2f} Mg "
              f"-> {ns:8.3f} ns/gather  ({n_g / per_rep / 1e9:.2f} Gg/s)",
              file=sys.stderr, flush=True)
        return {"compile_ok": True, "ms_per_rep": round(per_rep * 1e3, 4),
                "gathers": n_g, "ns_per_gather": round(ns, 4)}

    t: dict[str, dict] = {}
    tg = args.target_gathers
    for w in (128, 256, 512, 1024, 2048, 4096, 8192, 32768, 109184):
        steps = max(tg // (8 * w), 1)
        t[f"lane_w{w}"] = timed(f"lane (8,{w})", w, steps)
        if not t[f"lane_w{w}"]["compile_ok"]:
            break  # wider will fail too; don't risk more backend crashes
    # sublane gather (axis=0): 8-deep tables per lane column — the routing
    # primitive for cross-sublane reads
    t["sublane_w1024"] = timed("sublane (8,1024) ax0", 1024,
                               max(tg // (8 * 1024), 1), axis=0)
    # broadcast-row variant at the widest working lane width
    widest_ok = max((int(k.split("w")[1]) for k, v in t.items()
                     if k.startswith("lane_") and v.get("compile_ok")),
                    default=0)
    if widest_ok:
        t[f"bcast_w{widest_ok}"] = timed(
            f"bcast (8,{widest_ok})", widest_ok,
            max(tg // (8 * widest_ok), 1), broadcast=True)

    ok = {k: v for k, v in t.items() if v.get("compile_ok")}
    best = min(ok, key=lambda k: ok[k]["ns_per_gather"]) if ok else None
    payload = {"reps": reps, "modes": t, "best_mode": best,
               "widest_lane_ok": widest_ok}
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
