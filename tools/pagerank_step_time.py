"""Warm per-iteration time of ``cli.pagerank`` on one synthetic graph.

Generates the graph once, then runs ``cli.pagerank``'s ``main`` once per
``--runs`` entry on it, with ``--checkpoint-every`` cutting the iterations
into segments: every segment after the first runs a program that is
already compiled, and its ``secs`` over its iterations is the warm
per-iteration time.  It drives only the CLI's flags and reads only its
``--metrics-json``, so the same script times an older checkout: copy it
into that checkout's ``tools/`` and run it from there.

Usage: python tools/pagerank_step_time.py synthetic:N,E,SEED
           [--iterations 20] [--segment 10] [--mesh D] --runs a,b
(with ``--mesh`` each run is a ``--shard-strategy``, else a ``--spmv-impl``)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("input", help="synthetic:N,E,SEED")
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--segment", type=int, default=10)
    ap.add_argument("--mesh", type=int, default=0)
    ap.add_argument("--runs", required=True)
    args = ap.parse_args()

    from page_rank_and_tfidf_using_apache_spark_tpu.cli import pagerank as cli

    n, e, seed = (int(x) for x in args.input.split(":", 1)[1].split(","))
    graph = cli.synthetic_powerlaw(n, e, seed=seed)
    cli.synthetic_powerlaw = lambda *_a, **_kw: graph
    table = {}
    with tempfile.TemporaryDirectory(prefix="step_time_") as tmp:
        for run in args.runs.split(","):
            flag = "--shard-strategy" if args.mesh else "--spmv-impl"
            mj = os.path.join(tmp, f"{run}.json")
            argv = [args.input, str(args.iterations), "--dangling",
                    "redistribute", "--init", "uniform",
                    "--checkpoint-every", str(args.segment),
                    "--checkpoint-dir", os.path.join(tmp, f"{run}.ckpt"),
                    "--metrics-json", mj, flag, run]
            if args.mesh:
                argv += ["--mesh", str(args.mesh)]
            if cli.main(argv) != 0:
                raise SystemExit(f"{run}: cli.pagerank failed")
            with open(mj) as f:
                records = json.load(f)["records"]
            bad = [r for r in records
                   if r.get("event") in ("degraded", "exhausted")]
            if bad:
                raise SystemExit(f"{run}: left the device path: {bad}")
            secs = [r["secs"] for r in records if "iter" in r and "secs" in r]
            table[run] = {"segment_s": secs,
                          "warm_step_s": secs[-1] / args.segment}
            print(f"[{run}] segment_s={secs} "
                  f"warm_step_s={table[run]['warm_step_s']:.6f}",
                  file=sys.stderr, flush=True)
    print(json.dumps({"input": args.input, "mesh": args.mesh, "runs": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
