"""PageRank CLI — the reference's ``spark-submit pagerank.py <edges>
<iters>`` entry point (SURVEY.md A1/A5, §2.2 R10), positional args first,
every reconstructed-semantics ambiguity an explicit flag.

Usage::

    python -m page_rank_and_tfidf_using_apache_spark_tpu.cli.pagerank \
        edges.txt 20 --output ranks.txt --dangling redistribute
"""

from __future__ import annotations

import argparse
import json
import sys

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
    load_snap,
    save_ranks,
    synthetic_powerlaw,
)
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.utils.compile_cache import enable_compile_cache
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    PageRankConfig,
    load_tuned_profile,
    tuned_config,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder, Timer
from page_rank_and_tfidf_using_apache_spark_tpu.utils.profiling import trace


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pagerank",
        description="TPU-native PageRank over a SNAP-format edge list.",
    )
    p.add_argument("input", help="SNAP edge-list file, or 'synthetic:N,E[,seed]'")
    p.add_argument("iterations", nargs="?", type=int, default=20)
    p.add_argument("--output", help="write '<node>\\t<rank>' lines here")
    p.add_argument("--top-k", type=int, default=None, help="only save the top-k ranks")
    p.add_argument("--damping", type=float, default=0.85)
    p.add_argument("--tol", type=float, default=0.0, help="early-stop L1 tolerance")
    p.add_argument("--dangling", choices=["drop", "redistribute"], default="drop")
    p.add_argument("--init", choices=["one", "uniform"], default="one")
    p.add_argument("--spark-exact", action="store_true",
                   help="bit-exact canonical Spark example semantics")
    p.add_argument("--personalize", type=int, nargs="+", default=None,
                   metavar="NODE",
                   help="personalized PageRank source node(s), as ORIGINAL "
                        "ids from the input file")
    p.add_argument("--spmv-impl",
                   choices=["segment", "bcoo", "cumsum", "cumsum_mxu",
                            "hybrid", "sort_shuffle", "pallas"],
                   default="segment")
    p.add_argument("--head-coverage", type=float, default=None,
                   help="hybrid impl/strategy: edge-coverage threshold of "
                        "the dense high-in-degree head (default: tuned "
                        "profile, then TUNABLE_DEFAULTS)")
    p.add_argument("--head-row-width", type=int, default=None,
                   help="hybrid impl/strategy: dense row width (MXU lane "
                        "count; adapts down on small graphs; default: tuned "
                        "profile, then TUNABLE_DEFAULTS)")
    p.add_argument("--tuned-profile", default=None, metavar="PATH",
                   help="tuned-profile artifact to resolve unset knobs "
                        "from ('off' disables profile loading; default: "
                        "$GRAFT_TUNED_PROFILE, then the committed "
                        "tuned_profile_<backend>.json)")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--metrics-json", help="dump structured metrics JSON here")
    p.add_argument("--profile-dir", help="jax.profiler trace output dir")
    p.add_argument("--trace-dir", default=None,
                   help="obs run-telemetry dir: write <name>.<pid>.trace.jsonl"
                        " + manifest here (default: $GRAFT_TRACE_DIR)")
    p.add_argument("--mesh", type=int, default=0,
                   help="shard over this many devices (0 = single device)")
    p.add_argument("--shard-strategy",
                   choices=["auto", "hybrid", "owned"],
                   default="auto",
                   help="graph layout under --mesh: auto (hybrid while the "
                        "replicated state fits half of a chip's memory, "
                        "else owned) / hybrid (replicated ranks: equal "
                        "edge slices, dense head rows when the power-law "
                        "head covers a quarter of the arcs) / owned (rank "
                        "slices with a sparse boundary exchange)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    # The traced run covers the whole driver: manifest at startup, every
    # span/retry/checkpoint event flushed per-event to the JSONL trace,
    # run-end summary at exit (no-op without --trace-dir/GRAFT_TRACE_DIR).
    with obs.run("pagerank", trace_dir=args.trace_dir):
        return _main(args)


def _main(args) -> int:
    metrics = MetricsRecorder()

    with Timer() as t_load:
        if args.input.startswith("synthetic:"):
            parts = args.input.split(":", 1)[1].split(",")
            n, e = int(parts[0]), int(parts[1])
            seed = int(parts[2]) if len(parts) > 2 else 0
            graph = synthetic_powerlaw(n, e, seed=seed)
        else:
            graph = load_snap(args.input)
    metrics.record(event="load", nodes=graph.n_nodes, edges=graph.n_edges,
                   secs=t_load.elapsed)

    # knob resolution ladder: explicit flag > tuned profile (same-backend
    # only, ProvenanceError otherwise) > TUNABLE_DEFAULTS
    profile = (None if args.tuned_profile == "off"
               else load_tuned_profile(path=args.tuned_profile))
    cfg = tuned_config(
        PageRankConfig, profile,
        iterations=args.iterations,
        damping=args.damping,
        tol=args.tol,
        dangling=args.dangling,
        init=args.init,
        spark_exact=args.spark_exact,
        personalize=tuple(args.personalize) if args.personalize else None,
        spmv_impl=args.spmv_impl,
        head_coverage=args.head_coverage,
        head_row_width=args.head_row_width,
        dtype=args.dtype,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
    )

    with trace(args.profile_dir):
        if args.mesh:
            try:
                from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
                    pagerank_sharded,
                )
            except ImportError:
                print("error: the multi-chip sharded path (parallel/) is not "
                      "present in this build; drop --mesh", file=sys.stderr)
                return 2

            result = pagerank_sharded.run_pagerank_sharded(
                graph, cfg, n_devices=args.mesh, strategy=args.shard_strategy,
                metrics=metrics, resume=args.resume,
            )
        else:
            result = run_pagerank(graph, cfg, metrics=metrics, resume=args.resume)

    if args.output:
        save_ranks(args.output, graph, result.ranks, top_k=args.top_k)
    else:
        order = result.ranks.argsort()[::-1][: args.top_k or 10]
        for i in order:
            print(f"{graph.node_ids[i]}\t{result.ranks[i]:.10g}")

    summary = {
        "nodes": graph.n_nodes, "edges": graph.n_edges,
        "iterations": result.iterations, "l1_delta": result.l1_delta,
    }
    print(json.dumps(summary), file=sys.stderr)
    if args.metrics_json:
        metrics.dump(args.metrics_json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
