"""Closed loop of PageRank jobs on a graph resident on the cell's chips.

Set-up draws the configuration's Graph500 graph from the seed
(``traffic/kronecker.py``), hands its sorted arcs to the program's ingest
(``io.graph.from_sorted_arcs``, which checks them and counts the
out-degrees), and builds the program's resident sharded graph over the
cell's chips: ``parallel.pagerank_sharded.ShardedPageRank``, which picks
its strategy (the configuration asks for ``auto``), partitions the graph
and puts it on the mesh once.  Its programs are then compiled without
running a job.  Graphalytics times loading apart from processing; a job
here is the processing: start ranks put, ``iterations`` steps, ranks
pulled to the host.  The check compares every job's ranks with a float64
power iteration in row blocks on the same arcs
(``reference/pagerank_blocked.py``).
"""

from __future__ import annotations

import dataclasses
import resource
import sys
import time

import numpy as np

import harness
from reference import pagerank as ref
from reference import pagerank_blocked as blocked
from traffic import kronecker

SPANS = frozenset({harness.JOB})


@dataclasses.dataclass
class State:
    config: dict
    src: np.ndarray
    dst: np.ndarray
    n: int
    job: object  # the program's ShardedPageRank


def program_config(c: dict):
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    return PageRankConfig(iterations=c["iterations"], damping=c["damping"],
                          dangling=c["dangling"], init=c["init"], dtype=c["dtype"])


def setup(cell) -> State:
    # first, so that a program without the resident sharded graph fails
    # here at once, before the graph is drawn
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
        ShardedPageRank,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_sorted_arcs

    c = cell.config
    t0 = time.perf_counter()
    src, dst, n = kronecker.graph500(c["scale"], c["edge_factor"], c["initiator"],
                                     cell.seed)
    t1 = time.perf_counter()
    # the arcs come sorted by (dst, src) and unique: the program checks that
    # and counts the out-degrees itself
    graph = from_sorted_arcs(src, dst, n)
    t2 = time.perf_counter()
    job = ShardedPageRank(graph, program_config(c), n_devices=cell.chips,
                          strategy=c["strategy"])
    t3 = time.perf_counter()
    job.compile()
    t4 = time.perf_counter()
    (part,) = [r for r in job.metrics.records if r.get("event") == "partition"]
    print(f"setup vertices={n} arcs={src.size} draw_s={t1 - t0:.3f} graph_s={t2 - t1:.3f} "
          f"build_s={t3 - t2:.3f} (partition and put {part['secs']:.3f}, put "
          f"{part.get('put_secs', float('nan')):.3f}) "
          f"compile_s={t4 - t3:.3f} strategy={job.strategy} "
          f"segment_reduce={part['segment_reduce']} pad_frac={part['pad_frac']} "
          f"edges_per_device={part['edges_per_device']} "
          f"host_maxrss_gb={resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.3f}",
          file=sys.stderr)
    return State(config=c, src=src, dst=dst, n=n, job=job)


def window(state: State, seconds: float) -> harness.Window:
    t0, ends, ranks, host = harness.closed_loop(seconds, lambda: state.job.run().ranks)
    # a window holds one or two jobs, too few for the harness's slow-job
    # lines: each job's host readings tell a stall of the machine (the
    # ticker woke late) from a slower device
    for i, (a, b) in enumerate(zip(host, host[1:])):
        print(f"job {i} wall_s={b['t'] - a['t']:.6f} cpu_s=+{b['cpu_s'] - a['cpu_s']:.6g} "
              f"ticker_late_s={b['ticker_late_s']:.6g}", file=sys.stderr)
    iters = state.config["iterations"] * len(ranks)
    return harness.Window(
        t0=t0, t1=ends[-1], attempted=len(ranks), failed=0,
        end_to_end={"pagerank_iters_per_s": iters / (ends[-1] - t0)},
        counts={"iterations": iters, "jobs": len(ranks), "host": host,
                "n_nodes": state.n, "n_arcs": int(state.src.size)},
        outputs=ranks)


def release(state: State) -> None:
    state.job = None


def l1_gaps(state: State, outputs: list) -> float:
    """Largest L1 distance of a job's ranks from the reference's."""
    c = state.config
    r = blocked.pagerank(state.src, state.dst, state.n, c["iterations"], c["damping"])
    return max(float(np.abs(np.asarray(x, np.float64) - r).sum()) for x in outputs)


def check(state: State, win: harness.Window) -> dict:
    t = time.perf_counter()
    gap = l1_gaps(state, win.outputs)
    print(f"check reference_s={time.perf_counter() - t:.3f} "
          f"host_maxrss_gb={resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.3f}",
          file=sys.stderr)
    return {"pagerank_l1": (gap, state.config["limits"]["pagerank_l1"])}


def controls(state: State, win: harness.Window) -> dict:
    """The reference in a precision below the configuration's, put in the
    program's place: ``bf16`` computes in bfloat16 throughout (one arc at
    a time: affordable on the host up to scale ~22); ``bf16_ranks``
    accumulates in float32 and holds the ranks in bfloat16 between steps
    and at the end (a program that stores or pulls its ranks in
    bfloat16)."""
    import ml_dtypes

    c = state.config
    args = (state.src, state.dst, state.n, c["iterations"], c["damping"])
    return {"bf16": [ref.pagerank(*args, dtype=ml_dtypes.bfloat16)],
            "bf16_ranks": [blocked.pagerank(*args, dtype=np.float32,
                                            store=ml_dtypes.bfloat16)]}
