"""Closed loop of back-to-back PageRank jobs on one graph.

Set-up generates the configuration's graph from the seed, builds the
program's host graph from it and runs one job (which compiles, or loads
the programs from the persistent cache).  A job is the program's normal
entry, ``models.pagerank.run_pagerank(graph, cfg)``: layout and put,
``iterations`` steps, ranks pulled to the host.  The check compares every
job's ranks with a float64 power iteration on the same edges.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import harness
from reference import pagerank as ref
from traffic import graph as gen

SPANS = frozenset({harness.JOB})


@dataclasses.dataclass
class State:
    config: dict
    src: np.ndarray
    dst: np.ndarray
    graph: object
    cfg: object


def program_config(c: dict):
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    return PageRankConfig(iterations=c["iterations"], damping=c["damping"],
                          dangling=c["dangling"], init=c["init"], dtype=c["dtype"])


def setup(cell) -> State:
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_edges
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank

    c = cell.config
    src, dst = gen.web_edges(c["n_nodes"], c["n_edges"], seed=cell.seed, **c["graph"])
    graph = from_edges(src, dst, dedup=False, compact_ids=False)
    cfg = program_config(c)
    run_pagerank(graph, cfg)  # warm-up: compiles or loads every program
    return State(config=c, src=src, dst=dst, graph=graph, cfg=cfg)


def window(state: State, seconds: float) -> harness.Window:
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank

    t0, ends, ranks, host = harness.closed_loop(
        seconds, lambda: run_pagerank(state.graph, state.cfg).ranks)
    iters = state.config["iterations"] * len(ranks)
    return harness.Window(
        t0=t0, t1=ends[-1], attempted=len(ranks), failed=0,
        end_to_end={"pagerank_iters_per_s": iters / (ends[-1] - t0)},
        counts={"iterations": iters, "jobs": len(ranks), "host": host}, outputs=ranks)


def release(state: State) -> None:
    state.graph = None


def l1_gaps(state: State, outputs: list) -> float:
    """Largest L1 distance of a job's ranks from the reference's."""
    c = state.config
    r = ref.pagerank(state.src, state.dst, c["n_nodes"], c["iterations"], c["damping"])
    return max(float(np.abs(np.asarray(x, np.float64) - r).sum()) for x in outputs)


def check(state: State, win: harness.Window) -> dict:
    return {"pagerank_l1": (l1_gaps(state, win.outputs),
                            state.config["limits"]["pagerank_l1"])}


def controls(state: State, win: harness.Window) -> dict:
    """The reference in a precision below the configuration's, put in the
    program's place: ``bf16`` computes in bfloat16 throughout; ``bf16_ranks``
    accumulates in float32 and holds the ranks in bfloat16 between steps
    and at the end (a program that stores or pulls its ranks in
    bfloat16)."""
    import ml_dtypes

    c = state.config
    args = (state.src, state.dst, c["n_nodes"], c["iterations"], c["damping"])
    return {"bf16": [ref.pagerank(*args, dtype=ml_dtypes.bfloat16)],
            "bf16_ranks": [ref.pagerank(*args, dtype=np.float32, store=ml_dtypes.bfloat16)]}
