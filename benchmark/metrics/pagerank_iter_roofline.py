"""Share of the HBM roofline one PageRank iteration reaches (%): the least
time of an iteration (``costs.pagerank_iteration_bytes`` over the chip's
HBM bandwidth) over the device time of the jitted iteration program
``jit_run`` in the traced window per iteration run in it."""

import costs

PROGRAM = "jit_run"  # ops.pagerank.make_pagerank_runner's jitted loop


def read(run):
    secs = run.trace.program_s.get(PROGRAM) if run.trace else None
    iters = run.window.counts.get("iterations")
    if not secs or not iters:
        return None
    c = run.cell.config
    least = costs.pagerank_iteration_bytes(c["n_nodes"], c["n_edges"]) / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / (secs / iters)
