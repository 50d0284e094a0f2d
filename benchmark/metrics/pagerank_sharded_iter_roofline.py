"""Share of the HBM roofline of the chips one sharded PageRank iteration
reaches (%): the least time of an iteration (``costs.pagerank_iteration_bytes``
of the drawn graph's vertices and arcs, over the HBM bandwidth of all the
chips) over the device time per iteration of the sharded iteration
programs, ``jit_sharded_pagerank`` (``jit_sharded_pagerank_owned`` under
the owned strategy), mean per chip."""

import costs

PROGRAM = "jit_sharded_pagerank"  # parallel.pagerank_sharded.make_sharded_runner


def read(run):
    t = run.trace
    secs = sum(s for name, s in t.program_s.items() if name.startswith(PROGRAM)) if t else 0
    iters = run.window.counts.get("iterations")
    n, arcs = run.window.counts.get("n_nodes"), run.window.counts.get("n_arcs")
    if not secs or not iters or not n or not arcs:
        return None
    chips = max(t.n_devices, 1)
    least = costs.pagerank_iteration_bytes(n, arcs) / (chips * run.peaks()["hbm_bytes_per_s"])
    return 100.0 * least / (secs / iters)
