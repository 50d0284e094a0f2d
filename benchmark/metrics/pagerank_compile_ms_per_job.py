"""Host time a PageRank job spends tracing, lowering and compiling (ms per
job): the length of the union of the window's ``jax.trace``, ``jax.lower``
and ``jax.compile`` spans, each ``[t - secs, t]`` clipped to the window (a
jit traced inside another's trace counts once), over the jobs run.  0.0
when jobs ran and nothing compiled.  None from a program whose jobs publish
no ``pagerank.dispatch`` span: it has no compile spans to read either."""

PHASES = ("jax.trace", "jax.lower", "jax.compile")


def read(run):
    jobs = run.window.counts.get("jobs")
    if not jobs or not run.spans("pagerank.dispatch"):
        return None
    lo, hi = run.window.t0, run.window.t1
    spans = sorted((max(e["t"] - e["secs"], lo), min(e["t"], hi))
                   for name in PHASES for e in run.spans(name))
    total, covered_to = 0.0, lo
    for begin, end in spans:
        if end > covered_to:
            total += end - max(begin, covered_to)
            covered_to = end
    return total / jobs * 1e3
