"""Backend compiles per PageRank job, a persistent-cache fetch included:
the window's ``jax.compile`` spans over the jobs run.  0.0 when jobs ran
and nothing compiled.  None from a program whose jobs publish no
``pagerank.dispatch`` span: it has no compile spans to read either."""


def read(run):
    jobs = run.window.counts.get("jobs")
    if not jobs or not run.spans("pagerank.dispatch"):
        return None
    return len(run.spans("jax.compile")) / jobs
