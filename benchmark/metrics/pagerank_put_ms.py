"""Median host layout-and-put time of a PageRank job (ms): the program's
``put_graph`` record, ``preprocess_secs``."""

import statistics


def read(run):
    secs = [r["preprocess_secs"] for r in run.records("put_graph")]
    return statistics.median(secs) * 1e3 if secs else None
