"""Median time a TF-IDF build takes to pull its output to the host (ms):
the program's ``tfidf.result_pull`` span (the pair-count sync and the six
output arrays)."""

import statistics


def read(run):
    secs = [s["secs"] for s in run.spans("tfidf.result_pull")]
    return statistics.median(secs) * 1e3 if secs else None
