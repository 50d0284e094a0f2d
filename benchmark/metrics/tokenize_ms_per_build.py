"""Median host tokenize-and-hash time of a TF-IDF build (ms): the
program's ``io.tokenize`` span."""

import statistics


def read(run):
    secs = [s["secs"] for s in run.spans("io.tokenize")]
    return statistics.median(secs) * 1e3 if secs else None
