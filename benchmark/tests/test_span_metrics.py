"""The readers of the program's compile and pull spans, on synthetic runs:
overlapping compile spans count once, a window with jobs and no compile
reads 0.0, and a program without the spans reads None."""

import pytest

import harness
from conftest import BENCH


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_test_{name}")


def span(name, t, secs):
    return {"kind": "span_end", "name": name, "t": t, "secs": secs}


def run_of(events, counts, t0=100.0, t1=110.0):
    window = harness.Window(t0=t0, t1=t1, attempted=0, failed=0, end_to_end={},
                            counts=counts, outputs=None)
    return harness.Run(cell=None, window=window, events=events, trace=None, device_kind="cpu")


DISPATCH = [span("pagerank.dispatch", 102.0, 1.5), span("pagerank.dispatch", 106.0, 1.5)]


def test_compile_ms_counts_overlaps_once():
    events = DISPATCH + [
        span("jax.trace", 101.0, 0.2),  # [100.8, 101.0]
        span("jax.trace", 100.95, 0.1),  # nested inside the trace above
        span("jax.lower", 101.1, 0.1),  # [101.0, 101.1]
        span("jax.compile", 101.3, 0.15),  # [101.15, 101.3]
        span("jax.compile", 105.0, 0.05),  # [104.95, 105.0]
        span("pagerank.put_graph", 104.0, 3.0),  # not a compile phase
    ]
    got = reader("pagerank_compile_ms_per_job").read(run_of(events, {"jobs": 2}))
    assert got == pytest.approx((0.3 + 0.15 + 0.05) / 2 * 1e3)
    assert reader("pagerank_compiles_per_job").read(run_of(events, {"jobs": 2})) == 1.0


def test_compile_span_clipped_to_the_window():
    events = DISPATCH + [span("jax.compile", 100.5, 1.0)]  # began 0.5 s before t0
    got = reader("pagerank_compile_ms_per_job").read(run_of(events, {"jobs": 1}))
    assert got == pytest.approx(500.0)


def test_no_compile_in_the_window_reads_zero():
    run = run_of(DISPATCH, {"jobs": 2})
    assert reader("pagerank_compile_ms_per_job").read(run) == 0.0
    assert reader("pagerank_compiles_per_job").read(run) == 0.0


@pytest.mark.parametrize("name", ["pagerank_compile_ms_per_job", "pagerank_compiles_per_job"])
def test_compile_readers_without_the_program_spans(name):
    assert reader(name).read(run_of([span("jax.compile", 101.0, 0.1)], {"jobs": 2})) is None
    assert reader(name).read(run_of(DISPATCH, {"builds": 2})) is None


def test_pull_ms_is_the_median_pull_span():
    events = [span("tfidf.result_pull", 101.0 + i, s) for i, s in enumerate((0.02, 0.03, 0.05))]
    events.append(span("tfidf.pipeline", 104.0, 0.2))
    assert reader("tfidf_pull_ms_per_build").read(run_of(events, {"builds": 3})) == \
        pytest.approx(30.0)
    assert reader("tfidf_pull_ms_per_build").read(run_of([], {"builds": 3})) is None
