"""The check fails what it must: the reference in a precision below the
configuration's, put in the program's place (the controls), and the
program with a fault planted where its answers are produced.  Small sizes,
on the CPU; the same controls run on the chip at each cell's size through
``calibrate.py``."""

import dataclasses
import importlib

import pytest

import harness
from conftest import run_cell

CONTROLS = [("pagerank.webgoogle", "bf16"), ("pagerank.webgoogle", "bf16_ranks"),
            ("tfidf.20ng.build", "bf16"), ("tfidf.20ng.build", "bf16_weights")]


@pytest.mark.parametrize("name,control", CONTROLS, ids=[f"{c}-{k}" for c, k in CONTROLS])
def test_program_passes_and_control_fails(small_root, name, control):
    cell = harness.load_cell(small_root, name, 2**31 + 77, 1.0)
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    state = driver.setup(cell)
    win = driver.window(state, 1.0)
    driver.release(state)
    program = driver.check(state, win)
    assert all(v <= lim for v, lim in program.values()), program
    low = driver.controls(state, win)[control]
    read = driver.check(state, dataclasses.replace(win, outputs=low))
    assert any(v > lim for v, lim in read.values()), read


def _state_unchanged(monkeypatch):
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    monkeypatch.setattr(ops, "pagerank_step", lambda ranks, *a, **k: ranks)


def _rank_altered(monkeypatch):
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    make = ops.make_pagerank_runner

    def altered(n, cfg):
        run = make(n, cfg)

        def wrapped(dg, r0, e):
            ranks, iters, delta = run(dg, r0, e)
            return ranks.at[0].add(1e-2), iters, delta
        return wrapped
    monkeypatch.setattr(ops, "make_pagerank_runner", altered)


def _weight_altered(monkeypatch):
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops

    pipeline = ops.tfidf_pipeline

    def altered(*a, **k):
        out = pipeline(*a, **k)
        return out._replace(weight=out.weight.at[0].add(1e-2))
    monkeypatch.setattr(ops, "tfidf_pipeline", altered)


def _half_the_docs(monkeypatch):
    from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio

    tokenize = tio.tokenize_corpus

    def half(docs, **k):
        return tokenize([d if i % 2 == 0 else "" for i, d in enumerate(docs)], **k)
    monkeypatch.setattr(tio, "tokenize_corpus", half)


FAULTS = [
    ("pagerank.webgoogle", _state_unchanged),
    ("pagerank.webgoogle", _rank_altered),
    ("tfidf.20ng.build", _weight_altered),
    ("tfidf.20ng.build", _half_the_docs),
]


@pytest.mark.parametrize("name,plant", FAULTS, ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_planted_fault_reads_incorrect(small_root, monkeypatch, name, plant):
    plant(monkeypatch)
    out = run_cell(small_root, name, seed=2**31 + 99, seconds=1.0)
    assert out["correct"] is False, out["checks"]
