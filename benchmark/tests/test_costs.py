"""The roofline's bytes are a function of sizes alone."""

import inspect
import json
import types

import costs
import harness
from conftest import BENCH


def test_pagerank_bytes_from_sizes_only():
    assert list(inspect.signature(costs.pagerank_iteration_bytes).parameters) == [
        "n_nodes", "n_edges"]
    assert costs.pagerank_iteration_bytes(875_713, 5_105_039) == 58_354_572


def test_roofline_reader_counts_the_same_work_whatever_impl():
    reader = harness.load_module(BENCH / "metrics" / "pagerank_iter_roofline.py", "roofline")
    config = json.loads((BENCH / "configs" / "webgoogle.json").read_text())
    values = []
    for impl in ("segment", "hybrid", "sort_shuffle"):
        cell = types.SimpleNamespace(config=dict(config, spmv_impl=impl), root=BENCH.parent)
        run = harness.Run(cell=cell, window=types.SimpleNamespace(counts={"iterations": 40}),
                          events=[], trace=types.SimpleNamespace(program_s={"jit_run": 2.0}),
                          device_kind="TPU v5 lite")
        values.append(reader.read(run))
    least = 58_354_572 / 819e9
    assert values == [100.0 * least / 0.05] * 3
