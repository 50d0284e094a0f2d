"""CPU tests of the benchmark at small sizes.  They run from a copy of
``BENCHMARK.json`` and ``benchmark/`` whose configurations are shrunk, and
call the harness with its look for a chip skipped."""

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

SMALL = {
    "webgoogle": {"n_nodes": 3000, "n_edges": 24000,
                  "graph": {"in_exponent": 2.1, "in_max": 400, "out_exponent": 2.72,
                            "out_max": 80, "dangling_share": 0.15}},
    "newsgroups20": {"corpus": {"n_docs": 400, "mean_doc_tokens": 60, "min_doc_tokens": 8,
                                "zipf": 1.0, "n_words": 3000, "lengths_seed": 20}},
}


def shrink(root: Path) -> None:
    for name, sizes in SMALL.items():
        path = root / "benchmark" / "configs" / f"{name}.json"
        config = json.loads(path.read_text())
        config.update(sizes)
        path.write_text(json.dumps(config))


@pytest.fixture
def small_root(tmp_path) -> Path:
    """A copy of the benchmark with small configurations."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shrink(tmp_path)
    return tmp_path


def run_cell(root: Path, name: str, *, seed: int = 1, seconds: float = 1.0,
             trace: int = 0) -> dict:
    ns = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace,
                            keep_trace=None)
    return harness.run(ns, root=root, t_start=time.perf_counter(), allow_cpu=True)
