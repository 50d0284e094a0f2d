"""The harness finds a new cell, configuration and per-layer metric added
as files, and runs them; without a chip the entry point refuses."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT, run_cell

TINY_GRAPH = {"name": "tinygraph", "source": "https://example.org/tiny-graph",
              "n_nodes": 1500, "n_edges": 9000, "iterations": 5, "damping": 0.85,
              "dangling": "redistribute", "init": "uniform", "dtype": "float32",
              "graph": {"in_exponent": 2.1, "in_max": 200, "out_exponent": 2.72,
                        "out_max": 40, "dangling_share": 0.15}, "reduced": [],
              "limits": {"pagerank_l1": 1e-4}}
JOBS_METRIC = '''
def read(run):
    return float(run.window.counts["jobs"])
'''


def digests(root):
    out = {}
    for dirpath, _, files in os.walk(root / "benchmark"):
        for f in files:
            p = os.path.join(dirpath, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_found(small_root):
    before = digests(small_root)
    (small_root / "benchmark" / "configs" / "tinygraph.json").write_text(json.dumps(TINY_GRAPH))
    (small_root / "benchmark" / "metrics" / "pagerank_jobs_done.py").write_text(JOBS_METRIC)
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tinygraph", "source": TINY_GRAPH["source"],
                            "file": "benchmark/configs/tinygraph.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "pagerank.tiny", "config": "tinygraph",
                              "traffic": "pagerank_jobs", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "pagerank_iters_per_s":
            m["workloads"].append("pagerank.tiny")
    spec["per_layer"].append({"name": "pagerank_jobs_done", "unit": "jobs", "better": "higher",
                              "source": "host_clock", "layer": "drivers",
                              "moves": "pagerank_iters_per_s", "workloads": ["pagerank.tiny"]})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))

    out = run_cell(small_root, "pagerank.tiny", seconds=1.0)
    assert out["correct"] and set(out["metrics"]) == {"pagerank_iters_per_s", "setup_s"}
    assert out["metrics"]["pagerank_iters_per_s"]["value"] > 0
    traced = run_cell(small_root, "pagerank.tiny", seconds=1.0, trace=1)
    assert traced["correct"]
    assert traced["metrics"]["pagerank_jobs_done"]["value"] == traced["attempted"] >= 1
    assert list(traced)[-1] == "checks"
    after = digests(small_root)
    assert all(after[p] == h for p, h in before.items())  # no existing file edited


def _run_entry(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmark/run.py", "--workload", "pagerank.webgoogle",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_without_a_chip():
    proc = _run_entry(ROOT)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_entry_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0 and proc.stdout == ""
