"""The graph500 cell at small scale on the CPU: the Kronecker generator,
the blocked reference, the cell's files found by name, and the check
failing planted faults.  The cell runs on four chips, so this module asks
the CPU backend for four devices (before any test starts it) and skips
the cell's runs where the backend came up with fewer."""

import dataclasses
import importlib
import json
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402
from conftest import BENCH, run_cell  # noqa: E402
from reference import pagerank as ref  # noqa: E402
from reference import pagerank_blocked as blocked  # noqa: E402
from traffic import kronecker  # noqa: E402

CELL = "pagerank.graph500-25.4chip"
INITIATOR = [0.57, 0.19, 0.19, 0.05]
BIG_SEED = 2**31 + 4242


@pytest.fixture
def graph500_root(small_root):
    """The benchmark copy with the graph500 configuration at scale 12."""
    path = small_root / "benchmark" / "configs" / "graph500-25.json"
    config = json.loads(path.read_text())
    config["scale"] = 12
    path.write_text(json.dumps(config))
    return small_root


@pytest.fixture
def four_devices():
    import jax

    if jax.device_count() < 4:
        pytest.skip("the CPU backend started with fewer than 4 devices")


@pytest.mark.parametrize("scale", [12, 16])
def test_kronecker_graph_is_graphalytics_shaped(scale):
    src, dst, n = kronecker.graph500(scale, 16, INITIATOR, BIG_SEED)
    assert src.dtype == dst.dtype == np.int32 and src.size == dst.size
    again = kronecker.graph500(scale, 16, INITIATOR, BIG_SEED)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1]) and n == again[2]
    other = kronecker.graph500(scale, 16, INITIATOR, BIG_SEED + 1)
    assert other[0].size != src.size or not np.array_equal(src, other[0])
    key = dst.astype(np.int64) * n + src
    assert (np.diff(key) > 0).all()  # sorted by (dst, src), no duplicate
    assert not (src == dst).any()
    mirror = np.sort(src.astype(np.int64) * n + dst)
    assert np.array_equal(mirror, key)  # every arc's reverse is there
    assert np.array_equal(np.unique(dst), np.arange(n))  # no isolated vertex
    assert n < 1 << scale and src.size < 2 * 16 << scale
    degree = np.bincount(dst, minlength=n)
    assert degree.max() > 20 * degree.mean()  # a heavy tail


def test_kronecker_needs_a_distribution():
    with pytest.raises(ValueError):
        kronecker.graph500(10, 16, [0.5, 0.2, 0.2, 0.2], 1)


def test_blocked_reference_equals_plain_reference():
    src, dst, n = kronecker.graph500(14, 16, INITIATOR, BIG_SEED)
    want = ref.pagerank(src, dst, n, 20, 0.85)
    got = blocked.pagerank(src, dst, n, 20, 0.85)
    assert np.abs(got - want).max() < 1e-12 and abs(got.sum() - 1.0) < 1e-12
    # with dangling vertices too (a directed subset of the arcs)
    keep = src < dst
    want = ref.pagerank(src[keep], dst[keep], n, 20, 0.85)
    matrix = blocked.Matrix(src[keep], dst[keep], n, threads=3)
    assert np.abs(blocked.pagerank(None, None, n, 20, 0.85, matrix=matrix)
                  - want).max() < 1e-12


def test_cell_files_are_found_and_run(graph500_root, four_devices):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 4
    for m in spec["per_layer"]:
        if CELL in m.get("workloads", []):
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    out = run_cell(graph500_root, CELL, seed=BIG_SEED, seconds=1.0)
    assert out["correct"] and set(out["metrics"]) == {"pagerank_iters_per_s", "setup_s"}
    assert out["device"]["count"] == 4 and out["checks"]["pagerank_l1"]["value"] < 1e-5
    traced = run_cell(graph500_root, CELL, seed=BIG_SEED + 1, seconds=1.0, trace=1)
    assert traced["correct"] and traced["attempted"] >= 1
    # set-up compiled every program of a job: the window compiles nothing
    # (what is left is the trace of a cached jit call or two)
    assert traced["metrics"]["pagerank_compiles_per_job"]["value"] == 0.0
    assert traced["metrics"]["pagerank_compile_ms_per_job"]["value"] < 1.0


def _unchanged(monkeypatch):
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import pagerank_sharded as ps

    make = ps.make_sharded_runner

    def planted(sg, cfg, mesh):
        run = make(sg, cfg, mesh)

        @jax.jit
        def unchanged(ranks0, *arrays):
            _, iters, delta = run(ranks0, *arrays)
            return ranks0, iters, delta
        return unchanged
    monkeypatch.setattr(ps, "make_sharded_runner", planted)


def _rank_altered(monkeypatch):
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import pagerank_sharded as ps

    make = ps.make_sharded_runner

    def planted(sg, cfg, mesh):
        run = make(sg, cfg, mesh)

        @jax.jit
        def altered(ranks0, *arrays):
            ranks, iters, delta = run(ranks0, *arrays)
            return ranks.at[0].add(1e-2), iters, delta
        return altered
    monkeypatch.setattr(ps, "make_sharded_runner", planted)


@pytest.mark.parametrize("plant", [_unchanged, _rank_altered], ids=lambda f: f.__name__)
def test_planted_fault_reads_incorrect(graph500_root, four_devices, monkeypatch, plant):
    plant(monkeypatch)
    out = run_cell(graph500_root, CELL, seed=BIG_SEED + 2, seconds=1.0)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("control", ["bf16", "bf16_ranks"])
def test_program_passes_and_control_fails(graph500_root, four_devices, control):
    cell = harness.load_cell(graph500_root, CELL, BIG_SEED + 3, 1.0)
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    state = driver.setup(cell)
    win = driver.window(state, 1.0)
    driver.release(state)
    program = driver.check(state, win)
    assert all(v <= lim for v, lim in program.values()), program
    low = driver.controls(state, win)[control]
    read = driver.check(state, dataclasses.replace(win, outputs=low))
    assert any(v > lim for v, lim in read.values()), read


def test_readers_of_the_cell_trace(graph500_root):
    """The cell's readers on a reduced trace shaped like a v5e run's: the
    step's all-reduce is ``psum.12`` there, the program
    ``jit_sharded_pagerank``."""
    xplane = harness.load_module(BENCH / "trace" / "xplane.py", "bench_xplane_g500")
    trace = xplane.Reduced(
        window_s=73.79, n_devices=4, busy_s=73.42,
        op_s={"fusion.56": 31.78, "fusion.80": 31.63, "psum.12": 1.95,
              "collective-permute-done.1": 0.05, "all-reduce-start.3": 0.02,
              "select_add_fusion.25": 0.07},
        program_s={"jit_sharded_pagerank": 73.4}, idle_s={})
    cell = harness.load_cell(graph500_root, CELL, 1, 30.0)
    win = harness.Window(t0=0.0, t1=73.79, attempted=1, failed=0, end_to_end={},
                         counts={"iterations": 20, "jobs": 1, "n_nodes": 17_060_584,
                                 "n_arcs": 1_047_205_424}, outputs=[])
    dispatch = {"kind": "span_end", "name": "pagerank.dispatch", "t": 0.01, "secs": 0.001}
    run = harness.Run(cell=cell, window=win, events=[dispatch], trace=trace,
                      device_kind="TPU v5 lite")
    read = {m["name"]: harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                           f"bench_metric_{m['name']}").read(run)
            for m in cell.per_layer}
    assert set(read) == {"pagerank_sharded_iter_roofline", "pagerank_collective_ms_per_iter",
                         "device_idle_pct.pagerank", "pagerank_compile_ms_per_job",
                         "pagerank_compiles_per_job"}
    assert read["pagerank_collective_ms_per_iter"] == pytest.approx(1e3 * 2.02 / 20)
    least = (8 * 1_047_205_424 + 20 * 17_060_584) / (4 * 819e9)
    assert read["pagerank_sharded_iter_roofline"] == pytest.approx(100 * least / (73.4 / 20))
    assert read["device_idle_pct.pagerank"] == pytest.approx(100 * (1 - 73.42 / 73.79))
    assert read["pagerank_compiles_per_job"] == read["pagerank_compile_ms_per_job"] == 0.0
    empty = dataclasses.replace(run, trace=None, events=[])
    assert all(harness.load_module(BENCH / "metrics" / f"{m}.py", f"bench_metric_{m}").read(empty)
               is None for m in read)
