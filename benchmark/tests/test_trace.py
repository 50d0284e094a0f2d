"""The trace reduction on a small trace recorded on a v5e
(``record_fixture.py``): three runs of a jitted ``step`` in a
``bench.window``, each followed by 20 ms of host sleep."""

import harness
from conftest import BENCH

FIXTURE = BENCH / "tests" / "fixtures" / "chip_step.xplane.pb"


def test_reduce_recorded_chip_trace():
    xplane = harness.load_module(BENCH / "trace" / "xplane.py", "bench_xplane_test")
    r = xplane.reduce(str(FIXTURE), window="bench.window", labels=frozenset({"bench.job"}))
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s < 1.0
    assert r.program_s.get("jit_step", 0) > 0
    assert abs(sum(r.idle_s.values()) + r.busy_s - r.window_s) < 1e-6
    # the three sleeps happen inside bench.window and outside bench.job
    assert 0.055 < r.idle_s["bench.window"] < 0.2
    assert all(v >= 0 for v in r.op_s.values())
    assert sum(r.op_s.values()) <= r.busy_s * 1.001 + 1e-9
