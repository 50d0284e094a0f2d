"""The benchmark harness: finds a cell's files by name, runs set-up, the
measured window and the check, and prints the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; everything else is found from those
names, so a later PR adds a cell, a configuration or a per-layer metric by
adding files and entries, and edits none:

- ``benchmark/configs/<config>.json``: sizes, stated semantics and the
  limit of each number the check compares;
- ``benchmark/traffic/mixes/<traffic>.json``: traffic parameters and the
  name of the driver that runs them;
- ``benchmark/drivers/<driver>.py``: ``setup(cell)``, ``window(state,
  seconds)``, ``release(state)`` and ``check(state, window)``;
- ``benchmark/metrics/<metric>.py``: ``read(run)``, a per-layer metric
  from the program's spans and records, the window and the reduced trace,
  or None where it finds nothing to read;
- ``benchmark/peaks.json``: published peaks by ``device_kind``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any

WINDOW = "bench.window"  # TraceAnnotation around every measured window


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    root: Path  # checkout root: BENCHMARK.json and benchmark/
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class Window:
    """What a driver's measured window did."""

    t0: float  # perf_counter at window start
    t1: float  # perf_counter at window end
    attempted: int
    failed: int
    end_to_end: dict  # end-to-end metric name -> value
    counts: dict  # work done in the window, for the per-layer readers
    outputs: Any  # what the check compares


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""

    cell: Cell
    window: Window
    events: list  # program span ends and metric records during the window
    trace: Any  # trace.xplane.Reduced
    device_kind: str

    def spans(self, name: str) -> list:
        return [e for e in self.events if e["kind"] == "span_end" and e["name"] == name]

    def records(self, event: str) -> list:
        return [e for e in self.events if e["kind"] == "metric" and e.get("event") == event]

    def peaks(self) -> dict:
        table = json.loads((self.cell.root / "benchmark" / "peaks.json").read_text())
        if self.device_kind not in table["devices"]:
            raise ValueError(f"no peaks for device_kind {self.device_kind!r} in peaks.json")
        return table["devices"][self.device_kind]


class Recorder:
    """obs-bus sink keeping the program's span ends and metric records,
    and every degraded/exhausted event (a run that fell off the chip)."""

    def __init__(self, keep_all: bool):
        self.keep_all = keep_all
        self.events: list = []
        self.faults: list = []
        self._lock = threading.Lock()

    def emit(self, event: dict) -> None:
        kind = event.get("kind")
        if kind in ("degraded", "exhausted"):
            with self._lock:
                self.faults.append(event)
        elif self.keep_all and kind in ("span_end", "metric"):
            with self._lock:
                self.events.append(event)

    def between(self, t0: float, t1: float) -> list:
        with self._lock:
            return [e for e in self.events if t0 <= e["t"] <= t1]


class CompileCounter:
    """Counts backend compiles (a persistent-cache fetch included)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


JOB = "bench.job"  # TraceAnnotation around each job of a closed loop


class Ticker:
    """A thread that wakes every ``period`` seconds and keeps how late it
    woke at most: a stall of the machine (the process not run) makes it
    late too; a wait on the device, which releases the GIL, does not."""

    def __init__(self, period: float = 0.02):
        self.period = period
        self.late_s = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, name="bench-ticker", daemon=True)

    def _tick(self) -> None:
        while True:
            t = time.perf_counter()
            if self._stop.wait(self.period):
                return
            self.late_s = max(self.late_s, time.perf_counter() - t - self.period)

    def take(self) -> float:
        late, self.late_s = self.late_s, 0.0
        return late

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def host_sample(ticker: Ticker) -> dict:
    """The host's readings that tell a stall of the machine from one of
    the process: the ticker's largest lateness since the last sample, and
    this process's CPU time."""
    return {"t": time.perf_counter(), "at": time.time(), "ticker_late_s": ticker.take(),
            "cpu_s": time.process_time()}


def closed_loop(seconds: float, job) -> tuple[float, list, list, list]:
    """Run ``job()`` back to back until ``seconds`` have passed: returns
    the window's start, each job's end, each job's output and the host's
    readings at the start and after each job.  The last job ends after the
    nominal end; rates are taken to its end."""
    from jax.profiler import TraceAnnotation

    outs, ends = [], []
    with Ticker() as ticker, TraceAnnotation(WINDOW):
        host = [host_sample(ticker)]
        t0 = time.perf_counter()
        while not ends or ends[-1] - t0 < seconds:
            with TraceAnnotation(JOB):
                outs.append(job())
            ends.append(time.perf_counter())
            host.append(host_sample(ticker))
    return t0, ends, outs, host


def report_slow_jobs(host: list, slow: float = 1.25) -> None:
    """Print to stderr each job that took over ``slow`` times the median
    job, with what the host's readings did meanwhile, and the window's
    totals: a stall in which the ticker woke late and the process used no
    more CPU than in any job is the machine's."""
    if len(host) < 3:
        return
    walls = [b["t"] - a["t"] for a, b in zip(host, host[1:])]
    med = sorted(walls)[len(walls) // 2]

    for i, (a, b) in enumerate(zip(host, host[1:])):
        if walls[i] > slow * med:
            print(f"slow_job {i} at={b['at']:.3f} wall_s={walls[i]:.6f} median_s={med:.6f} "
                  f"cpu_s=+{b['cpu_s'] - a['cpu_s']:.6g} ticker_late_s={b['ticker_late_s']:.6g}",
                  file=sys.stderr)
    print(f"host_window jobs={len(walls)} median_s={med:.6f} max_s={max(walls):.6f} "
          f"cpu_s=+{host[-1]['cpu_s'] - host[0]['cpu_s']:.6g} "
          f"ticker_late_max_s={max(h['ticker_late_s'] for h in host):.6g}", file=sys.stderr)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, name: str, seed: int, seconds: float) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload named {name!r} in BENCHMARK.json")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / f"{entry['config']}.json").read_text())
    traffic = json.loads((bench / "traffic" / "mixes" / f"{entry['traffic']}.json").read_text())

    def here(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(name=name, config=config, traffic=traffic, chips=int(entry["chips"]),
                seed=seed, seconds=seconds, root=root,
                end_to_end=[m for m in spec["end_to_end"] if here(m)],
                per_layer=[m for m in spec["per_layer"] if here(m)])


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``; every program is
    written to it, however fast it compiled, so a second run compiles
    nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmark: no accelerator; JAX's default device is "
                         f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def _reduce_trace(root: Path, trace_dir: str, labels: frozenset):
    import glob

    # by path: a plain ``import trace`` would find the standard library's
    xplane = load_module(root / "benchmark" / "trace" / "xplane.py", "bench_xplane")
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"benchmark: {len(paths)} .xplane.pb files under {trace_dir}")
    return xplane.reduce(paths[0], window=WINDOW, labels=labels), paths[0]


def prepare(root: Path, workload: str, seed: int, seconds: float, allow_cpu: bool):
    """The cell, the chips it runs on and its driver module, with the
    compile cache on; ``allow_cpu`` (tests only) skips the look for a chip."""
    cell = load_cell(root, workload, int(seed) % (1 << 63), float(seconds))
    enable_compile_cache(root)
    devices = devices_for(cell.chips, allow_cpu)
    return cell, devices, importlib.import_module(f"drivers.{cell.traffic['driver']}")


def run(argv_ns, *, root: Path, t_start: float, allow_cpu: bool = False) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    trace = bool(int(argv_ns.trace))
    cell, devices, driver = prepare(root, argv_ns.workload, argv_ns.seed,
                                    argv_ns.seconds, allow_cpu)

    from page_rank_and_tfidf_using_apache_spark_tpu import obs

    recorder = Recorder(keep_all=trace)
    obs.bus().attach(recorder)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        state = driver.setup(cell)
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        n_compiles0 = compiles.n
        win = driver.window(state, cell.seconds)
        window_compiles = compiles.n - n_compiles0
        report_slow_jobs(win.counts.get("host", []))
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            labels = frozenset(e["name"] for e in recorder.events if e["kind"] == "span_end")
            reduced, xplane_path = _reduce_trace(root, trace_dir, labels | driver.SPANS)
            keep = getattr(argv_ns, "keep_trace", None)
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(xplane_path, os.path.join(keep, f"{cell.name}.{cell.seed}.xplane.pb"))
            shutil.rmtree(trace_dir, ignore_errors=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        driver.release(state)
        checks = driver.check(state, win)
    finally:
        obs.bus().detach(recorder)
    checks["degraded_events"] = (float(len(recorder.faults)), 0.0)
    correct = all(v <= lim for v, lim in checks.values())

    metrics = {}
    if trace:
        ctx = Run(cell=cell, window=win, events=recorder.between(win.t0, win.t1),
                  trace=reduced, device_kind=devices[0].device_kind)
        for m in cell.per_layer:
            reader = load_module(root / "benchmark" / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(win.end_to_end, setup_s=win.t0 - t_start)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"driver {cell.traffic['driver']} gives no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": int(win.attempted), "failed": int(win.failed),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top(reduced.op_s),
                            "idle_gaps": reduced.top(reduced.idle_s)}
    print(f"benchmark: {cell.name} seed={cell.seed} window_s={win.t1 - win.t0:.6f} "
          f"attempted={win.attempted} failed={win.failed} "
          f"compiles_in_window={window_compiles}", file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAILED'}",
              file=sys.stderr)
    return out
