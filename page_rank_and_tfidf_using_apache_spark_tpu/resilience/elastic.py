"""Elastic mesh degradation: shrink-and-resume on device loss (ISSUE 5).

Spark reschedules a lost executor's partitions onto the surviving pool;
a sharded XLA program cannot — its collectives are compiled against one
mesh, so a dead device kills every step that touches it (JAMPI, arxiv
2007.01811: a barrier-style sharded step must be *rebuilt*, not retried,
when the group shrinks).  What it CAN do — DrJAX's observation (arxiv
2403.07128) — is be re-expressed over a different leaf count without
changing semantics.  This module supplies the runtime pieces that turn
that into a degradation rung for the sharded runners:

- a process-global :class:`DeviceHealth` registry of lost logical devices
  (fed by chaos injection today, real XLA device errors in production);
- :func:`probe_devices` — a cheap per-device liveness check;
- :func:`plan_shrink` — pick the surviving devices (power-of-two shrink,
  ``parallel.mesh.shrink_devices``), name the ladder rung taken
  (``mesh_shrink`` while >1 device survives, ``single_device`` at the
  1-device end of the chain, ``cpu`` when the accelerator pool is gone
  and the CPU backend must host the 1-device mesh), or report that
  nothing survives (None -> the caller's ladder is exhausted).

The runner-side halves live next to the runners: ``parallel/
pagerank_sharded.py`` re-partitions the graph over the new mesh (its
layout planned again for the surviving device count) and
``parallel/tfidf_sharded.py`` re-slices the in-flight
super-chunk; ``models/driver.py`` orchestrates the rung inside the
segment loop.  Every shrink publishes a ``mesh.shrink`` span and ONE
``degraded`` event carrying old/new device counts, so a degraded run is
attributable from its trace artifact alone (tools/trace_report.py).

Env knob: ``GRAFT_ELASTIC`` ("0" disables the rung — device loss then
falls through to the pre-existing ladder ends: CPU re-lowering for
single-chip paths, ``ResilienceExhausted`` + checkpoint for sharded).
Rung names are declared in ``utils/config.DEGRADE_LADDER``; the
``ladder-rung-drift`` lint rule keeps declaration and implementation in
sync.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Iterator, Sequence

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos


class DeviceHealth:
    """Thread-safe registry of lost *logical* device indices (positions in
    ``jax.devices()`` — the same index space the chaos grammar's
    ``device_lost@dev:K`` names)."""

    def __init__(self) -> None:
        self._lost: set[int] = set()
        self._lock = threading.Lock()

    def mark_lost(self, index: int) -> bool:
        """Record device ``index`` as dead; True if newly marked."""
        with self._lock:
            if index in self._lost:
                return False
            self._lost.add(index)
            return True

    def is_lost(self, index: int) -> bool:
        with self._lock:
            return index in self._lost

    def lost(self) -> frozenset:
        with self._lock:
            return frozenset(self._lost)

    def reset(self) -> None:
        with self._lock:
            self._lost.clear()


_health = DeviceHealth()


def health() -> DeviceHealth:
    """The process-global device-health registry."""
    return _health


def reset_health() -> None:
    """Forget all recorded losses (tests; a fresh run of a fresh process
    never needs this)."""
    _health.reset()


def enabled() -> bool:
    return os.environ.get("GRAFT_ELASTIC", "1") != "0"


# Lexical markers real XLA/PJRT runtimes put in device-loss errors, for
# the production path where the exception is not an injected
# chaos.DeviceLostError.
_DEVICE_LOSS_MARKERS = ("DEVICE_LOST", "device is lost", "device lost")


def is_device_loss(exc: BaseException) -> bool:
    if isinstance(exc, chaos.DeviceLostError):
        return True
    return any(m in str(exc) for m in _DEVICE_LOSS_MARKERS)


def unwrap_device_loss(exc: BaseException) -> BaseException | None:
    """The device-loss error carried by ``exc``: the exception itself, or
    the terminal error inside a :class:`~.executor.ResilienceExhausted`
    (an inner guarded call with no elastic rung of its own exhausts with
    the loss as its ``last_error`` — the shrink-rerun re-entry path needs
    to see through that wrapper).  None when ``exc`` is not a loss."""
    if is_device_loss(exc):
        return exc
    from page_rank_and_tfidf_using_apache_spark_tpu.resilience.executor import (
        ResilienceExhausted,
    )

    if isinstance(exc, ResilienceExhausted) and is_device_loss(exc.last_error):
        return exc.last_error
    return None


def device_index(exc: BaseException) -> int | None:
    """The lost logical device index an error names, or None (whole-backend
    loss / no attribution — plan_shrink then relies on probing)."""
    dev = getattr(exc, "device", None)
    return int(dev) if isinstance(dev, int) else None


def probe_devices(devices: Sequence) -> list:
    """The subset of ``devices`` that are both un-marked in the health
    registry and answer a trivial put/get round-trip.  The probe is the
    production-path detector (a dead chip throws on the put); under chaos
    the registry alone decides, because simulated host devices never
    actually die."""
    import jax
    import numpy as np

    alive = []
    for d in devices:
        if _health.is_lost(d.id):
            continue
        try:
            # one scalar RTT per device, by design: the probe's entire job
            # is touching each device individually, and it runs only on
            # the (rare) shrink path, never per step
            jax.device_get(jax.device_put(np.int32(1), d))  # graftlint: disable=host-sync-in-loop
        except Exception:
            _health.mark_lost(d.id)
            continue
        alive.append(d)
    return alive


@dataclasses.dataclass(frozen=True)
class ShrinkPlan:
    """One planned mesh-shrink step: the devices the rebuilt mesh will
    span, the ladder rung this constitutes, and the old/new counts the
    ``degraded`` event and ``mesh.shrink`` span publish."""

    devices: tuple
    old_count: int
    new_count: int
    rung: str  # a utils/config.DEGRADE_LADDER member


def plan_shrink(mesh_devices: Sequence) -> ShrinkPlan | None:
    """Plan the next shrink for a mesh currently spanning ``mesh_devices``.

    Survivors are probed, truncated to a power-of-two count
    (``parallel.mesh.shrink_devices``), and — when the loss could not be
    attributed to any single device but the step keeps dying — forced to
    strictly fewer devices than before, so the ladder always makes
    progress.  With no surviving accelerator device the plan falls back to
    a 1-device mesh on the CPU backend (the ``cpu`` rung); None means not
    even that exists and the ladder is exhausted.
    """
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import mesh as pmesh

    devices = list(mesh_devices)
    old = len(devices)
    alive = probe_devices(devices)
    survivors = pmesh.shrink_devices(alive)
    if len(survivors) == old and old > 1:
        # nothing attributable died, yet the sharded step keeps failing:
        # halve anyway rather than rebuild the same mesh forever
        survivors = survivors[: pmesh.largest_pow2(old - 1)]
    if survivors:
        rung = "mesh_shrink" if len(survivors) > 1 else "single_device"
        return ShrinkPlan(tuple(survivors), old, len(survivors), rung)

    # Accelerator pool gone: host the 1-device mesh on the CPU backend.
    # Only when the dying mesh was NOT already CPU-backed — the health
    # registry indexes the default backend's devices, so a dead CPU mesh
    # has no fresh CPU pool to fall to (and the index spaces would alias).
    if any(getattr(d, "platform", None) == "cpu" for d in devices):
        return None
    import jax

    try:
        cpus = list(jax.devices("cpu"))
    except RuntimeError:
        cpus = []
    if not cpus:
        return None
    return ShrinkPlan((cpus[0],), old, 1, "cpu")


@contextlib.contextmanager
def publish_shrink(
    site: str,
    plan: ShrinkPlan,
    exc: BaseException,
    metrics=None,
) -> Iterator[None]:
    """The one shrink-event contract both sharded runners publish through:
    a ``mesh.shrink`` span wrapping the rebuild work, exactly ONE
    ``degraded`` event carrying the rung and old/new device counts (+ the
    mirrored metrics record), so trace_report's transitions and the
    ladder-rung-drift lint see an identical schema from every rung."""
    with obs.span("mesh.shrink", site=site, ladder=plan.rung,
                  devices_old=plan.old_count, devices_new=plan.new_count):
        obs.emit(
            "degraded", site=site, ladder=plan.rung,
            devices_old=plan.old_count, devices_new=plan.new_count,
            error=f"{type(exc).__name__}: {exc}"[:200],
        )
        obs.counter("degraded")
        if metrics is not None:
            metrics.record(
                event="degraded", site=site, ladder=plan.rung,
                devices_old=plan.old_count, devices_new=plan.new_count,
            )
        yield
