"""Run manifest: the who/where/how header every traced run writes at
startup and finalizes at exit.

Reference counterpart: the Spark application page — app id, executors,
resolved ``SparkConf``.  Here the manifest records the backend and device
topology (when jax is already imported — writing a manifest never forces
the jax import chain in), the resolved value of every declared ``GRAFT_*``
knob (``utils/config.GRAFT_ENV_KNOBS`` — the same registry the
``env-knob-drift`` lint rule enforces), the git sha, and run identity.
Jax-free processes (the bench parent) never import this package; they
read finished manifests through the stdlib-only ``tools/trace_report.py``.

The startup write is atomic (tmp + rename) and self-sufficient: a child
that is later SIGKILLed still leaves ``status: "running"`` plus its full
environment snapshot — evidence, not a mystery.  ``finalize`` rewrites the
file with the end state (status, wall seconds, event count, the
counter/gauge/histogram summary).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any


def _git_sha() -> str | None:
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        proc = subprocess.run(
            ["git", "-C", root, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    return proc.stdout.strip() or None if proc.returncode == 0 else None


def _device_snapshot() -> dict[str, Any]:
    """Backend + topology, only when the process has already started a
    jax backend — the manifest write itself must never be what pulls the
    jax import chain in, nor what claims a chip (a router that only
    spawns replicas must leave the chip to them)."""
    if "jax" not in sys.modules:
        return {"backend": None, "devices": None, "device_count": None}
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return {"backend": None, "devices": None, "device_count": None}
    try:
        import jax

        devs = jax.devices()
        return {
            "backend": jax.default_backend(),
            "devices": [str(d) for d in devs],
            "device_count": len(devs),
        }
    except Exception as exc:  # noqa: BLE001 — a dead backend is itself evidence
        return {
            "backend": f"error:{type(exc).__name__}",
            "devices": None,
            "device_count": None,
        }


def knob_snapshot() -> dict[str, str | None]:
    """Resolved value (or None) of every declared GRAFT_* knob."""
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
        GRAFT_ENV_KNOBS,
    )

    return {k: os.environ.get(k) for k in sorted(GRAFT_ENV_KNOBS)}


def _tuned_profile_snapshot() -> dict[str, Any] | None:
    """Provenance of the tuned profile this run resolved knobs from, or
    None when no profile applies — a run manifest must say which tuned
    values shaped it (the ``profile-drift`` tier-3 check audits committed
    profiles; this records what a *specific run* actually saw).  A broken
    or wrong-backend profile is itself evidence: record the error instead
    of raising inside manifest writing."""
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.artifacts import (
        ProvenanceError,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
        TunedProfileError,
        load_tuned_profile,
    )

    try:
        prof = load_tuned_profile()
    except (TunedProfileError, ProvenanceError) as exc:
        return {"error": f"{type(exc).__name__}: {exc}"}
    if prof is None:
        return None
    return {
        "path": str(prof.path) if prof.path is not None else None,
        "backend": prof.backend,
        "git_sha": prof.git_sha,
        "source": prof.source,
        "knobs": dict(prof.knobs),
    }


def _atomic_write(path: str, doc: dict[str, Any]) -> None:
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2, default=str)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def write_manifest(
    path: str,
    name: str,
    trace_path: str | None,
    extra: dict[str, Any] | None = None,
) -> dict[str, Any]:
    """Write the startup manifest; returns the document."""
    doc: dict[str, Any] = {
        "name": name,
        "status": "running",
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "python": sys.version.split()[0],
        "started_wall": time.time(),
        "trace_path": trace_path,
        "git_sha": _git_sha(),
        "lint_clean": None,  # filled by callers that ran the gate (bench.py)
        "knobs": knob_snapshot(),
        "tuned_profile": _tuned_profile_snapshot(),
    }
    doc.update(_device_snapshot())
    if extra:
        doc.update(extra)
    _atomic_write(path, doc)
    return doc


def finalize_manifest(
    path: str,
    doc: dict[str, Any],
    *,
    status: str,
    events: int,
    summary: dict[str, Any] | None = None,
    extra: dict[str, Any] | None = None,
) -> dict[str, Any]:
    """Rewrite the manifest with the run's end state."""
    doc = dict(doc)
    doc["status"] = status
    doc["finished_wall"] = time.time()
    doc["wall_secs"] = doc["finished_wall"] - doc["started_wall"]
    doc["events"] = events
    if summary is not None:
        doc["summary"] = summary
    # the backend may only have resolved after startup (lazy jax import)
    if doc.get("backend") is None:
        doc.update(_device_snapshot())
    if extra:
        doc.update(extra)
    _atomic_write(path, doc)
    return doc
