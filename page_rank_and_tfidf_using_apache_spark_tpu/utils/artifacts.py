"""Backend-provenance guard for measured cost artifacts.

The repo's cost artifacts (``xla_cost_tpu.json``, ``gather_micro_tpu.json``,
``breakdown_tpu.json``) drive kernel design AND the tier-3 intensity
ratchet (analysis/cost.py).  The round-5 failure mode this module exists
for: the TPU is unreachable, a tool re-runs on the CPU backend, and a
CPU-measured table silently replaces a TPU-measured one — after which
every consumer (including CI gates) reasons from numbers measured on the
wrong machine.

Two rules, enforced at write time:

- every artifact is stamped with the ``backend`` it was measured on
  (uniformly, by this helper — not ad hoc per tool);
- a tool may not overwrite an artifact stamped ``"backend": "tpu"`` with a
  record measured on any other backend unless the operator passes
  ``--force`` (the tools wire that flag through ``force=``).

Stdlib-only so the tools can import it before jax is up.
"""

from __future__ import annotations

import json
import os
import tempfile
from pathlib import Path


class ProvenanceError(RuntimeError):
    """Refusing to overwrite a TPU-measured artifact with a non-TPU run."""


def read_backend(path: str | Path) -> str | None:
    """Backend stamp of an existing artifact (None: missing/unreadable)."""
    try:
        record = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    backend = record.get("backend")
    return str(backend) if backend is not None else None


def check_overwrite(
    path: str | Path | None, backend: str, *, force: bool = False
) -> None:
    """Raise :class:`ProvenanceError` when writing a ``backend``-measured
    record to ``path`` would downgrade a TPU-stamped artifact (and
    ``force`` is not set).  The tools call this right after the backend is
    known — BEFORE spending minutes measuring — so a doomed run fails
    fast; :func:`write_artifact` re-checks at write time regardless."""
    if path is None:
        return
    existing = read_backend(path)
    if existing == "tpu" and backend != "tpu" and not force:
        raise ProvenanceError(
            f"{path} records a TPU-measured run but this run measures on "
            f"backend {backend!r}; refusing to overwrite the TPU baseline "
            "(re-run on the TPU, write to a different --out, or pass "
            "--force to downgrade it deliberately)"
        )


def write_artifact(
    path: str | Path | None,
    record: dict,
    *,
    backend: str,
    force: bool = False,
) -> dict:
    """Stamp ``record["backend"]`` and write it as one JSON line.

    Refuses (``ProvenanceError``) to overwrite an artifact whose stamp is
    ``"tpu"`` with a record measured on a different backend, unless
    ``force``.  ``path=None`` stamps without writing (tools always print
    the record to stdout regardless).  Returns the stamped record.
    """
    record = {"backend": backend, **record}
    if path is None:
        return record
    check_overwrite(path, backend, force=force)
    # tmp + atomic rename, never an in-place truncate-and-rewrite: a kill
    # mid-write must leave the previous (possibly TPU-stamped) record
    # intact, not a torn JSON that read_backend() calls unreadable — the
    # graftlint tier-5 atomic-write-drift class
    target = Path(path)
    fd, tmp = tempfile.mkstemp(dir=str(target.parent) or ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(record) + "\n")
        os.replace(tmp, str(target))
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return record
