"""Retry/deadline executor guarding every dispatch and host-sync boundary.

Spark's resilience came from lineage recomputation; on TPU the equivalents
are (in escalation order) **retry** the failed dispatch/sync on-device,
**degrade** — shrink a sharded mesh onto the surviving devices
(resilience/elastic.py) or re-lower a single-chip segment for the CPU
backend — and finally **resume** from the last atomic checkpoint
(utils/checkpoint.py).  This module implements retry plus the generic
rung-walking (``fallbacks``), and hands the terminal state to callers as a
structured :class:`ResilienceExhausted` carrying the latest checkpoint
path.  Rung names are declared in ``utils/config.DEGRADE_LADDER``.

Every long-running path (models/driver.py segments, the streaming and
sharded TF-IDF chunk drains) routes its host round-trips through
:func:`run_guarded` or the :func:`device_get` / :func:`block_until_ready`
wrappers; the graftlint rule ``unguarded-host-sync`` keeps it that way.

Env knobs (also see README "Failure model and recovery"):

- ``GRAFT_RETRY_MAX``        max retries per guarded call (default 3)
- ``GRAFT_SYNC_DEADLINE_S``  per-call watchdog deadline in seconds;
                             0 (default) disables the watchdog thread
- ``GRAFT_BACKOFF_BASE_S``   first backoff delay (default 0.05)
- ``GRAFT_BACKOFF_MAX_S``    backoff ceiling (default 2.0)
- ``GRAFT_CHAOS``            fault-injection plan (resilience/chaos.py)

Retries are only issued for *transient* failures (injected ``ChaosError``,
a blown sync deadline, or an XLA runtime error carrying a retryable status
marker).  ``DeviceLostError`` — and transient failures that exhaust the
retry budget — fall through to the degradation ladder; every other error
propagates unchanged.  Backoff jitter is
deterministic (hash of site and attempt), so chaos tests replay exactly.

Retry safety: every guarded callable here is re-invocable — ``device_get``
re-reads live device buffers, and the compiled segment runners are
functional (same inputs in, same ranks out), so a retried dispatch cannot
double-apply work.

Telemetry (ISSUE 4): every rung publishes a structured event on the obs
bus — ``retry`` / ``backoff`` per retried attempt, ``watchdog`` when the
sync deadline fires, ``degraded`` on the CPU rung, ``exhausted`` before
raising — so a traced run's JSONL file records *which* site failed, how
many retries it ate and what each backoff cost, durably, even when the
process is later killed.  ``metrics.record`` mirrors the retry/degraded
events into the legacy per-run recorder for callers that pass one.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from typing import Any, Callable

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import chaos, elastic
from page_rank_and_tfidf_using_apache_spark_tpu.utils import checkpoint as ckpt
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder


class SyncDeadlineExceeded(RuntimeError):
    """A guarded call blew its GRAFT_SYNC_DEADLINE_S watchdog — the
    signature of a hung host sync.  Transient: the retry
    re-issues the sync against the still-live device buffers."""


class ResilienceExhausted(RuntimeError):
    """Every rung of the ladder failed.  Carries what the caller needs to
    restart-from-snapshot: the site, the last error, and the most recent
    checkpoint path (None when the caller checkpoints nowhere)."""

    def __init__(
        self,
        site: str,
        attempts: int,
        last_error: BaseException,
        last_checkpoint: str | None,
    ):
        self.site = site
        self.attempts = attempts
        self.last_error = last_error
        self.last_checkpoint = last_checkpoint
        resume = (
            f"resume from checkpoint {last_checkpoint}"
            if last_checkpoint
            else "no checkpoint available; restart from scratch"
        )
        super().__init__(
            f"resilience exhausted at {site!r} after {attempts} attempt(s): "
            f"{type(last_error).__name__}: {last_error} — {resume}"
        )


# Status markers XLA/PJRT put in retryable runtime errors.  Lexical match on
# the message keeps this dependency-free (the exception classes moved
# between jaxlib versions).
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "ABORTED",
    "CANCELLED",
)
# ...except where the same status reports something a retry cannot
# change: XLA reports a device out-of-memory as RESOURCE_EXHAUSTED, at
# compile time ("XLA:TPU compile permanent error. Ran out of memory in
# memory space hbm") and when a buffer is allocated ("Attempting to
# allocate ..."), and the same program on the same data fails the same way.
_PERMANENT_MARKERS = (
    "out of memory",
    "attempting to allocate",
    "permanent error",
)


def is_transient(exc: BaseException) -> bool:
    if isinstance(exc, (chaos.ChaosError, SyncDeadlineExceeded)):
        return True
    # A fully-exhausted inner ladder is not transient by definition — and
    # its message quotes the inner error, so the marker scan below would
    # otherwise re-classify it.  Matters for nested guards: the delta
    # fetch inside models/pagerank.py's invoke exhausts under the outer
    # pagerank_step guard, whose retry must NOT re-dispatch (the runner
    # donated its rank carry).
    if isinstance(exc, (chaos.DeviceLostError, ResilienceExhausted)):
        return False
    msg = str(exc)
    if any(m in msg.lower() for m in _PERMANENT_MARKERS):
        return False
    return any(m in msg for m in _TRANSIENT_MARKERS)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    deadline_s: float = 0.0  # 0 = no watchdog thread

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            max_retries=int(os.environ.get("GRAFT_RETRY_MAX", 3)),
            backoff_base_s=float(os.environ.get("GRAFT_BACKOFF_BASE_S", 0.05)),
            backoff_max_s=float(os.environ.get("GRAFT_BACKOFF_MAX_S", 2.0)),
            deadline_s=float(os.environ.get("GRAFT_SYNC_DEADLINE_S", 0.0)),
        )


def backoff_delay(site: str, attempt: int, policy: RetryPolicy) -> float:
    """Exponential backoff with deterministic jitter: attempt k (1-based)
    waits base * 2^(k-1) * (1 + frac), frac in [0, 0.5) derived from a hash
    of (site, attempt) — decorrelates concurrent retriers without RNG state
    (chaos tests replay bit-identically)."""
    raw = policy.backoff_base_s * (2.0 ** (attempt - 1))
    h = hashlib.sha256(f"{site}:{attempt}".encode()).digest()
    frac = h[0] / 512.0  # [0, 0.498]
    return min(raw * (1.0 + frac), policy.backoff_max_s)


def _attempt(fn: Callable[[], Any], site: str, policy: RetryPolicy) -> Any:
    """One guarded attempt: chaos hook + fn, under the watchdog when a
    deadline is set.  The watchdog runs the attempt on a fresh daemon
    thread and abandons it on timeout — a thread wedged inside a dead
    device runtime cannot be killed from Python, only orphaned."""

    def watched() -> Any:
        chaos.on_call(site)
        return fn()

    if policy.deadline_s <= 0:
        return watched()

    box: dict[str, Any] = {}

    def runner() -> None:
        try:
            box["result"] = watched()
        except BaseException as exc:  # noqa: BLE001 — re-raised on the caller side
            box["error"] = exc

    t = threading.Thread(target=runner, name=f"resilience-{site}", daemon=True)
    t.start()
    t.join(policy.deadline_s)
    if t.is_alive():
        obs.emit("watchdog", site=site, deadline_s=policy.deadline_s)
        obs.counter("watchdog_fires")
        raise SyncDeadlineExceeded(
            f"guarded call at {site!r} exceeded the {policy.deadline_s}s "
            "sync deadline (hung host sync); abandoning the attempt thread"
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


def attempt_once(
    fn: Callable[[], Any],
    *,
    site: str,
    policy: RetryPolicy | None = None,
) -> Any:
    """ONE chaos-hooked, watchdog-deadlined attempt with no retry loop, no
    rungs and no ``exhausted`` emission — for callers that own their
    recovery (the elastic shrink-*rerun*, which on a further device loss
    must re-enter its own ladder rather than have this layer declare
    exhaustion).  Faults propagate raw; ``fn`` must be re-invocable."""
    policy = policy or RetryPolicy.from_env()
    return _attempt(fn, site, policy)


def _retry_pause(
    site: str,
    attempts: int,
    exc: BaseException,
    policy: RetryPolicy,
    metrics: MetricsRecorder | None,
) -> None:
    """The shared between-attempts pause of both retry loops: emit the
    ``retry`` event/counter (and mirror it to the caller's metrics), sleep
    the backoff, then emit ``backoff``.  The backoff event is emitted
    AFTER the sleep: it records that the backoff completed (a kill
    mid-backoff then shows a retry with no backoff event), which is what
    distinguishes it from the retry event."""
    delay = backoff_delay(site, attempts, policy)
    err = f"{type(exc).__name__}: {exc}"[:200]
    obs.emit("retry", site=site, attempt=attempts, error=err,
             backoff_s=round(delay, 4))
    obs.counter("retries")
    if metrics is not None:
        metrics.record(event="retry", site=site, attempt=attempts,
                       error=err, backoff_s=round(delay, 4))
    time.sleep(delay)
    obs.emit("backoff", site=site, attempt=attempts, secs=round(delay, 4))
    obs.histogram("backoff_secs", delay)


def retry_transient(
    fn: Callable[[], Any],
    *,
    site: str,
    policy: RetryPolicy | None = None,
    metrics: MetricsRecorder | None = None,
) -> Any:
    """:func:`run_guarded`'s transient-retry half WITHOUT the terminal
    rung-walking or ``exhausted`` emission: transient faults retry with the
    same backoff/telemetry, but persistent faults (device loss) and an
    expired retry budget propagate RAW to the caller.

    For call sites whose recovery lives at a coarser granularity than one
    guarded call — the staged ingest pipeline (``dataflow.ingest``): a
    device loss at an H2D put on the transfer thread is handled by the
    pipeline's recovery point (tear down, shrink/salvage, re-stage from
    retained host copies), so an ``exhausted`` event here would misreport
    a recoverable loss as a dead ladder.  Same precedent as
    :func:`attempt_once` (the elastic shrink-rerun's re-entry path).
    ``fn`` must be re-invocable."""
    policy = policy or RetryPolicy.from_env()
    attempts = 0
    while True:
        attempts += 1
        try:
            return _attempt(fn, site, policy)
        except Exception as exc:
            if not is_transient(exc) or attempts > policy.max_retries:
                raise
            _retry_pause(site, attempts, exc, policy, metrics)


def run_guarded(
    fn: Callable[[], Any],
    *,
    site: str,
    policy: RetryPolicy | None = None,
    metrics: MetricsRecorder | None = None,
    checkpoint_dir: str | None = None,
    fallbacks: "list[tuple[str | None, Callable[[BaseException], Any]]] | None" = None,
) -> Any:
    """Run ``fn`` under the full degradation ladder.

    1. up to ``policy.max_retries`` retries with exponential backoff, for
       transient failures only;
    2. the ``fallbacks`` rungs in order — each a ``(ladder, fn(exc))``
       pair — but only when the failure is a device loss
       (:func:`elastic.unwrap_device_loss`) or a transient failure that
       used up its retries.  Any other error (a compile error, a kernel
       the chip's compiler refused, a shape error) is re-raised
       unchanged: no rung may finish on another backend a run the device
       could not do.  A named rung publishes the ``degraded`` event here
       before running (``ladder`` must be declared in
       utils/config.DEGRADE_LADDER — the lint gate); ``ladder=None`` hands
       emission to the rung itself, for rungs like the elastic mesh
       shrink that only *decide* whether they apply (and what they
       degraded to) once they inspect the failure.  A rung that raises
       passes the ladder to the next.
    3. :class:`ResilienceExhausted` carrying the latest checkpoint under
       ``checkpoint_dir`` so the caller (or the operator) can resume.

    ``fn`` must be safe to re-invoke (pure dispatch / buffer re-read).
    """
    policy = policy or RetryPolicy.from_env()
    attempts = 0
    last_exc: Exception | None = None
    while attempts <= policy.max_retries:
        attempts += 1
        try:
            return _attempt(fn, site, policy)
        # Exception, not BaseException: KeyboardInterrupt / SystemExit must
        # propagate — a Ctrl-C is an operator decision, not a device fault
        # for the ladder to "recover" from.
        except Exception as exc:
            last_exc = exc
            if not is_transient(exc):
                break
            if attempts > policy.max_retries:
                break
            _retry_pause(site, attempts, exc, policy, metrics)

    assert last_exc is not None
    if not is_transient(last_exc) and elastic.unwrap_device_loss(last_exc) is None:
        raise last_exc
    for ladder, rung_fn in fallbacks or []:
        if ladder is not None:
            err = f"{type(last_exc).__name__}: {last_exc}"[:200]
            obs.emit("degraded", site=site, ladder=ladder,
                     after_attempts=attempts, error=err)
            obs.counter("degraded")
            if metrics is not None:
                metrics.record(
                    event="degraded", site=site, ladder=ladder,
                    after_attempts=attempts, error=err,
                )
        try:
            return rung_fn(last_exc)
        except Exception as exc:  # try the next rung; interrupts propagate
            last_exc = exc

    last_ckpt = ckpt.latest_checkpoint(checkpoint_dir) if checkpoint_dir else None
    obs.emit(
        "exhausted", site=site, attempts=attempts,
        error=f"{type(last_exc).__name__}: {last_exc}"[:200],
        checkpoint=last_ckpt,
    )
    obs.counter("exhausted")
    raise ResilienceExhausted(site, attempts, last_exc, last_ckpt) from last_exc


def device_get(
    tree: Any,
    *,
    site: str = "device_get",
    policy: RetryPolicy | None = None,
    metrics: MetricsRecorder | None = None,
    checkpoint_dir: str | None = None,
    fallbacks: "list[tuple[str | None, Callable[[BaseException], Any]]] | None" = None,
) -> Any:
    """Guarded ``jax.device_get``: ONE batched device->host pull per call
    (keep the VERDICT r5 single-round-trip discipline), retried/deadlined
    by the executor.  Device buffers outlive a failed pull, so re-issuing
    the transfer is always safe.  ``fallbacks`` rungs (e.g. the sharded
    runners' elastic mesh shrink) apply exactly as in :func:`run_guarded`."""
    import jax

    return run_guarded(
        lambda: jax.device_get(tree), site=site, policy=policy,
        metrics=metrics, checkpoint_dir=checkpoint_dir, fallbacks=fallbacks,
    )


def block_until_ready(
    tree: Any,
    *,
    site: str = "block_until_ready",
    policy: RetryPolicy | None = None,
    metrics: MetricsRecorder | None = None,
    checkpoint_dir: str | None = None,
) -> Any:
    """Guarded ``jax.block_until_ready`` fence."""
    import jax

    return run_guarded(
        lambda: jax.block_until_ready(tree), site=site, policy=policy,
        metrics=metrics, checkpoint_dir=checkpoint_dir,
    )
