"""Context-propagated span tracer.

Reference counterpart: the Spark UI stage/task timeline.  A *span* is one
named host-side phase (``obs.span("tfidf.chunk", chunk=24)``) with a
monotonic start/stop, an id, and a parent — nested spans form the per-run
call tree that ``tools/trace_report.py`` reconstructs into a wall-time
breakdown.

Design points:

- **Context propagation** rides on :mod:`contextvars`: each thread starts
  with an empty span stack, so spans opened on the streaming tokenizer
  thread nest among themselves and never steal the main thread's parent
  (the bug class the ``unsynced-thread-state`` lint patrols).  Explicit
  cross-thread parentage is available via ``span(..., parent=sid)``.
- **Crash evidence by construction**: ``span_begin`` is published (and the
  JSONL sink flushes it) *before* the body runs, so a SIGKILL mid-span
  leaves a begin with no end — exactly what trace_report reports as "the
  last incomplete span".  An exception ends the span with
  ``status="error:<Type>"`` and re-raises.
- **XLA bridge**: when jax is already imported, every span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so host phases line
  up with device timelines in a TensorBoard profile.  The bridge never
  *imports* jax (``"jax" in sys.modules`` gates it): a span can never be
  the thing that drags the jax import chain in.  (Truly jax-free
  processes — the bench parent — do not import this package at all; they
  read trace artifacts through the stdlib-only ``tools/trace_report.py``.)
- **Compile spans** come from JAX's own clock: ``obs.watch_compiles()``
  registers one ``jax.monitoring`` listener that turns each trace, lower
  and backend-compile phase into a ``jax.trace`` / ``jax.lower`` /
  ``jax.compile`` span, published after the fact through
  :meth:`SpanTracer.record` and parented to the span the compiling call
  ran in.  Such a span carries ``begin_wall``/``end_wall`` (JAX's
  ``time.time()`` stamps) and is not a ``TraceAnnotation``: it closed
  before it could be opened.
"""

from __future__ import annotations

import contextlib
import contextvars
import sys
import threading
from typing import Any, Iterator

import time

from page_rank_and_tfidf_using_apache_spark_tpu.obs.events import EventBus

_current_span: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "graft_obs_span", default=None
)


class SpanTracer:
    """Allocates span ids and publishes span_begin/span_end to a bus."""

    def __init__(self, bus: EventBus):
        self._bus = bus
        self._lock = threading.Lock()
        self._next_id = 1

    def _new_id(self) -> int:
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        return sid

    def current(self) -> int | None:
        """Span id of the innermost open span in this context (None at the
        top level — including on a freshly spawned thread)."""
        return _current_span.get()

    @contextlib.contextmanager
    def span(
        self, name: str, /, *, parent: int | None = None, **attrs: Any
    ) -> Iterator[int]:
        sid = self._new_id()
        par = parent if parent is not None else _current_span.get()
        t0 = time.perf_counter()
        self._bus.publish(
            "span_begin", span=sid, parent=par, name=name, attrs=attrs
        )
        token = _current_span.set(sid)
        status = "ok"
        with contextlib.ExitStack() as bridge:
            if "jax" in sys.modules:  # annotate, never import
                try:
                    from jax.profiler import TraceAnnotation

                    bridge.enter_context(TraceAnnotation(name))
                except Exception:  # noqa: BLE001 — the bridge is best-effort
                    pass
            try:
                yield sid
            except BaseException as exc:
                status = f"error:{type(exc).__name__}"
                raise
            finally:
                _current_span.reset(token)
                self._bus.publish(
                    "span_end",
                    span=sid,
                    parent=par,
                    name=name,
                    secs=time.perf_counter() - t0,
                    status=status,
                    attrs=attrs,
                )

    def record(
        self, name: str, begin_wall: float, end_wall: float, /, **attrs: Any
    ) -> int:
        """Publish one ``span_end`` for a span that already closed, timed
        by another clock (wall seconds, ``time.time()``).  Its parent is the
        innermost span open on the calling thread; there is no
        ``span_begin``, which ``tools/trace_report.py`` accepts."""
        sid = self._new_id()
        self._bus.publish(
            "span_end",
            span=sid,
            parent=_current_span.get(),
            name=name,
            secs=end_wall - begin_wall,
            status="ok",
            attrs=attrs,
            begin_wall=begin_wall,
            end_wall=end_wall,
        )
        return sid


# jax.monitoring time-span events -> span names
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# persistent-cache outcome events, fired inside a backend compile
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class CompileWatch:
    """``jax.monitoring`` listeners publishing JAX's compile phases as
    retroactive spans.  ``jax.compile`` spans carry ``cache``: ``hit`` (the
    executable came from the persistent cache), ``miss`` (compiled and
    written to it) or ``none`` (the cache was off, or did not keep it); the
    outcome event fires inside the compile on the compiling thread, so it
    is held per thread until that compile's span closes."""

    def __init__(self, tracer: SpanTracer):
        self._tracer = tracer
        self._outcome = threading.local()

    def on_event(self, event: str, **_kw: Any) -> None:
        outcome = _CACHE_OUTCOMES.get(event)
        if outcome is not None:
            self._outcome.value = outcome

    def on_span(
        self, event: str, start_time: float, end_time: float, **kw: Any
    ) -> None:
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        attrs: dict[str, Any] = {"fun": kw.get("fun_name", "")}
        if name == "jax.compile":
            attrs["cache"] = getattr(self._outcome, "value", "none")
            self._outcome.value = "none"
        self._tracer.record(name, start_time, end_time, **attrs)

    def install(self) -> None:
        """Register both listeners with ``jax.monitoring`` (imports jax)."""
        import jax.monitoring

        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_time_span_listener(self.on_span)
