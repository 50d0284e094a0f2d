"""obs/ — unified run telemetry (ISSUE 4).

The Spark-UI/event-log counterpart this reproduction was missing: every
long path publishes structured events onto one process-global bus
(:mod:`obs.events`), host phases open context-propagated spans bridged to
``jax.profiler.TraceAnnotation`` (:mod:`obs.trace`), and a traced run
writes a crash-safe per-event-flushed JSONL trace plus a startup/exit
manifest (:mod:`obs.runtime`, :mod:`obs.manifest`).  ``tools/trace_report.py``
(stdlib-only, importable from the jax-free bench parent) reconstructs
per-phase wall-time breakdowns, retry/chaos tallies per site, the chunk
timeline, and the last incomplete span from a trace file — a SIGKILLed
child yields a full accounting instead of a stderr tail.

Spark-UI correspondence (also in README "Observability"):

==========================  =============================================
Spark                       here
==========================  =============================================
event log                   ``<name>.<pid>.trace.jsonl`` (JSONL sink)
application page / conf     ``<name>.<pid>.manifest.json``
stage/task timeline         spans (``obs.span("tfidf.chunk", chunk=24)``)
stage counters              ``obs.counter/gauge/histogram`` + run summary
task failure / retry log    ``retry``/``backoff``/``watchdog``/``chaos``
                            /``degraded``/``exhausted`` events
==========================  =============================================

JAX's own trace/lower/compile phases become ``jax.*`` spans through
``obs.watch_compiles()``, which the jax-importing program modules call
(always on, no knob); the package itself never imports jax.

Env knobs: ``GRAFT_TRACE_DIR`` (default trace directory — a run started
with no explicit dir writes here; unset = in-memory only) and
``GRAFT_LOG_LEVEL`` (stderr log level, utils/metrics.py).  Both declared
in ``utils/config.GRAFT_ENV_KNOBS``.
"""

from page_rank_and_tfidf_using_apache_spark_tpu.obs.events import (
    Aggregates,
    EventBus,
    JsonlSink,
    MemorySink,
)
from page_rank_and_tfidf_using_apache_spark_tpu.obs.manifest import knob_snapshot
from page_rank_and_tfidf_using_apache_spark_tpu.obs.runtime import (
    Run,
    bus,
    counter,
    current_run,
    emit,
    end_run,
    gauge,
    histogram,
    run,
    span,
    start_run,
    tracer,
    watch_compiles,
)
from page_rank_and_tfidf_using_apache_spark_tpu.obs.trace import SpanTracer

# Live SLO instruments (ISSUE 11): rolling-window histograms / error
# budgets (obs.metrics) and the pull-based HTTP snapshot surface
# (obs.export).  Imported after runtime so their obs-package imports see
# a fully-initialized module.
from page_rank_and_tfidf_using_apache_spark_tpu.obs import export  # noqa: E402
from page_rank_and_tfidf_using_apache_spark_tpu.obs import federation  # noqa: E402
from page_rank_and_tfidf_using_apache_spark_tpu.obs import metrics  # noqa: E402
from page_rank_and_tfidf_using_apache_spark_tpu.obs.federation import (  # noqa: E402
    FleetHub,
)
from page_rank_and_tfidf_using_apache_spark_tpu.obs.metrics import (  # noqa: E402
    ErrorBudget,
    MetricsHub,
    RollingHistogram,
    StreamingHistogram,
    TelemetrySink,
    WindowedCounter,
)

__all__ = [
    "Aggregates",
    "ErrorBudget",
    "EventBus",
    "FleetHub",
    "JsonlSink",
    "MemorySink",
    "MetricsHub",
    "RollingHistogram",
    "Run",
    "SpanTracer",
    "StreamingHistogram",
    "TelemetrySink",
    "WindowedCounter",
    "export",
    "federation",
    "metrics",
    "bus",
    "counter",
    "current_run",
    "emit",
    "end_run",
    "gauge",
    "histogram",
    "knob_snapshot",
    "run",
    "span",
    "start_run",
    "tracer",
    "watch_compiles",
]
