"""Declarative registry of the package's jit entry points for tier-2
(semantic) analysis.

Each :class:`EntryPoint` names one jit-compiled program that production
code dispatches — the PageRank iteration loops (single-chip and sharded),
the TF-IDF batch pipeline, the streaming/sharded chunk-ingest kernels, the
finalize pass and query scoring — together with how to *trace* it on the
CPU backend from abstract ``ShapeDtypeStruct`` inputs: no FLOPs run, only
trace-time Python.  The semantic analyzer (``analysis/semantic.py``)
traces every registered entry under its declared shape matrix and checks
the invariants no lexical rule can see: compile count across the matrix,
64-bit dtype leaks under x64, host callbacks per traced step, and
collective axis names / communication volume against the declared mesh
contract.

Declaring a new jit entry point (see README "Static analysis"):

1. write a ``_build_<name>()`` returning a :class:`Traceable` — the
   function to trace, one ``(label, args)`` variant per point of the shape
   matrix production feeds it (apply the caller's real padding/bucketing
   policy when building the matrix, e.g. ``grow_chunk_cap``), and an
   ``anchor`` (the public function findings should point at);
2. append an :class:`EntryPoint` to ``ENTRY_POINTS`` with the budgets the
   program is designed to meet — ``max_compiles`` (distinct trace
   signatures the matrix may produce), ``transfer_budget`` (host-callback
   eqns per step, almost always 0), and for shard_map'd programs the
   declared ``axes`` plus a ``collective_budget``;
3. ``python -m page_rank_and_tfidf_using_apache_spark_tpu.analysis
   --tier 2`` must stay clean.

jax and the package modules are imported lazily inside the builders so
tier-1 linting never pays (or depends on) a jax import.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

# Shape-matrix sizes for the streaming ingest entries: raw per-chunk token
# counts as production sees them (mixed Wikipedia-scale chunks plus one
# exactly-at-capacity chunk).  The registry feeds them through the REAL
# caller-side padding policy (models.tfidf.grow_chunk_cap); if that policy
# ever stops bucketing, the distinct-signature count jumps past
# ``max_compiles`` and the recompile-per-shape gate fires.
CHUNK_TOKEN_MATRIX = (9_000, 120_000, 97_531, 131_072)


@dataclasses.dataclass(frozen=True)
class Traceable:
    """What the analyzer actually traces for one entry point."""

    fn: Callable  # callable accepting one variant's args
    variants: Sequence[tuple[str, tuple]]  # (label, args) per matrix point
    anchor: Callable | None = None  # public fn findings point at (else fn)
    # Tier-3 donation verifier surface: the *raw jitted* callable to
    # ``.lower()`` (``fn`` may be a partial/dispatch wrapper that hides the
    # jit boundary and its donate_argnums) plus its static kwargs.  None =
    # lower ``fn`` itself.
    donate_fn: Callable | None = None
    donate_kwargs: dict | None = None


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One registered jit entry point plus the budgets it must meet."""

    name: str
    module: str  # repo-relative path of the module under contract
    build: Callable[[], Traceable]
    # Other repo-relative modules the contract depends on (the shape policy
    # a shape matrix runs through, the mesh axis constants...): a
    # --changed-only run re-traces this entry when any of them changed,
    # not just ``module``.
    watch: tuple[str, ...] = ()
    max_compiles: int = 1  # distinct trace signatures the matrix may yield
    transfer_budget: int = 0  # host-callback eqns allowed per traced step
    axes: tuple[str, ...] = ()  # declared mesh axes (shard_map entries)
    collective_budget: int | None = None  # comm eqns per step (None = ungated)
    allow_64bit: bool = False  # opt out of the implicit-promotion gate
    suppress: frozenset = frozenset()  # semantic + cost rule ids to skip
    # ---- tier-3 (analysis/cost.py) budgets ----
    # Minimum static FLOP/HBM-byte arithmetic intensity per step (worst
    # variant).  Gating only while xla_cost_tpu.json carries a TPU backend
    # stamp; advisory otherwise.  None = ungated.
    intensity_floor: float | None = None
    # Static padding-waste budget: ``pad_plan()`` returns (label, pad_frac)
    # plan points evaluated WITHOUT dispatching (plan_partition /
    # stream_pad_plan); the worst point must stay <= pad_frac_ceiling.
    pad_plan: Callable[[], Sequence[tuple[str, float]]] | None = None
    pad_frac_ceiling: float | None = None
    # Buffer-donation contract: positional argnums of the traceable's
    # donate_fn whose buffers the lowered computation must alias to an
    # output.  None = unchecked; () = must alias nothing.
    donate: tuple[int, ...] | None = None


_PKG = "page_rank_and_tfidf_using_apache_spark_tpu"

# ---------------------------------------------------------------------------
# Donation-liveness contract (tier 4, ISSUE 12).
#
# ``EntryPoint.donate`` tells the tier-3 verifier which buffers the LOWERED
# computation must alias; this literal tells the tier-4 *lexical* analyzer
# which call-site spellings consume a donated buffer, so `use-after-donate`
# can dataflow-track the operand a caller passes at a donated position and
# flag any later host-side read (or re-dispatch) of that binding — the
# hazard models/pagerank.py dodges by hand at ``pagerank_delta_sync``.
#
# Each row is ``(callee leaf name as it appears at call sites, donated
# positional argnums, the registry entry names the convention serves)``:
# ``chunk_counts_carry`` is the streaming DF carry kernel called by name;
# ``runner`` is the conventional binding every fixpoint driver gives the
# compiled ``make_*_runner`` product (models/pagerank.py, dataflow/
# fixpoint.py's ``call`` closures), whose carry rides at argnum 1.
#
# The tier-4 analyzer validates this contract against ENTRY_POINTS in both
# directions (every donating entry must be served by a row; every row must
# name real donating entries with matching argnums), so the lexical surface
# and the lowered-aliasing surface cannot drift apart.  Parsed lexically —
# keep it a literal.
DONATED_CALLEES: tuple = (
    ("chunk_counts_carry", (3,), ("tfidf_chunk_ingest_carry",)),
    # the owned sharded runner donates its 4-leaf carry TUPLE at argnum 0
    # (tail slice, replicated head, lagged-delta slots) — _ShardedExec's
    # owned invoke binds the compiled product to this name so the
    # use-after-donate dataflow can see the consumption
    ("owned_runner", (0,), ("pagerank_sharded_owned",)),
    ("runner", (1,), (
        "pagerank_step",
        "pagerank_step_tol_cumsum",
        "pagerank_step_pallas",
        "pagerank_step_hybrid",
        "pagerank_step_sort_shuffle",
        "dataflow_ppr_batch",
        "dataflow_hits",
        "dataflow_components",
    )),
)

# ---------------------------------------------------------------------------
# Persistence contracts (tier 5, ISSUE 14).
#
# ``ARTIFACT_SCHEMAS`` declares every on-disk artifact family the runtime
# commits and reloads — the serving index array-dir, the segment manifest,
# checkpoint metadata, the run manifest, the measured cost artifacts — in
# the same two-way contract style as ``DONATED_CALLEES``: the lexical
# surface (which keys writers store, which keys readers load) and the
# declaration may not drift apart in either direction.
#
# Each row is ``(family, writers, readers, keys, aux_keys)``:
#
# - ``writers`` / ``readers`` are ``"<repo-relative path>::<function>"``
#   specs (``Class.method`` allowed for the function part; readers may
#   append ``::<receiver>`` to scope collection to one dict variable —
#   needed for reader modules like tools/trace_report.py that handle many
#   document shapes in one function);
# - ``keys`` is the family's full declared key space: array members plus
#   META/JSON document keys;
# - ``aux_keys`` (a subset of ``keys``) marks deliberately write-only
#   forensic keys — evidence for humans/ops tooling that no code path
#   loads back (the run manifest's argv/knob snapshot, the index META's
#   corpus stats).
#
# The tier-5 ``schema-pair-drift`` check (analysis/persistence.py)
# validates both directions: every declared key must be written by a
# writer; every non-aux key must be read by a reader (a member saved but
# never loaded — or loaded but never saved — is a finding); every lexical
# write/read of an undeclared key is drift.  Parsed lexically — keep it a
# literal.
ARTIFACT_SCHEMAS: tuple = (
    ("index",
     (f"{_PKG}/serving/artifact.py::save_index",
      f"{_PKG}/serving/segments.py::seal_segment",
      f"{_PKG}/serving/segments.py::merge_segments"),
     (f"{_PKG}/serving/artifact.py::load_index",
      f"{_PKG}/serving/segments.py::load_segment_set",
      f"{_PKG}/serving/segments.py::merge_segments"),
     ("doc", "term", "weight", "idf", "df", "term_offsets", "count",
      "doc_lengths", "ranks", "bm25_weight",
      "format", "n_docs", "vocab_bits", "nnz", "has_ranks", "has_bm25",
      "bm25_config", "tfidf_config", "doc_base", "merged_from"),
     # corpus stats + provenance: ops-facing META evidence; the reader
     # side reconstructs them from SegmentRef/arrays instead
     ("nnz", "has_ranks", "has_bm25", "doc_base", "merged_from")),
    ("segment_manifest",
     (f"{_PKG}/serving/segments.py::_write_manifest",
      f"{_PKG}/serving/segments.py::SegmentRef.to_json"),
     (f"{_PKG}/serving/segments.py::latest_manifest",
      f"{_PKG}/serving/segments.py::_replaced_by",
      f"{_PKG}/serving/segments.py::SegmentRef.from_json"),
     ("version", "config_hash", "n_docs", "nnz", "replaced", "segments",
      "name", "doc_base"),
     ()),
    ("checkpoint_meta",
     (f"{_PKG}/utils/checkpoint.py::save_checkpoint",
      f"{_PKG}/utils/checkpoint.py::save_array_dir"),
     (f"{_PKG}/utils/checkpoint.py::load_checkpoint",
      f"{_PKG}/utils/checkpoint.py::load_array_dir"),
     ("step", "config_hash", "extra"),
     ()),
    ("run_manifest",
     (f"{_PKG}/obs/manifest.py::write_manifest",
      f"{_PKG}/obs/manifest.py::finalize_manifest",
      f"{_PKG}/obs/manifest.py::_device_snapshot"),
     ("tools/trace_report.py::stitch::man",
      "tools/trace_report.py::render_human::man"),
     ("name", "status", "pid", "argv", "python", "started_wall",
      "trace_path", "git_sha", "lint_clean", "knobs", "tuned_profile",
      "backend", "devices",
      "device_count", "finished_wall", "wall_secs", "events", "summary"),
     # the SIGKILL-forensics payload: written for humans reading the file,
     # not reloaded by any code path
     ("argv", "python", "started_wall", "trace_path", "lint_clean",
      "knobs", "tuned_profile", "devices", "device_count", "finished_wall",
      "wall_secs", "events", "summary")),
    ("cost_artifact",
     (f"{_PKG}/utils/artifacts.py::write_artifact",),
     (f"{_PKG}/utils/artifacts.py::read_backend",),
     ("backend",),
     ()),
    # the autotuner's committed per-backend knob optimum (ISSUE 16):
    # written durably by the config layer (stage + durable_replace, same
    # provenance guard as the cost artifacts), loaded back through the one
    # knob-resolution ladder every runner uses.  git_sha/created_wall/
    # measured are sweep forensics — the loader carries them for manifests
    # but no code path branches on them.
    ("tuned_profile",
     (f"{_PKG}/utils/config.py::write_tuned_profile",),
     (f"{_PKG}/utils/config.py::load_tuned_profile::record",),
     ("backend", "knobs", "git_sha", "created_wall", "measured"),
     ()),
    # the serving fleet's committed generation floor (ISSUE 17): one JSON
    # doc next to the segment manifest, staged + durably replaced like
    # every other commit; committed_wall is rollout forensics only
    ("fabric_floor",
     (f"{_PKG}/serving/fabric.py::commit_floor",),
     (f"{_PKG}/serving/fabric.py::read_floor",),
     ("floor", "committed_wall"),
     ("committed_wall",)),
)

# ``COMMIT_LOCKS`` declares which lock serializes each on-disk protocol's
# read-modify-write commit step: ``(module, lock spelled as acquired,
# protected callee leaves)``.  The tier-5 ``commit-lock-drift`` check
# requires every lexical call to a protected callee in that module to sit
# under ``with <lock>`` (reusing tier 4's lock model), and validates the
# declaration itself — the lock and the callees must exist.  Parsed
# lexically — keep it a literal.
COMMIT_LOCKS: tuple = (
    # manifest generations are read-modify-write: an ingest append and a
    # background merge racing unserialized can resurrect replaced segments
    (f"{_PKG}/serving/segments.py", "_COMMIT_LOCK", ("_write_manifest",)),
)

# ---------------------------------------------------------------------------
# Wire-protocol contract (tier 6, ISSUE 18).
#
# ``WIRE_SCHEMAS`` declares the router↔replica HTTP protocol the serving
# fabric rides — every endpoint the fleet serves, in the same two-way
# contract style as ``DONATED_CALLEES``/``ARTIFACT_SCHEMAS``: the lexical
# surface (codes a handler returns, keys it writes, keys the router reads)
# and this declaration may not drift apart in either direction.  A drifted
# status code is a dropped-request class: the router's retry loop can only
# classify what the contract names.
#
# Each row is ``(endpoint, method, path, handler, readers, request_keys,
# response_keys, aux_response_keys, status_classes)``:
#
# - ``handler`` is a ``"<repo-relative path>::<function>[::<receiver>]"``
#   spec; the optional receiver scopes request-key *reads* to the parsed
#   request dict (``handle_query``'s ``req``) so a handler's other dict
#   lookups don't pollute the request surface;
# - ``readers`` are client-side specs (router/health-loop functions), each
#   optionally receiver-scoped the same way for response-key reads;
# - ``request_keys`` / ``response_keys`` are the full declared key spaces;
# - ``aux_response_keys`` (subset of ``response_keys``) marks evidence
#   keys written for harnesses/operators that no in-repo reader loads
#   (the echoed ``rid`` the conformance harness byte-compares, the 503
#   body's ``floor`` diagnostics);
# - ``status_classes`` pairs every status code the endpoint may emit with
#   the router-side class that handles it: ``success`` (consume),
#   ``terminal`` (raise to the caller — never retried), ``retryable``
#   (sibling retry under the SAME rid; 503-below-floor MUST be here), or
#   ``suspect`` (mark the replica and reroute).
#
# The tier-6 checks (analysis/protocol.py) validate both directions, and
# ``tools/protocol_harness.py`` replays the enumerated message space at a
# live replica asserting every observed code is declared.  Parsed
# lexically — keep it a literal.
WIRE_SCHEMAS: tuple = (
    ("query", "POST", "/query",
     f"{_PKG}/serving/fabric.py::_Replica.handle_query::req",
     (f"{_PKG}/serving/fabric.py::ServingFabric.query",),
     ("rid", "terms", "ranker"),
     ("rid", "replica", "generation", "scores", "docs", "error", "floor"),
     # rid/replica/generation: harness- and operator-facing echo; floor:
     # the 503 body's catch-up diagnostic — the router acts on the CODE
     ("rid", "replica", "generation", "floor"),
     ((200, "success"), (400, "terminal"), (503, "retryable"))),
    ("status", "GET", "/status",
     f"{_PKG}/serving/fabric.py::_Replica.handle_status",
     (f"{_PKG}/serving/fabric.py::ServingFabric._health_loop::status",
      f"{_PKG}/serving/fabric.py::ServingFabric.fleet_generation::s",
      f"{_PKG}/serving/fabric.py::ServingFabric.await_fleet_generation::s",
      f"{_PKG}/serving/fabric.py::ServingFabric.rolling_restart::s"),
     (),
     ("replica", "pid", "ready", "generation", "floor", "executions",
      "replays", "p50_ms", "p99_ms", "requests", "cache_hits",
      "refreshes", "peer_hits", "peer_misses", "peek_timeouts", "fills",
      "breaker_open", "peer_stores"),
     # identity + cache forensics: ops-facing, no router branch reads them
     ("replica", "pid", "cache_hits", "refreshes"),
     ((200, "success"),)),
    # sharded-cache peer endpoints (ISSUE 20).  /cache/peek is a pure
    # read (a miss is a SUCCESS with hit=false — the peeker computes
    # locally; no rid, no side effects); /cache/fill is the idempotent
    # owner write-back (rid-deduped exactly like /query, 503 below the
    # floor so stale fills are refused retryably); /peers is the
    # router's topology push after every membership change.
    ("cache_peek", "POST", "/cache/peek",
     f"{_PKG}/serving/fabric.py::_Replica.handle_cache_peek::req",
     (f"{_PKG}/serving/fabric.py::_Replica._peek_owner::out",),
     ("terms", "ranker"),
     ("hit", "generation", "scores", "docs", "error"),
     # error: the 400 body's diagnostic — the peeker acts on the CODE
     ("error",),
     ((200, "success"), (400, "terminal"))),
    ("cache_fill", "POST", "/cache/fill",
     f"{_PKG}/serving/fabric.py::_Replica.handle_cache_fill::req",
     (f"{_PKG}/serving/fabric.py::_Replica._fill_owner::resp",),
     ("rid", "terms", "ranker", "scores", "docs", "generation"),
     ("stored", "replica", "generation", "error", "floor"),
     # replica/generation: operator-facing echo; error/floor: the
     # 400/503 bodies' diagnostics — the filler acts on the CODE
     ("replica", "generation", "error", "floor"),
     ((200, "success"), (400, "terminal"), (503, "retryable"))),
    ("peers", "POST", "/peers",
     f"{_PKG}/serving/fabric.py::_Replica.handle_peers::req",
     (f"{_PKG}/serving/fabric.py::ServingFabric._push_peers",),
     ("peers", "slots"),
     ("ok", "peers", "error"),
     # the push is fire-and-forget: the router acts on the CODE only
     ("ok", "peers", "error"),
     ((200, "success"), (400, "terminal"))),
    ("healthz", "GET", "/healthz",
     f"{_PKG}/obs/export.py::_dispatch",
     (),
     (), (), (),
     ((200, "success"), (503, "retryable"))),
    ("metrics", "GET", "/metrics",
     f"{_PKG}/obs/export.py::_dispatch",
     (),
     (), (), (),
     ((200, "success"),)),
    ("snapshot", "GET", "/snapshot.json",
     f"{_PKG}/obs/export.py::_dispatch",
     (),
     (), (), (),
     ((200, "success"),)),
    # router-side fleet endpoints (ISSUE 19): the router's own exporter
    # serves the SAME obs/export.py dispatcher over the FleetHub, so the
    # merged fleet snapshot/metrics reuse the dispatcher's declared code
    # surface; the scrape path is in-contract via its declared reader —
    # FleetHub's fetch consumes a replica's /snapshot.json (whose
    # "mergeable" payload is opaque raw hub state, not wire keys)
    ("fleet_snapshot", "GET", "/snapshot.json",
     f"{_PKG}/obs/export.py::_dispatch",
     (f"{_PKG}/obs/federation.py::FleetHub._http_fetch",),
     (), (), (),
     ((200, "success"),)),
    ("fleet_metrics", "GET", "/metrics",
     f"{_PKG}/obs/export.py::_dispatch",
     (),
     (), (), (),
     ((200, "success"),)),
    # the dispatcher's catch-alls: "/" is the healthz alias, 404 is the
    # out-of-contract rejection, 500 the handler-exception backstop — the
    # conformance harness allows exactly these beyond a row's own codes
    ("fallback", "GET", "/",
     f"{_PKG}/obs/export.py::_dispatch",
     (),
     (), (), (),
     ((200, "success"), (404, "terminal"), (500, "suspect"),
      (503, "retryable"))),
)

# ---------------------------------------------------------------------------
# Metric-name contract (tier 2, ISSUE 19).
#
# ``METRIC_SCHEMAS`` declares every metric name the repo publishes — the
# run-aggregate namespace (``obs.counter/gauge/histogram``, folded into the
# run summary and trace) and the live-SLO namespace (``MetricsHub``
# counters/gauges/budgets, exported over ``/snapshot.json``/``/metrics``
# and federated across the fleet).  A renamed metric silently breaks every
# downstream reader — dashboards, ``tools/slo_watch.py``, ``trace_diff``
# gates, the federation merge — so the name space is a declared contract,
# not a convention.
#
# Each row is ``(name, kind, unit, sites)``:
#
# - ``name`` may contain ``*`` for template-published families
#   (``fabric_replica*_requests`` is an f-string gauge per replica id);
# - ``kind`` is ``counter`` / ``gauge`` / ``histogram`` / ``slo`` (error
#   budgets; fed by ``observe_request``, not a named publish call);
# - ``unit`` is documentation for operators (board column headers);
# - ``sites`` are the repo-relative modules that publish the name.
#
# The ``metric-name-drift`` check (analysis/rules.py) validates both
# directions: every literal publish call in the package must be covered by
# a row (name AND publishing module), and every row's name must appear in
# every site it claims.  Parsed lexically — keep it a literal.
METRIC_SCHEMAS: tuple = (
    # ---- run-aggregate namespace (obs.counter/gauge/histogram)
    ("degraded", "counter", "count",
     (f"{_PKG}/dataflow/fixpoint.py", f"{_PKG}/models/tfidf.py",
      f"{_PKG}/resilience/elastic.py", f"{_PKG}/resilience/executor.py",
      f"{_PKG}/resilience/process.py", f"{_PKG}/obs/metrics.py")),
    ("*.segment_secs", "histogram", "seconds",
     (f"{_PKG}/dataflow/fixpoint.py",)),
    ("h2d_overlap_frac", "gauge", "fraction",
     (f"{_PKG}/dataflow/ingest.py", f"{_PKG}/obs/metrics.py")),
    ("tfidf.chunks", "counter", "count", (f"{_PKG}/models/tfidf.py",)),
    ("tfidf.chunk_secs", "histogram", "seconds",
     (f"{_PKG}/models/tfidf.py",)),
    ("pagerank.comm_bytes_per_step", "gauge", "bytes",
     (f"{_PKG}/parallel/pagerank_sharded.py",)),
    ("chaos_injections", "counter", "count",
     (f"{_PKG}/resilience/chaos.py",)),
    ("watchdog_fires", "counter", "count",
     (f"{_PKG}/resilience/executor.py",)),
    ("retries", "counter", "count", (f"{_PKG}/resilience/executor.py",)),
    ("backoff_secs", "histogram", "seconds",
     (f"{_PKG}/resilience/executor.py",)),
    ("exhausted", "counter", "count",
     (f"{_PKG}/resilience/executor.py", f"{_PKG}/obs/metrics.py")),
    ("respawns", "counter", "count", (f"{_PKG}/resilience/process.py",)),
    ("fabric_replica*_requests", "gauge", "requests",
     (f"{_PKG}/serving/fabric.py",)),
    # sharded-cache + drain-handoff instruments (ISSUE 20)
    ("cache_peer_hits", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_peer_misses", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_peek_timeouts", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_fills", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_fill_errors", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_breaker_transitions", "counter", "count",
     (f"{_PKG}/serving/fabric.py",)),
    ("cache_peek_s", "histogram", "seconds",
     (f"{_PKG}/serving/fabric.py",)),
    ("fabric_drain_s", "histogram", "seconds",
     (f"{_PKG}/serving/fabric.py",)),
    ("fabric_handoff_s", "histogram", "seconds",
     (f"{_PKG}/serving/fabric.py",)),
    ("segment_commits", "counter", "count",
     (f"{_PKG}/serving/segments.py",)),
    ("segment_orphan_gcs", "counter", "count",
     (f"{_PKG}/serving/segments.py",)),
    ("segment_merges", "counter", "count",
     (f"{_PKG}/serving/segments.py",)),
    ("segment_merge_failures", "counter", "count",
     (f"{_PKG}/serving/segments.py",)),
    ("serve.cache_misses", "counter", "count",
     (f"{_PKG}/serving/server.py",)),
    ("serve.cache_hits", "counter", "count",
     (f"{_PKG}/serving/server.py",)),
    ("serve.batch_errors", "counter", "count",
     (f"{_PKG}/serving/server.py",)),
    ("serve.query_truncated", "counter", "count",
     (f"{_PKG}/serving/server.py",)),
    ("serve.latency_s", "histogram", "seconds",
     (f"{_PKG}/serving/server.py",)),
    ("serve.queue_wait_s", "histogram", "seconds",
     (f"{_PKG}/serving/server.py",)),
    ("checkpoint_saves", "counter", "count",
     (f"{_PKG}/utils/checkpoint.py",)),
    # bench parent's per-label sharded-PageRank comm-volume gauge
    ("owned_scale.comm_bytes.*", "gauge", "bytes", ("bench.py",)),
    ("artifact_saves", "counter", "count",
     (f"{_PKG}/utils/checkpoint.py",)),
    # ---- live-SLO namespace (MetricsHub; federated exactly, ISSUE 19)
    ("serve.requests", "counter", "requests", (f"{_PKG}/obs/metrics.py",)),
    ("serve.ok", "counter", "requests", (f"{_PKG}/obs/metrics.py",)),
    ("serve.errors", "counter", "requests", (f"{_PKG}/obs/metrics.py",)),
    ("chaos.injections", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("chaos.losses", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    # event-kind passthrough counters (ingest_event's kind sets): the
    # publish call is `self.count(kind)`, so the names live in the kind
    # tuples, not in call literals
    ("retry", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("backoff", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("watchdog", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("checkpoint_save", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("serve_start", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("soak_rebuild", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("soak_swap", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("soak_loss_injected", "counter", "count",
     (f"{_PKG}/obs/metrics.py",)),
    ("soak_recovered", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("soak_prior_refresh", "counter", "count",
     (f"{_PKG}/obs/metrics.py",)),
    ("ingest.chunks", "counter", "count", (f"{_PKG}/obs/metrics.py",)),
    ("ingest.tokens", "counter", "tokens", (f"{_PKG}/obs/metrics.py",)),
    # fleet-federation gauges (router-side FleetHub, ISSUE 19)
    ("fed_replicas", "gauge", "count", (f"{_PKG}/obs/federation.py",)),
    ("fed_stale_replicas", "gauge", "count",
     (f"{_PKG}/obs/federation.py",)),
    ("fed_staleness_s_max", "gauge", "seconds",
     (f"{_PKG}/obs/federation.py",)),
    # error budgets (MetricsHub.budgets keys; ErrorBudget instruments)
    ("availability", "slo", "fraction", (f"{_PKG}/obs/metrics.py",)),
    ("latency", "slo", "fraction", (f"{_PKG}/obs/metrics.py",)),
)

# ---------------------------------------------------------------------------
# Autotuning search-space contract (tier 3, ISSUE 16).
#
# ``TUNED_KNOBS`` declares the knob space ``tools/autotune.py`` sweeps and
# the tier-3 ``profile-drift`` check gates: one row per tunable —
# ``(knob name, candidate domain, affected registry entries)``.
#
# - the knob name must appear in ``utils/config.py``'s TUNABLE_DEFAULTS
#   (the single source of hand-picked defaults — domains here deliberately
#   do NOT repeat the default value's meaning; the default is always an
#   implicit member of the search space);
# - the domain is the full candidate grid the tuner enumerates BEFORE the
#   static cost model prunes it (pad-plan/intensity budget violations are
#   discarded unmeasured — the analysis is the search heuristic);
# - affected entries name the ENTRY_POINTS rows whose pad-plan budgets
#   prune this knob's candidates and whose microbenches score survivors.
#
# ``profile-drift`` validates the committed ``tuned_profile_<backend>.json``
# artifacts against this table in both directions (stale knob, missing
# backend stamp, out-of-domain value, declared-but-untuned), and validates
# the table itself against TUNABLE_DEFAULTS and ENTRY_POINTS — the space
# the tuner searches and the knobs the code reads cannot drift apart.
# Parsed lexically — keep it a literal (plain int/float domain values).
TUNED_KNOBS: tuple = (
    # hybrid SpMV dense-head layout: candidates outside the entry's
    # pad_frac ceiling (0.25) on the probe graph are pruned statically
    ("head_coverage", (0.25, 0.5, 0.75),
     ("pagerank_step_hybrid",)),
    ("head_row_width", (64, 128, 256),
     ("pagerank_step_hybrid",)),
    # sort_shuffle bucket padding: wider buckets shrink the reduction but
    # pay pad; the bucket pad fraction is computable without tracing
    ("shuffle_bucket_width", (4, 8, 16),
     ("pagerank_step_sort_shuffle",)),
    # owned-strategy replicated hub-head cap (boundary pad ceiling 0.30)
    ("owned_max_head", (1024, 4096, 8192),
     ("pagerank_sharded_owned",)),
    # staged ingest depths: scheduling-only (results bit-identical), so
    # no pad model prunes them — they ride to measurement unless the
    # paired pack target was already discarded
    ("prefetch", (0, 2, 4),
     ("tfidf_chunk_ingest_carry",)),
    ("pipeline_depth", (0, 2, 4),
     ("tfidf_chunk_ingest_carry",)),
    # streaming chunk re-packing target: 0 (caller chunking as-is) and
    # non-pow2 targets strand pad under the carried grow_chunk_cap pow2
    # policy — provably over the 0.20 drain/carry ceiling, pruned unmeasured
    ("pack_target_tokens", (0, 24000, 100000, 131072, 262144),
     ("tfidf_chunk_drain", "tfidf_chunk_ingest_carry")),
    # serving batch cap (query-batch pad ceiling 0.30)
    ("max_batch", (4, 8, 16),
     ("tfidf_score_query_batch",)),
    # impacted-list scoring bucket layout (impacted pad ceiling 0.62)
    ("impact_bucket_width", (4, 8, 16),
     ("tfidf_score_impacted_batch",)),
    ("impact_warm_buckets", (4096, 8192, 16384),
     ("tfidf_score_impacted_batch",)),
)

# ``--tier all`` runs two analyzers (semantic + cost) over the same
# registry in one process; building an entry — graph synthesis, mesh
# construction, partitioning per shrink-chain device count — is the
# expensive part of a lint pass, and the Traceable is immutable, so build
# once per process.  (Each tier still traces under its own config context:
# tier 2 under x64, tier 3 under production dtypes.)  Failures are NOT
# cached: a broken entry must re-raise in every tier that looks at it.
_BUILD_CACHE: "dict[EntryPoint, Traceable]" = {}


def build_traceable(ep: "EntryPoint") -> "Traceable":
    t = _BUILD_CACHE.get(ep)
    if t is None:
        t = _BUILD_CACHE[ep] = ep.build()
    return t


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _f32(shape):
    import numpy as np

    return _sds(shape, np.float32)


def _i32(shape):
    import numpy as np

    return _sds(shape, np.int32)


def _device_graph_spec(n: int, e: int):
    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.ops.pagerank import DeviceGraph

    return DeviceGraph(
        src=_i32((e,)),
        dst=_i32((e,)),
        inv_outdeg=_f32((n,)),
        dangling=_f32((n,)),
        has_outlinks=_f32((n,)),
        indptr=_sds((n + 1,), np.int32),
    )


# ----------------------------------------------------------------- pagerank


def _build_pagerank_scan() -> Traceable:
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, e = 64, 256
    cfg = PageRankConfig(iterations=4, dangling="redistribute", init="uniform")
    run = ops.make_pagerank_runner(n, cfg)
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("n64", (dg, _f32((n,)), _f32((n,))))],
        anchor=ops.pagerank_step,
    )


def _build_pagerank_while_cumsum() -> Traceable:
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, e = 64, 256
    cfg = PageRankConfig(iterations=8, tol=1e-6, spmv_impl="cumsum")
    run = ops.make_pagerank_runner(n, cfg)
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("n64-tol", (dg, _f32((n,)), _f32((n,))))],
        anchor=ops.make_pagerank_runner,
    )


def _shrink_chain(d0: int) -> list[int]:
    """The device counts the elastic rung can rebuild onto from ``d0``:
    the power-of-two shrink chain d0, d0/2, ..., 1 (resilience/elastic.py).
    Every sharded entry traces each of them, so the semantic gates
    (promotion, transfer census, collective budget) hold for the shrunk
    meshes a degraded run executes on — not only the healthy shape."""
    chain = []
    d = d0
    while d >= 1:
        chain.append(d)
        d //= 2
    return chain


def _sharded_pagerank_traceable(strategy: str) -> Traceable:
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        pagerank_sharded as ps,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
        NODES_AXIS,
        make_mesh,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    graph = synthetic_powerlaw(64, 256, seed=1)
    cfg = PageRankConfig(iterations=4, dangling="redistribute", init="uniform")
    runners: dict[int, object] = {}
    variants: list[tuple[str, tuple]] = []
    for d in _shrink_chain(min(4, len(jax.devices()))):
        mesh = make_mesh(d, NODES_AXIS)
        sg = ps.partition_graph(graph, d, strategy=strategy)
        runners[d] = ps.make_sharded_runner(sg, cfg, mesh)
        head = (
            (_i32(sg.head_src.shape), _i32(sg.head_node.shape))
            if strategy == "hybrid" else ()
        )
        args = (
            _f32((sg.n_pad,)),
            _i32(sg.src.shape),
            _i32(sg.dst.shape),
            _f32(sg.valid.shape),
            _i32(sg.local_indptr.shape),
            *head,
            _f32((sg.n_pad,)),
            _f32((sg.n_pad,)),
            _f32((sg.n_pad,)),
        )
        variants.append((f"{strategy}-d{d}", args))

    def dispatch(ranks, src, *rest):
        # per-device-count runners: the edge arrays are [d, e_dev], so the
        # leading dim names which compiled program this variant exercises
        return runners[src.shape[0]](ranks, src, *rest)

    return Traceable(
        fn=dispatch,
        variants=variants,
        anchor=ps.make_sharded_runner,
    )


def _sharded_pad_plan(strategy: str):
    """Static padding-waste plan points for a sharded entry: pad_frac of
    the partition *plan* (parallel.pagerank_sharded.plan_partition — no
    arrays materialized, no dispatch) on the registry's trace graph, one
    point per device count on the elastic shrink chain."""

    def plan() -> list[tuple[str, float]]:
        import jax

        from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
            synthetic_powerlaw,
        )
        from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
            plan_partition,
        )

        graph = synthetic_powerlaw(64, 256, seed=1)
        return [
            (
                f"{strategy}-d{d}",
                plan_partition(graph, d, strategy=strategy).pad_frac,
            )
            for d in _shrink_chain(min(4, len(jax.devices())))
        ]

    return plan


def _chunk_pad_plan() -> "list[tuple[str, float]]":
    """Static padding waste of the streaming ingest's grow_chunk_cap
    policy over the declared raw-token matrix."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
        stream_pad_plan,
    )

    return stream_pad_plan(CHUNK_TOKEN_MATRIX)


def _layout_device_graph_spec(layout: str):
    """DeviceGraph spec INCLUDING the static SpMV layout arrays: the
    layout shapes are graph-dependent, so they come from a real host
    build on the registry's trace graph (seed 1 — the same graph the
    sharded entries partition)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
        synthetic_powerlaw,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops

    graph = synthetic_powerlaw(64, 256, seed=1)
    # production (models.pagerank.put_graph_for) skips the raw edge
    # arrays for layout-backed impls — mirror that in the traced spec
    base = _device_graph_spec(graph.n_nodes, graph.n_edges)._replace(
        src=_i32((0,)), dst=_i32((0,)), indptr=_i32((0,))
    )
    if layout == "hybrid":
        hl = ops.build_hybrid_layout(graph)
        hybrid = ops.HybridLayout(
            head_ids=_i32(hl.head_ids.shape),
            head_src=_i32(hl.head_src.shape),
            head_row_node=_i32(hl.head_row_node.shape),
            tail_src=_i32(hl.tail_src.shape),
            tail_dst=_i32(hl.tail_dst.shape),
            tail_indptr=_i32(hl.tail_indptr.shape),
        )
        return graph.n_nodes, base._replace(hybrid=hybrid)
    bucket_src, bucket_node, _bucket_w = ops.build_shuffle_layout(graph)
    shuffle = ops.ShuffleLayout(
        bucket_src=_i32(bucket_src.shape), bucket_node=_i32(bucket_node.shape)
    )
    return graph.n_nodes, base._replace(shuffle=shuffle)


def _build_pagerank_hybrid() -> Traceable:
    """The degree-aware hybrid SpMV fixpoint runner: dense MXU head rows +
    segment tail (ops.spmv_hybrid), traced with the real layout shapes."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, dg = _layout_device_graph_spec("hybrid")
    cfg = PageRankConfig(iterations=4, dangling="redistribute",
                         init="uniform", spmv_impl="hybrid")
    run = ops.make_pagerank_runner(n, cfg)
    return Traceable(
        fn=run,
        variants=[("n64-hybrid", (dg, _f32((n,)), _f32((n,))))],
        anchor=ops.spmv_hybrid,
    )


def _build_pagerank_sort_shuffle() -> Traceable:
    """The sort-based static-shuffle SpMV fixpoint runner: fixed-width
    dst buckets, pure reshape->reduce contribution side."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, dg = _layout_device_graph_spec("sort_shuffle")
    cfg = PageRankConfig(iterations=4, dangling="redistribute",
                         init="uniform", spmv_impl="sort_shuffle")
    run = ops.make_pagerank_runner(n, cfg)
    return Traceable(
        fn=run,
        variants=[("n64-shuffle", (dg, _f32((n,)), _f32((n,))))],
        anchor=ops.spmv_sort_shuffle,
    )


def _build_pagerank_rowsum_pallas() -> Traceable:
    """The hybrid head's Pallas row-reduction kernel in interpret mode —
    tier-2/3 coverage of the on-chip dense reduce without a chip (the
    production hybrid path only takes it on a real TPU backend)."""
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import (
        pallas_kernels as pk,
    )

    fn = functools.partial(pk.rowsum_pallas, interpret=True)
    return Traceable(
        fn=fn,
        variants=[("r2048xw128", (_f32((2048, 128)),))],
        anchor=pk.rowsum_pallas,
    )


def _build_pagerank_sharded_owned() -> Traceable:
    """The owned-slices strategy (ISSUE 15): boundary butterfly + one
    head psum, 4-leaf donated carry — its own builder because the operand
    structure (lookup-index edge arrays, boundary pack indices, split
    tail/head state vectors) differs from every replicated strategy."""
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
        synthetic_powerlaw,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        pagerank_sharded as ps,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
        NODES_AXIS,
        make_mesh,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    graph = synthetic_powerlaw(64, 256, seed=1)
    cfg = PageRankConfig(iterations=4, dangling="redistribute", init="uniform")
    runners: dict[int, object] = {}
    variants: list[tuple[str, tuple]] = []
    for d in _shrink_chain(min(4, len(jax.devices()))):
        mesh = make_mesh(d, NODES_AXIS)
        sg = ps.partition_graph(graph, d, strategy="owned")
        sh = sg.owned
        runners[d] = ps.make_sharded_runner(sg, cfg, mesh)
        carry = (_f32((sh.n_pad,)), _f32((sh.h_pad,)), _f32((d,)), _f32(()))
        args = (
            carry,
            _i32(sh.tail_src_idx.shape), _i32(sh.tail_dst.shape),
            _f32(sh.tail_w.shape),
            _i32(sh.head_src_idx.shape), _i32(sh.head_slot.shape),
            _f32(sh.head_w.shape),
            _i32(sh.out_idx.shape),
            _f32((sh.n_pad,)), _f32((sh.n_pad,)),
            _f32((sh.h_pad,)), _f32((sh.h_pad,)),
            _f32((sh.n_pad,)), _f32((sh.h_pad,)),
        )
        variants.append((f"owned-d{d}", args))

    def dispatch(carry, tsrc, *rest):
        # the edge arrays are [d, e_dev]: the leading dim names which
        # compiled program this variant exercises
        return runners[tsrc.shape[0]](carry, tsrc, *rest)

    # The donation verifier lowers donate_fn with variants[0]'s args —
    # order the chain SMALLEST-first so that is the d=1 program: the CPU
    # backend's multi-device SPMD lowering drops input/output aliasing
    # entirely (0 aliased buffers at d>1 regardless of donate_argnums),
    # so the single-device lowering is the one place the donate_argnums
    # contract is statically checkable off-TPU.
    variants.reverse()
    return Traceable(
        fn=dispatch,
        variants=variants,
        anchor=ps.make_sharded_runner,
        donate_fn=runners[min(runners)],
    )


def _owned_pad_plan():
    """Both padding gauges of the owned plan on the trace graph, one
    point per shrink-chain device count: the edge-slot pad_frac (same
    gauge as every strategy) AND the boundary-buffer pad fraction (the
    'pad ceilings over boundary buffers' the ISSUE budgets).  d=1 has no
    exchange, so no boundary point (its 1-slot placeholder buffer is
    100% padding by construction and gauges nothing)."""

    def plan() -> list[tuple[str, float]]:
        import jax

        from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
            synthetic_powerlaw,
        )
        from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
            plan_partition,
        )

        graph = synthetic_powerlaw(64, 256, seed=1)
        points: list[tuple[str, float]] = []
        for d in _shrink_chain(min(4, len(jax.devices()))):
            p = plan_partition(graph, d, strategy="owned")
            points.append((f"owned-d{d}", p.pad_frac))
            if d > 1:
                points.append(
                    (f"owned-d{d}-boundary", p.owned.boundary_pad_frac)
                )
        return points

    return plan


def _owned_pair_variants(kind: str):
    """Shared builder half of the owned HITS/CC entries: per shrink-chain
    device count, the (forward, reverse) owned shards and the compiled
    runner, plus that count's abstract operand specs."""
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
        synthetic_powerlaw,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        workloads_sharded as ws,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
        NODES_AXIS,
        make_mesh,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
        ComponentsConfig,
        HitsConfig,
    )

    graph = synthetic_powerlaw(64, 256, seed=1)
    runners: dict[int, object] = {}
    variants: list[tuple[str, tuple]] = []
    for d in _shrink_chain(min(4, len(jax.devices()))):
        mesh = make_mesh(d, NODES_AXIS)
        sf, sr = ws.build_owned_pair(graph, d, "float32")
        fe = (_i32(sf.tail_src_idx.shape), _i32(sf.tail_dst.shape),
              _f32(sf.tail_w.shape), _i32(sf.out_idx.shape))
        re_ = (_i32(sr.tail_src_idx.shape), _i32(sr.tail_dst.shape),
               _f32(sr.tail_w.shape), _i32(sr.out_idx.shape))
        if kind == "hits":
            runners[d] = ws.make_hits_sharded_runner(
                sf, sr, HitsConfig(iterations=4, tol=0.0), mesh
            )
            carry = (_f32((sf.n_pad,)), _f32((sf.n_pad,)))
            args = (carry, *fe, *re_)
        else:
            runners[d] = ws.make_components_sharded_runner(
                sf, sr, ComponentsConfig(iterations=8), mesh
            )
            # the CC runner takes (fsrc, fdst, rsrc, rdst, fout, rout)
            args = (_i32((sf.n_pad,)), fe[0], fe[1], re_[0], re_[1],
                    fe[3], re_[3])
        variants.append((f"{kind}-owned-d{d}", args))

    def dispatch(carry, head, *rest):
        return runners[head.shape[0]](carry, head, *rest)

    return dispatch, variants


def _build_hits_sharded_owned() -> Traceable:
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        workloads_sharded as ws,
    )

    dispatch, variants = _owned_pair_variants("hits")
    return Traceable(fn=dispatch, variants=variants,
                     anchor=ws.make_hits_sharded_runner)


def _build_components_sharded_owned() -> Traceable:
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        workloads_sharded as ws,
    )

    dispatch, variants = _owned_pair_variants("cc")
    return Traceable(fn=dispatch, variants=variants,
                     anchor=ws.make_components_sharded_runner)


def _build_pagerank_sharded_edges() -> Traceable:
    return _sharded_pagerank_traceable("edges")


def _build_pagerank_sharded_hybrid() -> Traceable:
    return _sharded_pagerank_traceable("hybrid")


def _build_pagerank_sharded_nodes_balanced() -> Traceable:
    return _sharded_pagerank_traceable("nodes_balanced")


def _build_pagerank_sharded_src() -> Traceable:
    return _sharded_pagerank_traceable("src")


# -------------------------------------------------------------------- tfidf


def _build_tfidf_batch() -> Traceable:
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import IdfMode, TfMode

    cap, n_docs, vocab = 4096, 16, 1 << 10
    fn = functools.partial(
        ops.tfidf_pipeline,
        n_docs=n_docs,
        vocab=vocab,
        tf_mode=TfMode.FREQ,
        idf_mode=IdfMode.SMOOTH,
        l2_normalize=True,
    )
    return Traceable(
        fn=fn,
        variants=[("batch4k", (_i32((cap,)), _i32((cap,)), _i32((n_docs,))))],
        anchor=ops.tfidf_pipeline,
    )


def _build_tfidf_chunk_drain() -> Traceable:
    import functools
    import logging

    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import grow_chunk_cap
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
        MetricsRecorder,
    )

    # Run the declared raw-token matrix through the real streaming padding
    # policy, exactly as run_tfidf_streaming would: distinct caps == distinct
    # compiles of the chunk kernel.  The recorder's cap-bump log lines are
    # production telemetry — mute them for a lint pass.
    log = logging.getLogger("pr_tfidf_tpu")
    was_disabled = log.disabled
    log.disabled = True
    try:
        metrics = MetricsRecorder()
        cap = 0
        caps: list[int] = []
        for raw in CHUNK_TOKEN_MATRIX:
            cap, _ = grow_chunk_cap(raw, cap, metrics)
            caps.append(cap)
    finally:
        log.disabled = was_disabled
    variants = []
    for raw, cap in zip(CHUNK_TOKEN_MATRIX, caps):
        variants.append(
            (
                f"tokens{raw}",
                (_i32((cap,)), _i32((cap,)), _sds((cap,), np.bool_)),
            )
        )
    fn = functools.partial(ops.chunk_counts, vocab=1 << 10)
    return Traceable(fn=fn, variants=variants, anchor=ops.chunk_counts)


def _build_pagerank_pallas() -> Traceable:
    """The spmv_impl='pallas' fixpoint runner, traced in interpret mode.

    Mosaic only compiles on real TPUs, but ``_spmv`` flips the kernel to
    the Pallas *interpreter* whenever the trace-time backend is not TPU —
    so on the analyzer's pinned CPU backend the full runner (gather +
    pallas_call prefix sum + CSR diff + damping epilogue) traces into one
    jaxpr and every tier-2/tier-3 gate (promotion, transfer census,
    intensity, donation) covers the Pallas path too, chip or no chip."""
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import (
        pallas_kernels as pk,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, e = 64, 256
    cfg = PageRankConfig(iterations=4, dangling="redistribute",
                         init="uniform", spmv_impl="pallas")
    run = ops.make_pagerank_runner(n, cfg)
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("n64-pallas", (dg, _f32((n,)), _f32((n,))))],
        anchor=pk.spmv_pallas,
    )


def _build_tfidf_chunk_ingest_carry() -> Traceable:
    """The production streaming kernel: chunk counts + the device-resident
    donated DF carry (ops.chunk_counts_carry), shape matrix through the
    real grow_chunk_cap policy exactly like the legacy drain entry."""
    import functools
    import logging

    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import grow_chunk_cap
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
        MetricsRecorder,
    )

    vocab = 1 << 10
    log = logging.getLogger("pr_tfidf_tpu")
    was_disabled = log.disabled
    log.disabled = True
    try:
        metrics = MetricsRecorder()
        cap = 0
        caps: list[int] = []
        for raw in CHUNK_TOKEN_MATRIX:
            cap, _ = grow_chunk_cap(raw, cap, metrics)
            caps.append(cap)
    finally:
        log.disabled = was_disabled
    variants = []
    for raw, cap in zip(CHUNK_TOKEN_MATRIX, caps):
        variants.append(
            (
                f"tokens{raw}",
                (_i32((cap,)), _i32((cap,)), _sds((cap,), np.bool_),
                 _f32((vocab,))),
            )
        )
    fn = functools.partial(ops.chunk_counts_carry, vocab=vocab)
    return Traceable(
        fn=fn,
        variants=variants,
        anchor=ops.chunk_counts_carry,
        donate_fn=ops.chunk_counts_carry,
        donate_kwargs={"vocab": vocab},
    )


def _build_tfidf_sharded_ingest() -> Traceable:
    import jax
    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        tfidf_sharded as ts,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
        DATA_AXIS,
        make_mesh,
    )

    cap, vocab = 2048, 1 << 10
    kernels: dict[int, object] = {}
    variants: list[tuple[str, tuple]] = []
    for d in _shrink_chain(min(4, len(jax.devices()))):
        mesh = make_mesh(d, DATA_AXIS)
        kernels[d] = ts.make_sharded_counts_kernel(mesh, vocab)
        args = (
            _i32((d, cap)),
            _i32((d, cap)),
            _sds((d, cap), np.bool_),
        )
        variants.append((f"d{d}-cap{cap}", args))

    def dispatch(doc_ids, term_ids, valid):
        return kernels[doc_ids.shape[0]](doc_ids, term_ids, valid)

    return Traceable(
        fn=dispatch,
        variants=variants,
        anchor=ts.make_sharded_counts_kernel,
    )


def _build_tfidf_finalize() -> Traceable:
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import TfMode

    nnz, n_docs = 4096, 16
    fn = functools.partial(
        ops.finalize_weights, n_docs=n_docs, tf_mode=TfMode.FREQ, l2_normalize=True
    )
    return Traceable(
        fn=fn,
        variants=[
            ("nnz4k", (_i32((nnz,)), _f32((nnz,)), _i32((n_docs,)), _f32((nnz,))))
        ],
        anchor=ops.finalize_weights,
    )


def _build_tfidf_score_query() -> Traceable:
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops

    cap, n_docs, vocab, k = 2048, 32, 1 << 10, 8
    result = ops.TfidfResult(
        doc=_i32((cap,)),
        term=_i32((cap,)),
        weight=_f32((cap,)),
        n_pairs=_i32(()),
        valid=_f32((cap,)),
        idf=_f32((vocab,)),
        df=_f32((vocab,)),
    )
    fn = functools.partial(ops.score_query, n_docs=n_docs, k=k)
    return Traceable(
        fn=fn,
        variants=[("top8", (result, _f32((vocab,))))],
        anchor=ops.score_query,
    )


# Raw micro-batch sizes the serving drain loop sees in production (mixed
# single requests, partial batches, a full batch): run through the REAL
# serving padding policy (serving.server.batch_cap — grow_chunk_cap with
# min_bits=0) they must collapse to the power-of-two matrix the server
# warms, or the recompile gate fires — "zero per-request recompiles" as a
# statically checked contract, not a hope.
SERVE_BATCH_MATRIX = (1, 2, 3, 5, 7, 8, 11, 16)
SERVE_MAX_BATCH = 16


def _serve_pad_plan() -> "list[tuple[str, float]]":
    from page_rank_and_tfidf_using_apache_spark_tpu.serving.server import (
        serve_pad_plan,
    )

    return serve_pad_plan(SERVE_BATCH_MATRIX, SERVE_MAX_BATCH)


def _build_tfidf_score_query_batch() -> Traceable:
    """The warm serving path's batched scorer (serving/server.py drives
    it): one compiled program per padded batch cap, sparse [B, Q] queries,
    top-k fused on device."""
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.serving.server import (
        batch_cap,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
        MetricsRecorder,
    )

    cap, n_docs, vocab, k, q = 2048, 32, 1 << 10, 8, 16
    metrics = MetricsRecorder()
    variants = []
    for b in SERVE_BATCH_MATRIX:
        bc = batch_cap(b, SERVE_MAX_BATCH, metrics)
        variants.append(
            (
                f"batch{b}",
                (
                    _i32((cap,)), _i32((cap,)), _f32((cap,)), _f32((cap,)),
                    _i32((bc, q)), _f32((bc, q)), _f32((bc, q)),
                    _f32((n_docs,)),
                ),
            )
        )
    fn = functools.partial(
        ops.score_query_batch, n_docs=n_docs, vocab=vocab, k=k,
        use_prior=True,
    )
    return Traceable(fn=fn, variants=variants, anchor=ops.score_query_batch)


# Raw per-batch bucket counts the impacted-list planner produces in
# production (Σ ceil(run/W) over the batch's query terms): run through the
# REAL carried grow_chunk_cap policy (serving.server.impacted_pad_plan /
# the planner's cap state) they must collapse to a handful of pow2 caps —
# the bucket axis of the impacted serving shape matrix.
IMPACT_BUCKET_MATRIX = (23, 40, 150, 900, 64)


def _impacted_pad_plan() -> "list[tuple[str, float]]":
    from page_rank_and_tfidf_using_apache_spark_tpu.serving.server import (
        impacted_pad_plan,
    )

    return impacted_pad_plan(IMPACT_BUCKET_MATRIX)


def _build_tfidf_score_impacted_batch() -> Traceable:
    """The latency-shaped serving scorer (ISSUE 13, serving/server.py
    drives it): CSC-by-term posting runs padded into fixed-width buckets,
    one reshape→gather→scatter-add program per (batch cap, bucket cap)
    point — work ∝ the batch's query terms' posting runs, not nnz."""
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
        grow_chunk_cap,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.serving.server import (
        IMPACT_MIN_BUCKET_BITS,
        batch_cap,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import (
        MetricsRecorder,
    )

    nnz, n_docs, k, w = 2048, 32, 8, 8
    metrics = MetricsRecorder()
    # the bucket caps the declared raw counts produce under the carried
    # pow2 policy — the same state discipline the serving planner keeps
    bcap = 0
    bcaps = []
    for raw in IMPACT_BUCKET_MATRIX:
        bcap, _ = grow_chunk_cap(max(raw, 1), bcap, metrics,
                                 min_bits=IMPACT_MIN_BUCKET_BITS)
        bcaps.append(bcap)
    variants = []
    seen: set = set()
    for b, bc in zip(SERVE_BATCH_MATRIX, bcaps + bcaps[: max(
            0, len(SERVE_BATCH_MATRIX) - len(bcaps))]):
        cap = batch_cap(b, SERVE_MAX_BATCH, metrics)
        if (cap, bc) in seen:
            continue
        seen.add((cap, bc))
        variants.append(
            (
                f"b{cap}-c{bc}",
                (
                    _i32((cap,)),  # batch marker: dispatch reads batch here
                    _i32((nnz,)), _f32((nnz,)),
                    _i32((bc,)), _i32((bc,)), _i32((bc,)), _f32((bc,)),
                    _f32((n_docs,)),
                ),
            )
        )

    fn = functools.partial(
        ops.score_impacted_batch, n_docs=n_docs, bucket_width=w, k=k,
        use_prior=True,
    )

    def dispatch(marker, doc, weight, bs, bl, br, bqw, prior):
        # the padded batch cap is a static of the inner jit; the marker
        # array's length names which compiled program a variant exercises
        return fn(doc, weight, bs, bl, br, bqw, prior,
                  batch=marker.shape[0])

    # donate=() rides the default surface: the dispatch wrapper lowers
    # whole (marker included) and must record ZERO aliased inputs
    return Traceable(
        fn=dispatch,
        variants=variants,
        anchor=ops.score_impacted_batch,
    )


def _build_tfidf_topk_merge() -> Traceable:
    """Device-side per-segment top-k merge (serving across live delta
    segments): concat + re-rank + id globalization in one fused program;
    one compile per (segment count, batch cap) pair."""
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops

    b, k = 8, 8
    fn = functools.partial(ops.topk_merge, k=k)
    variants = []
    for s in (2, 3):
        scores = tuple(_f32((b, k)) for _ in range(s))
        ids = tuple(_i32((b, k)) for _ in range(s))
        bases = tuple(_i32(()) for _ in range(s))
        variants.append((f"s{s}", (scores, ids, bases)))
    return Traceable(fn=fn, variants=variants, anchor=ops.topk_merge)


# ---------------------------------------------------- dataflow workloads


def _build_ppr_batch() -> Traceable:
    """Batched personalized PageRank: the vmapped fixpoint runner with a
    [B, n] donated rank carry and [B, n] teleport matrix."""
    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import ppr
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    n, e, b = 64, 256, 4
    cfg = PageRankConfig(iterations=4, dangling="redistribute", init="uniform")
    run = ppr.make_ppr_batch_runner(n, cfg)
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("b4-n64", (dg, _f32((b, n)), _f32((b, n))))],
        anchor=ppr.make_ppr_batch_runner,
    )


def _build_hits() -> Traceable:
    """HITS: two interleaved SpMV passes (sorted dst combine + unsorted
    src combine) with per-step max normalization, [2, n] donated carry."""
    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import hits
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import HitsConfig

    n, e = 64, 256
    run = hits.make_hits_runner(n, HitsConfig(iterations=4, tol=0.0))
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("n64", (dg, _f32((2, n))))],
        anchor=hits.hits_step,
    )


def _build_components() -> Traceable:
    """Connected components: min-label propagation to fixpoint (while
    loop, changed-label-count delta), int32 donated label carry."""
    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import components
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
        ComponentsConfig,
    )

    n, e = 64, 256
    run = components.make_components_runner(n, ComponentsConfig(iterations=8))
    dg = _device_graph_spec(n, e)
    return Traceable(
        fn=run,
        variants=[("n64", (dg, _i32((n,))))],
        anchor=components.label_step,
    )


def _build_bm25_weights() -> Traceable:
    """BM25 re-weighting of the postings COO: two gathers + elementwise
    math, one compile per nnz shape (index build time)."""
    import functools

    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow import bm25

    nnz, n_docs, vocab = 4096, 16, 1 << 10
    fn = functools.partial(bm25.bm25_weights, n_docs=n_docs, k1=1.5, b=0.75)
    return Traceable(
        fn=fn,
        variants=[
            ("nnz4k", (_i32((nnz,)), _i32((nnz,)), _f32((nnz,)),
                       _i32((n_docs,)), _f32((vocab,))))
        ],
        anchor=bm25.bm25_weights,
    )


# ------------------------------------------------------------- the registry

ENTRY_POINTS: tuple[EntryPoint, ...] = (
    EntryPoint(
        name="pagerank_step",
        module=f"{_PKG}/ops/pagerank.py",
        build=_build_pagerank_scan,
        watch=(f"{_PKG}/dataflow/fixpoint.py",),
        # iterate-to-fixpoint runner: the rank carry (argnum 1 of
        # run(dg, ranks0, e)) is donated — verified against the lowered
        # aliasing by the tier-3 donation check
        donate=(1,),
        intensity_floor=0.05,  # static model measures 0.066
    ),
    EntryPoint(
        name="pagerank_step_tol_cumsum",
        module=f"{_PKG}/ops/pagerank.py",
        build=_build_pagerank_while_cumsum,
        watch=(f"{_PKG}/dataflow/fixpoint.py",),
        donate=(1,),
        intensity_floor=0.045,  # static model measures 0.054
    ),
    EntryPoint(
        name="pagerank_step_pallas",
        module=f"{_PKG}/ops/pallas_kernels.py",
        build=_build_pagerank_pallas,
        # the runner composes ops/pagerank.py machinery around the kernel
        watch=(f"{_PKG}/ops/pagerank.py", f"{_PKG}/dataflow/fixpoint.py"),
        donate=(1,),
        intensity_floor=0.04,  # static model measures 0.050
    ),
    EntryPoint(
        name="pagerank_step_hybrid",
        module=f"{_PKG}/ops/pagerank.py",
        build=_build_pagerank_hybrid,
        watch=(f"{_PKG}/dataflow/fixpoint.py",),
        donate=(1,),
        intensity_floor=0.05,  # static model measures 0.075
    ),
    EntryPoint(
        name="pagerank_step_sort_shuffle",
        module=f"{_PKG}/ops/pagerank.py",
        build=_build_pagerank_sort_shuffle,
        watch=(f"{_PKG}/dataflow/fixpoint.py",),
        donate=(1,),
        intensity_floor=0.05,  # static model measures 0.072
    ),
    EntryPoint(
        name="pagerank_rowsum_pallas",
        module=f"{_PKG}/ops/pallas_kernels.py",
        build=_build_pagerank_rowsum_pallas,
        # the hybrid impl routes its dense head through this kernel on a
        # real TPU backend (ops.pagerank.hybrid_rowsum)
        watch=(f"{_PKG}/ops/pagerank.py",),
        # the model charges the pre-kernel pad copy as extra HBM traffic,
        # so the static intensity is 0.050 (2 flops per element over ~2.5
        # array passes), not the kernel's own 0.25
        intensity_floor=0.045,
    ),
    EntryPoint(
        name="pagerank_sharded_edges",
        module=f"{_PKG}/parallel/pagerank_sharded.py",
        build=_build_pagerank_sharded_edges,
        watch=(
            f"{_PKG}/ops/pagerank.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # one psum per iteration: the contribs combine (replicated state
        # needs no dangling-mass or delta collective)
        collective_budget=1,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        # equal contiguous edge slices: padding is only the ceil remainder
        pad_plan=_sharded_pad_plan("edges"),
        pad_frac_ceiling=0.05,
        intensity_floor=0.035,  # static model: 0.047 at d=1 (worst)
    ),
    EntryPoint(
        name="pagerank_sharded_nodes_balanced",
        module=f"{_PKG}/parallel/pagerank_sharded.py",
        build=_build_pagerank_sharded_nodes_balanced,
        watch=(
            f"{_PKG}/ops/pagerank.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # all_gather(weighted ranks) + psum(dangling mass) + psum(delta)
        collective_budget=3,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        # RATCHETED with the hybrid/power-law PR: the optimal min-max
        # boundary search (plan_partition) brought the trace-graph worst
        # point from 0.47 to 0.10 at d=4 (and the 8-device dryrun plan
        # from 0.61 to 0.47, its node-granularity floor — one hub's
        # in-edge run cannot split across devices in this layout; the
        # 'hybrid' strategy exists to go below that floor).
        pad_plan=_sharded_pad_plan("nodes_balanced"),
        pad_frac_ceiling=0.25,
        intensity_floor=0.035,  # static model: 0.045 at d=4 (worst)
    ),
    EntryPoint(
        name="pagerank_sharded_hybrid",
        module=f"{_PKG}/parallel/pagerank_sharded.py",
        build=_build_pagerank_sharded_hybrid,
        watch=(
            f"{_PKG}/ops/pagerank.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # one psum combines head + tail partials (replicated state needs
        # no dangling-mass or delta collective)
        collective_budget=1,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        # row/edge-granular splits: only dense-row sentinels and two ceil
        # remainders pad (0.21 at d=4 on the hub-dense 256-edge trace
        # graph; 0.0001 at web-Google scale, where the ROADMAP "pad_frac
        # below 0.25 for the balanced strategies" goal is measured)
        pad_plan=_sharded_pad_plan("hybrid"),
        pad_frac_ceiling=0.25,
        intensity_floor=0.04,  # static model: 0.052 at d=4 (worst)
    ),
    EntryPoint(
        name="pagerank_sharded_owned",
        module=f"{_PKG}/parallel/pagerank_sharded.py",
        build=_build_pagerank_sharded_owned,
        watch=(
            f"{_PKG}/ops/pagerank.py",
            f"{_PKG}/ops/boundary.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # THE owned collective contract (ISSUE 15 acceptance): log2(d)
        # ppermute rounds of the boundary butterfly + exactly ONE psum —
        # the [H_pad+2] head combine whose spare slots carry the dangling
        # mass and the lagged delta, so neither adds a collective.  Worst
        # traced point is d=4: 2 ppermutes + 1 psum = 3.
        collective_budget=3,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        # two gauges per chain point: edge-slot pad_frac (ceil remainders
        # only — both edge classes split at edge granularity) and the
        # boundary-buffer pad fraction (pow2 width over max |S_j|; worst
        # trace-graph point 0.22 at d=2)
        pad_plan=_owned_pad_plan(),
        pad_frac_ceiling=0.30,
        # the 4-leaf owned carry (tail slice, replicated head, dslot,
        # gdelta) is donated at argnum 0 — per-chip state being O(n/d) is
        # the strategy's reason to exist, so the carry may not double
        donate=(0,),
        intensity_floor=0.03,  # static model: 0.042 at d=4 (worst)
    ),
    EntryPoint(
        name="pagerank_sharded_src",
        module=f"{_PKG}/parallel/pagerank_sharded.py",
        build=_build_pagerank_sharded_src,
        watch=(
            f"{_PKG}/ops/pagerank.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # reduce-scatter exchange + psum(dangling mass) + psum(delta)
        collective_budget=3,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        # push layout: out-degree is the bounded axis, padding stays small
        pad_plan=_sharded_pad_plan("src"),
        pad_frac_ceiling=0.25,
        intensity_floor=0.03,  # static model: 0.040 at d=4 (worst)
    ),
    EntryPoint(
        name="hits_sharded_owned",
        module=f"{_PKG}/parallel/workloads_sharded.py",
        build=_build_hits_sharded_owned,
        watch=(
            f"{_PKG}/ops/boundary.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # two boundary butterflies (2·log2(d) ppermutes) + two pmax norms
        # + the convergence psum: 7 at the traced d=4 worst
        collective_budget=7,
        max_compiles=3,
        intensity_floor=0.03,
    ),
    EntryPoint(
        name="components_sharded_owned",
        module=f"{_PKG}/parallel/workloads_sharded.py",
        build=_build_components_sharded_owned,
        watch=(
            f"{_PKG}/ops/boundary.py",
            f"{_PKG}/dataflow/fixpoint.py",
            f"{_PKG}/parallel/collectives.py",
        ),
        axes=("nodes",),
        # two boundary butterflies + the changed-count psum: 5 at d=4
        collective_budget=5,
        max_compiles=3,
        intensity_floor=0.01,
    ),
    EntryPoint(
        name="tfidf_batch_pipeline",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_batch,
        intensity_floor=0.09,  # static model measures 0.109
    ),
    EntryPoint(
        name="tfidf_chunk_drain",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_chunk_drain,
        # the shape matrix runs through models/tfidf.py grow_chunk_cap —
        # a policy change there must re-verify this contract
        watch=(f"{_PKG}/models/tfidf.py", f"{_PKG}/dataflow/ingest.py"),
        # The doubling cap policy may legally produce a handful of buckets
        # over a whole stream; the declared matrix must collapse to <= 3.
        max_compiles=3,
        # stream-aggregate padding of the doubling-cap policy (~0.13 on
        # the declared matrix; doubling bounds the worst steady state at
        # <0.5 but the declared workload must stay far under that)
        pad_plan=_chunk_pad_plan,
        pad_frac_ceiling=0.20,
        intensity_floor=0.25,  # static model: 0.265 at the smallest cap
    ),
    EntryPoint(
        name="tfidf_chunk_ingest_carry",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_chunk_ingest_carry,
        watch=(f"{_PKG}/models/tfidf.py", f"{_PKG}/dataflow/ingest.py"),
        max_compiles=3,
        pad_plan=_chunk_pad_plan,
        pad_frac_ceiling=0.20,
        # the ingest carry: the device DF accumulator (argnum 3) must be
        # donated so XLA updates it in place every chunk
        donate=(3,),
        intensity_floor=0.25,  # static model: 0.265 at the smallest cap
    ),
    EntryPoint(
        name="tfidf_sharded_ingest",
        module=f"{_PKG}/parallel/tfidf_sharded.py",
        build=_build_tfidf_sharded_ingest,
        watch=(
            f"{_PKG}/ops/tfidf.py",
            f"{_PKG}/parallel/mesh.py",
            f"{_PKG}/parallel/collectives.py",
            # the host loop is the staged pipeline now (ISSUE 10): a
            # change to the staging/commit discipline must re-verify the
            # sharded contracts (collective budget, shrink-chain compiles)
            f"{_PKG}/dataflow/ingest.py",
        ),
        axes=("data",),
        # exactly the DF psum — the one reduceByKey of the ingest step
        collective_budget=1,
        # one compile per device count on the elastic shrink chain (4,2,1)
        max_compiles=3,
        intensity_floor=0.15,  # static model measures 0.180
    ),
    EntryPoint(
        name="tfidf_finalize",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_finalize,
        intensity_floor=0.045,  # static model measures 0.061
    ),
    EntryPoint(
        name="tfidf_score_query",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_score_query,
        intensity_floor=0.04,  # static model measures 0.060
    ),
    EntryPoint(
        name="dataflow_ppr_batch",
        module=f"{_PKG}/dataflow/ppr.py",
        build=_build_ppr_batch,
        # the step math and the iterate skeleton live under these
        watch=(f"{_PKG}/ops/pagerank.py", f"{_PKG}/dataflow/fixpoint.py"),
        # the [B, n] rank carry (argnum 1) is donated, same contract as
        # the single-query runner
        donate=(1,),
        intensity_floor=0.06,  # static model measures 0.086 (b=4)
    ),
    EntryPoint(
        name="dataflow_hits",
        module=f"{_PKG}/dataflow/hits.py",
        build=_build_hits,
        watch=(f"{_PKG}/dataflow/combine.py", f"{_PKG}/dataflow/fixpoint.py"),
        donate=(1,),
        intensity_floor=0.04,  # static model measures 0.049
    ),
    EntryPoint(
        name="dataflow_components",
        module=f"{_PKG}/dataflow/components.py",
        build=_build_components,
        watch=(f"{_PKG}/dataflow/combine.py", f"{_PKG}/dataflow/fixpoint.py"),
        donate=(1,),
        intensity_floor=0.04,  # static model measures 0.052
    ),
    EntryPoint(
        name="dataflow_bm25_weights",
        module=f"{_PKG}/dataflow/bm25.py",
        build=_build_bm25_weights,
        # pure re-weighting pass: gathers + elementwise over the COO
        intensity_floor=0.10,  # static model measures 0.122
    ),
    EntryPoint(
        name="tfidf_score_query_batch",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_score_query_batch,
        # the padding policy lives in serving/server.py (batch_cap over
        # models/tfidf.py's grow_chunk_cap): a change to either must
        # re-verify the zero-per-request-recompile contract
        watch=(
            f"{_PKG}/serving/server.py",
            f"{_PKG}/models/tfidf.py",
            f"{_PKG}/dataflow/ingest.py",
        ),
        # one compile per padded batch cap: {1, 2, 4, 8, 16} at
        # max_batch 16 — the full warm set; anything beyond means an
        # unpadded batch shape reached jit
        max_compiles=5,
        pad_plan=_serve_pad_plan,
        # the declared raw-batch matrix fills 53 of 63 dispatched slots
        # (pad_frac ~0.159); the worst steady state of pow2 padding is
        # < 0.5, but the declared workload must stay well under it
        pad_frac_ceiling=0.30,
        # static model: 0.052 at batch cap 1 (worst — the per-request
        # fallback shape; batching raises intensity monotonically, the
        # quantitative case for the micro-batcher)
        intensity_floor=0.04,
    ),
    EntryPoint(
        name="tfidf_score_impacted_batch",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_score_impacted_batch,
        # the bucket planner + carried-cap policy live in serving/server.py
        # over grow_chunk_cap; the CSC offsets come from serving/artifact.py
        # (and segment sets re-derive them in serving/segments.py) — a
        # change to any of them must re-verify this contract
        watch=(
            f"{_PKG}/serving/server.py",
            f"{_PKG}/serving/artifact.py",
            f"{_PKG}/serving/segments.py",
            f"{_PKG}/models/tfidf.py",
            f"{_PKG}/dataflow/ingest.py",
        ),
        # one compile per (padded batch cap, carried bucket cap) point of
        # the declared matrices — anything beyond means an unpadded shape
        # reached jit on the latency path
        max_compiles=8,
        pad_plan=_impacted_pad_plan,
        # the declared raw bucket counts fill ~44% of the carried pow2
        # caps (pad_frac ~0.56 includes the 2**IMPACT_MIN_BUCKET_BITS
        # floor at tiny batches); bounded so planner drift cannot silently
        # triple the dispatched bucket axis
        pad_frac_ceiling=0.62,
        # donation contract: the scorer must alias NOTHING — every operand
        # (postings, weight table, prior) is reused by the next batch, so
        # a donation sneaking in would consume live serving state
        donate=(),
        intensity_floor=0.03,  # static model: 0.049 at b1-c64 (worst —
        # the single-request floor shape; larger batches amortize the
        # postings traffic exactly like the COO entry's matrix does)
    ),
    EntryPoint(
        name="tfidf_topk_merge",
        module=f"{_PKG}/ops/tfidf.py",
        build=_build_tfidf_topk_merge,
        watch=(f"{_PKG}/serving/server.py",),
        # one compile per live-segment count at the warmed batch cap
        max_compiles=2,
        # same must-alias-nothing contract as the scorer: per-segment
        # candidate buffers belong to their dispatches
        donate=(),
        intensity_floor=0.03,  # static model measures 0.053 (s2)
    ),
)
