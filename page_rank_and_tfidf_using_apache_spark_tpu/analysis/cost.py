"""graftlint tier 3: static cost-model analysis of registered jit entry
points.

Tier 2 (semantic.py) checks what a jaxpr *does* — collectives, callbacks,
dtypes.  This tier checks what it *costs*, still with zero dispatch: every
:class:`~.registry.EntryPoint` is traced on the CPU backend from abstract
``ShapeDtypeStruct`` inputs and three budget surfaces are gated:

- **intensity-floor** — a static FLOP / HBM-byte model over the traced
  equations (per *step*: loop bodies counted once, exactly tier 2's
  convention).  Bytes are the un-fused operand+result traffic of every
  leaf equation, so the modeled intensity is a *lower bound* on what a
  fusing compiler achieves — a conservative, internally consistent ratchet.
  An entry whose worst-variant intensity drops below its declared
  ``intensity_floor`` fails lint... unless the cost baseline artifact
  (``xla_cost_tpu.json``) was measured on a non-TPU backend, in which case
  the finding is **downgraded to advisory**: CPU-measured numbers must
  never gate kernel design (the round-5 no-chip failure mode — see
  utils/artifacts.py, which keeps a CPU run from silently overwriting a
  TPU-stamped artifact in the first place).
- **pad-frac-budget** — the static padding-waste analyzer: each entry's
  ``pad_plan`` evaluates its partition/padding strategy *plan* without
  materializing it (``parallel.pagerank_sharded.plan_partition`` for the
  shard strategies, ``models.tfidf.stream_pad_plan`` for the chunk-ingest
  ``grow_chunk_cap`` policy) and the worst plan point must stay under the
  declared ``pad_frac_ceiling``.  ``partition_graph`` materializes exactly
  the plan the linter budgets, and the plan numbers are cross-checked
  against the dryrun-measured ``pad_frac`` in MULTICHIP_r05.json by
  tests/test_cost_lint.py — so a partitioning change that inflates padding
  waste fails lint before any chip sees it.
- **donation-contract** — the buffer-donation verifier: entries declaring
  ``donate`` argnums are *lowered* (still CPU, still no execution) and the
  input/output aliasing recorded in the computation is compared against
  the contract, in both directions: a declared-but-absent donation (the
  un-donated ingest carry this tier's first sweep existed to catch) and an
  undeclared aliased input (a donation the registry does not know about)
  are both findings.

Every check honors the entry's ``suppress`` set, and findings flow through
the same fingerprint/baseline/ratchet machinery as tiers 1 and 2.  A
registry entry that fails to build/trace is a ``cost-entry-broken``
finding (tier 2 reports the same breakage as ``entry-point-broken``; the
distinct rule id keeps the two tiers' ratchet entries independent).
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from typing import Any, Iterable, Sequence

from page_rank_and_tfidf_using_apache_spark_tpu.analysis.findings import (
    Finding,
    assign_fingerprints,
)
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.registry import (
    ENTRY_POINTS,
    EntryPoint,
    Traceable,
    build_traceable,
)
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.semantic import (
    _anchor_location,
    _CALLBACK_PRIMS,
    _COMM_PRIMS,
    _iter_subjaxprs,
    _trace_signature,
    ensure_cpu_tracing_env,
)

COST_RULES: dict[str, str] = {
    "intensity-floor": (
        "static FLOP/byte arithmetic intensity fell below the entry's "
        "declared floor — the program got more memory-bound; advisory "
        "while the cost baseline artifact is not TPU-measured"
    ),
    "pad-frac-budget": (
        "static padding-waste fraction of the entry's partition/padding "
        "plan exceeds its declared ceiling — more dispatched work is "
        "padding than the budget allows"
    ),
    "donation-contract": (
        "declared donate argnums disagree with the lowered computation's "
        "input/output aliasing — a donation that does not happen (or one "
        "the registry does not declare)"
    ),
    "cost-entry-broken": (
        "a registered jit entry point no longer builds, traces or lowers "
        "for the tier-3 cost model — the registry contract is stale"
    ),
}

# Default cost baseline artifact: the XLA op-cost probe output.  Tier 3
# only reads its backend stamp — CPU-measured numbers downgrade the
# intensity ratchet to advisory (they must never gate kernel design).
COST_BASELINE_ARTIFACT = "xla_cost_tpu.json"

# --------------------------------------------------------------------------
# the per-equation FLOP/byte model
# --------------------------------------------------------------------------

# Container primitives: the eqn itself is free; its body is the cost.
_CONTAINERS = frozenset({
    "pjit", "jit", "xla_call", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "remat_call",
    "checkpoint", "scan", "while", "cond", "shard_map", "named_call",
})

# ~10 VPU ops per element: good enough to rank transcendental-heavy code.
_TRANSCENDENTAL = frozenset({
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "logistic",
    "erf", "erfc", "pow", "atan2", "cbrt",
})
_SQRTISH = frozenset({"sqrt", "rsqrt"})
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_window_sum",
    "reduce_window_max",
})
# Prefix scans: modeled at one add per element (XLA's actual lowering is
# O(n log n) HBM passes on TPU — which is exactly why cumsum_blocked and
# the Pallas carry kernel exist; the *model* stays lowering-agnostic).
_SCANS = frozenset({"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"})
_GATHERISH = frozenset({"gather", "take", "dynamic_slice", "take_along_axis"})
_SCATTERISH = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter-mul", "scatter_mul",
    "scatter-min", "scatter-max", "dynamic_update_slice", "segment_sum",
})
_MOVES = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "concatenate", "slice",
    "pad", "rev", "squeeze", "expand_dims", "copy", "convert_element_type",
    "bitcast_convert_type", "select_n", "stop_gradient", "device_put",
})
_MATERIALIZE = frozenset({"iota", "broadcast_in_dim"})


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for dim in shape:
        try:
            n *= int(dim)
        except (TypeError, ValueError):  # symbolic dim: count as 1
            pass
    try:
        import numpy as np

        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def _var_elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", None)
    if shape is None:
        return 0
    n = 1
    for dim in shape:
        try:
            n *= int(dim)
        except (TypeError, ValueError):
            pass
    return n


def _out_elems(eqn) -> int:
    return max(sum(_var_elems(v) for v in eqn.outvars), 1)


def _in_elems(eqn) -> int:
    return max(sum(_var_elems(v) for v in eqn.invars), 1)


def _dot_flops(eqn) -> int:
    """2·batch·M·N·K from dot_general's dimension numbers."""
    try:
        (contract, batch) = eqn.params["dimension_numbers"]
        lhs_c, _ = contract
        lhs = eqn.invars[0].aval.shape
        k = 1
        for dim in lhs_c:
            k *= int(lhs[dim])
        out = 1
        for dim in eqn.outvars[0].aval.shape:
            out *= int(dim)
        return 2 * out * max(k, 1)
    except Exception:
        return 2 * _out_elems(eqn)


def classify_eqn(eqn) -> tuple[str, int]:
    """(cost class, flops) for one leaf equation."""
    name = eqn.primitive.name
    if name == "dot_general":
        return "matmul", _dot_flops(eqn)
    if name in _CALLBACK_PRIMS:
        return "callback", 0
    if name in _COMM_PRIMS:
        return "comm", 0
    if name == "pallas_call":
        # Opaque on purpose: the kernel body runs in VMEM; its HBM cost is
        # the operands/results this eqn reads and writes.
        return "pallas", _out_elems(eqn)
    if name == "sort":
        n = _in_elems(eqn)
        return "sort", n * max(int(math.log2(max(n, 2))), 1)
    if name == "top_k":
        return "sort", _in_elems(eqn)
    if name in _SCANS:
        return "scan-prefix", _in_elems(eqn)
    if name in _REDUCE:
        return "reduce", _in_elems(eqn)
    if name in _GATHERISH:
        return "gather", 0
    if name in _SCATTERISH:
        # the combine runs once per UPDATE element (E for a segment_sum
        # into N bins), not per output element — take the largest operand
        largest = max(
            (_var_elems(v) for v in eqn.invars), default=_out_elems(eqn)
        )
        return "scatter", largest
    if name == "iota":
        return "materialize", 0
    if name in _MOVES:
        return "move", 0
    if name in _TRANSCENDENTAL:
        return "elementwise", 10 * _out_elems(eqn)
    if name in _SQRTISH:
        return "elementwise", 4 * _out_elems(eqn)
    # default: one VPU op per output element (add/mul/compare/...)
    return "elementwise", _out_elems(eqn)


def _leaf_eqns(jaxpr) -> Iterable[Any]:
    """Leaf (cost-bearing) equations of ``jaxpr``: container eqns (pjit,
    scan/while/cond bodies, shard_map...) are recursed into, not counted —
    their operands are exactly their body's operands, and counting both
    would double every byte.  Loop bodies are therefore counted ONCE: the
    model is per *step*, matching tier 2's census convention.

    Containment is decided by the ``_CONTAINERS`` allowlist, NOT by
    "carries a jaxpr param": primitives like ``scatter-add`` embed a tiny
    update jaxpr (one scalar add) while their real cost is the E-sized
    operand traffic of the eqn itself — recursing into those would erase
    exactly the segment_sum/scatter class this model exists to weigh.
    pallas_call is likewise a leaf (its body lives in VMEM, not HBM)."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            subs: list = []
            if eqn.primitive.name in _CONTAINERS:
                for v in eqn.params.values():
                    subs.extend(_iter_subjaxprs(v))
            if subs:
                stack.extend(subs)
            else:
                yield eqn


@dataclasses.dataclass
class CostSummary:
    """Static per-step cost model of one traced variant."""

    flops: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    comm_bytes: int = 0  # collective operand bytes (ICI, not HBM)
    materialized_bytes: int = 0  # iota/broadcast expansion + closed consts
    callback_eqns: int = 0
    eqns: int = 0
    classes: dict = dataclasses.field(default_factory=dict)

    @property
    def hbm_bytes(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1)

    def to_dict(self) -> dict:
        top = sorted(
            self.classes.items(),
            key=lambda kv: kv[1]["bytes"],
            reverse=True,
        )
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "intensity": round(self.intensity, 6),
            "comm_bytes": self.comm_bytes,
            "materialized_bytes": self.materialized_bytes,
            "callback_eqns": self.callback_eqns,
            "eqns": self.eqns,
            "classes": {k: v for k, v in top},
        }


def summarize_jaxpr(closed) -> CostSummary:
    """Walk a ClosedJaxpr and accumulate the static cost model."""
    import numpy as np

    s = CostSummary()
    for const in closed.consts:
        dtype = getattr(const, "dtype", None)
        shape = getattr(const, "shape", None)
        if dtype is None or shape is None:
            continue
        n = 1
        for dim in shape:
            n *= int(dim)
        s.materialized_bytes += n * np.dtype(dtype).itemsize
    for eqn in _leaf_eqns(closed.jaxpr):
        cls, flops = classify_eqn(eqn)
        read = sum(_aval_bytes(v) for v in eqn.invars)
        written = sum(_aval_bytes(v) for v in eqn.outvars)
        s.eqns += 1
        s.flops += flops
        s.bytes_read += read
        s.bytes_written += written
        if cls == "comm":
            s.comm_bytes += read
        if cls == "callback":
            s.callback_eqns += 1
        if cls == "materialize" or (
            eqn.primitive.name in _MATERIALIZE and written > read
        ):
            s.materialized_bytes += written
        c = s.classes.setdefault(cls, {"eqns": 0, "flops": 0, "bytes": 0})
        c["eqns"] += 1
        c["flops"] += flops
        c["bytes"] += read + written
    return s


# --------------------------------------------------------------------------
# baseline provenance
# --------------------------------------------------------------------------


def baseline_backend(path: Path) -> str | None:
    """Backend stamp of the cost baseline artifact (``"tpu"``, ``"cpu"``,
    or None when the artifact is missing/unreadable/unstamped) — the same
    reader the write-time provenance guard uses, so the two can never
    disagree about a stamp."""
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.artifacts import (
        read_backend,
    )

    return read_backend(path)


# --------------------------------------------------------------------------
# the tier-3 analyzer
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CostResult:
    """Tier-3 output: gating findings, non-gating advisories (intensity
    regressions while the cost baseline is not TPU-measured), and the full
    per-entry cost report for ``--cost-report``."""

    findings: list[Finding]
    advisories: list[Finding]
    report: dict

    @property
    def ok(self) -> bool:
        return not self.findings


def _analyze_entry_cost(
    ep: EntryPoint, root: Path, enforce_intensity: bool
) -> tuple[list[Finding], list[Finding], dict]:
    import jax

    findings: list[Finding] = []
    advisories: list[Finding] = []
    report: dict = {"entry": ep.name, "variants": {}}

    def add(rule: str, message: str, t: Traceable | None,
            advisory: bool = False) -> None:
        if rule in ep.suppress:
            return
        path, line, snippet = _anchor_location(ep, t, root)
        f = Finding(rule=rule, path=path, line=line, col=0,
                    message=f"[{ep.name}] {message}", snippet=snippet)
        (advisories if advisory else findings).append(f)

    try:
        t = build_traceable(ep)
    except Exception as exc:
        add("cost-entry-broken",
            f"entry point failed to build: {type(exc).__name__}: {exc}", None)
        return findings, advisories, report

    # ---- trace once per distinct signature; model each
    sigs: dict[tuple, tuple[str, tuple]] = {}
    for label, args in t.variants:
        sigs.setdefault(_trace_signature(jax, args), (label, args))
    worst: tuple[float, str] | None = None  # (intensity, label)
    for label, args in sigs.values():
        try:
            closed = jax.make_jaxpr(t.fn)(*args)
        except Exception as exc:
            add("cost-entry-broken",
                f"tracing variant {label!r} failed: "
                f"{type(exc).__name__}: {exc}", t)
            return findings, advisories, report
        summary = summarize_jaxpr(closed)
        report["variants"][label] = summary.to_dict()
        if worst is None or summary.intensity < worst[0]:
            worst = (summary.intensity, label)

    # ---- intensity-floor (ratchet; advisory without a TPU baseline)
    if ep.intensity_floor is not None and worst is not None:
        report["intensity_floor"] = ep.intensity_floor
        if worst[0] < ep.intensity_floor:
            add(
                "intensity-floor",
                f"static arithmetic intensity {worst[0]:.4f} flop/byte in "
                f"variant {worst[1]!r} fell below the declared floor "
                f"{ep.intensity_floor} — the step got more memory-bound"
                + ("" if enforce_intensity else
                   f" [ADVISORY: {COST_BASELINE_ARTIFACT} is not "
                   "TPU-measured; re-run the cost tools on a real TPU to "
                   "arm this gate]"),
                t,
                advisory=not enforce_intensity,
            )

    # ---- pad-frac-budget (static plan analyzer; backend-independent)
    if ep.pad_plan is not None:
        try:
            plan_points = list(ep.pad_plan())
        except Exception as exc:
            add("cost-entry-broken",
                f"pad plan failed: {type(exc).__name__}: {exc}", t)
            plan_points = []
        report["pad_plan"] = {lbl: round(frac, 4) for lbl, frac in plan_points}
        if ep.pad_frac_ceiling is not None and plan_points:
            report["pad_frac_ceiling"] = ep.pad_frac_ceiling
            worst_pad = max(plan_points, key=lambda p: p[1])
            if worst_pad[1] > ep.pad_frac_ceiling:
                add(
                    "pad-frac-budget",
                    f"static pad_frac {worst_pad[1]:.4f} at plan point "
                    f"{worst_pad[0]!r} exceeds the declared ceiling "
                    f"{ep.pad_frac_ceiling} — more than the budgeted "
                    "fraction of dispatched work is padding",
                    t,
                )

    # ---- donation-contract (lowered input/output aliasing verifier)
    if ep.donate is not None:
        label, args = t.variants[0]
        fn = t.donate_fn if t.donate_fn is not None else t.fn
        kwargs = dict(t.donate_kwargs or {})
        # jax drops donation from the lowering while debug_nans/debug_infs
        # are on (the NaN re-run needs the inputs alive).  Production never
        # runs with them; the test env does — lower with both off so the
        # verifier sees the aliasing production gets.
        dbg = [("jax_debug_nans", jax.config.jax_debug_nans),
               ("jax_debug_infs", jax.config.jax_debug_infs)]
        for knob, _ in dbg:
            jax.config.update(knob, False)
        try:
            if not hasattr(fn, "lower"):
                fn = jax.jit(fn)
            lowered = fn.lower(*args, **kwargs)
            text = lowered.as_text()
        except Exception as exc:
            add("cost-entry-broken",
                f"lowering variant {label!r} for the donation check "
                f"failed: {type(exc).__name__}: {exc}", t)
        else:
            expected = sum(
                len(jax.tree_util.tree_leaves(args[i])) for i in ep.donate
            )
            actual = text.count("tf.aliasing_output")
            report["donation"] = {"declared_buffers": expected,
                                  "aliased_buffers": actual}
            if actual < expected:
                add(
                    "donation-contract",
                    f"declares donate argnums {list(ep.donate)} "
                    f"({expected} buffer(s)) but the lowered computation "
                    f"aliases only {actual} input buffer(s) — the donation "
                    "does not happen (missing donate_argnums, or a "
                    "shape/dtype mismatch makes the donated buffer "
                    "unusable)",
                    t,
                )
            elif actual > expected:
                add(
                    "donation-contract",
                    f"lowered computation aliases {actual} input buffer(s) "
                    f"but the registry declares {expected} — an undeclared "
                    "donation; callers re-invoking with a consumed buffer "
                    "will fail on backends with real donation",
                    t,
                )
        finally:
            for knob, value in dbg:
                jax.config.update(knob, value)
    return findings, advisories, report


def run_cost(
    root: Path | None = None,
    entries: Sequence[EntryPoint] | None = None,
    only_modules: set[str] | None = None,
    baseline_path: Path | None = None,
) -> CostResult:
    """Run the tier-3 static cost analysis.

    Same restriction contract as :func:`semantic.run_semantic`:
    ``only_modules`` limits the run to entries whose module/watch set
    intersects it.  ``baseline_path`` overrides the cost baseline artifact
    whose backend stamp decides whether the intensity ratchet gates
    (TPU-measured) or advises (anything else).
    """
    from page_rank_and_tfidf_using_apache_spark_tpu.analysis.engine import repo_root

    root = root or repo_root()
    ensure_cpu_tracing_env()
    bl_path = baseline_path or (root / COST_BASELINE_ARTIFACT)
    backend = baseline_backend(bl_path)
    enforce_intensity = backend == "tpu"
    findings: list[Finding] = []
    advisories: list[Finding] = []
    report: dict = {
        "baseline_artifact": str(bl_path),
        "baseline_backend": backend,
        "intensity_gate": "enforcing" if enforce_intensity else "advisory",
        "entries": [],
    }
    for ep in entries if entries is not None else ENTRY_POINTS:
        if only_modules is not None and not (
            {ep.module, *ep.watch} & only_modules
        ):
            continue
        f, a, rep = _analyze_entry_cost(ep, root, enforce_intensity)
        findings.extend(f)
        advisories.extend(a)
        report["entries"].append(rep)
    return CostResult(
        findings=assign_fingerprints(findings),
        advisories=assign_fingerprints(advisories),
        report=report,
    )
