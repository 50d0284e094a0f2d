"""graftlint tier 2: jaxpr-level semantic analysis of registered jit entry
points.

The lexical tier (rules.py) sees source text; this tier sees what JAX
*traces*.  Every :class:`~.registry.EntryPoint` is traced with
``jax.make_jaxpr`` on the CPU backend from abstract ``ShapeDtypeStruct``
inputs — no FLOPs, no device transfers, a few hundred ms per entry — and
four invariants are checked against the entry's declared budgets:

- **recompile-per-shape** — the entry's shape matrix (raw workload sizes
  run through the caller's real padding/bucketing policy) must collapse to
  at most ``max_compiles`` distinct trace signatures.  More means
  unpadded/unbucketed shapes reach jit and production recompiles per
  shape (the failure class that RTT-bound round 5's streaming bench).
- **implicit-promotion** — traced under ``enable_x64`` with inputs pinned
  f32/i32, the jaxpr must contain no 64-bit aval anywhere (equation
  outputs or closed-over consts).  A hit means an unpinned constructor or
  a weak-type widening that makes CPU-test (x64 on) and TPU-prod (x64
  off) execute different dtypes.
- **transfer-census** — host-callback equations (``pure_callback`` /
  ``io_callback`` / ``debug_callback`` / ``debug_print`` / infeed /
  outfeed) per traced
  step, gated against ``transfer_budget`` (default 0: a compiled step
  must never round-trip to host — closing the loop the lexical
  ``unguarded-host-sync`` rule opened).
- **sharding-axis** — every collective's axis names must be declared in
  the entry's ``axes``, and the static count of communication equations
  per step must not exceed ``collective_budget`` (communication volume is
  gated at lint time, not discovered in a timed-out bench).

A registry entry that no longer builds/traces is itself a finding
(``entry-point-broken``): the registry is a contract, not a best effort.

Findings flow through the same fingerprint/baseline/ratchet machinery as
tier 1 — one baseline file, one gate.
"""

from __future__ import annotations

import inspect
import os
import sys
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

SEMANTIC_RULES: dict[str, str] = {
    "recompile-per-shape": (
        "shape matrix produces more distinct jit trace signatures than the "
        "entry's max_compiles — unpadded/unbucketed shapes reach jit"
    ),
    "implicit-promotion": (
        "64-bit aval inside a jaxpr traced under x64 from pinned f32/i32 "
        "inputs — an unpinned ctor or weak-type widening drifts dtypes "
        "between CPU tests and TPU production"
    ),
    "transfer-census": (
        "host-callback eqns per traced step exceed the entry's transfer "
        "budget — a compiled step must not round-trip to host"
    ),
    "sharding-axis": (
        "collective axis names outside the entry's declared mesh axes, or "
        "more communication eqns per step than its collective budget"
    ),
    "entry-point-broken": (
        "a registered jit entry point no longer builds or traces — the "
        "registry contract is stale"
    ),
    "collective-uniformity": (
        "a collective (psum/ppermute/all_gather/...) nested under a "
        "cond/while whose predicate depends on shard-varying operands — "
        "shards disagree about executing the collective, which is a "
        "deadlock on real hardware that CPU testing cannot reproduce"
    ),
}

# Primitives that cross the host boundary from inside a compiled program.
_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "callback",
        "infeed",
        "outfeed",
    }
)

# Communication primitives (what collective_budget counts).  axis_index is
# checked for axis-name consistency but costs no bytes, so it is excluded
# from the budget.
_COMM_PRIMS = frozenset(
    {
        "psum",
        "pmax",
        "pmin",
        "ppermute",
        "all_gather",
        "all_to_all",
        "psum_scatter",
        "reduce_scatter",
    }
)
_AXIS_PRIMS = _COMM_PRIMS | {"axis_index"}

# Collectives whose OUTPUT is identical on every shard of the reduced
# axis: a predicate derived from one of these is uniform again, so the
# canonical `while err > tol` fixpoint (err = psum of shard residuals)
# stays clean under the collective-uniformity check.
_UNIFORMIZING_PRIMS = frozenset({"psum", "pmax", "pmin", "all_gather"})


def ensure_cpu_tracing_env() -> None:
    """Pin tracing to the CPU backend with simulated devices.

    Must run before the first ``import jax`` to take full effect; when jax
    is already imported (pytest, an embedding process) the config API still
    forces the platform, and the mesh builders adapt to however many
    devices exist.
    """
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # already fixed by a plugin; tracing still works
        pass


def _iter_subjaxprs(value: Any) -> Iterable[Any]:
    if hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):  # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):  # raw Jaxpr
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _iter_subjaxprs(item)


def walk_eqns(jaxpr) -> list:
    """Every equation in ``jaxpr`` and its nested sub-jaxprs (pjit bodies,
    scan/while/cond branches, shard_map bodies ...)."""
    out: list = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            out.append(eqn)
            for v in eqn.params.values():
                stack.extend(_iter_subjaxprs(v))
    return out


def _is_literal(v) -> bool:
    return hasattr(v, "val")  # jax.core.Literal; Vars carry no .val


def _inner_jaxpr(value):
    return value.jaxpr if hasattr(value, "jaxpr") else value


def _subtree_comm_names(jaxpr) -> set[str]:
    return {
        e.primitive.name
        for e in walk_eqns(jaxpr)
        if e.primitive.name in _COMM_PRIMS
    }


def _propagate_varying(jaxpr, in_varying: list, in_shard: bool,
                       record) -> list:
    """Abstract interpretation of shard-varying-ness over ``jaxpr``.

    ``in_varying`` aligns with ``jaxpr.invars`` (True = the value may
    differ between shards).  Uniformizing collectives (psum/pmax/pmin/
    all_gather) launder varying-ness; ppermute/all_to_all/scatter
    variants and everything data-dependent propagate it.  Entering a
    ``shard_map`` body seeds every invar varying and arms ``in_shard``.
    At each ``cond``/``while`` met while armed, ``record(ctrl, comms,
    pred_varying)`` is called with the collectives its subtree contains
    — a varying predicate over a collective-bearing subtree is the
    deadlock this check exists for.  Conservative on unknown structure:
    unmatched sub-jaxpr arities degrade to any-in → all-varying, never
    to silence."""
    jr = _inner_jaxpr(jaxpr)
    vmap: dict = {}
    for v, tainted in zip(jr.invars, in_varying):
        vmap[v] = bool(tainted)
    for cv in jr.constvars:
        vmap[cv] = False  # closed-over consts are replicated

    def val(v) -> bool:
        return False if _is_literal(v) else vmap.get(v, False)

    for eqn in jr.eqns:
        name = eqn.primitive.name
        ins = [val(v) for v in eqn.invars]
        any_in = any(ins)

        if name == "shard_map":
            inner = _inner_jaxpr(eqn.params.get("jaxpr"))
            if inner is not None and hasattr(inner, "eqns"):
                _propagate_varying(
                    inner, [True] * len(inner.invars), True, record)
            for ov in eqn.outvars:  # per-shard results: varying
                vmap[ov] = True
            continue

        if name == "cond":
            pred_varying = ins[0] if ins else False
            branches = [
                _inner_jaxpr(b) for b in eqn.params.get("branches", ())
            ]
            comms: set[str] = set()
            out_any = [False] * len(eqn.outvars)
            for b in branches:
                comms |= _subtree_comm_names(b)
                inner_in = ins[1:]
                if len(b.invars) != len(inner_in):
                    inner_in = [any_in] * len(b.invars)
                bouts = _propagate_varying(b, inner_in, in_shard, record)
                out_any = [
                    a or (bouts[i] if i < len(bouts) else any_in)
                    for i, a in enumerate(out_any)
                ]
            if in_shard and comms:
                record("cond", comms, pred_varying)
            for ov, tainted in zip(eqn.outvars, out_any):
                vmap[ov] = tainted or pred_varying
            continue

        if name == "while":
            cj = _inner_jaxpr(eqn.params["cond_jaxpr"])
            bj = _inner_jaxpr(eqn.params["body_jaxpr"])
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            cond_consts = ins[:cn]
            body_consts = ins[cn:cn + bn]
            carry = list(ins[cn + bn:])
            # fixpoint: body may widen carry varying-ness across trips
            for _ in range(len(carry) + 2):
                bouts = _propagate_varying(
                    bj, body_consts + carry, in_shard, lambda *a: None)
                if len(bouts) != len(carry):
                    bouts = [any(bouts) or any_in] * len(carry)
                widened = [c or b for c, b in zip(carry, bouts)]
                if widened == carry:
                    break
                carry = widened
            couts = _propagate_varying(
                cj, cond_consts + carry, in_shard, record)
            pred_varying = any(couts)
            comms = _subtree_comm_names(cj) | _subtree_comm_names(bj)
            if in_shard and comms:
                record("while", comms, pred_varying)
            # recurse once more with the real recorder for NESTED ctrl
            _propagate_varying(bj, body_consts + carry, in_shard, record)
            for ov, tainted in zip(eqn.outvars, carry):
                vmap[ov] = tainted or pred_varying
            continue

        subs = []
        for v in eqn.params.values():
            subs.extend(_iter_subjaxprs(v))
        if subs:
            souts: list = []
            for sj in subs:
                inner_in = (
                    ins if len(sj.invars) == len(eqn.invars)
                    else [any_in] * len(sj.invars)
                )
                souts = _propagate_varying(sj, inner_in, in_shard, record)
            if len(subs) == 1 and len(souts) == len(eqn.outvars):
                for ov, tainted in zip(eqn.outvars, souts):
                    vmap[ov] = tainted
                continue
        out_val = False if name in _UNIFORMIZING_PRIMS else any_in
        for ov in eqn.outvars:
            vmap[ov] = out_val
    return [val(v) for v in jr.outvars]


def _divergent_collectives(closed_jaxpr) -> set:
    """``(ctrl, comm-primitive)`` pairs for every collective nested under
    a ``cond``/``while`` (inside a shard_map scope) whose predicate the
    varying-ness propagation marks shard-varying."""
    hits: set = set()

    def record(ctrl: str, comms: set, pred_varying: bool) -> None:
        if pred_varying:
            for c in sorted(comms):
                hits.add((ctrl, c))

    jr = _inner_jaxpr(closed_jaxpr)
    _propagate_varying(jr, [False] * len(jr.invars), False, record)
    return hits


def _sixty_four_bit(dtype) -> bool:
    import numpy as np

    try:
        return np.dtype(dtype).itemsize == 8
    except TypeError:
        return False


def _aval_dtype(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def _trace_signature(jax, args: tuple) -> tuple:
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(args)
    )


def _eqn_axis_names(eqn) -> set[str]:
    names: set[str] = set()
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if isinstance(v, str):
            names.add(v)
        elif isinstance(v, (list, tuple)):
            names.update(x for x in v if isinstance(x, str))
    return names


def _anchor_location(ep: EntryPoint, t: Traceable | None, root: Path) -> tuple[str, int, str]:
    """(repo-relative path, line, snippet) findings for this entry carry.
    Anchored at the entry's public function so fingerprints survive registry
    reshuffles; falls back to the declared module at line 1."""
    anchor = None
    if t is not None:
        anchor = t.anchor or t.fn
    path, line = ep.module, 1
    if anchor is not None:
        target = inspect.unwrap(anchor)
        try:
            src = Path(inspect.getsourcefile(target) or "")
            _, line = inspect.getsourcelines(target)
            path = src.resolve().relative_to(root.resolve()).as_posix()
        except (TypeError, OSError, ValueError):
            path, line = ep.module, 1
    snippet = ""
    full = root / path
    if full.exists():
        lines = full.read_text(encoding="utf-8").splitlines()
        if 1 <= line <= len(lines):
            snippet = lines[line - 1].strip()
    return path, line, snippet


def _x64_context():
    import jax

    return jax.enable_x64(True)


def _analyze_entry(ep: EntryPoint, root: Path) -> list[Finding]:
    import jax

    findings: list[Finding] = []

    def add(rule: str, message: str, t: Traceable | None) -> None:
        if rule in ep.suppress:
            return
        path, line, snippet = _anchor_location(ep, t, root)
        findings.append(
            Finding(
                rule=rule,
                path=path,
                line=line,
                col=0,
                message=f"[{ep.name}] {message}",
                snippet=snippet,
            )
        )

    try:
        t = build_traceable(ep)
    except Exception as exc:  # registry drifted from the code
        add(
            "entry-point-broken",
            f"entry point failed to build: {type(exc).__name__}: {exc}",
            None,
        )
        return findings

    # ---- recompile-per-shape: distinct signatures across the matrix
    sigs: dict[tuple, tuple[str, tuple]] = {}
    for label, args in t.variants:
        sigs.setdefault(_trace_signature(jax, args), (label, args))
    if len(sigs) > ep.max_compiles:
        labels = sorted(label for label, _ in sigs.values())
        add(
            "recompile-per-shape",
            f"{len(t.variants)} declared workload shapes produce "
            f"{len(sigs)} distinct jit signatures (budget "
            f"{ep.max_compiles}): {', '.join(labels)} — pad/bucket the "
            "shapes feeding this entry point",
            t,
        )

    # ---- trace once per distinct signature; pool the jaxpr-level checks
    promo: set[tuple[str, str]] = set()
    worst_transfers: tuple[int, str] = (0, "")
    worst_comms: tuple[int, str] = (0, "")
    comm_counts: dict[str, int] = {}
    undeclared_axes: set[str] = set()
    divergent: dict[tuple, str] = {}  # (ctrl, comm) -> first variant label
    for label, args in sigs.values():
        try:
            with _x64_context():
                closed = jax.make_jaxpr(t.fn)(*args)
        except Exception as exc:
            add(
                "entry-point-broken",
                f"tracing variant {label!r} failed: {type(exc).__name__}: {exc}",
                t,
            )
            return findings
        eqns = walk_eqns(closed.jaxpr)

        if not ep.allow_64bit:
            for const in closed.consts:
                dt = getattr(const, "dtype", None)
                if dt is not None and _sixty_four_bit(dt):
                    promo.add(("const", str(dt)))
            for eqn in eqns:
                for v in eqn.outvars:
                    dt = _aval_dtype(v)
                    if dt is not None and _sixty_four_bit(dt):
                        promo.add((eqn.primitive.name, str(dt)))

        transfers = sum(1 for e in eqns if e.primitive.name in _CALLBACK_PRIMS)
        if transfers > worst_transfers[0]:
            worst_transfers = (transfers, label)

        comms = 0
        for eqn in eqns:
            if eqn.primitive.name in _AXIS_PRIMS:
                undeclared_axes.update(_eqn_axis_names(eqn) - set(ep.axes))
            if eqn.primitive.name in _COMM_PRIMS:
                comms += 1
                comm_counts[eqn.primitive.name] = (
                    comm_counts.get(eqn.primitive.name, 0) + 1
                )
        if comms > worst_comms[0]:
            worst_comms = (comms, label)

        if ep.axes:  # sharded entries only: uniformity is a mesh property
            for pair in _divergent_collectives(closed.jaxpr):
                divergent.setdefault(pair, label)

    if promo:
        detail = ", ".join(f"{p}:{d}" for p, d in sorted(promo))
        add(
            "implicit-promotion",
            f"64-bit avals under x64 tracing from pinned 32-bit inputs: "
            f"{detail} — pin dtypes (dtype=jnp.int32/float32) at the "
            "flagged constructors",
            t,
        )

    if worst_transfers[0] > ep.transfer_budget:
        add(
            "transfer-census",
            f"{worst_transfers[0]} host-callback eqn(s) per step in variant "
            f"{worst_transfers[1]!r} (budget {ep.transfer_budget}) — a "
            "compiled step must not round-trip to host; hoist the callback "
            "out of the jit region or raise the budget with a review",
            t,
        )

    if undeclared_axes:
        add(
            "sharding-axis",
            f"collective axis name(s) {sorted(undeclared_axes)} not in the "
            f"declared mesh axes {list(ep.axes)} — the program and the "
            "registry disagree about the mesh contract",
            t,
        )
    if divergent:
        detail = ", ".join(
            f"{comm} under {ctrl} (variant {lbl!r})"
            for (ctrl, comm), lbl in sorted(divergent.items())
        )
        add(
            "collective-uniformity",
            f"collective(s) nested under shard-divergent control flow: "
            f"{detail} — shards disagree about executing the collective; "
            "on TPU this deadlocks the mesh (JAMPI's barrier-execution "
            "argument). Hoist the collective out of the branch/loop or "
            "make the predicate uniform (reduce it with psum/pmax first)",
            t,
        )

    if ep.collective_budget is not None and worst_comms[0] > ep.collective_budget:
        detail = ", ".join(f"{k}×{v}" for k, v in sorted(comm_counts.items()))
        add(
            "sharding-axis",
            f"{worst_comms[0]} communication eqn(s) per step in variant "
            f"{worst_comms[1]!r} (budget {ep.collective_budget}; {detail}) "
            "— extra collectives entered the step; fuse or re-budget with "
            "a review",
            t,
        )
    return findings


def run_semantic(
    root: Path | None = None,
    entries: Sequence[EntryPoint] | None = None,
    only_modules: set[str] | None = None,
) -> list[Finding]:
    """Trace and check registered entry points; returns fingerprinted
    findings (empty list == tier 2 clean).

    ``only_modules`` (repo-relative paths) restricts the run to entries
    whose contracted module — or any module on its ``watch`` list (shape
    policies, mesh constants) — is in the set: the ``--changed-only`` fast
    path.  When any ``analysis/`` file changed, pass None: the checker
    itself changed, so every contract gets re-verified.
    """
    from page_rank_and_tfidf_using_apache_spark_tpu.analysis.engine import repo_root

    root = root or repo_root()
    ensure_cpu_tracing_env()
    findings: list[Finding] = []
    for ep in entries if entries is not None else ENTRY_POINTS:
        if only_modules is not None and not (
            {ep.module, *ep.watch} & only_modules
        ):
            continue
        findings.extend(_analyze_entry(ep, root))
    return assign_fingerprints(findings)
