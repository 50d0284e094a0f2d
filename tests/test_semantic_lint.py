"""graftlint tier-2 (semantic / jaxpr-level) tests — ISSUE 3.

Mirrors the tier-1 test structure: for each semantic check a true positive
(a seeded EntryPoint that must fire), a true negative (the fixed shape must
stay quiet), and a suppressed positive (registry-level ``suppress`` must
silence it).  Fixture entry points are tiny synthetic programs traced the
same way the real registry entries are.

The regression layer at the bottom is the CI gate: every registered entry
point must build, trace on the CPU backend, and produce ZERO findings —
the tier-2 ratchet stays empty, matching ISSUE 3's acceptance bar.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.analysis import repo_root
from page_rank_and_tfidf_using_apache_spark_tpu.analysis import semantic
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.engine import (
    changed_python_files,
)
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.registry import (
    ENTRY_POINTS,
    EntryPoint,
    Traceable,
)

REPO = repo_root()


def run_entries(*entries: EntryPoint):
    return semantic.run_semantic(root=REPO, entries=list(entries))


def rules_hit(findings) -> set[str]:
    return {f.rule for f in findings}


def _sds(shape, dtype=None):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct(shape, dtype or np.float32)


# ------------------------------------------------------ recompile-per-shape


def _build_unpadded():
    """Raw workload sizes straight into jit: one compile per shape."""

    def f(x):
        return x * 2.0

    return Traceable(f, [(f"n{n}", (_sds((n,)),)) for n in (100, 177, 256)])


def _build_padded():
    """The same sizes through a pow2 padding policy: one compile."""

    def f(x):
        return x * 2.0

    return Traceable(f, [(f"n{n}", (_sds((256,)),)) for n in (100, 177, 256)])


def test_recompile_true_positive():
    ep = EntryPoint(name="unpadded", module="x.py", build=_build_unpadded)
    findings = run_entries(ep)
    assert "recompile-per-shape" in rules_hit(findings)
    assert any("3 distinct jit signatures" in f.message for f in findings)


def test_recompile_true_negative():
    ep = EntryPoint(name="padded", module="x.py", build=_build_padded)
    assert "recompile-per-shape" not in rules_hit(run_entries(ep))


def test_recompile_suppressed():
    ep = EntryPoint(
        name="unpadded",
        module="x.py",
        build=_build_unpadded,
        suppress=frozenset({"recompile-per-shape"}),
    )
    assert "recompile-per-shape" not in rules_hit(run_entries(ep))


# ------------------------------------------------------- implicit-promotion


def _build_promoting():
    """Unpinned iota: int64 under x64 — the count_pairs bug class this PR
    fixed (jnp.lexsort / bare jnp.arange inside the TF sort kernel)."""

    def f(x):
        import jax.numpy as jnp

        return x * jnp.arange(x.shape[0])

    return Traceable(f, [("v", (_sds((16,)),))])


def _build_pinned():
    def f(x):
        import jax.numpy as jnp

        return x * jnp.arange(x.shape[0], dtype=jnp.int32)

    return Traceable(f, [("v", (_sds((16,)),))])


def test_promotion_true_positive():
    ep = EntryPoint(name="promo", module="x.py", build=_build_promoting)
    findings = [f for f in run_entries(ep) if f.rule == "implicit-promotion"]
    assert findings and "int64" in findings[0].message


def test_promotion_true_negative():
    ep = EntryPoint(name="pinned", module="x.py", build=_build_pinned)
    assert "implicit-promotion" not in rules_hit(run_entries(ep))


def test_promotion_suppressed_by_allow_64bit():
    ep = EntryPoint(
        name="promo", module="x.py", build=_build_promoting, allow_64bit=True
    )
    assert "implicit-promotion" not in rules_hit(run_entries(ep))


def test_promotion_suppress_set():
    ep = EntryPoint(
        name="promo",
        module="x.py",
        build=_build_promoting,
        suppress=frozenset({"implicit-promotion"}),
    )
    assert "implicit-promotion" not in rules_hit(run_entries(ep))


# --------------------------------------------------------- transfer-census


def _build_callbacking():
    def f(x):
        import jax

        jax.debug.print("x = {x}", x=x)
        return x + 1.0

    return Traceable(f, [("v", (_sds((8,)),))])


def _build_pure():
    def f(x):
        return x + 1.0

    return Traceable(f, [("v", (_sds((8,)),))])


def test_transfer_true_positive():
    ep = EntryPoint(name="xfer", module="x.py", build=_build_callbacking)
    findings = [f for f in run_entries(ep) if f.rule == "transfer-census"]
    assert findings and "budget 0" in findings[0].message


def test_transfer_true_negative():
    ep = EntryPoint(name="clean", module="x.py", build=_build_pure)
    assert "transfer-census" not in rules_hit(run_entries(ep))


def test_transfer_within_budget():
    ep = EntryPoint(
        name="xfer", module="x.py", build=_build_callbacking, transfer_budget=1
    )
    assert "transfer-census" not in rules_hit(run_entries(ep))


def test_transfer_suppressed():
    ep = EntryPoint(
        name="xfer",
        module="x.py",
        build=_build_callbacking,
        suppress=frozenset({"transfer-census"}),
    )
    assert "transfer-census" not in rules_hit(run_entries(ep))


# ----------------------------------------------------------- sharding-axis


def _shard_mapped_psum(axis_in_mesh: str, axis_in_code: str):
    def build():
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices("cpu")[:1]), (axis_in_mesh,))

        def kernel(x):
            return jax.lax.psum(x, axis_in_code)

        mapped = shard_map(
            kernel, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
        )
        return Traceable(mapped, [("v", (_sds((8,)),))])

    return build


def test_sharding_axis_true_positive():
    ep = EntryPoint(
        name="ax",
        module="x.py",
        build=_shard_mapped_psum("data", "data"),
        axes=("nodes",),  # registry contract says nodes; program says data
    )
    findings = [f for f in run_entries(ep) if f.rule == "sharding-axis"]
    assert findings and "'data'" in findings[0].message


def test_sharding_axis_true_negative():
    ep = EntryPoint(
        name="ax",
        module="x.py",
        build=_shard_mapped_psum("nodes", "nodes"),
        axes=("nodes",),
        collective_budget=1,
    )
    assert "sharding-axis" not in rules_hit(run_entries(ep))


def test_collective_budget_true_positive():
    ep = EntryPoint(
        name="ax",
        module="x.py",
        build=_shard_mapped_psum("nodes", "nodes"),
        axes=("nodes",),
        collective_budget=0,
    )
    findings = [f for f in run_entries(ep) if f.rule == "sharding-axis"]
    assert findings and "communication eqn" in findings[0].message


def test_sharding_axis_suppressed():
    ep = EntryPoint(
        name="ax",
        module="x.py",
        build=_shard_mapped_psum("data", "data"),
        axes=("nodes",),
        collective_budget=0,
        suppress=frozenset({"sharding-axis"}),
    )
    assert "sharding-axis" not in rules_hit(run_entries(ep))


# ----------------------------------------------- collective-uniformity


def _shard_divergent(ctrl: str, uniform: bool):
    """A shard_mapped program whose ``ctrl`` (cond/while) wraps a psum.
    ``uniform=True`` reduces the predicate with a psum first (the owned
    fixpoint idiom) — globally identical by construction; False leaves
    it shard-varying: some shards would enter the collective, the rest
    never arrive."""

    def build():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("nodes",))

        def kernel(x):
            if ctrl == "cond":
                resid = jnp.sum(jnp.abs(x))
                if uniform:
                    resid = jax.lax.psum(resid, "nodes")
                return jax.lax.cond(
                    resid > 0.5,
                    lambda v: jax.lax.psum(v, "nodes"),
                    lambda v: v * 2.0,
                    x,
                )

            def cond_fn(c):
                resid = jnp.sum(jnp.abs(c))
                if uniform:
                    resid = jax.lax.psum(resid, "nodes")
                return resid > 0.5

            def body_fn(c):
                return jax.lax.psum(c, "nodes") * 0.25

            return jax.lax.while_loop(cond_fn, body_fn, x)

        mapped = shard_map(
            kernel, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
        )
        return Traceable(mapped, [("v", (_sds((8,)),))])

    return build


def test_collective_uniformity_tp_cond():
    ep = EntryPoint(
        name="div_cond",
        module="x.py",
        build=_shard_divergent("cond", uniform=False),
        axes=("nodes",),
        collective_budget=8,
    )
    findings = [f for f in run_entries(ep)
                if f.rule == "collective-uniformity"]
    assert findings and "psum under cond" in findings[0].message
    assert "Hoist" in findings[0].message


def test_collective_uniformity_tp_while():
    ep = EntryPoint(
        name="div_while",
        module="x.py",
        build=_shard_divergent("while", uniform=False),
        axes=("nodes",),
        collective_budget=8,
    )
    findings = [f for f in run_entries(ep)
                if f.rule == "collective-uniformity"]
    assert findings and "psum under while" in findings[0].message


def test_collective_uniformity_tn_reduced_cond_predicate():
    """A psum-reduced predicate is uniform by construction — the branch
    is taken identically on every shard, so the nested collective is
    safe.  This is the owned strategies' fixpoint idiom: they pass by
    analysis, not by exemption."""
    ep = EntryPoint(
        name="uni_cond",
        module="x.py",
        build=_shard_divergent("cond", uniform=True),
        axes=("nodes",),
        collective_budget=8,
    )
    assert "collective-uniformity" not in rules_hit(run_entries(ep))


def test_collective_uniformity_tn_reduced_while_predicate():
    ep = EntryPoint(
        name="uni_while",
        module="x.py",
        build=_shard_divergent("while", uniform=True),
        axes=("nodes",),
        collective_budget=8,
    )
    assert "collective-uniformity" not in rules_hit(run_entries(ep))


def test_collective_uniformity_suppressed():
    ep = EntryPoint(
        name="div_cond_ok",
        module="x.py",
        build=_shard_divergent("cond", uniform=False),
        axes=("nodes",),
        collective_budget=8,
        suppress=frozenset({"collective-uniformity"}),
    )
    assert "collective-uniformity" not in rules_hit(run_entries(ep))


def test_collective_uniformity_needs_declared_axes():
    """Unsharded entries (no ``axes`` contract) never run the uniformity
    walk — there is no mesh to diverge over."""
    ep = EntryPoint(
        name="unsharded",
        module="x.py",
        build=_shard_divergent("cond", uniform=False),
    )
    findings = run_entries(ep)
    assert "collective-uniformity" not in rules_hit(findings)


# ------------------------------------------------------- entry-point-broken


def test_broken_entry_is_a_finding():
    def build():
        raise ImportError("entry point moved")

    ep = EntryPoint(name="gone", module="x.py", build=build)
    findings = [f for f in run_entries(ep) if f.rule == "entry-point-broken"]
    assert findings and "ImportError" in findings[0].message


def test_untraceable_entry_is_a_finding():
    def build():
        def f(x):
            return x.nonexistent_attribute

        return Traceable(f, [("v", (_sds((4,)),))])

    ep = EntryPoint(name="sick", module="x.py", build=build)
    assert "entry-point-broken" in rules_hit(run_entries(ep))


# ------------------------------------------------------ the tier-2 CI gate


def test_registry_covers_every_jit_surface():
    """Each production jit surface keeps at least one registered contract."""
    modules = {ep.module for ep in ENTRY_POINTS}
    pkg = "page_rank_and_tfidf_using_apache_spark_tpu"
    assert f"{pkg}/ops/pagerank.py" in modules
    assert f"{pkg}/ops/tfidf.py" in modules
    assert f"{pkg}/parallel/pagerank_sharded.py" in modules
    assert f"{pkg}/parallel/tfidf_sharded.py" in modules
    assert f"{pkg}/dataflow/ppr.py" in modules
    assert f"{pkg}/dataflow/hits.py" in modules
    assert f"{pkg}/dataflow/components.py" in modules
    assert f"{pkg}/dataflow/bm25.py" in modules


def test_every_dataflow_jit_surface_is_registered():
    """ISSUE 9 CI gate: a module under dataflow/ that creates a jit entry
    point (lexically: any ``jax.jit`` use) without a registry entry — or
    at least a ``watch`` hook from one — fails tier-1.  A new workload
    cannot ship outside the tier-2 recompile/promotion/transfer gates and
    the tier-3 intensity/pad/donation budgets."""
    pkg = "page_rank_and_tfidf_using_apache_spark_tpu"
    covered = {ep.module for ep in ENTRY_POINTS}
    covered |= {w for ep in ENTRY_POINTS for w in ep.watch}
    missing = []
    for p in sorted((REPO / pkg / "dataflow").glob("*.py")):
        if "jax.jit" not in p.read_text(encoding="utf-8"):
            continue
        rel = f"{pkg}/dataflow/{p.name}"
        if rel not in covered:
            missing.append(rel)
    assert not missing, (
        f"dataflow modules with jit entry points but no analysis/registry.py "
        f"coverage: {missing} — declare an EntryPoint (see README 'Static "
        "analysis') before shipping the workload"
    )


def test_sharded_entries_trace_the_shrink_chain():
    """Every sharded entry declares one variant per device count on the
    elastic shrink chain (d, d/2, ..., 1) — the semantic gates must hold
    for the shrunk meshes a degraded run executes on, down to 1 device."""
    sharded = [
        ep for ep in ENTRY_POINTS
        if ep.name.startswith("pagerank_sharded")
        or ep.name == "tfidf_sharded_ingest"
    ]
    # edges/nodes_balanced/src/hybrid/owned + tfidf
    assert len(sharded) == 6
    for ep in sharded:
        t = ep.build()
        labels = [label for label, _ in t.variants]
        assert len(labels) >= 2, (ep.name, labels)
        assert any(label.endswith("-d1") or "d1-" in label for label in labels), (
            ep.name, labels,
        )
        assert len(labels) <= ep.max_compiles, (ep.name, labels)


def test_repo_semantic_clean():
    """Every registered entry point traces with ZERO findings — the tier-2
    ratchet stays empty (ISSUE 3 acceptance bar)."""
    findings = semantic.run_semantic(root=REPO)
    msg = "\n".join(f.render() + " :: " + f.message for f in findings)
    assert not findings, f"tier-2 findings (fix the code, not the gate):\n{msg}"


def test_semantic_findings_carry_real_anchors():
    """Findings must point at the entry's public function so the ratchet
    fingerprints survive registry refactors."""
    def build():
        import functools

        from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as ops

        fn = functools.partial(ops.chunk_counts, vocab=64)
        return Traceable(
            fn,
            [(f"n{n}", (_sds((n,), "int32"), _sds((n,), "int32"),
                        _sds((n,), "bool"))) for n in (64, 96)],
            anchor=ops.chunk_counts,
        )

    ep = EntryPoint(
        name="unpadded",
        module="page_rank_and_tfidf_using_apache_spark_tpu/ops/tfidf.py",
        build=build,
        max_compiles=1,
    )
    findings = [f for f in run_entries(ep) if f.rule == "recompile-per-shape"]
    assert findings
    f = findings[0]
    assert f.path == "page_rank_and_tfidf_using_apache_spark_tpu/ops/tfidf.py"
    assert f.line > 1 and f.snippet


def test_only_modules_respects_watch_list():
    """--changed-only must re-trace an entry when a watched dependency
    (shape policy, mesh constants) changed, not just its own module."""
    ep = EntryPoint(
        name="unpadded",
        module="x.py",
        watch=("policy.py",),
        build=_build_unpadded,
    )
    hit = semantic.run_semantic(
        root=REPO, entries=[ep], only_modules={"policy.py"}
    )
    assert "recompile-per-shape" in rules_hit(hit)
    skipped = semantic.run_semantic(
        root=REPO, entries=[ep], only_modules={"unrelated.py"}
    )
    assert skipped == []


# ------------------------------------------------------------ CLI plumbing


def test_cli_tier2_clean():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis", "--tier", "2"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_list_entry_points():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis",
         "--list-entry-points"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    for ep in ENTRY_POINTS:
        assert ep.name in proc.stdout


def test_changed_only_mode(tmp_path):
    """--changed-only lints exactly the files changed vs the base ref."""
    repo = tmp_path / "r"
    repo.mkdir()
    subprocess.run(["git", "init", "-q"], cwd=repo, check=True)
    subprocess.run(["git", "-C", str(repo), "config", "user.email", "t@t"],
                   check=True)
    subprocess.run(["git", "-C", str(repo), "config", "user.name", "t"],
                   check=True)
    (repo / "clean.py").write_text("x = 1\n")
    subprocess.run(["git", "-C", str(repo), "add", "."], check=True)
    subprocess.run(["git", "-C", str(repo), "commit", "-qm", "seed"],
                   check=True)
    assert changed_python_files(repo, "HEAD") == []

    (repo / "clean.py").write_text("x = 2\n")
    (repo / "new.py").write_text("y = 3\n")
    (repo / "notes.txt").write_text("not python\n")
    changed = changed_python_files(repo, "HEAD")
    assert [p.name for p in changed] == ["clean.py", "new.py"]


def test_cli_changed_only_runs_clean():
    """On the real repo the changed-only gate must run end to end (rc 0/1,
    never a crash), and rc must be 0 when the full gate is 0."""
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis",
         "--changed-only", "HEAD", "--tier", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
