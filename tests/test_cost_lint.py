"""graftlint tier-3 (static cost model) tests — ISSUE 6.

Mirrors the tier-1/tier-2 test structure: for each tier-3 check a true
positive (a seeded EntryPoint that must fire), a true negative (the clean
shape must stay quiet), and a suppressed positive (registry-level
``suppress`` must silence it).  Then the regression layer the tentpole is
really about:

- the **static pad_frac analyzer**: the plan the linter budgets is the
  plan ``partition_graph`` materializes;
- the **buffer-donation verifier** must hold on the fixed fixpoint and
  ingest-carry runners (declared donations really alias in the lowering);
- the whole registry must produce ZERO tier-3 findings (empty ratchet),
  and the backend-provenance guard must keep a CPU run from overwriting a
  TPU-measured cost artifact.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.analysis import repo_root
from page_rank_and_tfidf_using_apache_spark_tpu.analysis import cost
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.registry import (
    ENTRY_POINTS,
    EntryPoint,
    Traceable,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils import artifacts

REPO = repo_root()


def _sds(shape, dtype=None):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct(shape, dtype or np.float32)


def _tpu_baseline(tmp_path: Path) -> Path:
    p = tmp_path / "cost_tpu.json"
    p.write_text(json.dumps({"backend": "tpu", "ops": {}}))
    return p


def _cpu_baseline(tmp_path: Path) -> Path:
    p = tmp_path / "cost_cpu.json"
    p.write_text(json.dumps({"backend": "cpu", "ops": {}}))
    return p


def run_entries(*entries: EntryPoint, baseline: Path | None = None):
    return cost.run_cost(root=REPO, entries=list(entries),
                         baseline_path=baseline)


def rules_hit(findings) -> set[str]:
    return {f.rule for f in findings}


# ---------------------------------------------------------- intensity-floor


def _build_memory_bound():
    """x + 1: one flop per element over 8 read + 8 written bytes — static
    intensity ~0.125, far under a floor of 1.0."""

    def f(x):
        return x + 1.0

    return Traceable(f, [("v", (_sds((1024,)),))])


def test_intensity_true_positive_with_tpu_baseline(tmp_path):
    ep = EntryPoint(name="membound", module="x.py",
                    build=_build_memory_bound, intensity_floor=1.0)
    res = run_entries(ep, baseline=_tpu_baseline(tmp_path))
    findings = [f for f in res.findings if f.rule == "intensity-floor"]
    assert findings and "below the declared floor" in findings[0].message
    assert not res.advisories


def test_intensity_advisory_with_cpu_baseline(tmp_path):
    """The provenance downgrade: xla_cost_tpu.json stamped backend=cpu
    (no chip record yet) must not gate — the same regression
    surfaces as a non-gating advisory instead."""
    ep = EntryPoint(name="membound", module="x.py",
                    build=_build_memory_bound, intensity_floor=1.0)
    res = run_entries(ep, baseline=_cpu_baseline(tmp_path))
    assert "intensity-floor" not in rules_hit(res.findings)
    adv = [f for f in res.advisories if f.rule == "intensity-floor"]
    assert adv and "ADVISORY" in adv[0].message
    assert res.ok


def test_intensity_true_negative(tmp_path):
    ep = EntryPoint(name="membound", module="x.py",
                    build=_build_memory_bound, intensity_floor=0.01)
    res = run_entries(ep, baseline=_tpu_baseline(tmp_path))
    assert "intensity-floor" not in rules_hit(res.findings + res.advisories)


def test_intensity_suppressed(tmp_path):
    ep = EntryPoint(name="membound", module="x.py",
                    build=_build_memory_bound, intensity_floor=1.0,
                    suppress=frozenset({"intensity-floor"}))
    res = run_entries(ep, baseline=_tpu_baseline(tmp_path))
    assert "intensity-floor" not in rules_hit(res.findings + res.advisories)


# ---------------------------------------------------------- pad-frac-budget


def _build_trivial():
    def f(x):
        return x * 2.0

    return Traceable(f, [("v", (_sds((16,)),))])


def test_pad_frac_true_positive():
    ep = EntryPoint(name="padded", module="x.py", build=_build_trivial,
                    pad_plan=lambda: [("d4", 0.62), ("d2", 0.10)],
                    pad_frac_ceiling=0.25)
    res = run_entries(ep)
    findings = [f for f in res.findings if f.rule == "pad-frac-budget"]
    assert findings and "0.6200" in findings[0].message
    assert "'d4'" in findings[0].message  # attributes the worst plan point


def test_pad_frac_true_negative():
    ep = EntryPoint(name="padded", module="x.py", build=_build_trivial,
                    pad_plan=lambda: [("d4", 0.12)], pad_frac_ceiling=0.25)
    assert "pad-frac-budget" not in rules_hit(run_entries(ep).findings)


def test_pad_frac_suppressed():
    ep = EntryPoint(name="padded", module="x.py", build=_build_trivial,
                    pad_plan=lambda: [("d4", 0.62)], pad_frac_ceiling=0.25,
                    suppress=frozenset({"pad-frac-budget"}))
    assert "pad-frac-budget" not in rules_hit(run_entries(ep).findings)


# -------------------------------------------------------- donation-contract


def _build_undonated():
    """A carry-shaped program WITHOUT donate_argnums: the ingest-carry bug
    class this tier exists to catch."""

    def build():
        import jax

        f = jax.jit(lambda c, x: (c + x, x * 2.0))
        return Traceable(f, [("v", (_sds((8,)), _sds((8,))))])

    return build


def _build_donated():
    def build():
        import jax

        f = jax.jit(lambda c, x: (c + x, x * 2.0), donate_argnums=(0,))
        return Traceable(f, [("v", (_sds((8,)), _sds((8,))))])

    return build


def test_donation_declared_but_absent_is_a_finding():
    ep = EntryPoint(name="carry", module="x.py", build=_build_undonated(),
                    donate=(0,))
    findings = [f for f in run_entries(ep).findings
                if f.rule == "donation-contract"]
    assert findings and "does not happen" in findings[0].message


def test_donation_true_negative():
    ep = EntryPoint(name="carry", module="x.py", build=_build_donated(),
                    donate=(0,))
    res = run_entries(ep)
    assert "donation-contract" not in rules_hit(res.findings)


def test_undeclared_donation_is_a_finding():
    """The inverse direction: an aliased input the registry does not
    declare is a contract drift too (callers must know a buffer is
    consumed)."""
    ep = EntryPoint(name="carry", module="x.py", build=_build_donated(),
                    donate=())
    findings = [f for f in run_entries(ep).findings
                if f.rule == "donation-contract"]
    assert findings and "undeclared" in findings[0].message


def test_donation_unchecked_when_not_declared():
    ep = EntryPoint(name="carry", module="x.py", build=_build_donated())
    assert "donation-contract" not in rules_hit(run_entries(ep).findings)


def test_donation_suppressed():
    ep = EntryPoint(name="carry", module="x.py", build=_build_undonated(),
                    donate=(0,), suppress=frozenset({"donation-contract"}))
    assert "donation-contract" not in rules_hit(run_entries(ep).findings)


# --------------------------------------------------------- cost-entry-broken


def test_broken_entry_is_a_finding():
    def build():
        raise ImportError("entry point moved")

    ep = EntryPoint(name="gone", module="x.py", build=build)
    findings = [f for f in run_entries(ep).findings
                if f.rule == "cost-entry-broken"]
    assert findings and "ImportError" in findings[0].message


# ------------------------------------------- static plan vs the partition


def test_plan_is_what_partition_graph_materializes():
    """plan_partition and partition_graph cannot diverge: the materialized
    ShardedGraph carries exactly the planned pad_frac / widths."""
    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import (
        from_edges,
        synthetic_powerlaw,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        pagerank_sharded as ps,
    )

    powerlaw = synthetic_powerlaw(300, 2400, seed=5)
    # the same arcs weighted: no dense head
    weighted = from_edges(powerlaw.src, powerlaw.dst, compact_ids=False,
                          weight=np.linspace(0.5, 2.0, powerlaw.n_edges))
    for graph in (powerlaw, weighted):
        for d in (1, 2, 4):
            plan = ps.plan_partition(graph, d, strategy="hybrid")
            sg = ps.partition_graph(graph, d, strategy="hybrid")
            assert sg.pad_frac == plan.pad_frac, d
            assert sg.n_pad == plan.n_pad and sg.block == plan.block
            assert sg.src.shape == (d, plan.e_dev)
            head_k, w, rows, rows_dev = plan.head
            assert (head_k > 0) is (graph is powerlaw)
            assert sg.head_src.shape == (d, max(rows_dev, 1), max(w, 1))
            # every real (non-sentinel) head slot is one head edge
            real = int((sg.head_src != sg.n_pad).sum())
            assert real == graph.n_edges - int((sg.valid > 0).sum())


def test_stream_pad_plan_runs_the_real_cap_policy():
    """grow_chunk_cap doubling from a 2^14 start: caps 16384, 131072,
    131072, 131072 over the registry matrix — pad_frac ~0.127."""
    from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
        stream_pad_plan,
    )

    [(label, frac)] = stream_pad_plan((9_000, 120_000, 97_531, 131_072))
    assert label == "stream"
    total_raw = 9_000 + 120_000 + 97_531 + 131_072
    total_cap = 16_384 + 3 * 131_072
    assert frac == pytest.approx(1 - total_raw / total_cap, abs=1e-6)


# -------------------------------------------------- backend-provenance guard


def test_provenance_guard_refuses_cpu_over_tpu(tmp_path):
    p = tmp_path / "cost.json"
    artifacts.write_artifact(p, {"ops": {"x": 1}}, backend="tpu")
    assert artifacts.read_backend(p) == "tpu"
    with pytest.raises(artifacts.ProvenanceError, match="refusing"):
        artifacts.write_artifact(p, {"ops": {"x": 2}}, backend="cpu")
    assert json.loads(p.read_text())["ops"] == {"x": 1}  # untouched


def test_provenance_guard_force_and_benign_paths(tmp_path):
    p = tmp_path / "cost.json"
    # cpu over cpu: fine (same-grade refresh)
    artifacts.write_artifact(p, {"v": 1}, backend="cpu")
    artifacts.write_artifact(p, {"v": 2}, backend="cpu")
    assert json.loads(p.read_text()) == {"backend": "cpu", "v": 2}
    # tpu over cpu: an upgrade, always allowed
    artifacts.write_artifact(p, {"v": 3}, backend="tpu")
    # cpu over tpu with --force: deliberate downgrade
    rec = artifacts.write_artifact(p, {"v": 4}, backend="cpu", force=True)
    assert rec["backend"] == "cpu"
    assert artifacts.read_backend(p) == "cpu"
    # path=None stamps without writing
    rec = artifacts.write_artifact(None, {"v": 5}, backend="cpu")
    assert rec == {"backend": "cpu", "v": 5}


def test_cost_tools_wire_the_guard():
    """All three cost tools expose --force and route writes through
    utils/artifacts.py (the uniform backend stamp)."""
    for tool in ("xla_cost_micro.py", "gather_micro.py", "spmv_breakdown.py"):
        src = (REPO / "tools" / tool).read_text()
        assert "artifacts.write_artifact" in src, tool
        assert "--force" in src, tool


# ------------------------------------------------------ the tier-3 CI gate


def test_repo_cost_clean():
    """Every registered entry point passes tier 3 with ZERO findings — the
    ratchet stays empty (ISSUE 6 acceptance bar).  This is also the
    donation-verifier regression: the fixpoint and ingest-carry runners
    declare donations and the lowering must alias them."""
    res = cost.run_cost(root=REPO)
    msg = "\n".join(f.render() + " :: " + f.message for f in res.findings)
    assert not res.findings, f"tier-3 findings (fix the code, not the gate):\n{msg}"
    # floors are currently met, so no advisories either
    assert not res.advisories, [f.message for f in res.advisories]


def test_donated_runners_verify_in_the_report():
    """The fixed runners: donation declared == donation lowered."""
    res = cost.run_cost(root=REPO)
    by_name = {e["entry"]: e for e in res.report["entries"]}
    for name in ("pagerank_step", "pagerank_step_tol_cumsum",
                 "pagerank_step_pallas", "pagerank_step_hybrid",
                 "pagerank_step_sort_shuffle", "tfidf_chunk_ingest_carry"):
        don = by_name[name].get("donation")
        assert don, (name, by_name[name])
        assert don["aliased_buffers"] == don["declared_buffers"] >= 1, (
            name, don,
        )


def test_pallas_entry_is_registered_and_covered():
    """The Pallas spmv path has a registry entry (interpret mode on CPU),
    so tiers 2 and 3 cover it without a chip."""
    names = {ep.name for ep in ENTRY_POINTS}
    assert "pagerank_step_pallas" in names
    res = cost.run_cost(
        root=REPO,
        entries=[ep for ep in ENTRY_POINTS
                 if ep.name == "pagerank_step_pallas"],
    )
    assert not res.findings
    [entry] = res.report["entries"]
    # the pallas_call really appears as a costed leaf class
    classes = next(iter(entry["variants"].values()))["classes"]
    assert "pallas" in classes, classes


def test_intensity_gate_is_advisory_while_baseline_is_cpu():
    """The real repo artifact currently records backend=cpu (measured
    without a chip) — the tier-3 report must say the intensity gate is
    advisory."""
    res = cost.run_cost(root=REPO)
    backend = cost.baseline_backend(REPO / cost.COST_BASELINE_ARTIFACT)
    expected = "enforcing" if backend == "tpu" else "advisory"
    assert res.report["intensity_gate"] == expected
    assert res.report["baseline_backend"] == backend == "cpu"


def test_all_tiers_fit_the_interactive_budget():
    """ISSUE 6 acceptance: tiers 2 + 3 (the jax-tracing tiers) complete in
    well under the 10s CPU budget in-process (tools/ci.sh enforces the
    same bound per tier on the CLI, interpreter startup included)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.analysis import semantic

    t0 = time.perf_counter()
    sem = semantic.run_semantic(root=REPO)
    res = cost.run_cost(root=REPO)
    dt = time.perf_counter() - t0
    assert not sem and not res.findings
    assert dt < 10.0, f"tiers 2+3 took {dt:.1f}s (budget 10s)"


# ------------------------------------------------------------ CLI plumbing


def test_cli_tier3_clean():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis", "--tier", "3"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_tier_all_runs_three_tiers_clean():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis",
         "--tier", "all"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_cost_report():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis",
         "--tier", "3", "--cost-report", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    report = out["cost_report"]
    names = {e["entry"] for e in report["entries"]}
    assert {"pagerank_step", "tfidf_chunk_ingest_carry"} <= names
    sample = next(e for e in report["entries"] if e["entry"] == "pagerank_step")
    variant = next(iter(sample["variants"].values()))
    assert variant["flops"] > 0 and variant["hbm_bytes"] > 0
    assert 0 < variant["intensity"] < 10


def test_cli_list_rules_includes_tier3():
    proc = subprocess.run(
        [sys.executable, "-m",
         "page_rank_and_tfidf_using_apache_spark_tpu.analysis",
         "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    for rid in ("intensity-floor", "pad-frac-budget", "donation-contract"):
        assert rid in proc.stdout


# ------------------------------------------------------- tools/trace_diff.py


def _diff_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_diff_under_test", REPO / "tools" / "trace_diff.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_diff_attributes_the_regressed_phase(tmp_path):
    td = _diff_mod()
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    # driver-wrapped round vs bare bench record: both shapes must load
    old.write_text(json.dumps({"parsed": {"extra": {
        "breakdown": {"tfidf.stream": 10.0, "tfidf.finalize": 1.0},
        "breakdown_wall_secs": 11.2}}}))
    new.write_text(json.dumps({"extra": {
        "breakdown": {"tfidf.stream": 14.0, "tfidf.finalize": 1.02},
        "breakdown_wall_secs": 15.3}}))
    rc = td.main([str(old), str(new), "--json"])
    assert rc == 1  # a regression past the threshold fails the diff
    rows = td.diff_breakdowns(*[td.load_breakdown(str(p))[0]
                                for p in (old, new)])
    assert rows[0]["phase"] == "tfidf.stream"
    assert rows[0]["delta_secs"] == pytest.approx(4.0)
    assert rows[0]["delta_frac"] == pytest.approx(0.4)


def test_trace_diff_clean_within_threshold(tmp_path, capsys):
    td = _diff_mod()
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps({"extra": {"breakdown": {"p": 5.0}}}))
    b.write_text(json.dumps({"extra": {"breakdown": {"p": 5.2}}}))
    assert td.main([str(a), str(b), "--threshold", "0.10"]) == 0
    assert "no phase regressed" in capsys.readouterr().out


def test_trace_diff_rejects_rounds_without_breakdowns(tmp_path):
    td = _diff_mod()
    a = tmp_path / "a.json"
    a.write_text(json.dumps({"parsed": {"extra": {}}}))
    assert td.main([str(a), str(a)]) == 2
