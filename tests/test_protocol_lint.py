"""graftlint tier-6 tests (ISSUE 18): distributed wire-protocol
analysis, its derived conformance harness, and the seeded-mutation
acceptance gate.

Four layers, mirroring tests/test_persistence_lint.py:

1. **Fixture snippets** — per tier-6 check (endpoint-contract-drift,
   status-class-drift, retry-unsafe-effect, floor-monotonicity): a true
   positive, a true negative, and a suppressed positive.  Snippets are
   parsed, never executed.
2. **The declared contract** — ``WIRE_SCHEMAS`` drift is validated in
   both directions against fixture registries, and the real registry's
   rows must resolve (handlers, readers, the query row's 503-retryable
   class the floor protocol depends on).
3. **The whole-repo gate** — the tier-6 analyzer runs over the real
   wire surface and must report nothing beyond ``analysis/baseline.json``
   (currently empty: the first sweep's true positive — ``handle_query``
   crashing into an undeclared 500 on shape-malformed JSON — was fixed,
   not frozen), under the declared ``GRAFT_PROTO_BUDGET_S`` budget.
4. **The derived message space + seeded mutation** — the probe
   enumeration is pinned against the real contract, and one seeded
   contract mutation (deleting the query row's declared 503) must be
   caught BOTH statically (``endpoint-contract-drift``: the code emits
   an undeclared code) and on the wire (``tools/protocol_harness.py``:
   the observed floor refusal falls outside the declared set).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import textwrap
import time
from pathlib import Path

import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.analysis import (
    baseline_path,
    load_baseline,
    repo_root,
)
from page_rank_and_tfidf_using_apache_spark_tpu.analysis import __main__ as lint_cli
from page_rank_and_tfidf_using_apache_spark_tpu.analysis import protocol
from page_rank_and_tfidf_using_apache_spark_tpu.analysis.protocol import (
    PROTO_RULES,
    SCAN_MODULES,
    enumerate_message_space,
    run_protocol,
    wire_contract,
    wire_fingerprint,
)

REPO = repo_root()

_PKG = "page_rank_and_tfidf_using_apache_spark_tpu"


def wire(tmp_path: Path, files: dict[str, str], extra: tuple = ()):
    """Write a tiny repo tree and run the tier-6 analyzer over it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    models = protocol.build_models(tmp_path, extra=tuple(extra) or None)
    return run_protocol(root=tmp_path, models=models)


def rules_hit(findings) -> set[str]:
    return {f.rule for f in findings}


def _tool(name: str):
    spec = importlib.util.spec_from_file_location(
        f"protocol_test_{name}", REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------- fixture builder


def _wire_fixture(
    status='((200, "success"), (400, "terminal"), (503, "retryable"))',
    request_keys='("rid", "text")',
    response_keys='("rid", "text")',
    aux="()",
    resp_doc='{"rid": rid, "text": text}',
    pre_guard="pass",
    post_guard="pass",
    reader_extra="pass",
    reg_disable="",
    srv_extra="",
):
    """One declared POST endpoint with a dedup-guarded handler and a
    retrying reader (the router seat) — clean by construction; every
    parameter seeds exactly one drift."""
    registry = f"""
    WIRE_SCHEMAS = (  {reg_disable}
        ("echo",
         "POST",
         "/echo",
         "srv.py::Echo.handle_echo::req",
         ("srv.py::ask_echo::reply",),
         {request_keys},
         {response_keys},
         {aux},
         {status}),
    )
    """
    srv = f"""
    import json

    from urllib.error import HTTPError


    class Echo:
        def __init__(self):
            self._rid_cache = {{}}
            self.served = 0
            self.latencies = []

        def handle_echo(self, body):
            try:
                req = json.loads(body)
                rid = req["rid"]
                text = req["text"]
            except (ValueError, KeyError, TypeError):
                return (400, "text/plain", "bad request")
            if not self.ready():
                return (503, "text/plain", "below floor")
            {pre_guard}
            hit = self._rid_cache.get(rid)
            if hit is not None:
                return hit
            {post_guard}
            resp = (200, "application/json", json.dumps({resp_doc}))
            self._rid_cache[rid] = resp
            self.served += 1
            return resp

        def ready(self):
            return True


    def ask_echo(session, rid, text):
        doc = {{"rid": rid, "text": text}}
        for _attempt in range(3):
            try:
                reply = session.post("/echo", doc)
            except HTTPError as exc:
                if exc.code == 400:
                    raise
                continue
            {reader_extra}
            return reply["rid"], reply["text"]
        return None


    def serve(exporter, echo):
        return exporter(routes={{("POST", "/echo"): echo.handle_echo}})
    {srv_extra}
    """
    return {"analysis/registry.py": registry, "srv.py": srv}


def test_wire_fixture_clean(tmp_path):
    res = wire(tmp_path, _wire_fixture())
    assert not res.findings, "\n".join(f.render() for f in res.findings)


# ------------------------------------------------- endpoint-contract-drift


def test_undeclared_emitted_code_tp(tmp_path):
    """The seeded-mutation shape at fixture scale: drop the declared 503
    and the handler's floor refusal becomes an unclassified code."""
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "terminal"))'))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("503" in f.message and "dropped-request" in f.message
                        for f in hits)


def test_declared_code_never_emitted(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "terminal"), (410, "terminal"), '
               '(503, "retryable"))'))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("410" in f.message and "never emits" in f.message
                        for f in hits)


def test_undeclared_response_key_write(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        resp_doc='{"rid": rid, "text": text, "stowaway": 1}'))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("'stowaway'" in f.message for f in hits)
    assert any(f.path == "srv.py" for f in hits)  # anchored at the write


def test_reader_reads_undeclared_key(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        reader_extra='_ = reply["mystery"]'))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("'mystery'" in f.message for f in hits)


def test_declared_response_key_never_written(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        response_keys='("rid", "text", "ghost")'))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("'ghost'" in f.message and "no handler" in f.message
                        for f in hits)


def test_aux_exempts_write_only_response_key(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        response_keys='("rid", "text", "forensic")',
        aux='("forensic",)',
        resp_doc='{"rid": rid, "text": text, "forensic": 1}'))
    assert "endpoint-contract-drift" not in rules_hit(res.findings)


def test_registered_route_not_declared(tmp_path):
    res = wire(tmp_path, _wire_fixture(srv_extra="""

    def serve_extra(exporter, echo):
        return exporter(routes={("GET", "/extra"): echo.handle_echo})
    """))
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("/extra" in f.message and "does not declare"
                        in f.message for f in hits)


def test_stale_handler_row(tmp_path):
    files = _wire_fixture()
    files["analysis/registry.py"] = """
    WIRE_SCHEMAS = (
        ("echo",
         "POST",
         "/echo",
         "srv.py::no_such_handler::req",
         (),
         ("rid",),
         (),
         (),
         ((200, "success"),)),
    )
    """
    res = wire(tmp_path, files)
    hits = [f for f in res.findings if f.rule == "endpoint-contract-drift"]
    assert hits and any("does not resolve" in f.message for f in hits)


def test_endpoint_drift_suppressed(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        post_guard='resp418 = (418, "text/plain", "teapot")  '
                   "# graftlint: disable=endpoint-contract-drift "
                   "(easter egg, never routed)"))
    assert "endpoint-contract-drift" not in rules_hit(res.findings)


# ----------------------------------------------------- status-class-drift


def test_status_class_503_must_be_retryable(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "terminal"), (503, "terminal"))'))
    hits = [f for f in res.findings if f.rule == "status-class-drift"]
    assert hits and any("503" in f.message and "retryable" in f.message
                        for f in hits)


def test_status_class_retryable_but_router_raises(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "retryable"), '
               '(503, "retryable"))'))
    hits = [f for f in res.findings if f.rule == "status-class-drift"]
    assert hits and any("the router raises on it" in f.message
                        for f in hits)


def test_status_class_unknown_class(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "weird"), (503, "retryable"))'))
    hits = [f for f in res.findings if f.rule == "status-class-drift"]
    assert hits and any("unknown class 'weird'" in f.message for f in hits)


def test_status_class_suppressed(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        status='((200, "success"), (400, "terminal"), (503, "terminal"))',
        reg_disable="# graftlint: disable=status-class-drift "
                    "(fixture: split-brain contract under test)"))
    assert "status-class-drift" not in rules_hit(res.findings)


# ----------------------------------------------------- retry-unsafe-effect


def test_retry_unsafe_counter_before_guard(tmp_path):
    res = wire(tmp_path, _wire_fixture(pre_guard="self.served += 1"))
    hits = [f for f in res.findings if f.rule == "retry-unsafe-effect"]
    assert hits and any("BEFORE" in f.message for f in hits)


def test_retry_unsafe_mutator_call_before_guard(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        pre_guard="self.latencies.append(1.0)"))
    hits = [f for f in res.findings if f.rule == "retry-unsafe-effect"]
    assert hits and any("latencies.append()" in f.message for f in hits)


def test_retry_unsafe_commit_leaf_before_guard(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        pre_guard="commit_append(body, rid, text)"))
    hits = [f for f in res.findings if f.rule == "retry-unsafe-effect"]
    assert hits and any("commit_append() commit" in f.message for f in hits)


def test_retry_unsafe_interprocedural(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        pre_guard="self._bump()",
        srv_extra="""

    def _bump(self):
        self.served += 1
    """))
    hits = [f for f in res.findings if f.rule == "retry-unsafe-effect"]
    assert hits and any("via _bump()" in f.message for f in hits)


def test_retry_unsafe_tn_effects_behind_guard(tmp_path):
    res = wire(tmp_path, _wire_fixture())
    assert "retry-unsafe-effect" not in rules_hit(res.findings)


def test_retry_unsafe_no_guard_at_all(tmp_path):
    files = {
        "analysis/registry.py": """
    WIRE_SCHEMAS = (
        ("echo",
         "POST",
         "/echo",
         "srv.py::Echo.handle_echo::req",
         (),
         ("rid",),
         (),
         (),
         ((200, "success"), (400, "terminal"))),
    )
    """,
        "srv.py": """
    import json


    class Echo:
        def __init__(self):
            self.served = 0

        def handle_echo(self, body):
            try:
                req = json.loads(body)
                rid = req["rid"]
            except (ValueError, KeyError, TypeError):
                return (400, "text/plain", "bad request")
            self.served += 1
            return (200, "text/plain", rid)


    def serve(exporter, echo):
        return exporter(routes={("POST", "/echo"): echo.handle_echo})
    """,
    }
    res = wire(tmp_path, files)
    hits = [f for f in res.findings if f.rule == "retry-unsafe-effect"]
    assert hits and any("never consults" in f.message for f in hits)


def test_retry_unsafe_suppressed(tmp_path):
    res = wire(tmp_path, _wire_fixture(
        pre_guard="self.served += 1  "
                  "# graftlint: disable=retry-unsafe-effect "
                  "(monotonic attempt counter, replay-safe by design)"))
    assert "retry-unsafe-effect" not in rules_hit(res.findings)


# ----------------------------------------------------- floor-monotonicity


_FLOOR_REGISTRY = {"analysis/registry.py": "WIRE_SCHEMAS = ()\n"}

FLOOR_TN = """
import os


def durable_replace(src, dst):
    os.replace(src, dst)


def commit_floor(d, gen):
    tmp = os.path.join(d, ".floor.tmp")
    with open(tmp, "w") as f:
        f.write(str(gen))
    durable_replace(tmp, os.path.join(d, "FLOOR"))


class Replica:
    def __init__(self):
        self.floor = 0

    def observe(self, gen):
        if gen > self.floor:
            self.floor = gen

    def adopt(self, gen):
        self.floor = max(self.floor, gen)
"""

FLOOR_RAW_REPLACE_TP = """
import os


def commit_floor(d, gen):
    tmp = os.path.join(d, ".floor.tmp")
    with open(tmp, "w") as f:
        f.write(str(gen))
    os.replace(tmp, os.path.join(d, "FLOOR"))
"""

FLOOR_UNGUARDED_STORE_TP = """
class Replica:
    def __init__(self):
        self.floor = 0

    def rollback(self, gen):
        self.floor = gen
"""

FLOOR_SUPPRESSED = """
class Replica:
    def __init__(self):
        self.floor = 0

    def reset_for_test(self, gen):
        self.floor = gen  # graftlint: disable=floor-monotonicity (test-only fixture reset)
"""


def _floor(tmp_path, src):
    return wire(tmp_path, {**_FLOOR_REGISTRY, "floor.py": src},
                extra=("floor.py",))


def test_floor_tn(tmp_path):
    res = _floor(tmp_path, FLOOR_TN)
    assert "floor-monotonicity" not in rules_hit(res.findings)


def test_floor_raw_replace_tp(tmp_path):
    res = _floor(tmp_path, FLOOR_RAW_REPLACE_TP)
    hits = [f for f in res.findings if f.rule == "floor-monotonicity"]
    assert hits and any("durable_replace" in f.message for f in hits)


def test_floor_unguarded_store_tp(tmp_path):
    res = _floor(tmp_path, FLOOR_UNGUARDED_STORE_TP)
    hits = [f for f in res.findings if f.rule == "floor-monotonicity"]
    assert hits and any("ratchets up" in f.message for f in hits)


def test_floor_suppressed(tmp_path):
    res = _floor(tmp_path, FLOOR_SUPPRESSED)
    assert "floor-monotonicity" not in rules_hit(res.findings)


# ------------------------------------------------------- the real contract


def test_real_contract_resolves():
    contract = wire_contract(REPO)
    assert contract is not None and contract.rows
    endpoints = {r.endpoint for r in contract.rows}
    assert {"query", "status", "healthz", "metrics",
            "snapshot"} <= endpoints
    models = protocol.build_models(REPO)
    for row in contract.rows:
        assert protocol._resolve_spec(models, row.handler) is not None, \
            f"stale handler {row.handler!r}"
        assert row.status_classes, f"{row.endpoint}: no status classes"
    query = next(r for r in contract.rows if r.endpoint == "query")
    assert set(query.request_keys) == {"rid", "terms", "ranker"}
    assert (503, "retryable") in query.status_classes


def test_wire_fingerprint_is_stable_hex():
    fp = wire_fingerprint(REPO)
    assert fp is not None and re.fullmatch(r"[0-9a-f]{16}", fp)
    assert wire_fingerprint(REPO) == fp  # cached + deterministic


# ------------------------------------------------------ whole-repo ratchet


def test_whole_repo_protocol_clean_under_budget():
    """The acceptance gate: zero unratcheted tier-6 findings over the
    real wire surface, inside the declared GRAFT_PROTO_BUDGET_S budget
    (the first sweep's true positive — the malformed-shape 500 in
    handle_query — was fixed, not frozen)."""
    budget = float(os.environ.get("GRAFT_PROTO_BUDGET_S", 10))
    t0 = time.monotonic()
    res = run_protocol(root=REPO)
    elapsed = time.monotonic() - t0
    baseline = load_baseline(baseline_path(REPO))
    new = [f for f in res.findings if f.fingerprint not in baseline]
    assert not new, "\n".join(f.render() for f in new)
    assert elapsed < budget, f"tier-6 sweep took {elapsed:.1f}s"
    monitored = set(res.monitored)
    for mod in SCAN_MODULES:
        assert mod in monitored, mod


# ------------------------------------------------ derived message space


def test_message_space_derived_from_contract():
    probes = enumerate_message_space(REPO)
    assert probes
    q_kinds = {p["kind"] for p in probes if p.get("endpoint") == "query"}
    assert {"malformed-syntax", "malformed-shape", "missing-rid",
            "missing-terms", "wrong-method", "undeclared-key",
            "duplicate-rid", "stale-floor", "declared-codes"} <= q_kinds
    # ranker is parsed with .get -> optional, so dropping it must succeed
    assert "optional-ranker" in q_kinds
    stale = next(p for p in probes if p.get("endpoint") == "query"
                 and p["kind"] == "stale-floor")
    assert stale["expect"] == [503]
    assert any(p["kind"] == "unknown-path" for p in probes)


# ------------------------------------------------------------------- CLI


def test_cli_tier6_clean(capsys):
    rc = lint_cli.main(["--tier", "6"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "clean" in out


def test_cli_list_rules_has_tier6(capsys):
    rc = lint_cli.main(["--list-rules"])
    assert rc == 0
    out = capsys.readouterr().out
    for rule in PROTO_RULES:
        assert rule in out
    assert "[tier 6]" in out


def test_cli_wire_probes_json(capsys):
    rc = lint_cli.main(["--tier", "6", "--wire-probes", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    kinds = {p["kind"] for p in doc["wire_probes"]}
    assert {"duplicate-rid", "stale-floor", "unknown-path"} <= kinds


# ------------------------------------- seeded mutation + the live harness


def _mutated_contract():
    """The acceptance mutation: delete the query row's declared 503 —
    one undeclared status code."""
    real = wire_contract(REPO)
    rows = tuple(
        dataclasses.replace(row, status_classes=tuple(
            (c, cls) for c, cls in row.status_classes if c != 503))
        if row.endpoint == "query" else row
        for row in real.rows
    )
    return dataclasses.replace(real, rows=rows)


def test_seeded_mutation_caught_statically(monkeypatch):
    monkeypatch.setitem(protocol._contract_cache, str(REPO),
                        _mutated_contract())
    res = run_protocol(root=REPO)
    hits = [f for f in res.findings
            if f.rule == "endpoint-contract-drift" and "503" in f.message]
    assert hits, ("deleting the declared 503 must surface as an "
                  "emitted-but-undeclared code")


def _load_harness(monkeypatch):
    # the harness pins a deterministic fixture env at import; route that
    # through monkeypatch so an ambient chaos plan is restored afterwards
    for knob in ("GRAFT_CHAOS", "GRAFT_TRACE_DIR"):
        monkeypatch.delenv(knob, raising=False)
    return _tool("protocol_harness")


def test_harness_conformant_against_real_contract(monkeypatch):
    harness = _load_harness(monkeypatch)
    report = harness.run_harness(timeout_s=10.0)
    assert "fatal" not in report, report
    assert report["ok"] is True, report["violations"]
    assert report["probes"] >= 10
    assert report["replica_checks"] >= 2  # duplicate-rid + stale-floor
    assert report["router_checks"] >= 1
    assert report["fingerprint"] == wire_fingerprint(REPO)


def test_seeded_mutation_caught_on_the_wire(monkeypatch):
    """The other half of the acceptance gate: the SAME mutation fails
    the dynamic harness — the replica's floor refusal (503) is observed
    on the wire but no longer declared."""
    harness = _load_harness(monkeypatch)
    monkeypatch.setitem(protocol._contract_cache, str(REPO),
                        _mutated_contract())
    report = harness.run_harness(timeout_s=10.0)
    assert "fatal" not in report, report
    assert report["ok"] is False
    assert any("contract drift caught on the wire" in v["detail"]
               for v in report["violations"]), report["violations"]
