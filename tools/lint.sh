#!/usr/bin/env bash
# graftlint CI gate: fail on any finding not frozen in analysis/baseline.json.
#
# Runs ALL analysis tiers over the tier-1 surface (the package, tools/,
# bench.py): the lexical AST rules (tier 1), the semantic tier that traces
# every registered jit entry point on the CPU backend (tier 2: recompile /
# promotion / transfer-census / sharding gates), the static cost model
# (tier 3: FLOP/byte intensity floors, pad_frac budgets over the partition
# plans, and the buffer-donation verifier — intensity gates are advisory
# while xla_cost_tpu.json is not TPU-measured), the interprocedural
# concurrency & buffer-lifetime analyzer (tier 4: lock-order cycles,
# blocking-under-lock, use-after-donate, chaos-coverage drift,
# thread/lock registry drift — stdlib-only like tier 1), and the
# persistence & crash-consistency analyzer (tier 5: atomic-write drift,
# pointer-flip ordering, generation-deferred GC, ARTIFACT_SCHEMAS
# writer/reader drift, commit-lock drift — stdlib-only; --crash-points
# prints the derived SIGKILL surface tools/crash_harness.py replays),
# and the distributed wire-protocol analyzer (tier 6: endpoint /
# status-code / key drift against WIRE_SCHEMAS, status-class drift
# against the router's retry logic, retry-unsafe effects ahead of the
# request-id dedup guard, floor monotonicity — stdlib-only;
# --wire-probes prints the derived message space
# tools/protocol_harness.py replays).
# Exit 0 = clean under the ratchet; exit 1 = new findings — fix them,
# suppress with a justified "# graftlint: disable=<rule>" comment
# (lexical/concurrency/persistence/protocol) or a registry-level
# suppress entry (semantic/cost), or (outside ops//parallel/) baseline
# them with a justification.  Pass --tier 1|2|3|4|5|6 to run a single
# tier, --changed-only for the fast pre-commit path
# (tools/precommit.sh), --cost-report for the tier-3 per-entry cost
# table, --lock-graph for the tier-4 lock graph as DOT.
#
# The CPU backend is forced: these gates check code, never
# the chip.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    python -m page_rank_and_tfidf_using_apache_spark_tpu.analysis "$@"
