#!/usr/bin/env bash
# Fast pre-commit gate: lint ONLY the files changed vs a base ref.
#
#   tools/precommit.sh [BASE]     # default BASE = HEAD (worktree diff)
#
# Tier 1 scans just the changed files; tiers 2/3 re-trace only the jit
# entry points whose contracted module changed (all of them when analysis/
# itself changed); tiers 4, 5 and 6 still model the whole surface
# (interprocedural/cross-file facts do not restrict — all three models
# are pure AST, well under a second) but report only findings in the
# changed files.  tools/lint.sh remains the full-repo CI gate — this script is
# the editor-loop companion, typically <2s when nothing jit-adjacent
# moved.
#
# The CPU backend is forced: these gates check code, never
# the chip.
set -euo pipefail
cd "$(dirname "$0")/.."
BASE="${1:-HEAD}"
exec env JAX_PLATFORMS=cpu \
    python -m page_rank_and_tfidf_using_apache_spark_tpu.analysis \
        --changed-only "$BASE"
