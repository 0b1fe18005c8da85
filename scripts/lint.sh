#!/usr/bin/env bash
# The one lint entry point (docs/static_analysis.md):
#
#   1. ruff  — generic hygiene (undefined names, unused imports;
#              baseline rule set in pyproject.toml). Skipped with a
#              note when ruff is not installed — the container image
#              does not bake it in.
#   2. areal-lint over areal_tpu/ — repo-specific AST contract checks
#              (loop-only, blocking-async, env-knob, wire-schema,
#              wire-contract, metrics-registry, chaos-registry,
#              lock-order) + the generated-docs drift gates
#              (env_vars.md, metrics.md, fault_points.md).
#   3. areal-lint over tests/ + scripts/ — the CLIENT side of the
#              cross-process contracts only (wire routes, metric
#              names, AREAL_FAULTS chaos specs): a chaos test arming
#              a renamed point must fail HERE, not silently no-op on
#              a chip window.
#
# Exit nonzero if any gate fails: the single command PRs/CI wire in.

set -u
cd "$(dirname "$0")/.."
rc=0

if command -v ruff >/dev/null 2>&1; then
    echo "== lint: ruff =="
    ruff check areal_tpu scripts tests || rc=1
else
    echo "== lint: ruff not installed; skipping (baseline config in pyproject.toml) =="
fi

echo "== lint: areal-lint (areal_tpu + docs drift) =="
python scripts/areal_lint.py areal_tpu \
    --check-env-docs docs/env_vars.md \
    --check-metrics-docs docs/metrics.md \
    --check-fault-docs docs/fault_points.md || rc=1

echo "== lint: areal-lint (tests/scripts cross-process contracts) =="
python scripts/areal_lint.py tests scripts \
    --checker wire-contract \
    --checker metrics-registry \
    --checker chaos-registry || rc=1

exit $rc
