#!/usr/bin/env bash
# Tier-1 verification runner (ROADMAP.md). Collection errors ARE failures:
# pytest exits 2 on collection errors and nonzero on test failures; both
# fail this script. -p no:cacheprovider keeps the tree clean for CI diffing.
set -euo pipefail
cd "$(dirname "$0")/.."

# lint first (fast): config lives in pyproject.toml [tool.ruff]. The CI
# sandbox has no network, so tolerate an absent ruff instead of failing.
if command -v ruff >/dev/null 2>&1; then
    ruff check .
else
    echo "tier1: ruff not installed, skipping lint" >&2
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# tier-1 is the CPU lane: kernels in interpret mode (the sanitizer lane
# below needs it, and a TPU backend refuses it); the chip has chip_smoke.py
export JAX_PLATFORMS=cpu

# reprolint (DESIGN.md §9): lock discipline + tracer hygiene + span
# hygiene (TEL001) + the launch-capture kernel sanitizer. A hard gate —
# exit 1 on any live finding, exit 2 if the analyzer itself breaks; both
# fail tier-1.
python -m repro.analysis --strict

# telemetry export round-trip (DESIGN.md §10): emit spans + metrics in
# process, write Chrome-trace JSON + JSONL, parse both back, validate
# the schemas, render the report tables.
python -m repro.telemetry.report --selftest

# runtime kernel contracts: interpret-mode re-execution of the Karras
# and brute-force kNN kernels with REPRO_SANITIZE assertions armed, vs
# oracles (seconds-scale, N=2000, fixed seed).
python -m repro.analysis --sanitize-smoke
# DeprecationWarnings are errors: the legacy API-v1 spellings (space-first
# query/count/knn, DistributedTree query_knn-style methods) are warn-once
# shims, so any in-repo call site that sneaks back in fails tier-1 here.
python -m pytest -q -p no:cacheprovider \
    -W error::DeprecationWarning "$@"

