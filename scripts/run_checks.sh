#!/usr/bin/env bash
# Reproduce the full PR gate locally with one command:
#
#   1. tier-1 pytest        (the suite every PR must keep green; includes
#                            the seeded fault sweep in tests/test_faults.py —
#                            conservation + golden fingerprints under
#                            injected crashes/losses/stragglers — the
#                            DAG chain-equivalence sweep in tests/test_dag.py,
#                            and the sampled engine configurations of
#                            tests/test_engine_parity.py, each held to the
#                            golden fingerprint recorded for it;
#                            --fast keeps each suite's tier-1 prefix and
#                            skips the slow-marked bulk sweeps)
#   2. check_docs.py        (public-API docstring lint for repro.core)
#   3. perf marker          (pytest -m perf -> scripts/check_perf.py:
#                            reduced benchmark vs committed BENCH_pipeline.json,
#                            including the multitenant section — 3-tenant
#                            shared-heap scale row + the arbitration-beats-
#                            independent-replanning goodput comparison — and
#                            the dagsweep section: branched early-exit plans
#                            + the cascade-beats-expensive-only assertion)
#
# Usage:  scripts/run_checks.sh [--skip-perf|--fast]
#   --skip-perf  run only the tier-1 + docs gates; the perf gate
#                re-runs the pipeline benchmark and takes ~2 min.
#   --fast       like --skip-perf, but also deselect `slow` tests (the
#                heavy generative sweeps, e.g. the full engine-parity
#                suite, and the ~8-min multi-device MoE subprocess
#                compiles) — cuts the ~19-min tier to a few minutes; CI
#                runs the un-flagged full gate.

set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MARKER="not perf"
if [[ "${1:-}" == "--fast" ]]; then
    MARKER="not perf and not slow"
fi

echo "== [1/3] tier-1 test suite (-m \"$MARKER\") =="
python -m pytest -x -q -m "$MARKER"

echo "== [2/3] docstring gate (scripts/check_docs.py) =="
python scripts/check_docs.py

if [[ "${1:-}" == "--skip-perf" || "${1:-}" == "--fast" ]]; then
    echo "== [3/3] perf gate SKIPPED (${1:-}) =="
else
    echo "== [3/3] perf gate (pytest -m perf -> scripts/check_perf.py) =="
    python -m pytest -q -m perf
fi

echo "all gates clean"
