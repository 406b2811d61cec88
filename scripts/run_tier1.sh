#!/usr/bin/env bash
# Tier-1 verify: the command the driver runs (six xdist workers, a limit of
# 1,470 s), then dstpu-lint. Run from the repo root.
# tests/conftest.py keeps unit/analysis/ first, the torch modules last, and runs
# one test's body in a child of its worker; there is no other runner. The
# count of passes is guarded by the driver's floor (PERF_LEDGER.jsonl,
# `tests`), not here.
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist load --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
# dstpu-lint (ISSUE 14; prove upgrade ISSUE 15): zero unsuppressed findings
# across every pass (README, "Static analysis (dstpu-lint)"). Exit codes:
# 1 findings / 2 usage / 3 internal. Incremental mode first (per-file finding
# cache keyed on content hashes — byte-identical output to a full run, pinned
# by test); full-corpus fallback on usage/internal errors so a corrupt cache
# or missing git can never mask findings.
JAX_PLATFORMS=cpu python scripts/dstpu_lint.py --changed-only; lint_rc=$?
if [ "$lint_rc" -eq 2 ] || [ "$lint_rc" -eq 3 ]; then
  echo "tier-1: incremental lint unavailable (rc=$lint_rc), full run"
  JAX_PLATFORMS=cpu python scripts/dstpu_lint.py; lint_rc=$?
fi
[ "$lint_rc" -eq 0 ] || { echo "tier-1: dstpu-lint findings"; exit 1; }
exit $rc
