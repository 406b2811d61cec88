#!/usr/bin/env bash
# Tier-1 verify — the ROADMAP.md command, verbatim. Run from the repo root.
# The `-m 'not slow'` selection includes the quick continuous-batching
# serving tests (tests/unit/serving, marker `serving`), so tier-1
# exercises the scheduler/kv-slot/no-recompile path; the explicit check
# afterwards fails the script if that suite was ever emptied out.
# conftest.py prints a "module wall-clock (child subprocess)" section at
# the end of the run — the per-module duration summary that shows where
# the 870s budget goes when deciding which modules to demote to `slow`.
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
# the serving suite must exist and be non-empty (it rides the
# `-m 'not slow'` selection above; a second pytest invocation here was
# flaky under post-suite memory pressure, so guard on the files)
grep -rqs "def test_" tests/unit/serving || { echo "tier-1: serving tests missing"; exit 1; }
# likewise the observability suite (marker `observability`): the telemetry
# registry/sink + engine/serving instrumentation tests ride `-m 'not slow'`
grep -rqs "def test_" tests/unit/telemetry || { echo "tier-1: observability tests missing"; exit 1; }
# likewise the speculative-decoding suite (marker `speculative`): the
# lossless-greedy/rejection-sampling/zero-recompile invariants ride
# `-m 'not slow'` through tests/unit/serving/test_speculative.py
grep -qs "def test_" tests/unit/serving/test_speculative.py || { echo "tier-1: speculative tests missing"; exit 1; }
# likewise the prefix-cache suite (marker `prefix_cache`): block-paged
# KV + radix COW-losslessness/eviction/zero-recompile invariants ride
# `-m 'not slow'` through tests/unit/serving/test_prefix_cache.py
grep -qs "def test_" tests/unit/serving/test_prefix_cache.py || { echo "tier-1: prefix-cache tests missing"; exit 1; }
# likewise the SLO-scheduling suite (marker `slo`): chunked-prefill
# losslessness, priority/preemption KV-swap round-trip bit-identity and
# zero-recompile invariants ride `-m 'not slow'` through
# tests/unit/serving/test_slo.py
grep -qs "def test_" tests/unit/serving/test_slo.py || { echo "tier-1: slo tests missing"; exit 1; }
# likewise the serving-fabric suite (marker `fabric`): multi-replica
# failover losslessness under scripted chaos, circuit-breaker /
# shedding / supervisor invariants ride `-m 'not slow'` through
# tests/unit/serving/test_fabric.py
grep -qs "def test_" tests/unit/serving/test_fabric.py || { echo "tier-1: fabric tests missing"; exit 1; }
# likewise the training-resilience suite (marker `resilience`): anomaly
# classification, finite-grad guard, rewind-and-skip bit-identity,
# deterministic dataloader resume and SDC-audit invariants ride
# `-m 'not slow'` through tests/unit/runtime/test_resilience.py
grep -qs "def test_" tests/unit/runtime/test_resilience.py || { echo "tier-1: resilience tests missing"; exit 1; }
# likewise the tracing suite (marker `tracing`): span-graph lifecycle
# reconstruction incl. failover trace linking, armed-run greedy
# bit-identity, Chrome-trace validity and roofline attribution ride
# `-m 'not slow'` through tests/unit/serving/test_tracing.py and
# tests/unit/telemetry/test_spans.py
grep -qs "def test_" tests/unit/serving/test_tracing.py || { echo "tier-1: tracing tests missing"; exit 1; }
grep -qs "def test_" tests/unit/telemetry/test_spans.py || { echo "tier-1: span tests missing"; exit 1; }
# likewise the quantized-KV suite (marker `kvquant`): int8/fp8 block
# round-trip bounds, capacity ratios, fused dequant-kernel parity,
# greedy exact-match gate, COW/swap/prefix-hit invariants on quantized
# pools, and autotuned kernel-plan loading ride `-m 'not slow'` through
# tests/unit/serving/test_kv_quant.py
grep -qs "def test_" tests/unit/serving/test_kv_quant.py || { echo "tier-1: kv-quant tests missing"; exit 1; }
# likewise the SLO control-plane suite (marker `sloplane`): burn-rate
# window math + multi-window alert determinism, per-tenant accounting
# conservation, flight-recorder dump/postmortem reconstruction and
# report degrade paths ride `-m 'not slow'` through
# tests/unit/telemetry/test_slo_plane.py and
# tests/unit/serving/test_slo_plane.py
grep -qs "def test_" tests/unit/telemetry/test_slo_plane.py || { echo "tier-1: slo-plane tests missing"; exit 1; }
grep -qs "def test_" tests/unit/serving/test_slo_plane.py || { echo "tier-1: slo-plane serving tests missing"; exit 1; }
# likewise the static-analysis suite (marker `lint`): each dstpu-lint
# pass catches its seeded fixture violation and stays silent on the
# good twin, suppression/baseline round-trips, and the repo-clean
# end-to-end pin ride `-m 'not slow'` through tests/unit/analysis/
grep -qs "def test_" tests/unit/analysis/test_lint.py || { echo "tier-1: lint tests missing"; exit 1; }
# dstpu-lint (ISSUE 14; prove upgrade ISSUE 15): machine-enforce the
# static contracts — zero unsuppressed findings across host-sync (a
# reintroduced hot-path device_get fails here), recompile-hazard
# (unbucketed jit keys), typed-error (bare raises in serving/),
# jax-compat (direct version-gated imports), donation-safety,
# metric-names, slo-rules, plus the ISSUE 15 TPU-native families:
# pallas-tile (dtype tile quanta — an int8 window off the 32-row
# quantum fails here), pallas-dma (a dropped DMA .wait() fails here),
# vmem-budget (committed kernel plans must fit the ops/autotune.py
# VMEM table), and sharding-contract (interprocedural donation taint +
# the mesh-axis registry). Exit codes: 1 findings / 2 usage /
# 3 internal. Incremental mode first (per-file finding cache keyed on
# content hashes — byte-identical output to a full run, pinned by
# test); full-corpus fallback on usage/internal errors so a corrupt
# cache or missing git can never mask findings. LINT_BASELINE.json's
# committed budget stays the growth guard: the baseline only burns
# down. Wall-clock stays under 60 s (pinned by
# tests/unit/analysis/test_prove.py).
JAX_PLATFORMS=cpu python scripts/dstpu_lint.py --changed-only; lint_rc=$?
if [ "$lint_rc" -eq 2 ] || [ "$lint_rc" -eq 3 ]; then
  echo "tier-1: incremental lint unavailable (rc=$lint_rc), full run"
  JAX_PLATFORMS=cpu python scripts/dstpu_lint.py; lint_rc=$?
fi
[ "$lint_rc" -eq 0 ] || { echo "tier-1: dstpu-lint findings"; exit 1; }
exit $rc
