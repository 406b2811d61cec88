"""Shared fixtures for the analysis suite.

The whole-repo pins — clean end-to-end, the committed vmem-budget
artifact and the tier-1 wall-clock budget — all need the same expensive object: one cold full lint over the
committed tree (corpus parse + phase-1 index + every pass, exactly
what `scripts/dstpu_lint.py` runs).  Running it once per pin cost
tier-1 ~18 s; this session fixture pays for it once and hands the
timed result to each.

NOTE: the root conftest's crash-isolation harness runs each test
MODULE in its own child pytest process, so "session" scope really
means per-module — which is why every whole-repo pin lives in
test_lint.py: one child, one lint run.
"""

import os
import time
from types import SimpleNamespace

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


@pytest.fixture(scope="session")
def repo_full_lint():
    from deepspeed_tpu.analysis import Baseline, run_lint

    t0 = time.monotonic()
    result = run_lint(REPO, baseline=Baseline.load(
        os.path.join(REPO, "LINT_BASELINE.json")))
    elapsed = time.monotonic() - t0
    return SimpleNamespace(result=result, elapsed=elapsed)
