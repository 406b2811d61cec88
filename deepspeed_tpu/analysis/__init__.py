"""dstpu-lint — AST invariant checker for the repo's machine-enforceable
contracts (ISSUE 14; corpus-level dataflow + Pallas/TPU passes:
ISSUE 15 "dstpu-prove").

ISSUE 15 upgraded the per-file scanner to a two-phase corpus analysis:
phase 1 (:mod:`~deepspeed_tpu.analysis.index`) builds the module/symbol
index, import-resolved call graph, and per-function donation/aliasing
summaries; phase 2 passes receive the corpus through
:meth:`LintPass.begin` and check interprocedural contracts — donated
buffers followed through helpers (:mod:`~deepspeed_tpu.analysis.taint`
+ the ``sharding-contract`` pass), Pallas tile quanta and DMA pairing
(``pallas-tile``/``pallas-dma``), and VMEM budgets shared with
ops/autotune.py (``vmem-budget``).  Incremental runs
(:mod:`~deepspeed_tpu.analysis.incremental`) cache per-file findings
by content hash with dependent-region invalidation;
:mod:`~deepspeed_tpu.analysis.sarif` emits SARIF 2.1.0 for CI.

Every perf/robustness win since PR 2 rests on invariants the test suite
can only probe dynamically and per-site: zero recompiles after warmup,
no host synchronization inside engine hot loops except at declared
fences, typed errors in the serving paths, and metric-name discipline.  This package makes those contracts *static*: one shared AST
walk over ``deepspeed_tpu/``, a registry of passes that each encode one
contract, inline suppressions that require a written justification, and
a committed baseline for grandfathered findings that may only burn down.

Entry points:

  * :func:`run_lint` — programmatic (used by tests and the CLI);
  * ``scripts/dstpu_lint.py`` — the CLI, wired into run_tier1.sh;
  * ``scripts/check_metric_names.py`` / ``check_slo_rules.py`` — thin
    shims over the :mod:`~deepspeed_tpu.analysis.passes.metric_names`
    and :mod:`~deepspeed_tpu.analysis.passes.slo_rules` passes (their
    CLIs and exit-code contracts predate the framework and are pinned
    by tests).

See the README "Static analysis" section for the pass catalog, the
suppression syntax, and the baseline burn-down workflow.
"""

from __future__ import annotations

from deepspeed_tpu.analysis.core import (  # noqa: F401
    EXIT_CLEAN, EXIT_FINDINGS, EXIT_INTERNAL, EXIT_USAGE,
    Baseline, BaselineEntry, Corpus, Directive, FileContext, Finding,
    LintPass, LintResult, load_passes, registered_passes, run_lint)
