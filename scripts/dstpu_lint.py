#!/usr/bin/env python
"""dstpu-lint CLI — machine-enforce the repo's static contracts
(ISSUE 14; corpus-level dataflow + Pallas/TPU passes: ISSUE 15).

Usage:
    python scripts/dstpu_lint.py [--root R] [--passes a,b] [--json]
                                 [--baseline PATH | --no-baseline]
                                 [--write-baseline]
                                 [--changed-only] [--cache PATH]
                                 [--sarif PATH|-]
                                 [--list-passes] [--show-suppressed]

Runs every registered pass (deepspeed_tpu/analysis/passes/) over
``deepspeed_tpu/``: host-sync, recompile-hazard, typed-error,
donation-safety, metric-names, slo-rules, and the ISSUE 15
TPU-native families — pallas-tile (dtype tile quanta), pallas-dma
(start/wait pairing), vmem-budget (scratch + committed plans vs the
ops/autotune.py capacity table), sharding-contract (interprocedural
donation taint through the phase-1 call-graph summaries + the mesh
axis registry).  Wired into scripts/run_tier1.sh — a reintroduced
hot-path ``device_get``, an unbucketed jit cache key, a dropped DMA
``.wait()``, an int8 window off the 32-row tile quantum, or a donated
buffer read through a helper fails CI.

``--changed-only`` reuses per-file findings cached by content hash —
changed files invalidate their reverse-import dependent region, and
the (cheap) phase-1 index is rebuilt fresh each run so summaries are
never stale (``git diff --name-only`` feeds only a stderr diagnostic;
content hashes alone decide invalidation); output is byte-identical
to a full run, pinned by test.  ``--sarif PATH`` additionally emits SARIF 2.1.0 for CI diff
annotation ("-" = stdout, replacing the text report).

Typed exit codes:
    0  clean — zero unsuppressed findings, baseline in sync
    1  findings (or stale baseline entries / baseline over budget)
    2  usage error (bad arguments, unreadable baseline)
    3  internal error (a pass crashed — a lint bug, never a tree bug)

Suppressions (justification REQUIRED, see README "Static analysis"):
    # dstpu-lint: fence=<why this sync point is sanctioned>
    # dstpu-lint: disable=<pass>[,<pass>] -- <why>

Baseline burn-down: LINT_BASELINE.json grandfathers old findings with a
written justification each; stale entries and any growth past the
committed ``budget`` fail the lint, so the file only shrinks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from deepspeed_tpu.analysis import (
        EXIT_CLEAN, EXIT_FINDINGS, EXIT_INTERNAL, EXIT_USAGE, Baseline,
        load_passes, run_lint)
    from deepspeed_tpu.analysis.core import (DEFAULT_BASELINE_NAME,
                                             BaselineEntry, build_corpus)

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=None,
                    help="repo root (default: this script's parent)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass ids (default: all)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: <root>/"
                         f"{DEFAULT_BASELINE_NAME} when it exists)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline file")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings as the baseline "
                         "(edit in a justification per entry before "
                         "committing)")
    ap.add_argument("--changed-only", action="store_true",
                    help="incremental: reuse cached per-file findings "
                         "for unchanged files (git diff seeds the "
                         "changed set; content hashes are "
                         "authoritative; output identical to a full "
                         "run)")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help="finding-cache path (default: "
                         "<root>/.dstpu_lint_cache.json)")
    ap.add_argument("--sarif", default=None, metavar="PATH",
                    help="also write SARIF 2.1.0 output ('-' = stdout, "
                         "replacing the text report)")
    ap.add_argument("--list-passes", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="list suppressed/baselined findings too")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    passes = load_passes()
    if args.list_passes:
        for pid in sorted(passes):
            print(f"{pid:18s} {passes[pid].title}")
        return EXIT_CLEAN

    pass_ids = None
    if args.passes:
        pass_ids = [p.strip() for p in args.passes.split(",") if p.strip()]

    baseline = None
    baseline_path = args.baseline or os.path.join(root,
                                                  DEFAULT_BASELINE_NAME)
    if not args.no_baseline and not args.write_baseline \
            and os.path.exists(baseline_path):
        try:
            baseline = Baseline.load(baseline_path)
        except (OSError, ValueError, KeyError, TypeError,
                AttributeError) as e:
            print(f"dstpu-lint: unreadable baseline {baseline_path}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return EXIT_USAGE

    from deepspeed_tpu.analysis.core import UnknownPassError
    try:
        corpus = build_corpus(root)
        cache = None
        if args.changed_only:
            from deepspeed_tpu.analysis.incremental import (
                DEFAULT_CACHE_NAME, LintCache, git_changed_files)
            cache_path = args.cache or os.path.join(root,
                                                    DEFAULT_CACHE_NAME)
            cache = LintCache.load(
                cache_path, root,
                pass_ids=pass_ids or sorted(passes))
            git_changed = git_changed_files(root)
            if git_changed is None:
                print("dstpu-lint: git unavailable — hash-only "
                      "incremental run", file=sys.stderr)
            else:
                print(f"dstpu-lint: git reports {len(git_changed)} "
                      "changed file(s); content hashes decide",
                      file=sys.stderr)
            invalidated = cache.prepare(corpus)
            if invalidated:
                print(f"dstpu-lint: re-linting {len(invalidated)} "
                      "file(s) (changed + dependent region)",
                      file=sys.stderr)
        result = run_lint(root, pass_ids=pass_ids, baseline=baseline,
                          corpus=corpus, file_cache=cache)
        if cache is not None:
            cache.save()
    except UnknownPassError as e:
        print(f"dstpu-lint: {e.args[0]}", file=sys.stderr)
        return EXIT_USAGE
    except Exception as e:  # a pass crashed: lint bug, typed as such
        import traceback
        traceback.print_exc()
        print(f"dstpu-lint: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return EXIT_INTERNAL

    if args.write_baseline:
        bl = Baseline(budget=len(result.findings), entries=[
            BaselineEntry(
                pass_id=f.pass_id, path=f.path, symbol=f.symbol,
                message=f.message,
                justification="TODO(burn-down): justify or fix")
            for f in result.findings])
        try:
            bl.dump(baseline_path)
        except OSError as e:
            print(f"dstpu-lint: cannot write {baseline_path}: {e}",
                  file=sys.stderr)
            return EXIT_USAGE
        print(f"dstpu-lint: wrote {len(bl.entries)} baseline entr(ies) "
              f"-> {baseline_path}; edit each justification before "
              "committing")
        return EXIT_CLEAN if not result.findings else EXIT_FINDINGS

    if args.sarif:
        from deepspeed_tpu.analysis.sarif import to_sarif
        doc = to_sarif(result, passes)
        if args.sarif == "-":
            print(json.dumps(doc, indent=2))
            return EXIT_CLEAN if result.clean else EXIT_FINDINGS
        try:
            with open(args.sarif, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        except OSError as e:
            print(f"dstpu-lint: cannot write {args.sarif}: {e}",
                  file=sys.stderr)
            return EXIT_USAGE
        print(f"dstpu-lint: wrote SARIF -> {args.sarif}")

    if args.json:
        print(json.dumps(result.to_json(), indent=2))
    else:
        for f in result.findings:
            print(f.format(), file=sys.stderr)
        for e in result.stale_baseline:
            print(f"{e.path}: error: [baseline] stale entry "
                  f"[{e.pass_id}] {e.message!r} matches nothing — "
                  "remove it (burn-down)", file=sys.stderr)
        if result.over_budget:
            print(f"LINT_BASELINE.json: error: [baseline] "
                  f"{result.over_budget} entr(ies) over the committed "
                  "budget — the baseline only burns down; fix or "
                  "suppress new findings instead", file=sys.stderr)
        if args.show_suppressed:
            for f, d in result.suppressed:
                print(f"suppressed ({d.kind}={d.reason}): {f.format()}")
            for f, e in result.baselined:
                print(f"baselined ({e.justification}): {f.format()}")
        n_base = len(result.baselined)
        status = "CLEAN" if result.clean else "FINDINGS"
        print(f"dstpu-lint {status}: {result.files_scanned} files, "
              f"{len(result.passes_run)} pass(es), "
              f"{len(result.findings)} finding(s), "
              f"{len(result.suppressed)} suppressed, "
              f"{n_base} baselined, "
              f"{len(result.stale_baseline)} stale baseline entr(ies)")
    return EXIT_CLEAN if result.clean else EXIT_FINDINGS


if __name__ == "__main__":
    raise SystemExit(main())
