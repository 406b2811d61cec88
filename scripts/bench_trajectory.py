#!/usr/bin/env python
"""Collate per-round bench JSONs into a per-metric trend table.

Usage:
    python scripts/bench_trajectory.py                # BENCH_r*.json in repo root
    python scripts/bench_trajectory.py --full out.json  # + a fresh full bench JSON
    python scripts/bench_trajectory.py --json --threshold 0.15

The repo accumulates one ``BENCH_r<NN>.json`` per review round (shape:
``{"n": <round>, "parsed": {...bench.py main JSON...}}``) plus ad-hoc
full bench outputs — but until now nothing read them back, so the bench
trajectory was flying blind (ISSUE 11 satellite). This script flattens
every numeric leaf of each round's ``parsed`` payload into a dotted
metric path (``serving.bf16.decode_ms_per_token``), lines the rounds up
into per-metric series, and flags the newest value against the previous
round with a NOISE THRESHOLD (default 10% relative):

  * ``regression``  — moved past the threshold in the BAD direction
  * ``improvement`` — moved past the threshold in the GOOD direction
  * ``stable``      — within the threshold
  * ``new``/``gone`` — metric appeared/disappeared this round

Direction sense is a suffix heuristic: metrics named like latencies
(``*_ms``, ``*_ms_per_token``, ``*latency*``, ``*p50/p95/p99*``,
``*overhead*``) are lower-is-better; throughputs/ratios/MFU are
higher-is-better. Stdlib only — runs anywhere; unit-tested against the
checked-in round files (tests/unit/telemetry/test_trajectory.py).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import OrderedDict

_LOWER_IS_BETTER = re.compile(
    r"(_ms($|_)|_ms\.|latency|p50|p95|p99|overhead|ms_per_token"
    r"|n_bad|error|recompile|shed|failed)")


def lower_is_better(metric: str) -> bool:
    return bool(_LOWER_IS_BETTER.search(metric))


def flatten(obj, prefix="", out=None):
    """Numeric leaves of a nested dict as {dotted.path: float} (bools
    and non-numeric strings are skipped — they are config echoes, not
    trends)."""
    if out is None:
        out = OrderedDict()
    if isinstance(obj, dict):
        for k, v in obj.items():
            flatten(v, prefix + str(k) + ".", out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    return out


def load_rounds(paths, full=None):
    """[(round_label, flat_metrics)] ordered by round. Round files carry
    their index in ``n``; a ``--full`` bench JSON (bench.py stdout) is
    appended as the newest point."""
    rounds = []
    for p in paths:
        with open(p) as f:
            d = json.load(f)
        parsed = d.get("parsed") if isinstance(d, dict) else None
        if not isinstance(parsed, dict):
            continue
        rounds.append((int(d.get("n", len(rounds) + 1)),
                       os.path.basename(p), flatten(parsed)))
    rounds.sort(key=lambda r: r[0])
    out = [(f"r{n:02d}", flat) for n, _, flat in rounds]
    if full:
        with open(full) as f:
            d = json.load(f)
        if not isinstance(d, dict):
            raise ValueError(f"--full {full}: expected a JSON object")
        out.append(("full", flatten(d)))
    return out


def _measured_spread(metric, flat):
    """IQR-derived relative noise for a metric that reports a measured
    spread (ISSUE 12 variance discipline): benches that emit
    ``<base>.median`` + ``<base>.iqr`` window statistics carry their
    OWN noise estimate, so the regression gate for ``<base>.median``
    (and a bare ``<base>`` echoing it) widens to the measured IQR
    instead of relying on the fixed global threshold alone. A
    best-of-windows HEADLINE whose spread rides under a sibling key
    uses the ``<metric>_windows`` convention (bench.py's top-level
    ``value`` + ``value_windows.{median,iqr,n}``). Returns None when
    the round carries no spread for this metric."""
    if metric.endswith(".median"):
        base = metric[:-len(".median")]
    else:
        base = metric
    for spread_base in (base, base + "_windows"):
        iqr = flat.get(spread_base + ".iqr")
        med = flat.get(spread_base + ".median", flat.get(metric))
        if iqr is not None and med:
            return abs(iqr) / abs(med)
    return None


def trend(rounds, threshold=0.10):
    """Per-metric series + newest-vs-previous flag. Returns
    {metric: {"series": {label: value}, "flag": ..., "delta_pct": ...}}
    over the union of metrics, sorted by path. Metrics whose last path
    component is ``iqr``/``n`` are spread METADATA, flagged ``spread``
    and never counted as regressions; a metric accompanied by a
    measured spread is gated at ``max(threshold, IQR/median)`` of the
    newer round — the bench's own window noise."""
    if not rounds:
        return {}
    labels = [lbl for lbl, _ in rounds]
    metrics = sorted({m for _, flat in rounds for m in flat})
    out = OrderedDict()
    last_lbl = labels[-1]
    last_flat = rounds[-1][1]
    for m in metrics:
        series = OrderedDict((lbl, flat[m]) for lbl, flat in rounds
                             if m in flat)
        rec = {"series": series}
        present = list(series)
        if m.rsplit(".", 1)[-1] in ("iqr", "n"):
            rec["flag"] = "spread"
        elif last_lbl not in series:
            rec["flag"] = "gone"
        elif len(present) == 1:
            rec["flag"] = "new"
        else:
            prev = series[present[-2]]
            cur = series[present[-1]]
            if prev == 0:
                rec["flag"] = "stable" if cur == 0 else "new_nonzero"
            else:
                delta = (cur - prev) / abs(prev)
                rec["delta_pct"] = round(delta * 100.0, 2)
                eff = threshold
                spread = _measured_spread(m, last_flat)
                if spread is not None:
                    eff = max(eff, spread)
                    rec["threshold_pct"] = round(eff * 100.0, 2)
                if abs(delta) <= eff:
                    rec["flag"] = "stable"
                else:
                    worse = delta > 0 if lower_is_better(m) else delta < 0
                    rec["flag"] = "regression" if worse else "improvement"
        out[m] = rec
    return out


def _trend_rows(t, only_flagged=False):
    rows = []
    for m, rec in t.items():
        if only_flagged and rec["flag"] in ("stable", "new", "gone",
                                            "spread"):
            continue
        series = rec["series"]
        vals = " ".join(f"{lbl}={v:g}" for lbl, v in series.items())
        delta = (f"{rec['delta_pct']:+.1f}%" if "delta_pct" in rec
                 else "-")
        rows.append((m, rec["flag"], delta, vals))
    return rows


def render(t, only_flagged=False):
    rows = _trend_rows(t, only_flagged)
    if not rows:
        return "bench trajectory: no metrics" + \
            (" flagged" if only_flagged else " found")
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(("metric", "flag", "delta", "series"))]
    lines = ["  ".join(h.ljust(w) for h, w in
                       zip(("metric", "flag", "delta", "series"), widths)),
             "  ".join("-" * w for w in widths)]
    for r in rows:
        lines.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(lines)


def render_markdown(t, rounds, only_flagged=False):
    """GitHub-flavored markdown trend report (ISSUE 13 satellite) —
    pasteable into a PR description or review round: one table row per
    metric, flags bolded so regressions jump out, and a summary line
    up top. ``|`` in metric paths (none today) would be escaped by the
    cell join; series cells use the same ``label=value`` form as the
    text renderer."""
    rows = _trend_rows(t, only_flagged)
    n_reg = sum(r["flag"] == "regression" for r in t.values())
    n_imp = sum(r["flag"] == "improvement" for r in t.values())
    lines = [
        f"## Bench trajectory",
        "",
        f"{len(rounds)} round(s) ({', '.join(lbl for lbl, _ in rounds)}), "
        f"{len(t)} metric(s): **{n_reg} regression(s)**, "
        f"{n_imp} improvement(s).",
        "",
    ]
    if not rows:
        lines.append("_no metrics" +
                     (" flagged_" if only_flagged else " found_"))
        return "\n".join(lines)
    lines.append("| metric | flag | delta | series |")
    lines.append("| --- | --- | --- | --- |")
    for m, flag, delta, vals in rows:
        shown = f"**{flag}**" if flag in ("regression", "improvement") \
            else flag
        cells = [str(c).replace("|", "\\|")
                 for c in (f"`{m}`", shown, delta, vals)]
        lines.append("| " + " | ".join(cells) + " |")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="*",
                   help="round files (default: BENCH_r*.json in repo root)")
    p.add_argument("--full", default=None,
                   help="a full bench.py JSON output, appended as the "
                        "newest point")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="relative noise threshold (default 0.10 = 10%%)")
    p.add_argument("--flagged", action="store_true",
                   help="show only regressions/improvements")
    p.add_argument("--json", action="store_true",
                   help="emit the trend dict as JSON")
    p.add_argument("--markdown", action="store_true",
                   help="emit the trend table as GitHub-flavored "
                        "markdown (one row per metric, regression/"
                        "improvement flags bolded)")
    args = p.parse_args(argv)
    paths = args.paths
    if not paths:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    if not paths and not args.full:
        print("bench_trajectory: no BENCH_r*.json files found",
              file=sys.stderr)
        return 2
    rounds = load_rounds(paths, full=args.full)
    t = trend(rounds, threshold=args.threshold)
    if args.json:
        print(json.dumps({"threshold": args.threshold, "rounds":
                          [lbl for lbl, _ in rounds], "metrics": t},
                         indent=2))
    elif args.markdown:
        print(render_markdown(t, rounds, only_flagged=args.flagged))
    else:
        n_reg = sum(r["flag"] == "regression" for r in t.values())
        n_imp = sum(r["flag"] == "improvement" for r in t.values())
        print(f"bench trajectory — {len(rounds)} rounds, {len(t)} metrics, "
              f"{n_reg} regression(s), {n_imp} improvement(s) "
              f"@ {args.threshold:.0%} threshold\n")
        print(render(t, only_flagged=args.flagged))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
