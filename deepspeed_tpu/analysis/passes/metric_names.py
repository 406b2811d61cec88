"""metric-names — README metric docs exactly cover telemetry call sites.

Migrated from ``scripts/check_metric_names.py`` (ISSUE 11 satellite)
onto the pass framework; the script is now a thin shim over this module
and its CLI/exit-code contract is unchanged (pinned by
tests/unit/telemetry/test_spans.py).  The contract: every counter /
gauge / histogram / event name the code emits appears in README.md
(operators grep the README, not the source), and nothing documented is
emitted by nothing.  f-strings become wildcard patterns
(``f"serving/ttft_ms/p{c}"`` -> ``serving/ttft_ms/p*``); README
``<placeholder>`` segments normalize to ``*``; coverage matches either
direction.
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re
from typing import Dict, List

from deepspeed_tpu.analysis.core import (Corpus, Finding, LintPass,
                                         register)

PREFIXES = ("train", "serving", "fabric", "resilience", "device", "entry",
            "host", "checkpoint", "elastic", "slo", "telemetry")
_NAME_RE = re.compile(
    r"^(?:%s)/[A-Za-z0-9_][A-Za-z0-9_/<>*-]*$" % "|".join(PREFIXES))
# methods whose first string argument is a metric/event name
_METHODS = {"counter", "gauge", "histogram", "event", "record_event",
            "_count", "_gauge", "_observe"}


def _pattern_of(node) -> "str | None":
    """Metric-name pattern of a str/f-string AST node (formatted pieces
    become '*'), or None for non-strings."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        return "".join(parts)
    return None


def _names_in_tree(tree, relpath: str, out: Dict[str, List[str]]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = node.func
        name = (func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name)
                else None)
        if name not in _METHODS:
            continue
        pat = _pattern_of(node.args[0])
        if pat is None or not _NAME_RE.match(pat):
            continue
        out.setdefault(pat, []).append(f"{relpath}:{node.lineno}")


def code_names(root: str) -> dict:
    """{pattern: [file:line, ...]} over every telemetry call site under
    the directory ``root`` (path-based, kept for the shim CLI and the
    tests that drive it on synthetic trees)."""
    out: Dict[str, List[str]] = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, "r", encoding="utf-8") as f:
                    tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue
            _names_in_tree(
                tree, os.path.relpath(path, os.path.dirname(root)), out)
    return out


def readme_names(readme_path: str) -> dict:
    """{pattern: [line_no, ...]} over backticked metric-like tokens,
    ``<placeholder>`` segments normalized to ``*``."""
    out: Dict[str, List[int]] = {}
    with open(readme_path, "r", encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            for tok in re.findall(r"`([^`]+)`", line):
                if not _NAME_RE.match(tok):
                    continue
                pat = re.sub(r"<[^>]*>", "*", tok)
                out.setdefault(pat, []).append(i)
    return out


def _covered(name: str, patterns) -> bool:
    """A name (possibly itself a wildcard pattern) is covered when any
    pattern on the other side matches it — either direction, so
    ``serving/ttft_ms/p*`` (code f-string) pairs with
    ``serving/ttft_ms/p<class>`` (doc placeholder)."""
    for p in patterns:
        if p == name or fnmatch.fnmatchcase(name, p) \
                or fnmatch.fnmatchcase(p, name):
            return True
    return False


def drift(code: dict, docs: dict):
    """(undocumented, stale) between the two sides."""
    undocumented = {n: sites for n, sites in code.items()
                    if not _covered(n, docs)}
    stale = {n: lines for n, lines in docs.items()
             if not _covered(n, code)}
    return undocumented, stale


@register
class MetricNamesPass(LintPass):
    id = "metric-names"
    title = "README metric docs exactly cover telemetry call sites"

    def finalize(self, corpus: Corpus):
        code: Dict[str, List[str]] = {}
        for ctx in corpus.files:
            if ctx.tree is not None:
                _names_in_tree(ctx.tree, ctx.relpath, code)
        readme = os.path.join(corpus.root, "README.md")
        if not os.path.exists(readme):
            yield Finding(self.id, "README.md", 1, 0,
                          "README.md missing: metric names cannot be "
                          "checked against the operator docs")
            return
        docs = readme_names(readme)
        undocumented, stale = drift(code, docs)
        for n in sorted(undocumented):
            path, _, line = undocumented[n][0].rpartition(":")
            yield Finding(
                self.id, path, int(line), 0,
                f"metric `{n}` is emitted by code but not documented in "
                "README.md",
                suggestion="add it to the README metric tables "
                "(operators grep the README, not the source)")
        for n in sorted(stale):
            yield Finding(
                self.id, "README.md", stale[n][0], 0,
                f"metric `{n}` is documented in README.md but emitted "
                "by nothing",
                suggestion="remove the stale doc row (or restore the "
                "emitting call site)")
