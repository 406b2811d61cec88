"""Shared static-analysis framework: walker, registry, findings, baseline.

One :class:`FileContext` per source file (source + AST + enclosing-symbol
map + suppression directives, parsed ONCE); a :class:`Corpus` over the
tree; :class:`LintPass` subclasses registered by id.  A pass sees each
in-scope file (:meth:`LintPass.check_file`) and, for whole-corpus
contracts like metric-name coverage, the assembled corpus
(:meth:`LintPass.finalize`).

Suppression directives (comments, parsed from the token stream so a
``#`` inside a string never counts):

  * ``# dstpu-lint: disable=<pass>[,<pass>...] -- <justification>``
    silences the named passes.  The justification is REQUIRED — a
    directive without one is itself a finding.
  * ``# dstpu-lint: fence=<reason>`` is the host-sync allowlist form:
    it marks a *sanctioned* device→host synchronization point (sentinel
    drain, telemetry fence, token emission) rather than a grandfathered
    sin, and only silences the ``host-sync`` pass.

A directive trailing code applies to the whole (possibly multi-line)
statement it sits on; a directive on a comment-only line applies to
the next code line's statement (stacked standalone directives all
target the same statement).  Directives that silence nothing are
reported (burn-down: stale suppressions must go).

Baseline: ``LINT_BASELINE.json`` at the repo root grandfathers findings
by (pass, path, symbol, message) with a required justification and a
``budget`` that the entry count may never exceed — entries that no
longer match anything are reported as stale so the file only shrinks.

Typed exit codes for every CLI built on this framework:
``EXIT_CLEAN`` (0) nothing unsuppressed; ``EXIT_FINDINGS`` (1)
unsuppressed findings / stale baseline / budget exceeded;
``EXIT_USAGE`` (2) unreadable input or bad arguments;
``EXIT_INTERNAL`` (3) a pass crashed (a lint bug, never a tree bug).
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

EXIT_CLEAN = 0
EXIT_FINDINGS = 1
EXIT_USAGE = 2
EXIT_INTERNAL = 3


class UnknownPassError(KeyError):
    """An unknown pass id was requested (a usage error, EXIT_USAGE) —
    distinct from a KeyError raised by a buggy pass mid-run, which is an
    internal error (EXIT_INTERNAL)."""

DEFAULT_BASELINE_NAME = "LINT_BASELINE.json"

# directive grammar: "dstpu-lint:" then disable=<ids> -- <why>, or
# fence=<why> (spelled indirectly here so this comment is not itself one)
_DIRECTIVE_RE = re.compile(
    r"#\s*dstpu-lint:\s*(?P<kind>disable|fence)\s*=\s*(?P<rest>.*)$")


# --------------------------------------------------------------- findings
@dataclass(frozen=True)
class Finding:
    """One contract violation at one site."""

    pass_id: str
    path: str            # repo-relative, forward slashes
    line: int
    col: int
    message: str
    severity: str = "error"          # "error" | "warning"
    symbol: str = ""                 # enclosing Class.function qualname
    suggestion: str = ""             # the exact fix/shim to use

    def format(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        sym = f" [{self.symbol}]" if self.symbol else ""
        out = f"{loc}: {self.severity}: [{self.pass_id}]{sym} {self.message}"
        if self.suggestion:
            out += f"\n    fix: {self.suggestion}"
        return out

    def to_json(self) -> dict:
        return {"pass": self.pass_id, "path": self.path, "line": self.line,
                "col": self.col, "severity": self.severity,
                "symbol": self.symbol, "message": self.message,
                "suggestion": self.suggestion}

    @classmethod
    def from_json(cls, d: dict) -> "Finding":
        """Inverse of :meth:`to_json` (the incremental cache round-trips
        findings through JSON; the pair is pinned by a test)."""
        return cls(pass_id=d["pass"], path=d["path"], line=int(d["line"]),
                   col=int(d["col"]), message=d["message"],
                   severity=d.get("severity", "error"),
                   symbol=d.get("symbol", ""),
                   suggestion=d.get("suggestion", ""))


# -------------------------------------------------------------- directives
@dataclass
class Directive:
    """One inline suppression comment."""

    line: int                  # line the directive SILENCES
    kind: str                  # "disable" | "fence"
    passes: Tuple[str, ...]    # empty for fence (host-sync only)
    reason: str
    src_line: int = 0          # line the COMMENT itself is on
    used: int = 0

    def silences(self, finding: Finding) -> bool:
        if self.kind == "fence":
            return finding.pass_id == "host-sync"
        return finding.pass_id in self.passes


def _next_code_line(lines: List[str], lineno: int) -> int:
    """First line after ``lineno`` that carries code (skips blank and
    comment-only lines, so stacked standalone directives all target the
    same statement)."""
    j = lineno + 1
    while j <= len(lines):
        s = lines[j - 1].strip()
        if s and not s.startswith("#"):
            return j
        j += 1
    return lineno + 1


def parse_directives(source: str, path: str = "<src>",
                     ) -> Tuple[Dict[int, List[Directive]], List[Finding]]:
    """Extract suppression directives from the comment tokens.

    Returns ``({line: [Directive, ...]}, [malformed-directive findings])``.
    A trailing comment's directive silences its own line's statement; a
    comment-only line's directive silences the next code line's.
    """
    directives: Dict[int, List[Directive]] = {}
    errors: List[Finding] = []
    lines = source.splitlines()
    try:
        tokens = [(t.start, t.string) for t in tokenize.generate_tokens(
            io.StringIO(source).readline) if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, SyntaxError, IndentationError):
        # tolerate half-written files: fall back to a line regex (a '#'
        # inside a string could false-positive here, acceptable for the
        # degraded path)
        tokens = [((i, line.index("#")), line[line.index("#"):])
                  for i, line in enumerate(lines, 1) if "#" in line]
    for (lineno, col), text in tokens:
        m = _DIRECTIVE_RE.search(text)
        if not m:
            continue
        kind, rest = m.group("kind"), m.group("rest").strip()
        standalone = lineno <= len(lines) and \
            lines[lineno - 1][:col].strip() == ""
        target = _next_code_line(lines, lineno) if standalone else lineno
        if kind == "fence":
            if not rest:
                errors.append(Finding(
                    "lint-directive", path, lineno, col,
                    "fence directive without a reason: write "
                    "`# dstpu-lint: fence=<why this sync is sanctioned>`"))
                continue
            d = Directive(target, "fence", (), rest, src_line=lineno)
        else:
            left, sep, just = rest.partition("--")
            pass_ids = tuple(p.strip() for p in left.split(",") if p.strip())
            just = just.strip()
            if not pass_ids or not sep or not just:
                errors.append(Finding(
                    "lint-directive", path, lineno, col,
                    "disable directive needs pass ids AND a justification: "
                    "`# dstpu-lint: disable=<pass>[,<pass>] -- <why>`"))
                continue
            d = Directive(target, "disable", pass_ids, just,
                          src_line=lineno)
        directives.setdefault(target, []).append(d)
    return directives, errors


# ------------------------------------------------------------ file context
class FileContext:
    """One parsed source file: AST, enclosing-symbol map, directives."""

    def __init__(self, root: str, path: str):
        self.root = root
        self.path = path
        self.relpath = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            self.source = f.read()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[str] = None
        try:
            self.tree = ast.parse(self.source, filename=path)
        except SyntaxError as e:
            self.parse_error = f"{type(e).__name__}: {e}"
        self.directives, self.directive_errors = parse_directives(
            self.source, self.relpath)
        self._symbols: Dict[int, str] = {}
        # smallest statement span covering each line (for compound
        # statements only the header lines count — a directive deep in
        # an `if` body must not silence a finding on its test)
        self._stmt_span: Dict[int, Tuple[int, int]] = {}
        if self.tree is not None:
            self._map_symbols(self.tree, ())
            for node in ast.walk(self.tree):
                if not isinstance(node, ast.stmt):
                    continue
                start = node.lineno
                end = getattr(node, "end_lineno", start)
                body = getattr(node, "body", None)
                if isinstance(body, list) and body \
                        and hasattr(body[0], "lineno"):
                    end = max(start, body[0].lineno - 1)
                for ln in range(start, end + 1):
                    prev = self._stmt_span.get(ln)
                    if prev is None or end - start < prev[1] - prev[0]:
                        self._stmt_span[ln] = (start, end)

    def _map_symbols(self, node: ast.AST, stack: Tuple[str, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                sub = stack + (child.name,)
                for n in ast.walk(child):
                    if hasattr(n, "lineno"):
                        # innermost scope wins: overwrite as we descend
                        self._symbols[id(n)] = ".".join(sub)
                self._map_symbols(child, sub)
            else:
                self._map_symbols(child, stack)

    def symbol(self, node: ast.AST) -> str:
        return self._symbols.get(id(node), "")

    def stmt_span(self, line: int) -> Tuple[int, int]:
        """Line range of the smallest statement covering ``line`` —
        suppression directives apply statement-wide, so a fence trailing
        ANY line of a wrapped call silences the whole call."""
        return self._stmt_span.get(line, (line, line))

    def finding(self, pass_id: str, node: ast.AST, message: str, *,
                severity: str = "error", suggestion: str = "") -> Finding:
        return Finding(pass_id, self.relpath, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message,
                       severity=severity, symbol=self.symbol(node),
                       suggestion=suggestion)


@dataclass
class Corpus:
    """Every parsed file plus the repo root (for README.md etc.)."""

    root: str
    files: List[FileContext] = field(default_factory=list)

    def by_relpath(self, relpath: str) -> Optional[FileContext]:
        for ctx in self.files:
            if ctx.relpath == relpath:
                return ctx
        return None


# ------------------------------------------------------------------ passes
class LintPass:
    """Base pass. Subclasses set ``id``/``title``/``scope`` and override
    :meth:`check_file` (per-file) and/or :meth:`finalize` (whole corpus,
    runs after every file was visited).  Passes that need phase-1
    interprocedural context (ISSUE 15) override :meth:`begin`, which
    runs once per lint with the assembled corpus BEFORE any file is
    visited — the place to grab the shared
    :func:`~deepspeed_tpu.analysis.index.ensure_index`."""

    id: str = ""
    title: str = ""
    #: relpath prefixes this pass cares about; empty = every file
    scope: Tuple[str, ...] = ()
    #: relpaths never visited (e.g. the shim a pass routes callers to)
    exempt: Tuple[str, ...] = ()

    def in_scope(self, relpath: str) -> bool:
        if any(relpath == e or relpath.startswith(e) for e in self.exempt):
            return False
        if not self.scope:
            return True
        return any(relpath == s or relpath.startswith(s)
                   for s in self.scope)

    def begin(self, corpus: Corpus) -> None:
        """Phase-1 hook: runs once with the whole corpus before any
        :meth:`check_file` call (build/borrow the shared index here)."""

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        return ()

    def finalize(self, corpus: Corpus) -> Iterable[Finding]:
        return ()


_REGISTRY: Dict[str, LintPass] = {}


def register(cls):
    """Class decorator: instantiate and register a pass by its id."""
    inst = cls()
    if not inst.id:
        raise ValueError(f"pass {cls.__name__} has no id")
    if inst.id in _REGISTRY:
        raise ValueError(f"duplicate pass id {inst.id!r}")
    _REGISTRY[inst.id] = inst
    return cls


def load_passes() -> Dict[str, LintPass]:
    """Import the pass modules (populating the registry) and return it."""
    from deepspeed_tpu.analysis import passes  # noqa: F401

    return dict(_REGISTRY)


def registered_passes() -> Dict[str, LintPass]:
    return dict(_REGISTRY)


# ---------------------------------------------------------------- baseline
@dataclass
class BaselineEntry:
    pass_id: str
    path: str
    symbol: str
    message: str
    justification: str
    count: int = 1
    matched: int = 0

    def matches(self, f: Finding) -> bool:
        return (self.pass_id == f.pass_id and self.path == f.path
                and self.symbol == f.symbol and self.message == f.message)

    def to_json(self) -> dict:
        out = {"pass": self.pass_id, "path": self.path,
               "symbol": self.symbol, "message": self.message,
               "justification": self.justification}
        if self.count != 1:
            out["count"] = self.count
        return out


@dataclass
class Baseline:
    budget: int = 0
    entries: List[BaselineEntry] = field(default_factory=list)

    @property
    def total(self) -> int:
        return sum(e.count for e in self.entries)

    @classmethod
    def load(cls, path: str) -> "Baseline":
        """Parse a baseline file; raises ValueError on malformed input
        (mapped to EXIT_USAGE by CLIs)."""
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        if not isinstance(raw, dict):
            raise ValueError("baseline must be a JSON object")
        entries = []
        for i, e in enumerate(raw.get("entries", [])):
            just = str(e.get("justification", "")).strip()
            if not just:
                raise ValueError(
                    f"baseline entry {i} has no justification — every "
                    "grandfathered finding must say why it is allowed")
            entries.append(BaselineEntry(
                pass_id=e["pass"], path=e["path"],
                symbol=e.get("symbol", ""), message=e["message"],
                justification=just, count=int(e.get("count", 1))))
        return cls(budget=int(raw.get("budget",
                                      sum(e.count for e in entries))),
                   entries=entries)

    def dump(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"budget": self.budget,
                       "entries": [e.to_json() for e in self.entries]},
                      f, indent=2, sort_keys=True)
            f.write("\n")


# ------------------------------------------------------------------ runner
@dataclass
class LintResult:
    findings: List[Finding] = field(default_factory=list)       # unsuppressed
    suppressed: List[Tuple[Finding, Directive]] = field(default_factory=list)
    baselined: List[Tuple[Finding, BaselineEntry]] = field(default_factory=list)
    stale_baseline: List[BaselineEntry] = field(default_factory=list)
    over_budget: int = 0            # baseline entries past the budget
    files_scanned: int = 0
    passes_run: Tuple[str, ...] = ()

    @property
    def clean(self) -> bool:
        return not self.findings and not self.stale_baseline \
            and self.over_budget == 0

    def to_json(self) -> dict:
        per_pass: Dict[str, int] = {}
        for f in self.findings:
            per_pass[f.pass_id] = per_pass.get(f.pass_id, 0) + 1
        return {
            "version": 1,
            "files_scanned": self.files_scanned,
            "passes_run": list(self.passes_run),
            "findings": [f.to_json() for f in self.findings],
            "findings_per_pass": per_pass,
            "suppressed": [
                {**f.to_json(), "directive": d.kind, "reason": d.reason}
                for f, d in self.suppressed],
            "baselined": [
                {**f.to_json(), "justification": e.justification}
                for f, e in self.baselined],
            "stale_baseline": [e.to_json() for e in self.stale_baseline],
            "over_budget": self.over_budget,
            "clean": self.clean,
        }


def iter_py_files(root: str,
                  subdirs: Sequence[str] = ("deepspeed_tpu",)) -> List[str]:
    out = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        if os.path.isfile(base):
            out.append(base)
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def build_corpus(root: str,
                 subdirs: Sequence[str] = ("deepspeed_tpu",)) -> Corpus:
    corpus = Corpus(root=root)
    for path in iter_py_files(root, subdirs):
        corpus.files.append(FileContext(root, path))
    return corpus


def run_lint(root: str, *, pass_ids: Optional[Sequence[str]] = None,
             baseline: Optional[Baseline] = None,
             subdirs: Sequence[str] = ("deepspeed_tpu",),
             report_unused_directives: Optional[bool] = None,
             corpus: Optional[Corpus] = None,
             file_cache=None) -> LintResult:
    """Run the registered passes over ``root`` and fold in suppressions
    and the baseline.  ``pass_ids=None`` runs every registered pass;
    unused-directive reporting defaults to on only for full runs (a
    directive for a pass that was not selected is not stale).  Pass a
    pre-built ``corpus`` to reuse already-parsed files (the CLI builds
    it once, for the file cache and the passes).

    ``file_cache`` (incremental mode, ISSUE 15): any object with
    ``lookup(ctx) -> Optional[List[Finding]]`` and ``store(ctx,
    findings)``.  A hit replaces the per-file pass execution for that
    file; finalize passes, directive folding and the baseline always
    run fresh, so a cached and a cold run report identical findings by
    construction (pinned by test).  The cache provider is responsible
    for invalidating entries whose INTERPROCEDURAL inputs changed (see
    :mod:`deepspeed_tpu.analysis.incremental`).
    """
    all_passes = load_passes()
    if pass_ids is None:
        selected = list(all_passes.values())
    else:
        unknown = [p for p in pass_ids if p not in all_passes]
        if unknown:
            raise UnknownPassError(
                f"unknown pass id(s): {', '.join(unknown)} "
                f"(have: {', '.join(sorted(all_passes))})")
        selected = [all_passes[p] for p in pass_ids]
    if report_unused_directives is None:
        report_unused_directives = pass_ids is None

    if corpus is None:
        corpus = build_corpus(root, subdirs)
    for p in selected:
        p.begin(corpus)
    raw: List[Finding] = []
    for ctx in corpus.files:
        for fnd in ctx.directive_errors:
            raw.append(fnd)
        if ctx.parse_error is not None:
            raw.append(Finding("lint-parse", ctx.relpath, 1, 0,
                               f"file does not parse: {ctx.parse_error}"))
            continue
        cached = file_cache.lookup(ctx) if file_cache is not None else None
        if cached is not None:
            raw.extend(cached)
            continue
        file_findings: List[Finding] = []
        for p in selected:
            if p.in_scope(ctx.relpath):
                file_findings.extend(p.check_file(ctx))
        if file_cache is not None:
            file_cache.store(ctx, file_findings)
        raw.extend(file_findings)
    for p in selected:
        raw.extend(p.finalize(corpus))

    result = LintResult(files_scanned=len(corpus.files),
                        passes_run=tuple(p.id for p in selected))
    ctx_by_relpath = {c.relpath: c for c in corpus.files}

    # 1. inline suppressions
    survivors: List[Finding] = []
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.col, f.pass_id)):
        ctx = ctx_by_relpath.get(f.path)
        directive = None
        if ctx is not None and f.pass_id not in ("lint-directive",
                                                 "lint-parse"):
            start, end = ctx.stmt_span(f.line)
            for ln in range(start, end + 1):
                for d in ctx.directives.get(ln, ()):
                    if d.silences(f):
                        directive = d
                        break
                if directive is not None:
                    break
        if directive is not None:
            directive.used += 1
            result.suppressed.append((f, directive))
        else:
            survivors.append(f)

    # 2. stale (unused) directives — suppressions must silence something
    if report_unused_directives:
        for ctx in corpus.files:
            for ds in ctx.directives.values():
                for d in ds:
                    if d.used == 0:
                        survivors.append(Finding(
                            "lint-directive", ctx.relpath,
                            d.src_line or d.line, 0,
                            f"unused {d.kind} directive (nothing on line "
                            f"{d.line} triggers the suppressed pass) — "
                            "remove it",
                            symbol=""))

    # 3. baseline
    if baseline is not None:
        for e in baseline.entries:
            e.matched = 0
        still: List[Finding] = []
        for f in survivors:
            entry = next((e for e in baseline.entries
                          if e.matched < e.count and e.matches(f)), None)
            if entry is not None:
                entry.matched += 1
                result.baselined.append((f, entry))
            else:
                still.append(f)
        survivors = still
        # stale entries only mean something when the pass that produced
        # them actually ran — never report them on --passes subset runs
        ran = set(result.passes_run)
        result.stale_baseline = [
            e for e in baseline.entries
            if e.matched < e.count and e.pass_id in ran]
        if baseline.total > baseline.budget:
            result.over_budget = baseline.total - baseline.budget

    result.findings = survivors
    return result
