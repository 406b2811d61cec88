"""dstpu-lint framework + pass tests (ISSUE 14).

Covers: each pass catches its seeded fixture violation and stays silent
on the good twin; suppression directives (fence / disable) round-trip
and demand a justification; the baseline grandfathers, goes stale, and
may never grow past its committed budget; the CLI's typed exit codes;
the seeded hot-path regression the acceptance criteria pin (a
reintroduced `device_get` or unbucketed jit key FAILS the lint); and —
the point of the whole exercise — one end-to-end run over the real
repo pinned CLEAN.
"""

import importlib.util
import json
import os
import shutil

import pytest

from deepspeed_tpu.analysis import (EXIT_CLEAN, EXIT_FINDINGS, EXIT_USAGE,
                                    Baseline, load_passes, run_lint)
from deepspeed_tpu.analysis.core import (Finding, parse_directives)

pytestmark = [pytest.mark.lint, pytest.mark.quick]

HERE = os.path.dirname(__file__)
FIXTURES = os.path.join(HERE, "fixtures")
REPO = os.path.abspath(os.path.join(HERE, "..", "..", ".."))
SCRIPTS = os.path.join(REPO, "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _plant(tmp_path, relpath, content=None, fixture=None):
    """Install a source file into a synthetic repo tree."""
    dst = tmp_path / relpath
    dst.parent.mkdir(parents=True, exist_ok=True)
    if fixture is not None:
        shutil.copyfile(os.path.join(FIXTURES, fixture), dst)
    else:
        dst.write_text(content)
    return dst


# ------------------------------------------------------- fixture corpus
# (pass id, fixture stem, scope-relative install path, min bad findings)
PAIRS = [
    ("host-sync", "host_sync", "deepspeed_tpu/serving/fx.py", 5),
    ("recompile-hazard", "recompile", "deepspeed_tpu/serving/fx.py", 3),
    ("typed-error", "typed_error", "deepspeed_tpu/serving/fx.py", 4),
    ("donation-safety", "donation", "deepspeed_tpu/runtime/fx.py", 2),
]


@pytest.mark.parametrize("pass_id,stem,relpath,n_bad",
                         PAIRS, ids=[p[0] for p in PAIRS])
def test_pass_catches_bad_silent_on_good(tmp_path, pass_id, stem,
                                         relpath, n_bad):
    bad_root = tmp_path / "bad"
    _plant(bad_root, relpath, fixture=f"{stem}_bad.py")
    res = run_lint(str(bad_root), pass_ids=[pass_id])
    hits = [f for f in res.findings if f.pass_id == pass_id]
    assert len(hits) >= n_bad, \
        f"{pass_id} missed its seeded violations: {res.findings}"
    # every finding carries the schema the CLI/JSON contract promises
    for f in hits:
        assert f.path.endswith("fx.py") and f.line > 0 and f.message
        assert f.suggestion, "each finding names the exact fix to use"

    good_root = tmp_path / "good"
    _plant(good_root, relpath, fixture=f"{stem}_good.py")
    res = run_lint(str(good_root), pass_ids=[pass_id])
    assert [f for f in res.findings if f.pass_id == pass_id] == [], \
        f"{pass_id} false-positives on the good twin: {res.findings}"


def test_metric_names_pass_on_synthetic_tree(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/m.py",
           "def f(reg, c):\n"
           "    reg.counter(\"serving/undocumented_thing\").inc()\n"
           "    reg.gauge(f\"fabric/replica_load/{c}\").set(1.0)\n")
    (tmp_path / "README.md").write_text(
        "docs: `fabric/replica_load/<name>` and `train/ghost_metric`\n")
    res = run_lint(str(tmp_path), pass_ids=["metric-names"])
    msgs = [f.message for f in res.findings]
    assert any("serving/undocumented_thing" in m and "not documented" in m
               for m in msgs)
    assert any("train/ghost_metric" in m and "emitted by nothing" in m
               for m in msgs)
    # the wildcard pairing stays silent
    assert not any("replica_load" in m for m in msgs)


def test_slo_rules_pass_fires_on_bad_config(tmp_path):
    # the pass only arms on trees that ship the default config
    _plant(tmp_path, "deepspeed_tpu/telemetry/slo.py", "x = 1\n")
    p = load_passes()["slo-rules"]
    bad = {"slis": [{"name": "x", "kind": "latency", "metric": "m",
                     "threshold_ms": 1, "objective": 0.999}],
           "rules": [{"sli": "x", "short_s": 60, "long_s": 3600,
                      "burn": 5000}]}
    p.config_override = bad
    try:
        res = run_lint(str(tmp_path), pass_ids=["slo-rules"])
    finally:
        p.config_override = None
    assert any("can never fire" in f.message for f in res.findings)
    # and the shipped default is valid (also covered by the e2e pin)
    res = run_lint(str(tmp_path), pass_ids=["slo-rules"])
    assert res.findings == []


# ------------------------------------------------------------ directives
def test_fence_and_disable_suppression_round_trip(tmp_path):
    body = ("import jax\n"
            "def step(self, out):\n"
            "    return int(jax.device_get(out))\n")
    root = tmp_path / "r1"
    _plant(root, "deepspeed_tpu/serving/fx.py", body)
    res = run_lint(str(root), pass_ids=["host-sync"])
    assert len(res.findings) == 1

    for directive in (
            "  # dstpu-lint: fence=token emission",
            "  # dstpu-lint: disable=host-sync -- legacy site, PR-N fixes"):
        root = tmp_path / directive[15:20].strip().replace("=", "")
        _plant(root, "deepspeed_tpu/serving/fx.py",
               body.replace("jax.device_get(out))",
                            "jax.device_get(out))" + directive))
        res = run_lint(str(root), pass_ids=["host-sync"])
        assert res.findings == [] and len(res.suppressed) == 1
        fnd, d = res.suppressed[0]
        assert fnd.pass_id == "host-sync" and d.reason


def test_standalone_directive_covers_next_line(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    # dstpu-lint: fence=batched sentinel drain\n"
           "    return jax.device_get(out)\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"])
    assert res.findings == [] and len(res.suppressed) == 1


def test_directive_requires_justification():
    d, errs = parse_directives("x = 1  # dstpu-lint: disable=host-sync\n")
    assert d == {} and len(errs) == 1 and "justification" in errs[0].message
    d, errs = parse_directives("x = 1  # dstpu-lint: fence=\n")
    assert d == {} and len(errs) == 1 and "reason" in errs[0].message
    d, errs = parse_directives(
        "x = 1  # dstpu-lint: disable=host-sync -- measured: fence-free\n")
    assert errs == [] and d[1][0].passes == ("host-sync",)


def test_unused_directive_is_flagged(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "x = 1  # dstpu-lint: fence=nothing to fence here\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"],
                   report_unused_directives=True)
    assert any(f.pass_id == "lint-directive" and "unused" in f.message
               for f in res.findings)


# -------------------------------------------------------------- baseline
def test_baseline_grandfathers_then_goes_stale(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    return jax.device_get(out)\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"])
    assert len(res.findings) == 1
    f = res.findings[0]
    bl = Baseline(budget=1, entries=[])
    from deepspeed_tpu.analysis import BaselineEntry
    bl.entries.append(BaselineEntry(
        pass_id=f.pass_id, path=f.path, symbol=f.symbol,
        message=f.message, justification="grandfathered: PR-N removes"))
    res = run_lint(str(tmp_path), pass_ids=["host-sync"], baseline=bl)
    assert res.clean and len(res.baselined) == 1

    # fix the violation: the baseline entry is now STALE -> not clean
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py", "x = 1\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"], baseline=bl)
    assert not res.clean and len(res.stale_baseline) == 1

    # growth guard: entries past the committed budget -> not clean
    bl2 = Baseline(budget=0, entries=list(bl.entries))
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    return jax.device_get(out)\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"], baseline=bl2)
    assert not res.clean and res.over_budget == 1


def test_baseline_rejects_missing_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"budget": 1, "entries": [
        {"pass": "host-sync", "path": "x.py", "message": "m"}]}))
    with pytest.raises(ValueError, match="justification"):
        Baseline.load(str(p))


def test_baseline_default_budget_is_count_weighted(tmp_path):
    """A budget-less baseline defaults to its count-weighted total — a
    count>1 entry must not start life over budget."""
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"entries": [
        {"pass": "host-sync", "path": "x.py", "message": "m",
         "justification": "legacy", "count": 3}]}))
    bl = Baseline.load(str(p))
    assert bl.budget == 3 and bl.total == 3


def test_committed_baseline_is_burned_down():
    """The repo ships ZERO grandfathered findings; this number may only
    move toward (or stay at) zero — raising it needs a justification
    visible in this diff."""
    bl = Baseline.load(os.path.join(REPO, "LINT_BASELINE.json"))
    assert bl.total == 0
    assert bl.budget == 0


# ----------------------------------------------- seeded regression (CI pin)
def test_seeded_hot_path_violations_fail_the_lint(tmp_path):
    """Acceptance-criteria pin: a reintroduced hot-path device_get and an
    unbucketed jit cache key each FAIL the lint (and therefore tier-1,
    which runs scripts/dstpu_lint.py)."""
    _plant(tmp_path, "deepspeed_tpu/serving/engine.py",
           "import jax\n"
           "class E:\n"
           "    def step(self, toks):\n"
           "        out = self._decode(toks)\n"
           "        return jax.device_get(out)\n"
           "    def prefill(self, prompt, x):\n"
           "        self._compiled[len(prompt)] = jax.jit(self.fwd)\n"
           "        return self._compiled[len(prompt)](x)\n")
    res = run_lint(str(tmp_path),
                   pass_ids=["host-sync", "recompile-hazard"])
    by_pass = {f.pass_id for f in res.findings}
    assert "host-sync" in by_pass
    assert "recompile-hazard" in by_pass
    # and through the CLI: typed exit code 1
    mod = _load_script("dstpu_lint")
    assert mod.main(["--root", str(tmp_path), "--no-baseline"]) \
        == EXIT_FINDINGS


# --------------------------------------------------- review-hardened edges
def test_donation_conditional_early_return_still_flags(tmp_path):
    """A nested `return` on one branch must not launder a donation read
    on the fallthrough path; a donate+return INSIDE one branch must not
    taint the other branch."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(x, cond):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    y = step(x)\n"
           "    if cond:\n"
           "        return y\n"
           "    return x.sum()\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert len(res.findings) == 1 and res.findings[0].line == 7
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(params, host_opt):\n"
           "    if host_opt is not None:\n"
           "        cast = jax.jit(h, donate_argnums=0)\n"
           "        return cast(params)\n"
           "    return jax.jit(init)(params)   "
           "# dstpu-lint: disable=recompile-hazard -- fixture\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert res.findings == []


def test_donation_nested_function_reports_once(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def outer():\n"
           "    def inner(state, batch):\n"
           "        f = jax.jit(step, donate_argnums=(0,))\n"
           "        y = f(state, batch)\n"
           "        return state.params\n"
           "    return inner\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert len(res.findings) == 1, res.findings


def test_recompile_jit_in_loop_immediate_invoke_reports_once(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def f(xs):\n"
           "    out = []\n"
           "    for x in xs:\n"
           "        out.append(jax.jit(g)(x))\n"
           "    return out\n")
    res = run_lint(str(tmp_path), pass_ids=["recompile-hazard"])
    assert len(res.findings) == 1, res.findings


def test_host_sync_bare_asarray_resolved_through_imports(tmp_path):
    """`from jax.numpy import asarray` is an upload (silent); numpy's is
    a transfer (flagged)."""
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "from jax.numpy import asarray\n"
           "def f(self):\n"
           "    return asarray(self.cache.lengths)\n")
    assert run_lint(str(tmp_path),
                    pass_ids=["host-sync"]).findings == []
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "from numpy import asarray\n"
           "def f(self):\n"
           "    return asarray(self.cache.lengths)\n")
    assert len(run_lint(str(tmp_path),
                        pass_ids=["host-sync"]).findings) == 1


def test_directive_covers_wrapped_statement(tmp_path):
    """A fence trailing the closing line of a wrapped call silences the
    finding on the call's FIRST line (directives apply statement-wide),
    and stacked standalone directives all target the next code line."""
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    tok = int(jax.device_get(\n"
           "        out))  # dstpu-lint: fence=token emission\n"
           "    return tok\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"],
                   report_unused_directives=True)
    assert res.findings == [] and len(res.suppressed) == 1

    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    # dstpu-lint: fence=token emission\n"
           "    # dstpu-lint: disable=recompile-hazard -- warm path\n"
           "    return int(jax.device_get(jax.jit(f)(out)))\n")
    res = run_lint(str(tmp_path),
                   pass_ids=["host-sync", "recompile-hazard"],
                   report_unused_directives=True)
    assert res.findings == [] and len(res.suppressed) == 2


def test_cli_write_errors_are_usage_not_findings(tmp_path, capsys):
    """OSError on report/baseline writes and malformed baseline entries
    exit 2 (usage), never aliasing EXIT_FINDINGS."""
    mod = _load_script("dstpu_lint")
    _plant(tmp_path, "deepspeed_tpu/ok.py", "x = 1\n")
    (tmp_path / "README.md").write_text("no metrics\n")
    assert mod.main(["--root", str(tmp_path), "--sarif",
                     str(tmp_path / "no" / "dir" / "x.json")]) == EXIT_USAGE
    (tmp_path / "LINT_BASELINE.json").write_text(
        json.dumps({"entries": ["not-a-dict"]}))
    assert mod.main(["--root", str(tmp_path)]) == EXIT_USAGE
    capsys.readouterr()


def test_donation_binding_is_position_aware(tmp_path):
    """Calls through a name BEFORE it is bound to the donating jit must
    not taint (and the same name rebound later still does)."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(x, plain_fn, g):\n"
           "    step = plain_fn\n"
           "    y = step(x)\n"
           "    z = x + 1\n"              # legit: step not donating yet
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    w = step(z)\n"
           "    return z.sum()\n")        # BAD: z donated above
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert [f.line for f in res.findings] == [8], res.findings


def test_host_sync_numpy_module_alias(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import numpy as onp\n"
           "def f(self):\n"
           "    return onp.asarray(self.cache.lengths)\n")
    assert len(run_lint(str(tmp_path),
                        pass_ids=["host-sync"]).findings) == 1


def test_unused_standalone_directive_reports_comment_line(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "x = 0\n"
           "y = 1\n"
           "# dstpu-lint: fence=stale fence above clean code\n"
           "z = 2\n")
    res = run_lint(str(tmp_path), pass_ids=["host-sync"],
                   report_unused_directives=True)
    (f,) = [f for f in res.findings if f.pass_id == "lint-directive"]
    assert f.line == 3, f


# ------------------------------------------------------------ CLI contract
def test_cli_typed_exit_codes(tmp_path, capsys):
    mod = _load_script("dstpu_lint")
    # clean synthetic tree -> 0
    _plant(tmp_path, "deepspeed_tpu/ok.py", "x = 1\n")
    (tmp_path / "README.md").write_text("no metrics\n")
    assert mod.main(["--root", str(tmp_path)]) == EXIT_CLEAN
    # unknown pass -> usage error
    assert mod.main(["--root", str(tmp_path), "--passes", "nope"]) \
        == EXIT_USAGE
    # unreadable baseline -> usage error
    (tmp_path / "LINT_BASELINE.json").write_text("{not json")
    assert mod.main(["--root", str(tmp_path)]) == EXIT_USAGE
    capsys.readouterr()


def test_cli_json_output(tmp_path, capsys):
    mod = _load_script("dstpu_lint")
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(out):\n"
           "    return jax.device_get(out)\n")
    rc = mod.main(["--root", str(tmp_path), "--passes", "host-sync",
                   "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == EXIT_FINDINGS and out["clean"] is False
    (f,) = out["findings"]
    assert f["pass"] == "host-sync" and f["path"].endswith("fx.py")
    assert f["line"] == 3 and f["suggestion"]


def test_cli_list_passes(capsys):
    mod = _load_script("dstpu_lint")
    assert mod.main(["--list-passes"]) == EXIT_CLEAN
    out = capsys.readouterr().out
    for pid in ("host-sync", "recompile-hazard", "typed-error",
                "donation-safety", "metric-names",
                "slo-rules", "pallas-tile", "pallas-dma",
                "vmem-budget", "sharding-contract"):
        assert pid in out


# -------------------------------------------------------- the real tree
def test_repo_lints_clean_end_to_end(repo_full_lint):
    """THE pin: the framework lands already having paid for itself —
    every true positive in the current tree is fixed or carries a
    justified suppression, so the repo lints clean.  (The run itself
    is the shared session fixture — one cold full lint feeds every
    whole-repo pin.)"""
    res = repo_full_lint.result
    assert res.clean, "\n".join(f.format() for f in res.findings)
    assert res.files_scanned > 100
    # the fence inventory is non-trivial: the contract is DECLARED syncs
    assert len(res.suppressed) >= 30
    assert all(d.reason for _, d in res.suppressed)


def test_vmem_budget_committed_repo_artifact_is_clean(repo_full_lint):
    """ISSUE 15: the committed AUTOTUNE_KERNELS_MEASURED.json plans all
    fit the capacity table the vmem-budget pass shares with autotune."""
    res = repo_full_lint.result
    assert "vmem-budget" in res.passes_run
    vmem = [f for f in res.findings if f.pass_id == "vmem-budget"]
    assert vmem == [], [f.format() for f in vmem]


def test_full_lint_wall_clock_under_budget(repo_full_lint):
    """ISSUE 15 S6: the phase-1 index must not regress tier-1 — a cold
    full run over the repo (build corpus + index + all passes, the
    CLI's whole hot path, timed once in the shared session fixture)
    stays under 60 s on this sandbox."""
    assert repo_full_lint.result.clean
    assert repo_full_lint.elapsed < 60.0, \
        f"full lint took {repo_full_lint.elapsed:.1f}s"


def test_typed_error_hierarchy_compat():
    """typed-error satellite: the new types keep the ISSUE 9 compat rule
    (ValueError/RuntimeError lineage) so pre-typed except sites hold."""
    from deepspeed_tpu.serving.errors import (EngineConfigError,
                                              EngineInvariantError,
                                              EngineTypeError,
                                              KVLifecycleError,
                                              ServingError)

    assert issubclass(EngineConfigError, ValueError)
    assert issubclass(KVLifecycleError, ValueError)
    assert issubclass(EngineInvariantError, RuntimeError)
    assert issubclass(EngineTypeError, TypeError)
    for t in (EngineConfigError, KVLifecycleError, EngineInvariantError,
              EngineTypeError):
        assert issubclass(t, ServingError)
    # the stdlib lineage holds at the converted wrong-type site
    from deepspeed_tpu.serving.speculative import normalize_speculative
    with pytest.raises(TypeError):
        normalize_speculative(3.7)
    # a real converted site raises the typed error AND the legacy family
    from deepspeed_tpu.serving.kv_quant import normalize_kv_dtype
    with pytest.raises(EngineConfigError):
        normalize_kv_dtype("int3")
    with pytest.raises(ValueError):
        normalize_kv_dtype("int3")
