"""dstpu-prove tests (ISSUE 15): phase-1 corpus index, the four
TPU-native pass families, interprocedural donation taint, the donation
false-negative regressions, incremental lint identity, SARIF output,
and the seeded real-kernel mutations that pin the teeth of the whole
exercise (a mutated kernel in a tmp copy must fail the lint, and the
unmutated control must not).
"""

import importlib.util
import json
import os
import shutil

import pytest

from deepspeed_tpu.analysis import EXIT_FINDINGS, run_lint
from deepspeed_tpu.analysis.core import Finding, build_corpus
from deepspeed_tpu.analysis.incremental import (DEFAULT_CACHE_NAME,
                                                LintCache)
from deepspeed_tpu.analysis.index import CorpusIndex, ensure_index, \
    module_name
from deepspeed_tpu.analysis.sarif import (SARIF_SUBSET_SCHEMA, to_sarif,
                                          validate_sarif)

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
    dst = tmp_path / relpath
    dst.parent.mkdir(parents=True, exist_ok=True)
    if fixture is not None:
        shutil.copyfile(os.path.join(FIXTURES, fixture), dst)
    else:
        dst.write_text(content)
    return dst


# --------------------------------------------- new-pass fixture twins
# (pass id, fixture stem, install path, min bad findings)
PAIRS = [
    ("pallas-tile", "pallas_tile", "deepspeed_tpu/ops/fx.py", 5),
    ("pallas-dma", "pallas_dma", "deepspeed_tpu/ops/fx.py", 3),
    ("vmem-budget", "vmem_budget", "deepspeed_tpu/ops/fx.py", 2),
    ("sharding-contract", "sharding_contract",
     "deepspeed_tpu/runtime/fx.py", 6),
]


@pytest.mark.parametrize("pass_id,stem,relpath,n_bad",
                         PAIRS, ids=[p[0] for p in PAIRS])
def test_new_pass_catches_bad_silent_on_good(tmp_path, pass_id, stem,
                                             relpath, n_bad):
    bad_root = tmp_path / "bad"
    _plant(bad_root, relpath, fixture=f"{stem}_bad.py")
    res = run_lint(str(bad_root), pass_ids=[pass_id])
    hits = [f for f in res.findings if f.pass_id == pass_id]
    assert len(hits) >= n_bad, \
        f"{pass_id} missed its seeded violations: {res.findings}"
    for f in hits:
        assert f.path.endswith("fx.py") and f.line > 0 and f.message
        assert f.suggestion, "each finding names the exact fix to use"

    good_root = tmp_path / "good"
    _plant(good_root, relpath, fixture=f"{stem}_good.py")
    res = run_lint(str(good_root), pass_ids=[pass_id])
    assert [f for f in res.findings if f.pass_id == pass_id] == [], \
        f"{pass_id} false-positives on the good twin: {res.findings}"


# ----------------------------------------- interprocedural acceptance
def test_donation_through_helper_flagged_fresh_helper_not(tmp_path):
    """THE acceptance fixture: fn A donates into helper B which reads
    the buffer -> flagged; the safe pattern (helper consumes and
    returns fresh, caller rebinds) -> silent."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def helper(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n"
           "def loop(state, batch):\n"
           "    out = helper(state, batch)\n"
           "    return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert len(res.findings) == 1 and res.findings[0].line == 7, \
        res.findings
    assert "helper" in res.findings[0].message

    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def helper(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n"
           "def loop(state, batch):\n"
           "    state = helper(state, batch)\n"
           "    return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings


def test_donation_across_modules(tmp_path):
    """The summary flows through an import: helper in one file, caller
    in another."""
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "import jax\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/loop.py",
           "from deepspeed_tpu.runtime.helpers import consume\n"
           "def run(state, batch):\n"
           "    out = consume(state, batch)\n"
           "    return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.path for f in res.findings] == \
        ["deepspeed_tpu/runtime/loop.py"], res.findings


def test_cross_method_attr_donation(tmp_path):
    """A donating callable bound on self in __init__ taints calls from
    OTHER methods (the gap the per-scope pass cannot see); the
    canonical rebind stays clean."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "class E:\n"
           "    def __init__(self, fn):\n"
           "        self._step = jax.jit(fn, donate_argnums=(0,))\n"
           "    def bad(self, state, batch):\n"
           "        new = self._step(state, batch)\n"
           "        return state.params\n"
           "    def ok(self, state, batch):\n"
           "        state = self._step(state, batch)\n"
           "        return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [7], res.findings
    assert "self._step" in res.findings[0].message


def test_alias_through_helper_taints_both_names(tmp_path):
    """returns-alias-of-arg summaries feed the taint: `alias =
    view(state)` with `def view(a): return a` makes the two names ONE
    buffer, so donating the alias stales `state` too; a helper that
    returns a FRESH value does not link them."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def view(a):\n"
           "    return a\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n"
           "def run(state, batch):\n"
           "    alias = view(state)\n"
           "    out = consume(alias, batch)\n"
           "    return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [10], res.findings

    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def fresh(a):\n"
           "    return a + 1\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n"
           "def run(state, batch):\n"
           "    y = fresh(state)\n"
           "    out = consume(y, batch)\n"
           "    return state.params\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings


def test_axis_index_literal_checked(tmp_path):
    """`axis_index(axis)` takes the axis FIRST — its literal is held to
    the registry like every (value, axis) collective's."""
    _plant(tmp_path, "deepspeed_tpu/m.py",
           "import jax\n"
           "a = jax.lax.axis_index('dta')\n"
           "b = jax.lax.axis_index('data')\n"
           "c = jax.lax.psum(b, 'data')\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [2], res.findings
    assert "`dta`" in res.findings[0].message


def test_unbound_method_call_args_not_shifted(tmp_path):
    """``Engine.step(eng, state)`` passes self EXPLICITLY: the donated
    param maps to the matching call arg 1:1 (no bound-call shift), so
    the read of the donated `state` flags and `eng` does not."""
    _plant(tmp_path, "deepspeed_tpu/runtime/eng.py",
           "import jax\n"
           "class Engine:\n"
           "    def __init__(self, fn):\n"
           "        self._fn = jax.jit(fn, donate_argnums=(1,))\n"
           "    def step(self, state, batch):\n"
           "        return self._fn(self, state)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "from deepspeed_tpu.runtime.eng import Engine\n"
           "def run(eng, state, batch):\n"
           "    y = Engine.step(eng, state, batch)\n"
           "    tok = state.tokens\n"
           "    return eng, tok\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    fx = [f for f in res.findings if f.path.endswith("fx.py")]
    assert [f.line for f in fx] == [4], res.findings
    assert "`state`" in fx[0].message


def test_same_module_unbound_method_call_resolves(tmp_path):
    """``Engine.step(eng, state)`` where Engine lives in the SAME
    module as the caller resolves through the module-prefixed FQN —
    the cross-module twin above must not be the only shape caught —
    while a local rebind of `Engine` shadows the chain entirely."""
    common = (
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self, fn):\n"
        "        self._fn = jax.jit(fn, donate_argnums=(1,))\n"
        "    def step(self, state, batch):\n"
        "        return self._fn(self, state)\n"
        "def run(eng, state, batch):\n"
        "{shadow}"
        "    y = Engine.step(eng, state, batch)\n"
        "    tok = state.tokens\n"
        "    return eng, tok\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           common.format(shadow=""))
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [9], res.findings
    assert "`state`" in res.findings[0].message

    shadowed = tmp_path / "shadowed"
    _plant(shadowed, "deepspeed_tpu/runtime/fx.py",
           common.format(shadow="    Engine = object()\n"))
    res = run_lint(str(shadowed), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings


def test_closure_donation_does_not_pollute_enclosing_summary(tmp_path):
    """A nested closure's donating call must not mark the ENCLOSING
    factory as donating (calling the factory only builds the closure),
    and a nested `def inner(state): return state` must not mark the
    factory returns-alias-of-arg."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "_step = jax.jit(g, donate_argnums=(0,))\n"
           "def schedule(state):\n"
           "    def deferred():\n"
           "        return _step(state)\n"
           "    return deferred\n"
           "def make_ident(state):\n"
           "    def inner(s):\n"
           "        return s\n"
           "    return inner\n"
           "def run(state):\n"
           "    cb = schedule(state)\n"
           "    h = make_ident(state)\n"
           "    x = state.tokens\n"
           "    return cb, h, x\n")
    idx = ensure_index(build_corpus(str(tmp_path)))
    assert idx.functions["deepspeed_tpu.runtime.fx.schedule"].donates \
        == set()
    assert idx.functions[
        "deepspeed_tpu.runtime.fx.make_ident"].returns_args == set()
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings


def test_local_rebind_shadows_module_donor(tmp_path):
    """A local `step = factory()` shadows a same-named module-level
    donating callable — the call must not resolve to the donor."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "step = jax.jit(f, donate_argnums=(0,))\n"
           "def run(state, factory):\n"
           "    step = factory()\n"
           "    out = step(state)\n"
           "    return state.tokens\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings

    # the unshadowed twin DOES resolve to the module-level donor
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "step = jax.jit(f, donate_argnums=(0,))\n"
           "def run(state, factory):\n"
           "    out = step(state)\n"
           "    return state.tokens\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [5], res.findings


def test_axis_registry_parsed_from_corpus(tmp_path):
    """The registry tracks parallel/topology.py, not a hard-coded copy:
    a tree that declares its own axes accepts them and rejects the
    defaults."""
    _plant(tmp_path, "deepspeed_tpu/parallel/topology.py",
           'RING_AXIS = "ring"\n'
           'MESH_AXES = (RING_AXIS,)\n')
    _plant(tmp_path, "deepspeed_tpu/m.py",
           "from jax.sharding import PartitionSpec as P\n"
           "a = P('ring')\n"
           "b = P('data')\n")
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 1 and "`data`" in msgs[0], res.findings


def test_default_axes_match_topology():
    """The fallback registry (synthetic trees without topology.py) is
    pinned to the real one."""
    from deepspeed_tpu.analysis.passes.sharding_contract import \
        DEFAULT_AXES
    from deepspeed_tpu.parallel.topology import MESH_AXES

    assert tuple(DEFAULT_AXES) == tuple(MESH_AXES)


# ------------------------------------------- donation regressions (S3)
def test_donation_augassign_reads_donated_buffer(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(x, g):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    y = step(x)\n"
           "    x += 1\n"
           "    return y\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert [f.line for f in res.findings] == [5], res.findings


def test_donation_try_finally_read_after_return(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(x, g, log):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    try:\n"
           "        y = step(x)\n"
           "        return y\n"
           "    finally:\n"
           "        log(x.sum())\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert [f.line for f in res.findings] == [8], res.findings


def test_donation_tuple_bound_callable(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(x, a, b):\n"
           "    g, h = jax.jit(a, donate_argnums=(0,)), jax.jit(b)\n"
           "    y = g(x)\n"
           "    return x.sum()\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert [f.line for f in res.findings] == [5], res.findings


def test_same_method_bind_reported_once(tmp_path):
    """A donating self-attr bound AND called in the same method belongs
    to donation-safety alone — the source sets stay disjoint, so the
    one defect yields exactly ONE finding across both passes."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "class E:\n"
           "    def warmup(self, b):\n"
           "        self._step = jax.jit(f, donate_argnums=(0,))\n"
           "        out = self._step(self.state, b)\n"
           "        return self.state.tokens\n")
    res = run_lint(str(tmp_path),
                   pass_ids=["donation-safety", "sharding-contract"])
    assert [f.pass_id for f in res.findings] == ["donation-safety"], \
        res.findings


def test_multi_method_bind_still_reported_once(tmp_path):
    """A donating self-attr REBOUND in a second method must not defeat
    the disjointness guard: the bind-and-call method's read stays
    donation-safety's alone (one finding, not two), and a THIRD method
    calling the attr only gets positions every bind provably donates
    (disagreeing binds intersect to nothing — silent)."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "class E:\n"
           "    def warmup(self, b):\n"
           "        self._step = jax.jit(f, donate_argnums=(0,))\n"
           "        out = self._step(self.state, b)\n"
           "        return self.state.tokens\n"
           "    def retune(self, g):\n"
           "        self._step = jax.jit(g, donate_argnums=(0,))\n"
           "    def run(self, state, b):\n"
           "        out = self._step(state, b)\n"
           "        return state.tokens\n")
    res = run_lint(str(tmp_path),
                   pass_ids=["donation-safety", "sharding-contract"])
    assert sorted((f.pass_id, f.line) for f in res.findings) == \
        [("donation-safety", 6), ("sharding-contract", 11)], res.findings

    # binds that DISAGREE on positions intersect to nothing: the
    # cross-method component goes silent, the same-method read stays
    disagree = tmp_path / "disagree"
    _plant(disagree, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "class E:\n"
           "    def warmup(self, b):\n"
           "        self._step = jax.jit(f, donate_argnums=(0,))\n"
           "        out = self._step(self.state, b)\n"
           "        return self.state.tokens\n"
           "    def retune(self, g):\n"
           "        self._step = jax.jit(g, donate_argnums=(1,))\n"
           "    def run(self, state, b):\n"
           "        out = self._step(state, b)\n"
           "        return state.tokens\n")
    res = run_lint(str(disagree),
                   pass_ids=["donation-safety", "sharding-contract"])
    assert [(f.pass_id, f.line) for f in res.findings] == \
        [("donation-safety", 6)], res.findings


def test_donation_try_finally_fallthrough_not_tainted(tmp_path):
    """A return inside try-with-finally defers its taint-clear past the
    finally body — the finally read still flags, but the post-try
    fallthrough (only reachable when the donating branch was not taken)
    must stay clean."""
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(self, b, cond, g):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    try:\n"
           "        if cond:\n"
           "            out = step(self.state, b)\n"
           "            return out\n"
           "    finally:\n"
           "        pass\n"
           "    return self.state\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert res.findings == [], res.findings


def test_donation_canonical_rebinds_still_clean(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           "import jax\n"
           "def f(self, batch, step_fn):\n"
           "    step = jax.jit(step_fn, donate_argnums=(0,))\n"
           "    self.state, m = step(self.state, batch)\n"
           "    self.state, m = step(self.state, batch)\n"
           "    return self.state.params, m\n")
    res = run_lint(str(tmp_path), pass_ids=["donation-safety"])
    assert res.findings == [], res.findings


# ------------------------------------------------------- phase-1 index
def _index_tree(tmp_path) -> CorpusIndex:
    _plant(tmp_path, "deepspeed_tpu/a.py",
           "import jax\n"
           "from deepspeed_tpu.b import sink\n"
           "def donate_direct(x):\n"
           "    f = jax.jit(g, donate_argnums=(0,))\n"
           "    return f(x)\n"
           "def hop(x):\n"
           "    return donate_direct(x)\n"
           "def two_hop(x):\n"
           "    return hop(x)\n"
           "def ident(x, y):\n"
           "    return x\n"
           "def rec_a(x):\n"
           "    return rec_b(x)\n"
           "def rec_b(x):\n"
           "    return rec_a(x)\n"
           "def uses_sink(x):\n"
           "    return sink(x)\n")
    _plant(tmp_path, "deepspeed_tpu/b.py",
           "def sink(x):\n"
           "    return None\n")
    return ensure_index(build_corpus(str(tmp_path)))


def test_index_module_names():
    assert module_name("deepspeed_tpu/ops/decode_step.py") == \
        "deepspeed_tpu.ops.decode_step"
    assert module_name("deepspeed_tpu/serving/__init__.py") == \
        "deepspeed_tpu.serving"


def test_index_donation_fixpoint_two_hops(tmp_path):
    idx = _index_tree(tmp_path)
    fns = idx.functions
    assert fns["deepspeed_tpu.a.donate_direct"].donates == {0}
    assert fns["deepspeed_tpu.a.hop"].donates == {0}
    assert fns["deepspeed_tpu.a.two_hop"].donates == {0}
    assert fns["deepspeed_tpu.a.ident"].donates == set()


def test_index_returns_alias_and_imports(tmp_path):
    idx = _index_tree(tmp_path)
    assert idx.functions["deepspeed_tpu.a.ident"].returns_args == {0}
    # import graph: a imports b; b's dependents include a
    deps = idx.dependents_of({"deepspeed_tpu/b.py"})
    assert "deepspeed_tpu/a.py" in deps


def test_init_relative_imports_resolve_at_package_level(tmp_path):
    """A package __init__'s `from .helpers import consume` anchors at
    the package ITSELF (module_name strips `.__init__`), so donation
    summaries resolve through it."""
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "import jax\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/__init__.py",
           "from .helpers import consume\n"
           "def boot(state, batch):\n"
           "    out = consume(state, batch)\n"
           "    return state.params\n")
    idx = ensure_index(build_corpus(str(tmp_path)))
    assert idx.imports["deepspeed_tpu.runtime"]["consume"] == \
        "deepspeed_tpu.runtime.helpers.consume"
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert [f.path for f in res.findings] == \
        ["deepspeed_tpu/runtime/__init__.py"], res.findings


def test_jit_helpers_have_one_implementation():
    """The jit/donate-argnums parsers live in index.py ONLY — taint.py
    and passes/_ast_util.py re-export (a drift would silently split the
    per-scope pass from the interprocedural summaries)."""
    from deepspeed_tpu.analysis import index, taint
    from deepspeed_tpu.analysis.passes import _ast_util

    assert taint.is_jit_call is index.is_jit_call
    assert _ast_util.is_jit_call is index.is_jit_call
    assert taint.donated_positions is index.donated_positions
    assert taint.attr_chain is index.attr_chain
    assert _ast_util.attr_chain is index.attr_chain


def test_donation_scopes_have_one_definition():
    """The two donation halves (per-scope donation-safety and the
    interprocedural sharding-contract component) cover ONE surface —
    adding an engine directory to one tuple but not the other would
    silently split their coverage."""
    from deepspeed_tpu.analysis.passes import donation, sharding_contract

    assert sharding_contract.DONATION_SCOPES is donation.SCOPES


def test_index_sccs_group_mutual_recursion(tmp_path):
    idx = _index_tree(tmp_path)
    sccs = [c for c in idx.sccs() if len(c) > 1]
    assert sccs and {"deepspeed_tpu.a.rec_a",
                     "deepspeed_tpu.a.rec_b"} in sccs


def test_index_memoized_on_corpus(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/a.py", "x = 1\n")
    corpus = build_corpus(str(tmp_path))
    assert ensure_index(corpus) is ensure_index(corpus)


# --------------------------------------------------- incremental (S1)
def _findings_blob(res) -> str:
    return json.dumps([f.to_json() for f in res.findings],
                      sort_keys=True)


def _seed_tree(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/serving/fx.py",
           "import jax\n"
           "def step(self, out):\n"
           "    return jax.device_get(out)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "import jax\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/loop.py",
           "from deepspeed_tpu.runtime.helpers import consume\n"
           "def run(state, batch):\n"
           "    out = consume(state, batch)\n"
           "    return state.params\n")


PASSES_INC = ["host-sync", "sharding-contract"]


def test_incremental_findings_identical_to_full_run(tmp_path):
    """Cold full run, cache-populating run, and all-hit cached run must
    produce byte-identical findings (the acceptance pin)."""
    _seed_tree(tmp_path)
    root = str(tmp_path)
    cold = run_lint(root, pass_ids=PASSES_INC)
    assert len(cold.findings) == 2      # device_get + donated read

    cache_path = str(tmp_path / DEFAULT_CACHE_NAME)
    cache = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus = build_corpus(root)
    cache.prepare(corpus)
    warm = run_lint(root, pass_ids=PASSES_INC, corpus=corpus,
                    file_cache=cache)
    cache.save()
    assert _findings_blob(warm) == _findings_blob(cold)
    assert cache.misses > 0 and cache.hits == 0

    cache2 = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus2 = build_corpus(root)
    assert cache2.prepare(corpus2) == set()      # nothing invalidated
    hot = run_lint(root, pass_ids=PASSES_INC, corpus=corpus2,
                   file_cache=cache2)
    assert _findings_blob(hot) == _findings_blob(cold)
    assert cache2.misses == 0 and cache2.hits == len(corpus2.files)


def test_incremental_cross_file_invalidation(tmp_path):
    """Changing ONLY the helper file must re-lint its importer: the
    caller's cached cleanliness depended on the helper's summary."""
    root = str(tmp_path)
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "def consume(state, batch):\n"
           "    return (state, batch)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/loop.py",
           "from deepspeed_tpu.runtime.helpers import consume\n"
           "def run(state, batch):\n"
           "    out = consume(state, batch)\n"
           "    return state.params\n")
    cache_path = str(tmp_path / DEFAULT_CACHE_NAME)
    cache = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus = build_corpus(root)
    cache.prepare(corpus)
    res = run_lint(root, pass_ids=PASSES_INC, corpus=corpus,
                   file_cache=cache)
    cache.save()
    assert res.findings == []

    # the helper starts donating; loop.py is untouched on disk
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "import jax\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n")
    cache2 = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus2 = build_corpus(root)
    region = cache2.prepare(corpus2)
    assert "deepspeed_tpu/runtime/loop.py" in region
    res2 = run_lint(root, pass_ids=PASSES_INC, corpus=corpus2,
                    file_cache=cache2)
    assert [f.path for f in res2.findings] == \
        ["deepspeed_tpu/runtime/loop.py"]


def test_incremental_deleted_module_invalidates_importers(tmp_path):
    """Deleting the helper must re-lint its importer: the caller's
    cached FINDING depended on the (now gone) helper's summary, and the
    fresh index no longer knows the deleted relpath's module name."""
    root = str(tmp_path)
    _plant(tmp_path, "deepspeed_tpu/runtime/helpers.py",
           "import jax\n"
           "def consume(state, batch):\n"
           "    step = jax.jit(g, donate_argnums=(0,))\n"
           "    return step(state, batch)\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/loop.py",
           "from deepspeed_tpu.runtime.helpers import consume\n"
           "def run(state, batch):\n"
           "    out = consume(state, batch)\n"
           "    return state.params\n")
    cache_path = str(tmp_path / DEFAULT_CACHE_NAME)
    cache = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus = build_corpus(root)
    cache.prepare(corpus)
    res = run_lint(root, pass_ids=PASSES_INC, corpus=corpus,
                   file_cache=cache)
    cache.save()
    assert [f.path for f in res.findings] == \
        ["deepspeed_tpu/runtime/loop.py"]

    os.remove(tmp_path / "deepspeed_tpu/runtime/helpers.py")
    cold = run_lint(root, pass_ids=PASSES_INC)
    cache2 = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus2 = build_corpus(root)
    region = cache2.prepare(corpus2)
    assert "deepspeed_tpu/runtime/loop.py" in region
    warm = run_lint(root, pass_ids=PASSES_INC, corpus=corpus2,
                    file_cache=cache2)
    assert _findings_blob(warm) == _findings_blob(cold)


def test_incremental_autotune_table_is_global_input(tmp_path):
    """ops/autotune.py feeds the vmem-budget capacity table into files
    that never import it — editing it must drop the whole cache."""
    from deepspeed_tpu.analysis.incremental import GLOBAL_INPUTS
    assert "deepspeed_tpu/ops/autotune.py" in GLOBAL_INPUTS

    root = str(tmp_path)
    _plant(tmp_path, "deepspeed_tpu/ops/autotune.py", "DEFAULT = 16\n")
    _seed_tree(tmp_path)
    cache_path = str(tmp_path / DEFAULT_CACHE_NAME)
    cache = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus = build_corpus(root)
    cache.prepare(corpus)
    run_lint(root, pass_ids=PASSES_INC, corpus=corpus, file_cache=cache)
    cache.save()

    _plant(tmp_path, "deepspeed_tpu/ops/autotune.py", "DEFAULT = 8\n")
    cache2 = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus2 = build_corpus(root)
    region = cache2.prepare(corpus2)
    assert region == set(cache.entries), \
        "a capacity-table edit must invalidate every entry"


def test_incremental_cache_bound_to_pass_set_and_code(tmp_path):
    _seed_tree(tmp_path)
    root = str(tmp_path)
    cache_path = str(tmp_path / DEFAULT_CACHE_NAME)
    cache = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    corpus = build_corpus(root)
    cache.prepare(corpus)
    run_lint(root, pass_ids=PASSES_INC, corpus=corpus, file_cache=cache)
    cache.save()
    # different pass set -> cold cache
    other = LintCache.load(cache_path, root, pass_ids=["host-sync"])
    assert other.entries == {}
    # tampered fingerprint -> cold cache
    raw = json.loads(open(cache_path).read())
    raw["fingerprint"] = "stale"
    open(cache_path, "w").write(json.dumps(raw))
    stale = LintCache.load(cache_path, root, pass_ids=PASSES_INC)
    assert stale.entries == {}


def test_finding_json_round_trip():
    f = Finding("pallas-dma", "deepspeed_tpu/ops/x.py", 7, 3, "msg",
                severity="warning", symbol="K._kern", suggestion="fix")
    assert Finding.from_json(f.to_json()) == f


def test_cli_changed_only_without_git(tmp_path, capsys):
    """--changed-only outside a git repo degrades to a hash-only run
    with identical findings and exit codes."""
    mod = _load_script("dstpu_lint")
    _seed_tree(tmp_path)
    (tmp_path / "README.md").write_text("no metrics\n")
    rc1 = mod.main(["--root", str(tmp_path), "--changed-only",
                    "--no-baseline"])
    assert rc1 == EXIT_FINDINGS
    assert (tmp_path / DEFAULT_CACHE_NAME).exists()
    rc2 = mod.main(["--root", str(tmp_path), "--changed-only",
                    "--no-baseline"])
    assert rc2 == EXIT_FINDINGS
    capsys.readouterr()


# --------------------------------------------------------- SARIF (S2)
def _sarif_doc(tmp_path):
    mod = _load_script("dstpu_lint")
    _seed_tree(tmp_path)
    (tmp_path / "README.md").write_text("no metrics\n")
    out = tmp_path / "lint.sarif"
    rc = mod.main(["--root", str(tmp_path), "--no-baseline",
                   "--sarif", str(out)])
    return rc, json.loads(out.read_text())


def test_sarif_output_validates(tmp_path, capsys):
    rc, doc = _sarif_doc(tmp_path)
    assert rc == EXIT_FINDINGS       # SARIF never launders exit codes
    assert validate_sarif(doc) == []
    jsonschema = pytest.importorskip("jsonschema")
    jsonschema.validate(doc, SARIF_SUBSET_SCHEMA)
    capsys.readouterr()


def test_sarif_results_map_findings(tmp_path, capsys):
    _, doc = _sarif_doc(tmp_path)
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "dstpu-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    results = run["results"]
    assert len(results) == 2
    by_rule = {r["ruleId"] for r in results}
    assert by_rule == {"host-sync", "sharding-contract"} <= rule_ids
    for r in results:
        assert r["level"] == "error"
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].startswith("deepspeed_tpu/")
        assert loc["region"]["startLine"] >= 1
    capsys.readouterr()


def test_sarif_validator_rejects_malformed():
    assert validate_sarif({"version": "2.1.0"})        # missing runs
    bad = {"$schema": "x", "version": "2.1.0", "runs": [
        {"tool": {"driver": {"name": "d"}},
         "results": [{"ruleId": "r", "level": "fatal",
                      "message": {"text": "m"}, "locations": []}]}]}
    probs = validate_sarif(bad)
    assert any("level" in p for p in probs)
    assert any("locations" in p for p in probs)


def test_dma_pairing_checked_in_class_methods(tmp_path):
    """A kernel moved into a class method is still a DMA root: a
    start with no wait there must flag."""
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           "from jax.experimental.pallas import tpu as pltpu\n"
           "class K:\n"
           "    def kern(self, src, dst, sem):\n"
           "        dma = pltpu.make_async_copy(src, dst, sem)\n"
           "        dma.start()\n")
    res = run_lint(str(tmp_path), pass_ids=["pallas-dma"])
    assert len(res.findings) == 1, res.findings
    assert "wait" in res.findings[0].message


def test_dma_factory_bound_handle_pairs_across_spellings(tmp_path):
    """A name bound to a DMA-factory result keys like the call: the
    mixed spelling `h = chunk_dma(0); h.start(); chunk_dma(0).wait()`
    pairs up (no false positive), and the factory-bound dropped-wait
    twin still flags."""
    common = (
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def kern(src, dst, sems):\n"
        "    def chunk_dma(i):\n"
        "        return pltpu.make_async_copy(src.at[i], dst.at[i],\n"
        "                                     sems.at[i])\n"
        "    h = chunk_dma(0)\n"
        "    h.start()\n"
        "    {tail}\n")
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           common.format(tail="chunk_dma(0).wait()"))
    res = run_lint(str(tmp_path), pass_ids=["pallas-dma"])
    assert res.findings == [], res.findings

    bad = tmp_path / "bad"
    _plant(bad, "deepspeed_tpu/ops/fx.py",
           common.format(tail="return dst"))
    res = run_lint(str(bad), pass_ids=["pallas-dma"])
    assert len(res.findings) == 1, res.findings
    assert "never awaited" in res.findings[0].message


def test_vmem_table_parsed_from_analyzed_corpus(tmp_path):
    """The capacity table comes from the CORPUS's ops/autotune.py when
    it ships one — linting --root some-other-tree must use that tree's
    constants, not the installed package's (same convention as the
    sharding-contract axis registry)."""
    _plant(tmp_path, "deepspeed_tpu/ops/autotune.py",
           "DEFAULT_VMEM_MB = 4\n"
           "SCOPED_VMEM_MAX_MB = 8\n")
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           "import jax.numpy as jnp\n"
           "from jax.experimental import pallas as pl\n"
           "def _kern(x_ref, o_ref):\n"
           "    o_ref[...] = x_ref[...]\n"
           "def run(x):\n"
           "    return pl.pallas_call(\n"
           "        _kern,\n"
           "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
           "        compiler_params=pltpu.CompilerParams(\n"
           "            vmem_limit_bytes=40 * 1024 * 1024),\n"
           "    )(x)\n")
    res = run_lint(str(tmp_path), pass_ids=["vmem-budget"])
    assert any("exceeds the scoped-VMEM max (8 MB)" in f.message
               for f in res.findings), res.findings


def test_non_donating_rebind_silences_attr_channel(tmp_path):
    """A self-attr rebound to a PLAIN callable in another method may or
    may not donate at runtime — the channel is unprovable and must go
    silent (can miss, never hallucinate); the jit-only twin still
    flags."""
    common = (
        "import jax\n"
        "class E:\n"
        "    def __init__(self, f):\n"
        "        self._step = jax.jit(f, donate_argnums=(0,))\n"
        "    def configure(self, f):\n"
        "{rebind}"
        "    def run(self, state, b):\n"
        "        out = self._step(state, b)\n"
        "        return state.tokens\n")
    _plant(tmp_path, "deepspeed_tpu/runtime/fx.py",
           common.format(rebind="        self._step = f\n"))
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings

    jit_only = tmp_path / "jit_only"
    _plant(jit_only, "deepspeed_tpu/runtime/fx.py",
           common.format(rebind="        pass\n"))
    res = run_lint(str(jit_only), pass_ids=["sharding-contract"])
    assert [f.line for f in res.findings] == [9], res.findings


def test_vmem_unfoldable_limit_budgets_at_scoped_max(tmp_path):
    """A declared-but-unfoldable vmem_limit_bytes (plan-resolved at
    runtime) budgets the scratch audit at the scoped MAX, not the
    16 MB default — the pass can miss, never hallucinate."""
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           "import jax.numpy as jnp\n"
           "from jax.experimental import pallas as pl\n"
           "from jax.experimental.pallas import tpu as pltpu\n"
           "def _kern(x_ref, o_ref, buf):\n"
           "    o_ref[...] = x_ref[...]\n"
           "def run(x, plan):\n"
           "    return pl.pallas_call(\n"
           "        _kern,\n"
           "        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],\n"
           "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
           "        scratch_shapes=[pltpu.VMEM((2048, 2560), jnp.float32)],\n"
           "        compiler_params=pltpu.CompilerParams(\n"
           "            vmem_limit_bytes=plan.vmem_mb << 20),\n"
           "    )(x)\n")
    # 2048*2560*4 = 20 MB scratch: over the 16 MB default, under the
    # 128 MB scoped max the unfoldable declared limit may reach
    res = run_lint(str(tmp_path), pass_ids=["vmem-budget"])
    assert res.findings == [], res.findings


def test_shared_kernel_conflicting_dtypes_fold_unknown(tmp_path):
    """A kernel reused by call sites with DIFFERENT operand dtypes has
    no provable window quantum — the merged dtype folds to unknown and
    the pass stays silent (no caller is authoritative); with agreeing
    int8 callers the 8-row window still flags."""
    common = (
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def _kern(x_ref, o_ref, sem):\n"
        "    dma = pltpu.make_async_copy(\n"
        "        x_ref.at[pl.ds(0, 8), pl.ds(0, 128)],\n"
        "        o_ref.at[pl.ds(0, 8), pl.ds(0, 128)], sem)\n"
        "    dma.start()\n"
        "    dma.wait()\n"
        "def run(x8, x32):\n"
        "    k = pl.pallas_call(\n"
        "        _kern,\n"
        "        in_specs=[pl.BlockSpec((32, 128), lambda i: (i, 0))],\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.int8),\n"
        "        scratch_shapes=[pltpu.SemaphoreType.DMA],\n"
        "    )(x8.astype(jnp.int8))\n"
        "    f = pl.pallas_call(\n"
        "        _kern,\n"
        "        in_specs=[pl.BlockSpec((32, 128), lambda i: (i, 0))],\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.{d2}),\n"
        "        scratch_shapes=[pltpu.SemaphoreType.DMA],\n"
        "    )(x32.astype(jnp.{d2}))\n"
        "    return k, f\n")
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           common.format(d2="float32"))
    res = run_lint(str(tmp_path), pass_ids=["pallas-tile"])
    assert res.findings == [], res.findings

    agree = tmp_path / "agree"
    _plant(agree, "deepspeed_tpu/ops/fx.py", common.format(d2="int8"))
    res = run_lint(str(agree), pass_ids=["pallas-tile"])
    assert res.findings, "agreeing int8 callers must still flag"


def test_loop_rebound_window_size_folds_unknown(tmp_path):
    """A window size rebound by a TUPLE for-target (`for rows, v in
    ...`) or an AnnAssign is no longer a provable constant — the env
    folds it to unknown and the pass stays silent, while the straight
    single-assignment twin (incl. an annotated `rows: int = 8`) still
    flags the off-quantum int8 window."""
    common = (
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def _kern(x_ref, o_ref, sem):\n"
        "{binds}"
        "    dma = pltpu.make_async_copy(\n"
        "        x_ref.at[pl.ds(0, rows), pl.ds(0, 128)],\n"
        "        o_ref.at[pl.ds(0, rows), pl.ds(0, 128)], sem)\n"
        "    dma.start()\n"
        "    dma.wait()\n"
        "def run(x8):\n"
        "    return pl.pallas_call(\n"
        "        _kern,\n"
        "        in_specs=[pl.BlockSpec((32, 128), lambda i: (i, 0))],\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.int8),\n"
        "        scratch_shapes=[pltpu.SemaphoreType.DMA],\n"
        "    )(x8.astype(jnp.int8))\n")
    silent = {
        "tuple-for": "    rows = 8\n"
                     "    for rows, _v in ((8, 0),):\n"
                     "        pass\n",
        "annassign": "    rows = 8\n"
                     "    rows: int = _dyn()\n",
    }
    for name, binds in silent.items():
        root = tmp_path / name
        _plant(root, "deepspeed_tpu/ops/fx.py", common.format(binds=binds))
        res = run_lint(str(root), pass_ids=["pallas-tile"])
        assert res.findings == [], (name, res.findings)

    for name, binds in {"plain": "    rows = 8\n",
                        "annotated": "    rows: int = 8\n"}.items():
        root = tmp_path / name
        _plant(root, "deepspeed_tpu/ops/fx.py", common.format(binds=binds))
        res = run_lint(str(root), pass_ids=["pallas-tile"])
        assert res.findings, f"{name}: 8-row int8 window must flag"


def test_out_specs_blockspecs_validated(tmp_path):
    """T3 holds out_specs to the tile quanta too — an off-quantum OUT
    block is exactly as corrupting as an off-quantum IN block."""
    _plant(tmp_path, "deepspeed_tpu/ops/fx.py",
           "import jax.numpy as jnp\n"
           "from jax.experimental import pallas as pl\n"
           "def _kern(x_ref, o_ref):\n"
           "    o_ref[...] = x_ref[...]\n"
           "def run(x):\n"
           "    return pl.pallas_call(\n"
           "        _kern,\n"
           "        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],\n"
           "        out_specs=pl.BlockSpec((7, 100), lambda i: (i, 0)),\n"
           "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
           "    )(x)\n")
    res = run_lint(str(tmp_path), pass_ids=["pallas-tile"])
    assert len(res.findings) == 2, res.findings     # 100 lanes + 7 rows
    assert all(f.line == 9 for f in res.findings), res.findings


# ------------------------------------------- vmem artifact gate (S4+)
def test_vmem_budget_flags_unfittable_committed_plan(tmp_path):
    _plant(tmp_path, "deepspeed_tpu/ok.py", "x = 1\n")
    (tmp_path / "AUTOTUNE_KERNELS_MEASURED.json").write_text(json.dumps({
        "metric": "kernel_plan_autotune", "backend": "cpu",
        "plans": {
            "decode_step": {
                # 4*bg*hkv*cs*dh*e = 4*64*8*4096*128*2 = 2 GB vs 40 MB
                "b64_hkv8_s8192_dh128_e2": {
                    "bg": 64, "cs": 4096, "vmem_mb": 40},
                "b4_hkv4_s256_dh64_e2": {
                    "bg": 4, "cs": 256, "vmem_mb": 512},
            },
            "int8_matmul_dma": {
                "d8192_e8192": {"bd": 8192, "be": 8192},
            },
        }}))
    res = run_lint(str(tmp_path), pass_ids=["vmem-budget"])
    msgs = "\n".join(f.message for f in res.findings)
    assert len(res.findings) == 3, res.findings
    assert "cannot fit" in msgs and "outside the scoped clamp" in msgs


def test_vmem_budget_floor_matches_runtime_clamp(tmp_path):
    """The committed-plan range check mirrors decode_step's
    _entry_vmem_mha clamp exactly: vmem_mb below DEFAULT_VMEM_MB is
    silently re-clamped UP on device, so the lint must flag it."""
    from deepspeed_tpu.ops import autotune
    _plant(tmp_path, "deepspeed_tpu/ok.py", "x = 1\n")
    (tmp_path / "AUTOTUNE_KERNELS_MEASURED.json").write_text(json.dumps({
        "plans": {"decode_step": {
            "b4_hkv4_s256_dh64_e2": {"bg": 4, "cs": 256, "vmem_mb": 8},
        }}}))
    res = run_lint(str(tmp_path), pass_ids=["vmem-budget"])
    assert len(res.findings) == 1, res.findings
    assert "outside the scoped clamp" in res.findings[0].message
    assert f"[{autotune.DEFAULT_VMEM_MB}, " \
        f"{autotune.SCOPED_VMEM_MAX_MB}]" in res.findings[0].message


# (test_vmem_budget_committed_repo_artifact_is_clean lives in
# test_lint.py with the other whole-repo pins: the crash-isolation
# harness runs each module in its own child process, so the shared
# full-lint fixture is only shared within ONE module.)


# ------------------------------------- seeded real-kernel mutations
def _mutate(tmp_path, relpath, needle, replacement, count=1):
    src = open(os.path.join(REPO, relpath)).read()
    assert src.count(needle) >= count, f"mutation needle drifted: " \
        f"{needle!r} not in {relpath}"
    _plant(tmp_path, relpath, src.replace(needle, replacement, count))


def _control(tmp_path, relpath):
    _plant(tmp_path, relpath,
           open(os.path.join(REPO, relpath)).read())


MUTATIONS = [
    # shrink the int8 weight-tile DMA window to 8 rows (32-row quantum)
    ("int8-window", "deepspeed_tpu/ops/int8_matmul.py",
     "src.at[pl.ds(di * bd, bd), pl.ds(ei * be, be)]",
     "src.at[pl.ds(di * bd, 8), pl.ds(ei * be, be)]",
     "pallas-tile"),
    # drop the V-chunk DMA wait in the fused decode walk
    ("drop-chunk-wait", "deepspeed_tpu/ops/decode_step.py",
     "                chunk_dma(slot, c, v_ref, vbuf, 1).wait()\n",
     "                pass\n", "pallas-dma"),
    # drop the new-token V-window fetch wait
    ("drop-window-wait", "deepspeed_tpu/ops/decode_step.py",
     "        fv.wait()\n", "", "pallas-dma"),
    # the same in the per-slot walk: a row's V-chunk wait
    ("drop-slot-chunk-wait", "deepspeed_tpu/ops/decode_step.py",
     "                each_row(g, c, lambda *r: "
     "chunk_copy(*r, c, slot, 1).wait())\n",
     "                pass\n", "pallas-dma"),
]


@pytest.mark.parametrize("name,relpath,needle,repl,pass_id",
                         MUTATIONS, ids=[m[0] for m in MUTATIONS])
def test_kernel_mutation_fails_lint(tmp_path, name, relpath, needle,
                                    repl, pass_id):
    _mutate(tmp_path, relpath, needle, repl)
    res = run_lint(str(tmp_path), pass_ids=[pass_id])
    assert res.findings, f"mutation {name} not caught by {pass_id}"
    assert all(f.pass_id == pass_id for f in res.findings)

    ctl = tmp_path / "ctl"
    _control(ctl, relpath)
    res = run_lint(str(ctl), pass_ids=[pass_id])
    assert res.findings == [], \
        f"control copy of {relpath} is not clean: {res.findings}"


def test_donated_helper_mutation_fails_lint(tmp_path):
    """Append a donated-read-through-helper to a tmp copy of the real
    training engine: the interprocedural pass must fail the lint."""
    relpath = "deepspeed_tpu/runtime/engine.py"
    src = open(os.path.join(REPO, relpath)).read()
    _plant(tmp_path, relpath, src + (
        "\n\ndef _mutant_helper(state, batch):\n"
        "    import jax\n"
        "    _step = jax.jit(_mutant_helper, donate_argnums=(0,))\n"
        "    return _step(state, batch)\n"
        "\n\ndef _mutant_loop(state, batch):\n"
        "    _mutant_helper(state, batch)\n"
        "    return state.params\n"))
    res = run_lint(str(tmp_path), pass_ids=["sharding-contract"])
    assert len(res.findings) == 1 and \
        res.findings[0].symbol == "_mutant_loop", res.findings

    ctl = tmp_path / "ctl"
    _control(ctl, relpath)
    res = run_lint(str(ctl), pass_ids=["sharding-contract"])
    assert res.findings == [], res.findings


def test_mutations_fail_through_the_cli(tmp_path, capsys):
    """And the CLI (hence tier-1) exits non-zero on a seeded mutation."""
    mod = _load_script("dstpu_lint")
    _mutate(tmp_path, "deepspeed_tpu/ops/int8_matmul.py",
            "src.at[pl.ds(di * bd, bd), pl.ds(ei * be, be)]",
            "src.at[pl.ds(di * bd, 8), pl.ds(ei * be, be)]")
    (tmp_path / "README.md").write_text("no metrics\n")
    assert mod.main(["--root", str(tmp_path), "--no-baseline"]) \
        == EXIT_FINDINGS
    capsys.readouterr()


# The tier-1 latency pin (S6, test_full_lint_wall_clock_under_budget)
# also lives in test_lint.py, for the same one-module reason.
