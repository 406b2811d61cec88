"""Test harness setup.

Forces an 8-device CPU-emulated mesh (SURVEY.md §4: the
``--xla_force_host_platform_device_count`` trick gives true multi-device unit
tests without hardware — something the reference's NCCL-forked harness,
tests/unit/common.py, could not do).
"""

import os

# Must be set before the first jax backend initialisation.
_COLLECTIVE_FLAGS = ("--xla_cpu_collective_call_terminate_timeout_seconds=300"
                     " --xla_cpu_collective_timeout_seconds=300")


def _collective_flags_supported() -> bool:
    """XLA treats unknown XLA_FLAGS as FATAL (parse_flags_from_env.cc aborts
    the process), and the collective-timeout flags exist only in some jaxlib
    builds — adding them blindly turns every test process into an instant
    SIGABRT. Probe once in a subprocess; children inherit the cached verdict
    via the environment."""
    cached = os.environ.get("DSTPU_XLA_COLLECTIVE_FLAGS_OK")
    if cached is not None:
        return cached == "1"
    import subprocess
    import sys
    env = dict(os.environ, XLA_FLAGS=_COLLECTIVE_FLAGS, JAX_PLATFORMS="cpu")
    try:
        ok = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            env=env, capture_output=True, timeout=120).returncode == 0
    except Exception:
        ok = False
    os.environ["DSTPU_XLA_COLLECTIVE_FLAGS_OK"] = "1" if ok else "0"
    return ok


_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "collective_call_terminate" not in _flags and _collective_flags_supported():
    # this sandbox exposes ONE cpu core: 8 virtual-device collective threads
    # timeshare it, and long XLA compiles can starve a rendezvous past the
    # default ~20/40s warn/terminate deadlines → spurious hard aborts.
    # Give the rendezvous generous deadlines instead.
    # (warn_stuck_seconds is NOT registered in this jaxlib's flag parser and
    # would be a fatal XLA_FLAGS error)
    #
    # 300s (not more): with the per-module subprocess isolation below, a
    # genuinely wedged collective should abort the CHILD quickly so the
    # parent can retry the module, rather than stall the suite for 15 min.
    _flags += " " + _COLLECTIVE_FLAGS
os.environ["XLA_FLAGS"] = _flags
os.environ["DSTPU_ACCELERATOR"] = "cpu"
if not os.environ.get("DSTPU_TEST_CACHE"):
    # no environment brings a compile cache into the suite (the deadlock
    # described below), neither here nor in a process a test starts
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

# NO persistent compile cache: deserializing a cached XLA:CPU executable
# that contains SUBGROUP collectives (e.g. data-axis allreduce on a tp>1
# mesh) deterministically deadlocks the collective rendezvous — device
# threads end up parked across different collectives of the same run while
# fresh compiles of the identical program run fine (reproduced:
# tests/unit/model_parallelism hangs on a cache HIT, passes after
# `rm -rf` of the cache dir; full-mesh-only programs are unaffected).
# Until the upstream runtime rebuilds collective state on deserialization,
# repeat-compile time is the price of a deadlock-free suite.
if os.environ.get("DSTPU_TEST_CACHE"):       # opt-in escape hatch
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["DSTPU_TEST_CACHE"])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# Tests run on the virtual 8-device CPU backend whatever the environment
# says (the env var alone is not enough once jax has read its config).
jax.config.update("jax_platforms", "cpu")

# NO async dispatch on the CPU test backend: overlapping executions have
# deadlocked multi-axis collective programs mid-suite (~50% of full-suite
# runs wedge inside test_llama_trains' first step with device threads
# parked outside any rendezvous — scheduler starvation among concurrent
# executions time-sharing one core). Synchronous dispatch removes the
# class; it costs nothing here because one core has no real overlap.
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Crash isolation: run each test module in a forked-off child process.
#
# Rationale (reference parity): the reference runs every distributed test in
# a forked child (tests/unit/common.py:86 DistributedExec) precisely so one
# hung NCCL rendezvous cannot kill the whole session.  The XLA:CPU virtual
# 8-device mesh has an analogous hazard on this 1-core sandbox: a starved
# collective rendezvous hard-aborts the process (SIGABRT) after the
# terminate timeout — observed killing full-suite runs at
# test_tp.py::test_llama_trains even with sync dispatch + per-test queue
# drains.  The abort is a scheduler-starvation artifact, not a test bug, so
# the harness owns it: the parent pytest process never touches a device;
# each module's tests execute in a child `pytest` subprocess whose reports
# stream back over a JSONL file.  If a child crashes or times out, the
# module is retried (completed tests keep their first result); only after
# the final attempt are un-run tests reported as failures.
#
# Escape hatch: DSTPU_NO_ISOLATE=1 runs everything in-process (useful for
# pdb).  Children are marked with DSTPU_TEST_CHILD=1.
# ---------------------------------------------------------------------------
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_MODULE_TIMEOUT = int(os.environ.get("DSTPU_MODULE_TIMEOUT", "1800"))
_MODULE_ATTEMPTS = int(os.environ.get("DSTPU_MODULE_ATTEMPTS", "3"))


def pytest_runtest_logreport(report):
    """In a child process, stream every report to the parent as JSONL."""
    path = os.environ.get("DSTPU_CHILD_REPORT")
    if not path:
        return
    lr = report.longrepr
    if isinstance(lr, tuple):
        lr = list(lr)
    elif lr is not None:
        lr = str(lr)
    with open(path, "a") as f:
        f.write(json.dumps({
            "nodeid": report.nodeid, "when": report.when,
            "outcome": report.outcome, "longrepr": lr,
            "duration": report.duration,
        }) + "\n")
        f.flush()


def _replay(session, item, reports):
    """Re-emit a completed child test's reports through the parent's hooks
    so counting, -x/maxfail, and the terminal summary behave natively."""
    from _pytest.reports import TestReport

    session.ihook.pytest_runtest_logstart(
        nodeid=item.nodeid, location=item.location)
    for r in reports:
        lr = r["longrepr"]
        if isinstance(lr, list):
            lr = tuple(lr)
        session.ihook.pytest_runtest_logreport(report=TestReport(
            nodeid=item.nodeid, location=item.location, keywords={},
            outcome=r["outcome"], longrepr=lr, when=r["when"],
            sections=[], duration=r["duration"], user_properties=[]))
    session.ihook.pytest_runtest_logfinish(
        nodeid=item.nodeid, location=item.location)


def _synthesize_failure(session, item, message):
    from _pytest.reports import TestReport

    session.ihook.pytest_runtest_logstart(
        nodeid=item.nodeid, location=item.location)
    session.ihook.pytest_runtest_logreport(report=TestReport(
        nodeid=item.nodeid, location=item.location, keywords={},
        outcome="failed", longrepr=message, when="call",
        sections=[], duration=0.0, user_properties=[]))
    session.ihook.pytest_runtest_logfinish(
        nodeid=item.nodeid, location=item.location)


# module path -> cumulative child wall-clock seconds (all attempts), so
# tier-1 output shows where the 870s budget actually goes — the basis
# for deciding which modules to demote to `slow` when the cap bites
_MODULE_WALLS = {}


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _MODULE_WALLS or os.environ.get("DSTPU_TEST_CHILD"):
        return
    terminalreporter.section("module wall-clock (child subprocess)")
    ranked = sorted(_MODULE_WALLS.items(), key=lambda kv: -kv[1])
    total = sum(_MODULE_WALLS.values())
    for mod, wall in ranked[:15]:
        terminalreporter.write_line(f"{wall:8.1f}s  {mod}")
    if len(ranked) > 15:
        rest = sum(w for _, w in ranked[15:])
        terminalreporter.write_line(
            f"{rest:8.1f}s  ({len(ranked) - 15} more modules)")
    terminalreporter.write_line(f"{total:8.1f}s  total")


def _run_module_child(session, items, attempts=None):
    """Run `items` (all from one module) in child subprocesses, retrying on
    crash/timeout.  Returns when every item has been reported."""
    pending = list(items)
    last_crash = None
    attempts = attempts or _MODULE_ATTEMPTS
    for attempt in range(attempts):
        if not pending:
            return
        fd, report_path = tempfile.mkstemp(suffix=".jsonl")
        os.close(fd)
        env = dict(os.environ,
                   DSTPU_TEST_CHILD="1", DSTPU_CHILD_REPORT=report_path)
        cmd = [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
               "--no-header", *(it.nodeid for it in pending)]
        crashed = None
        try:
            proc = subprocess.run(
                cmd, cwd=str(session.config.rootpath), env=env,
                capture_output=True, text=True, timeout=_MODULE_TIMEOUT)
            if proc.returncode not in (0, 1):  # 1 = ordinary test failures
                crashed = (f"child exited rc={proc.returncode}\n"
                           f"--- child tail ---\n{proc.stdout[-3000:]}\n"
                           f"{proc.stderr[-2000:]}")
        except subprocess.TimeoutExpired as e:
            out = (e.stdout or b"")
            out = out.decode("utf-8", "replace") if isinstance(out, bytes) else out
            crashed = (f"child timed out after {_MODULE_TIMEOUT}s\n"
                       f"--- child tail ---\n{out[-3000:]}")
        # Collect per-test reports; a test is 'done' once its teardown
        # report arrived (partial phases from a crashed attempt discarded).
        by_node = {}
        try:
            with open(report_path) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue  # line truncated by a crash mid-write
                    by_node.setdefault(r["nodeid"], []).append(r)
        finally:
            os.unlink(report_path)
        still_pending = []
        for it in pending:
            if session.shouldfail or session.shouldstop:
                return
            reps = by_node.get(it.nodeid, [])
            if any(r["when"] == "teardown" for r in reps):
                _replay(session, it, reps)
            elif crashed is None:
                # child finished cleanly but never ran it (e.g. child -x);
                # shouldn't happen since the child gets no -x — report it.
                _synthesize_failure(
                    session, it, "child pytest finished without running this "
                    "test (no report received)")
            else:
                still_pending.append(it)
        pending = still_pending
        if crashed and pending and attempt + 1 < attempts:
            tr = session.config.pluginmanager.get_plugin("terminalreporter")
            if tr:
                tr.write_line(
                    f"\n[isolate] {items[0].nodeid.split('::')[0]}: attempt "
                    f"{attempt + 1} crashed ({crashed.splitlines()[0]}); "
                    f"retrying {len(pending)} test(s)", yellow=True)
        last_crash = crashed
    for it in pending:
        _synthesize_failure(
            session, it,
            f"test did not complete in {attempts} isolated child "
            f"attempts\n{last_crash or ''}")


# Under xdist (`-n`, the tier-1 command) the workers' own loop runs the
# tests in the worker, so the module isolation above does not apply there,
# and a starved rendezvous aborts the WORKER: "worker 'gw5' crashed while
# running test_tp.py::test_llama_trains", one failure and no retry (two of
# five whole runs of PR 35's tree, the driver's among them). These tests run
# in a child of the worker, retried when the child crashes.
_XDIST_ISOLATED = ("unit/model_parallelism/test_tp.py::test_llama_trains",)
_XDIST_ATTEMPTS = 5


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    if (os.environ.get("DSTPU_TEST_CHILD")
            or os.environ.get("DSTPU_NO_ISOLATE")
            or not os.environ.get("PYTEST_XDIST_WORKER")
            or not any(item.nodeid.endswith(n) for n in _XDIST_ISOLATED)):
        return None
    _run_module_child(item.session, [item], attempts=_XDIST_ATTEMPTS)
    # what pytest's own protocol does behind every item: the item before
    # this one left the collectors it shares with it set up (test_tp.py's
    # module where it ran there too), and nothing here tears them down. The
    # worker's next item, if xdist hands it one of another module, then
    # fails its set-up with "previous item was not torn down properly" (one
    # whole run of PR 50's tree, by the scheduling alone)
    item.session._setupstate.teardown_exact(nextitem)
    return True


def pytest_runtestloop(session):
    if (os.environ.get("DSTPU_TEST_CHILD")
            or os.environ.get("DSTPU_NO_ISOLATE")
            or session.config.option.collectonly
            or not session.items):
        return None  # default in-process loop
    if getattr(session.config.option, "usepdb", False):
        return None  # debugging needs in-process execution
    # Group by module, preserving the (torch-last) collection order.
    import time as _time

    groups_ = {}
    for it in session.items:
        groups_.setdefault(it.nodeid.split("::")[0], []).append(it)
    for mod_path, mod_items in groups_.items():
        t0 = _time.perf_counter()
        try:
            _run_module_child(session, mod_items)
        finally:
            _MODULE_WALLS[mod_path] = (_MODULE_WALLS.get(mod_path, 0.0)
                                       + _time.perf_counter() - t0)
        if session.shouldfail:
            raise session.Failed(session.shouldfail)
        if session.shouldstop:
            raise session.Interrupted(session.shouldstop)
    return True


# Modules that import torch must run LAST: on a single-core host, torch's
# runtime (once loaded) starves XLA:CPU's multi-device collective rendezvous
# threads — a later 8-device ppermute/psum times out after 20s and the
# process aborts (observed: tests/unit/model_parallelism after
# tests/unit/inference). Ordering all jax-collective tests before the first
# torch import sidesteps the interaction deterministically.
_TORCH_MODULES = ("test_policies", "test_bert", "test_inference",
                  "test_diffusion")

# Quick tier (round-4 VERDICT #9; the reference's CI split,
# .github/workflows/nv-torch-latest-v100.yml:60). Whole modules whose
# measured child-process wall time is small — mostly spec/host logic with
# little XLA compilation. `pytest -m quick` must stay under ~5 min; when
# adding a module here, time it first. Individual tests elsewhere can
# opt in with @pytest.mark.quick.
_QUICK_MODULES = (
    "parallel/test_topology.py",
    "runtime/pipe/test_schedule.py",
    "runtime/test_config.py",
    "runtime/test_tiling.py",
    "launcher/test_launcher.py",
    "aux/test_tuners.py",
    "aux/test_aux_subsystems.py",
    "aux/test_data_pipeline.py",
    "utils/test_debug.py",
    "ops/test_aio.py",
)


# Post-seed modules (PR 3 observability, PR 4 speculative decoding) run
# after every pre-existing module (but before the torch-last group):
# under the 870s tier-1 timeout the suite is budget-bound, and inserting
# new modules mid-stream would push seed modules past the cutoff —
# appending keeps the seed's dot accumulation unchanged and spends only
# LEFTOVER budget on the new tests.
_OBSERVABILITY_MODULES = ("unit/monitor/", "unit/telemetry/",
                          "utils/test_timer", "utils/test_comms_logging")
_LATE_MODULES = _OBSERVABILITY_MODULES + (
    "unit/serving/test_speculative",
    "unit/serving/test_prefix_cache",
    "unit/serving/test_slo",
    "unit/serving/test_fabric",
    "unit/runtime/test_resilience",
    "unit/serving/test_tracing",
    "unit/serving/test_kv_quant",
    "unit/telemetry/test_slo_plane",
    "unit/serving/test_slo_plane",
    "unit/serving/test_autoscale",
    # PR 35: the EXAONE-MoE family's modules (131 s and 31 s of compiles):
    # in directory order they ran beside unit/model_parallelism and
    # starved test_tp.py::test_llama_trains' rendezvous (below)
    "unit/inference/test_exaone_moe",
    "unit/benchmarks/test_exaone_moe",
    # PR 36: three tiny families' serving programs in one module (about
    # 100 s of compiles), kept away from that rendezvous too
    "unit/serving/test_overlapped_decode",
    # PR 41: whole decode steps compiled for the described v5e at the
    # cells' widths (8 to 16 s each, on every core): in directory order
    # unit/ops follows unit/model_parallelism
    "unit/ops/test_tpu_compile",
    # PR 46: the latent-attention family's modules (96 s and 32 s of
    # compiles), kept away from that rendezvous as the EXAONE-MoE ones are
    "unit/inference/test_sarvam_mla",
    "unit/benchmarks/test_sarvam_mla",
    # PR 48: the delta-rule family's modules (125 s and 35 s of compiles),
    # kept away from that rendezvous as the two families' above are
    "unit/inference/test_solar_kda",
    "unit/benchmarks/test_solar_kda",)

# Dead-last group, AFTER even the torch modules: pure-AST, device-free
# suites (the dstpu-lint/prove analysis tests never launch a collective,
# so the torch-starvation hazard above cannot touch them). These are
# also the newest modules — under the budget-bound 870s tier-1 timeout
# they must spend only leftover budget, after every seed test
# (including the torch-last parity group) has reported its dot.
_POST_TORCH_MODULES = ("unit/analysis/",)


def _order_rank(it):
    if any(m in it.nodeid for m in _POST_TORCH_MODULES):
        return 3
    if any(m in it.nodeid for m in _TORCH_MODULES):
        return 2
    if any(m in it.nodeid for m in _LATE_MODULES):
        return 1
    return 0


def pytest_collection_modifyitems(config, items):
    items.sort(key=_order_rank)
    for it in items:
        if any(m in it.nodeid for m in _QUICK_MODULES):
            it.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _reset_groups():
    """Each test starts with fresh global topology state, and no async
    device work survives past its test: per-device queues are FIFO, so a
    tiny blocked computation per device guarantees every straggler
    dispatched by this test has completed before the next test's
    collectives launch (cross-test stragglers have deadlocked
    tests/unit/model_parallelism mid-suite on this 1-core host)."""
    from deepspeed_tpu.utils import groups

    groups.reset()
    yield
    try:
        import jax.numpy as jnp

        arrs = [jax.device_put(jnp.zeros(()), d) for d in jax.devices()]
        jax.block_until_ready([a + 1 for a in arrs])
    except Exception:
        pass
    groups.reset()


@pytest.fixture
def topology8():
    from deepspeed_tpu.parallel.topology import build_topology

    return build_topology()
