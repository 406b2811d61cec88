"""Test harness setup: an 8-device CPU-emulated mesh (SURVEY.md §4: the
``--xla_force_host_platform_device_count`` trick gives true multi-device unit
tests without hardware — something the reference's NCCL-forked harness,
tests/unit/common.py, could not do), and what keeps its collectives alive
under the tier-1 command's six xdist workers."""

import os
import subprocess
import sys

# Must be set before the first jax backend initialisation.
_COLLECTIVE_FLAGS = ("--xla_cpu_collective_call_terminate_timeout_seconds=300"
                     " --xla_cpu_collective_timeout_seconds=300")


def _collective_flags_supported() -> bool:
    """XLA treats unknown XLA_FLAGS as FATAL (parse_flags_from_env.cc aborts
    the process), and the collective-timeout flags exist only in some jaxlib
    builds. Probe once in a subprocess; children inherit the verdict via the
    environment."""
    cached = os.environ.get("DSTPU_XLA_COLLECTIVE_FLAGS_OK")
    if cached is not None:
        return cached == "1"
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
    # 8 virtual-device collective threads share the cores with the other
    # workers' compiles, which can starve a rendezvous past the default
    # ~20/40s warn/terminate deadlines → spurious hard aborts.
    # (warn_stuck_seconds is NOT registered in this jaxlib: fatal.)
    _flags += " " + _COLLECTIVE_FLAGS
os.environ["XLA_FLAGS"] = _flags
os.environ["DSTPU_ACCELERATOR"] = "cpu"
# NO persistent compile cache, from no environment, neither here nor in a
# process a test starts: deserializing a cached XLA:CPU executable with
# SUBGROUP collectives (a data-axis allreduce on a tp>1 mesh) deadlocks the
# rendezvous; fresh compiles of the identical program run fine (reproduced:
# tests/unit/model_parallelism hangs on a cache HIT, passes after `rm -rf`).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402
import numpy as np  # noqa: E402

# The virtual 8-device CPU backend whatever the environment says (the env var
# alone is not enough once jax has read its config).
jax.config.update("jax_platforms", "cpu")

# NO async dispatch on the CPU test backend: overlapping executions have
# deadlocked multi-axis collective programs mid-suite (device threads parked
# outside any rendezvous). Synchronous dispatch removes the class.
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402


# Under six workers' load this test's step can deadlock on its own collectives
# (one child in four, PR 51: of its 8 device threads three wait in an
# all-gather over devices [1, 3, 5, 7], four in a collective-permute over all
# eight and one in an all-reduce over [4, 5], each for a partner parked in
# another; `rendezvous.cc` says so after 20 s) and XLA aborts the process at
# the terminate time-out ("worker 'gw5' crashed while running test_tp.py::
# test_llama_trains": two of five whole runs of PR 35's tree). So the test's
# BODY runs in a child `pytest` of the worker, retried when the child dies or
# hangs; set-up, teardown and the ONE report stay the worker's. The bound fits
# the test (20 to 45 s a child under load): 65 s a child, 260 s in all.
_ISOLATED_TEST = "unit/model_parallelism/test_tp.py::test_llama_trains"
_CHILD_TIMEOUT_S = 65
_CHILD_ATTEMPTS = 4


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    if ("PYTEST_XDIST_WORKER" not in os.environ
            or not pyfuncitem.nodeid.endswith(_ISOLATED_TEST)):
        return None
    # the child is a plain one-process pytest: nothing of xdist reaches it
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_XDIST")}
    cmd = [sys.executable, "-m", "pytest", "-q", "--no-header", "-p",
           "no:cacheprovider", "-p", "no:xdist", pyfuncitem.nodeid]
    for attempt in range(_CHILD_ATTEMPTS):
        try:
            proc = subprocess.run(
                cmd, cwd=str(pyfuncitem.config.rootpath), env=env,
                capture_output=True, text=True, timeout=_CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            died = f"timed out after {_CHILD_TIMEOUT_S}s"
        else:
            if proc.returncode == 0:
                return True
            if proc.returncode == 1:         # the test itself failed
                pytest.fail(proc.stdout[-3000:], pytrace=False)
            died = f"rc={proc.returncode}\n{proc.stderr[-2000:]}"
        print(f"[isolate] attempt {attempt + 1}: child {died}", file=sys.stderr)
    pytest.fail(f"no child finished in {_CHILD_ATTEMPTS} attempts; the last "
                f"{died}", pytrace=False)


# Modules that import torch run after every jax-collective test, longest
# first: torch's runtime, once loaded in a process, starves XLA:CPU's
# rendezvous threads — a later 8-device ppermute/psum in that worker times out
# and the process aborts (observed: tests/unit/model_parallelism after
# tests/unit/inference). A worker that took one is handed only what follows.
_TORCH_MODULES = ("test_inference", "test_diffusion", "test_policies",
                  "test_bert")

# Before everything: pure-AST, device-free suites that launch no collective
# (the hazard above cannot touch them), 91 tests in half a minute of
# test-seconds. They used to fill the run's tail, after the torch modules;
# there a run that the tier-1 limit cut lost all of them for the five seconds
# of wall they take (PR 55: the driver's runs stopped 90 tests short, these).
# The torch modules end on their own shortest tests, so the tail is as even.
_FIRST_MODULES = ("unit/analysis/",)

# Quick tier (the reference's CI split, .github/workflows/
# nv-torch-latest-v100.yml:60): whole modules of mostly spec/host logic with
# little XLA compilation. `pytest -m quick` must stay under ~5 min; time a
# module before adding it. Single tests opt in with @pytest.mark.quick.
_QUICK_MODULES = (
    "parallel/test_topology.py", "runtime/pipe/test_schedule.py",
    "runtime/test_config.py", "runtime/test_tiling.py",
    "launcher/test_launcher.py", "aux/test_tuners.py",
    "aux/test_aux_subsystems.py", "aux/test_data_pipeline.py",
    "utils/test_debug.py", "ops/test_aio.py",
)


def _order_rank(it):
    """`_FIRST_MODULES`, then directory order, then `_TORCH_MODULES` by their
    place: the one ordering."""
    path = it.nodeid.split("::")[0]
    if any(m in path for m in _FIRST_MODULES):
        return -1
    return max((i + 1 for i, m in enumerate(_TORCH_MODULES) if m in path),
               default=0)


def pytest_collection_modifyitems(config, items):
    items.sort(key=_order_rank)
    for it in items:
        if any(m in it.nodeid for m in _QUICK_MODULES):
            it.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _reset_groups():
    """Each test starts with fresh global topology state, and no async
    device work survives past its test: per-device queues are FIFO, so a tiny
    blocked computation per device guarantees every straggler of this test
    has completed before the next test's collectives launch (cross-test
    stragglers have deadlocked tests/unit/model_parallelism mid-suite)."""
    from deepspeed_tpu.utils import groups

    groups.reset()
    yield
    try:
        arrs = [jax.device_put(np.zeros(()), d) for d in jax.devices()]
        jax.block_until_ready([a + 1 for a in arrs])
    except Exception:
        pass
    groups.reset()
