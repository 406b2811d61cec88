"""What every kind's runner shares: finding a cell's files by name, the device
guard, the compile cache, the profiler session and the device report."""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import tempfile
from typing import Iterator, Mapping, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoAcceleratorError(RuntimeError):
    """JAX found no TPU, an unknown one, or fewer chips than the cell asks."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[Mapping] = None) -> dict:
    """A cell of ``BENCHMARK.json`` with its configuration and traffic files
    read: everything is found by name."""
    bench = bench or benchmark_json()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"it has {sorted(cells)}")
    cell = dict(cells[name])
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        cell["config_file"] = json.load(f)
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    return cell


def metrics_of(cell_name: str, group: str, bench: Optional[Mapping] = None
               ) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports: those
    with no ``workloads`` key and those that list the cell."""
    bench = bench or benchmark_json()
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def module(package: str, name: str):
    """``benchmarks.<package>.<name>``, found by name."""
    return importlib.import_module(f"benchmarks.{package}.{name}")


def setup_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else at
    the fixed ``benchmarks/.cache`` (the path is part of the cache's key).
    Call before the first ``import jax``."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(BENCH_DIR, ".cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_guard(chips: int, *, rehearse: bool = False) -> dict:
    """The device as JAX reports it and its row of ``peaks.json``. Raises
    unless it is a known TPU with at least ``chips`` chips. ``rehearse``
    (tests only; the command line has no such switch) skips the guard."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = load_json("peaks.json")["devices"]
    if rehearse:
        return {"device": device, "peak": peaks["TPU v5 lite"], "devices":
                devs[:chips]}
    if device["platform"] != "tpu":
        raise NoAcceleratorError(f"JAX found no TPU: {device}")
    if device["kind"] not in peaks:
        raise NoAcceleratorError(
            f"device kind {device['kind']!r} is not in benchmarks/peaks.json")
    if len(devs) < chips:
        raise NoAcceleratorError(
            f"the cell asks for {chips} chip(s), JAX sees {len(devs)}")
    device["count"] = chips
    return {"device": device, "peak": peaks[device["kind"]],
            "devices": devs[:chips]}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use",
                               stats.get("bytes_in_use", 0)))
    return int(max(peaks))


class Profile:
    """A profiler session whose raw files live in a temporary directory and
    are deleted after reduction."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self._window = None

    def start(self):
        import jax

        # Python function events would be most of the file and slow the host
        # loop that is being measured; TraceAnnotations are host events and
        # stay. The HLO proto is not read.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.on = True

    def open_window(self):
        import jax
        from benchmarks.trace_reduce import WINDOW_ANNOTATION

        self._window = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._window.__enter__()

    def close_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self):
        import jax

        self.close_window()
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self):
        """Stop, read the trace, delete the raw files."""
        from benchmarks import trace_reduce

        if not self.on:     # the run ended before the traced part began
            shutil.rmtree(self.dir, ignore_errors=True)
            return None
        self.stop()
        try:
            return trace_reduce.load(trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def profile_if(trace: bool) -> Iterator[Optional[Profile]]:
    prof = Profile() if trace else None
    try:
        yield prof
    finally:
        if prof is not None:
            prof.stop()
            shutil.rmtree(prof.dir, ignore_errors=True)
