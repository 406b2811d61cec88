"""One reducer from a profiler trace to what the per-layer metrics read.

``load(path)`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
``Trace`` of plain event lists; everything else here is arithmetic on
intervals, so the tests run it on hand-made events.

An event is ``(name, start_s, end_s)`` on the profile's own clock, which
host and device planes share. Device planes are the ``/device:TPU:<n>``
ones; their ``XLA Ops`` line carries one event for each operation, with
control flow (``while``, ``conditional``) as a parent around its body, so
times by name are *self* times: an event's duration less its children's.
Their ``XLA Modules`` line carries one event for each run of a jitted
program, named ``jit_<function>(<fingerprint>)``: an operation ran under the
program whose event holds its start, so a reduction can be held to one
program (``program``: a regex on that name) where a window runs several.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench/window"
# A device event is named by its whole HLO instruction, which also names its
# operands: only the result name, at the start, says what ran. The last name
# is the TPU's wait for a collective it started earlier and split into a
# matmul (``%async-collective-done.4 = ... fusion(...)``).
COLLECTIVE = re.compile(
    r"^%[\w.\-]*(all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute|async-collective-done)")


@dataclasses.dataclass
class Trace:
    """``device_ops[d]``: events of device d's op line. ``host``: the
    ``dstpu/*`` and ``bench/*`` annotations of every host thread.
    ``window``: the traced window on the profile's clock.
    ``device_programs[d]``: events of device d's module line, one for each
    run of a jitted program."""

    device_ops: Dict[int, List[Event]]
    host: List[Event]
    window: Interval
    device_programs: Dict[int, List[Event]] = dataclasses.field(
        default_factory=dict)
    _self: Dict[int, list] = dataclasses.field(default_factory=dict,
                                               repr=False, compare=False)

    def self_times(self, device: int):
        """``self_times`` of one device's op line, computed once."""
        if device not in self._self:
            self._self[device] = self_times(self.device_ops[device])
        return self._self[device]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``. The window is the ``bench/window`` annotation
    the kind's runner opens around the traced work; a trace without one is
    refused, because an idle share needs a window the host defined."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    device_programs: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = device_ops if line.name == OPS_LINE else device_programs
                into.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif not m:
                host.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(("dstpu/", "bench/")))
    windows = [e for e in host if e[0] == WINDOW_ANNOTATION]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_ANNOTATION!r} annotation")
    window = (min(w[1] for w in windows), max(w[2] for w in windows))
    return Trace(device_ops, host, window, device_programs)


# ------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the disjoint sorted ``a`` that no interval of the disjoint
    sorted ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# --------------------------------------------------------------- reductions
def busy_intervals(events: Iterable[Event], window: Interval
                   ) -> List[Interval]:
    return union(clip(((s, e) for _, s, e in events), window))


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.device_ops:
        return 0.0
    return sum(total(busy_intervals(ev, trace.window))
               for ev in trace.device_ops.values()) / len(trace.device_ops)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_seconds(trace) / trace.window_s


def self_times(events: Iterable[Event]) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self_seconds)`` for each event of one line: its
    duration less that of the events nested directly inside it."""
    evs = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    out: List[List] = []
    stack: List[int] = []
    for name, s, e in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [(n, s, e, max(t, 0.0)) for n, s, e, t in out]


def program_intervals(trace: Trace, device: int, program: str
                      ) -> List[Interval]:
    """When the programs whose names the regex finds ran on one device:
    disjoint sorted intervals, not clipped to the window."""
    rx = re.compile(program)
    return union((s, e) for name, s, e in
                 trace.device_programs.get(device, ()) if rx.search(name))


def program_seconds(trace: Trace, program: str) -> float:
    """Seconds inside the window in which a program the regex finds was
    running, from its first operation to its last, averaged over the
    devices."""
    if not trace.device_ops:
        return 0.0
    return sum(total(clip(program_intervals(trace, dev, program),
                          trace.window))
               for dev in trace.device_ops) / len(trace.device_ops)


def time_by_name(trace: Trace, pattern: Optional[str] = None,
                 program: Optional[str] = None) -> Dict[str, float]:
    """Self seconds by operation name inside the window, averaged over the
    devices; with ``pattern``, only names the regex finds; with ``program``,
    only operations that started under a program whose name that regex
    finds (none, in a trace without a module line)."""
    rx = re.compile(pattern) if pattern else None
    acc: Dict[str, float] = {}
    lo, hi = trace.window
    for dev in trace.device_ops:
        under = (None if program is None
                 else program_intervals(trace, dev, program))
        starts = [iv[0] for iv in under or ()]
        for name, s, e, t in trace.self_times(dev):
            if e <= lo or s >= hi or (rx and not rx.search(name)):
                continue
            if under is not None:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= under[i][1]:
                    continue
            acc[name] = acc.get(name, 0.0) + t
    n = max(len(trace.device_ops), 1)
    return {k: v / n for k, v in acc.items()}


def matched_seconds(trace: Trace, pattern: str,
                    program: Optional[str] = None) -> float:
    return sum(time_by_name(trace, pattern, program).values())


def exposed_collective_seconds(trace: Trace) -> float:
    """Seconds inside collective operations (``COLLECTIVE``: by result name,
    waits for asynchronous ones included) during which no other operation
    ran on that device, averaged over the devices. Parents that only wrap
    other events (control flow) are neither collective nor compute."""
    acc = 0.0
    for dev in trace.device_ops:
        leaves = [(n, s, e) for n, s, e, t in trace.self_times(dev)
                  if t >= 0.5 * (e - s)]
        coll = busy_intervals((ev for ev in leaves
                               if COLLECTIVE.search(ev[0])), trace.window)
        other = busy_intervals((ev for ev in leaves
                                if not COLLECTIVE.search(ev[0])),
                               trace.window)
        acc += total(subtract(coll, other))
    return acc / max(len(trace.device_ops), 1)


def idle_gaps(trace: Trace, device: Optional[int] = None, longest: int = 50
              ) -> List[Tuple[str, float]]:
    """The ``longest`` idle gaps of one device (the lowest numbered by
    default) inside the window, longest first, each named by the ``dstpu/*``
    annotation that covers most of it on the host, or ``none``."""
    if not trace.device_ops:
        return []
    dev = min(trace.device_ops) if device is None else device
    busy = busy_intervals(trace.device_ops[dev], trace.window)
    gaps = sorted(subtract([trace.window], busy),
                  key=lambda g: g[0] - g[1])[:longest]
    spans = [ev for ev in trace.host if ev[0].startswith("dstpu/")]
    out = []
    for s, e in gaps:
        best, cover = "none", 0.0
        for name, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append((best, e - s))
    return sorted(out, key=lambda g: -g[1])


HLO_EVENT = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?.*?\s([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """A device event is named by its whole HLO instruction. Keep the result
    name, the first result shape and the opcode; mark a Pallas kernel."""
    m = HLO_EVENT.match(name)
    if not m:
        return name[:96]
    result, shape, opcode = m.group(1), (m.group(2) or "").lstrip("("), m.group(3)
    if "tpu_custom_call" in name:
        opcode = "pallas_kernel"
    return f"{result} {opcode} {shape}".strip()


def breakdown(trace: Trace, n_ops: int = 10, n_gaps: int = 5) -> dict:
    """The ten operations with most device time and the longest idle gaps."""
    ops = sorted(time_by_name(trace).items(), key=lambda kv: -kv[1])[:n_ops]
    return {"device_ops": [[short_name(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace)[:n_gaps]]}


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
