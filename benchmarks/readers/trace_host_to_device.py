"""How long the device stands idle at one edge of a host annotation, as a
median over the annotation's events inside the traced window, in
milliseconds, on the lowest numbered device.

``edge: "start"`` (launch latency): from the event's start to the start of
the first device operation at or after it; an event that starts while the
device is busy counts 0. ``edge: "end"`` (return latency): from the end of
the last device operation that ended inside the event to the event's end; an
event inside which no operation ended says nothing and is left out.
params: ``annotation`` (a host event's name), ``edge``."""
import bisect

from benchmarks import stats, trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    lo, hi = trace.window
    events = [(s, e) for name, s, e in trace.host
              if name == params["annotation"] and s >= lo and e <= hi]
    ops = trace.device_ops[min(trace.device_ops)]
    waits = []
    if params["edge"] == "start":
        busy = trace_reduce.union((s, e) for _, s, e in ops)
        starts = [s for s, _ in busy]
        for s, _ in events:
            i = bisect.bisect_right(starts, s)
            if i and busy[i - 1][1] > s:
                waits.append(0.0)
            elif i < len(busy):
                waits.append(busy[i][0] - s)
    elif params["edge"] == "end":
        ends = sorted(e for _, _, e in ops)
        for s, e in events:
            i = bisect.bisect_right(ends, e)
            if i and ends[i - 1] >= s:
                waits.append(e - ends[i - 1])
    else:
        raise ValueError(f"edge {params['edge']!r} is not start or end")
    return stats.median(waits) * 1e3 if waits else None
