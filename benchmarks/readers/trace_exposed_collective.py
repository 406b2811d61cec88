"""Share of the traced window spent in collective operations while nothing
else ran on that device, in percent: events whose result name is a
collective's (``trace_reduce.COLLECTIVE``), the wait for an asynchronous one
included; an operation that only reads a collective's result is compute.
No params."""
from benchmarks import trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    return 100.0 * trace_reduce.exposed_collective_seconds(trace) / trace.window_s
