"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in percent. No params."""
from benchmarks import trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    return 100.0 * trace_reduce.idle_share(trace)
