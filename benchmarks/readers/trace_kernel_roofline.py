"""A kernel's share of its roofline: the least time the chip could take for
the work the traced window needed (the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s) over the device time of the events whose names the
pattern finds, in percent. The work is counted by ``benchmarks/work/<work>.py``,
found by name: ``work(observations)`` gives ``(flops, bytes)`` from shapes.
params: ``pattern`` (regex on device event names), ``work`` (that file's name)."""
from benchmarks import flops, harness, trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    seconds = trace_reduce.matched_seconds(trace, params["pattern"])
    if seconds <= 0.0:
        return None
    n_flops, n_bytes = harness.module("work", params["work"]).work(obs)
    least, _ = flops.roofline_seconds(n_flops, n_bytes, obs["peak"])
    return 100.0 * least / seconds
