"""Share of the device's busy time inside the traced window that went to
the operations whose names a pattern finds (self times, averaged over the
chips), in percent. A Pallas kernel's ``name=`` is the result name of its
event, so ``dstpu_flash_(fwd|bwd_)`` finds the three flash kernels whatever wraps
them. params: ``pattern`` (regex on device event names)."""
from benchmarks import trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    busy = trace_reduce.busy_seconds(trace)
    matched = trace_reduce.matched_seconds(trace, params["pattern"])
    if busy <= 0.0 or matched <= 0.0:
        return None
    return 100.0 * matched / busy
