"""A kernel's share of its roofline: the least time the chip could take for
the work the traced window needed (the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s) over the device time of the events whose names the
pattern finds, in percent. The work is counted by ``benchmarks/work/<work>.py``,
found by name: ``work(observations)`` gives ``(flops, bytes)`` from shapes.
With ``program``, only the events that ran under a jitted program whose name
that regex finds are read, and the work file is asked for that program's work
alone: ``work(observations, phase)``. Nothing where the kernel did not run or
its work counts nothing: a 0 would say that a roofline fell silent.
params: ``pattern`` (regex on device event names), ``work`` (that file's name),
optional ``program`` (regex on the device's module line) with ``phase`` (what
the work file calls that program's work, ``decode`` or ``prefill``)."""
from benchmarks import flops, harness, trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    seconds = trace_reduce.matched_seconds(trace, params["pattern"],
                                           params.get("program"))
    if seconds <= 0.0:
        return None
    phase = (params["phase"],) if "program" in params else ()
    n_flops, n_bytes = harness.module("work", params["work"]).work(obs, *phase)
    least, _ = flops.roofline_seconds(n_flops, n_bytes, obs["peak"])
    return 100.0 * least / seconds if least > 0.0 else None
