"""A whole program's share of the chip's peak: the model FLOPs that the work of
the traced window needed, over the peak bf16 FLOP/s times the device seconds
in which the programs named were running (each run from its first operation
to its last, idle bubbles inside it included; averaged over the chips), in
percent. The FLOPs are one device's, counted by a ``*_model_flops`` function
of ``benchmarks/flops.py`` found by name. Where the kernels' rooflines say how
near each kernel is to ITS bound, this says how much of the chip the step as a
whole uses: a step bound by weight bytes reads a few percent, and a kernel
taken off the path leaves its roofline silent and this one standing.
params: ``program`` (regex on the names of the device's module line,
``jit_<function>(...)``), ``flops`` (that function's name)."""
from benchmarks import flops, trace_reduce


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    seconds = trace_reduce.program_seconds(trace, params["program"])
    if seconds <= 0.0:
        return None
    needed = getattr(flops, params["flops"])(obs)
    if needed <= 0.0:
        return None
    return 100.0 * needed / (obs["peak"]["bf16_tflops"] * 1e12 * seconds)
