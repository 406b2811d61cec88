"""A kernel's share of its roofline: the least time the chip could take for
the work the traced window needed (the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s, both from shapes by ``benchmarks/flops.py``) over the
device time of the events whose names the pattern finds, in percent.
params: ``pattern`` (regex on device event names), ``work`` (a name below)."""
from benchmarks import flops, trace_reduce


def _flash_train(obs):
    t = obs["train"]
    return flops.flash_train_work(
        obs["shapes"], rows=t["rows_per_device_step"] * t["traced_steps"],
        seq_len=t["seq_len"])


def _decode_attn(obs):
    """Cache rows the decode steps of the traced window needed: a token
    committed at decode step i of a request read prompt_len + i rows."""
    lo, hi = obs["trace_span"]
    lens = [r["prompt_len"] + i for r in obs["requests"]
            for i, t in enumerate(r["token_times"]) if i and lo <= t < hi]
    return flops.decode_attn_work(obs["shapes"], context_lens=lens)


WORK = {"flash_train": _flash_train, "decode_attn": _decode_attn}


def read(params, obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    seconds = trace_reduce.matched_seconds(trace, params["pattern"])
    if seconds <= 0.0:
        return None
    n_flops, n_bytes = WORK[params["work"]](obs)
    least, _ = flops.roofline_seconds(n_flops, n_bytes, obs["peak"])
    return 100.0 * least / seconds
