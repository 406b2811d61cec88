"""The work decode attention needed in the traced window of a serving run:
a token committed at decode step i of a request read ``prompt_len + i`` cache
rows. Reads ``shapes``, ``requests`` and ``trace_span`` of a
``serve_open_loop`` run's observations."""
from benchmarks import flops


def work(obs):
    lo, hi = obs["trace_span"]
    lens = [r["prompt_len"] + i for r in obs["requests"]
            for i, t in enumerate(r["token_times"]) if i and lo <= t < hi]
    return flops.decode_attn_work(obs["shapes"], context_lens=lens)
