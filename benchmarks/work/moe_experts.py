"""The work the routed experts' grouped matmuls needed in the traced window of
a serving run, from shapes, the engine's spans and the run's own counters.

Decode: every ``decode_step`` span that starts in the window is one step. What
a step needed is the run's mean (the counters cover the whole run, the window
is its last seconds at the same offered rate): ``serving/moe_experts_touched``
over ``serving/decode_steps`` experts, each read once (three matrices of
``width x expert_mlp``), and ``serving/moe_assignments_held`` over
``serving/decode_steps`` (token, expert) pairs, each ``6 x width x
expert_mlp`` FLOPs. An expert no token chose needed nothing.

Prefill: a request admitted in the window ran its prompt once. Of its
``prompt_len x experts_per_token`` pairs a sparse layer, the run's held share
(``moe_assignments_held`` over ``moe_assignments``; an eighth where the
counters are missing) was computed here, and every held expert of every
sparse layer was read once (a prompt of 96 tokens leaves an expert without a
token once in 400).

Approximations: the activations' bytes are left out (under 1% of the weights'
in decode); the mean step stands for the window's steps; a prefill that
straddles the window's edge is counted whole or not at all, by its admission;
the least time is taken of the summed FLOPs and bytes, which is at most the
sum of the calls' own least times, so the share can only read low by it.
Reads ``shapes``, ``counters``, ``spans``, ``requests`` and ``trace_span`` of
a ``serve_open_loop`` run's observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s, c = obs["shapes"], obs["counters"]
    expert_elems = 3.0 * s["width"] * s["expert_mlp"]
    steps_run = max(c.get("serving/decode_steps", 0), 1)
    steps = sum(1 for sp in obs["spans"]
                if sp["name"] == "decode_step" and lo <= sp["start"] < hi)
    touched = c.get("serving/moe_experts_touched", 0) / steps_run
    pairs = c.get("serving/moe_assignments_held", 0) / steps_run
    pairs_seen = c.get("serving/moe_assignments", 0)
    share = (c.get("serving/moe_assignments_held", 0) / pairs_seen
             if pairs_seen else s["experts_held"] / s["experts"])
    admitted = [r["prompt_len"] for r in obs["requests"]
                if lo <= r["admitted"] < hi]
    pairs_all = (steps * pairs + sum(admitted) * s["experts_per_token"]
                 * share * s["sparse_layers"])
    experts_read = (steps * touched
                    + len(admitted) * s["sparse_layers"] * s["experts_held"])
    return 2.0 * expert_elems * pairs_all, 2.0 * expert_elems * experts_read
