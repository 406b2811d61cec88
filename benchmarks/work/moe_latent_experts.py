"""The work a LATENT expert layer's grouped matmuls needed in the traced window
of a serving run: as ``work/moe_experts.py``, from the same counters and spans,
but an expert is TWO matrices of ``latent x expert_mlp`` (``relu(l W1)^2 W2``,
no gate matrix, in a latent narrower than the stream), so a (token, expert)
pair is ``4 x latent x expert_mlp`` FLOPs and an expert read ``2 x latent x
expert_mlp`` elements. The two latent projections are plain matmuls outside
the grouped kernel and are not counted here.

Decode: every ``decode_step`` span that starts in the window is one step, and
what a step needed is the run's mean: ``serving/moe_experts_touched`` over
``serving/decode_steps`` experts read once each, ``serving/moe_assignments_held``
over ``serving/decode_steps`` pairs. Prefill: a request admitted in the window
ran its prompt once; of its ``prompt_len x experts_per_token`` pairs a sparse
layer the run's held share was computed here, and every held expert of every
sparse layer was read once. The approximations are ``work/moe_experts.py``'s.
Reads ``shapes`` (``latent``, ``expert_mlp``, ``experts``, ``experts_held``,
``experts_per_token``, ``sparse_layers``), ``counters``, ``spans``,
``requests`` and ``trace_span`` of a ``serve_open_loop`` run's observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s, c = obs["shapes"], obs["counters"]
    expert_elems = 2.0 * s["latent"] * s["expert_mlp"]
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
