"""The work the routed experts' grouped matmuls needed in the traced window of
a serving run, from shapes, the engine's spans and the run's own counters.

An expert is ``expert_matrices`` matrices (default 3: gate, up, down) of
``expert_in_width x expert_mlp`` (default ``width``, the stream's): a family
whose experts are of another form states the two keys in its ``shapes()``
(``nemotron_h``: ``relu(l W1)^2 W2`` in a latent narrower than the stream, 2
matrices of ``latent x expert_mlp``; the two latent projections are plain
matmuls outside the grouped kernel and are not counted). A (token, expert)
pair is 2 FLOPs an element, an expert read 2 bytes an element.

Decode: the counters cover the whole run and the window is its last seconds,
so what the window's steps needed is the run's mean A LIVE SLOT-STEP times
the window's live slot-steps: ``serving/moe_experts_touched`` experts, each
read once, and ``serving/moe_assignments_held`` (token, expert) pairs, both
over ``serving/slot_iterations_active``, times the tokens that decode steps
committed inside the window (every token of a request but its first). An
expert no token chose needed nothing. The offered rate is the same all run,
but the slots live in one window of 3 s are not the run's mean (MiMo's window
carries 1.7 a step for the run's 2.4: a mean STEP read its experts at 92 to
96% of their roofline there, its slot-steps at 66; LongCat's 6.6 for 8.2: 86
and 69; K-EXAONE's 3.6 for 3.1: 52 and 60; my chip runs, PR 66). A window with fewer slots a step than the run
shares an expert among fewer tokens, so its reads are undercounted and the
share reads low, never high, by it. A program without that counter: the run's
mean a STEP (over ``serving/decode_steps``) times the ``decode_step`` spans
that start in the window, as before PR 66.

Prefill: a request admitted in the window ran its prompt once. Of its
``prompt_len x experts_per_token`` pairs a sparse layer, the run's held share
(``moe_assignments_held`` over ``moe_assignments``; an eighth where the
counters are missing) was computed here, and every held expert of every
sparse layer was read once (a prompt of 96 tokens leaves an expert without a
token once in 400).

``phase``: ``"decode"`` or ``"prefill"`` counts that program's work alone, for
a metric that reads that program's events alone (``trace_kernel_roofline``'s
``program``); ``None`` counts both, and is their sum.

Approximations: the activations' bytes are left out (under 1% of the weights'
in decode); the mean slot-step stands for the window's; a prefill that
straddles the window's edge is counted whole or not at all, by its admission;
the least time is taken of the summed FLOPs and bytes, which is at most the
sum of the calls' own least times, so the share can only read low by it.
Reads ``shapes``, ``counters``, ``spans``, ``requests`` and ``trace_span`` of
a ``serve_open_loop`` run's observations."""

PHASES = (None, "decode", "prefill")


def work(obs, phase=None):
    if phase not in PHASES:
        raise ValueError(f"phase {phase!r} is none of {PHASES}")
    lo, hi = obs["trace_span"]
    s, c = obs["shapes"], obs["counters"]
    in_width = s["expert_in_width"] if "expert_in_width" in s else s["width"]
    expert_elems = (float(s.get("expert_matrices", 3)) * in_width
                    * s["expert_mlp"])
    pairs_all = experts_read = 0.0
    if phase != "prefill":
        slot_steps_run = c.get("serving/slot_iterations_active", 0)
        if slot_steps_run:
            share = sum(1 for r in obs["requests"]
                        for i, t in enumerate(r["token_times"])
                        if i > 0 and lo <= t < hi) / slot_steps_run
        else:
            share = sum(1 for sp in obs["spans"] if sp["name"] == "decode_step"
                        and lo <= sp["start"] < hi) / max(
                            c.get("serving/decode_steps", 0), 1)
        pairs_all += share * c.get("serving/moe_assignments_held", 0)
        experts_read += share * c.get("serving/moe_experts_touched", 0)
    if phase != "decode":
        pairs_seen = c.get("serving/moe_assignments", 0)
        held = (c.get("serving/moe_assignments_held", 0) / pairs_seen
                if pairs_seen else s["experts_held"] / s["experts"])
        admitted = [r["prompt_len"] for r in obs["requests"]
                    if lo <= r["admitted"] < hi]
        pairs_all += (sum(admitted) * s["experts_per_token"] * held
                      * s["sparse_layers"])
        experts_read += len(admitted) * s["sparse_layers"] * s["experts_held"]
    return 2.0 * expert_elems * pairs_all, 2.0 * expert_elems * experts_read
