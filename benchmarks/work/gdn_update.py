"""The work the one-token scalar-decay delta-rule update needed in the traced
window of a serving run. A token committed at decode step i > 0 of a request
inside the window was one active slot of one decode iteration: on each
delta-rule layer that slot's state ``[value heads, keys, values]`` was read
once and written once, and updated with 7 FLOPs an element (the decay, the
prediction's multiply-add, the rank-one write's multiply-add, the read-out's
multiply-add). That is what the mathematics needs whatever implements it:
slots that were idle in an iteration needed nothing, a route that passes the
state more than once reads lower, and the convolution's tails are not counted.
Reads ``shapes`` (``gdn_layers``, ``gdn_value_heads``, ``gdn_key_dim``,
``gdn_value_dim``, ``gdn_state_bytes``), ``requests`` and ``trace_span`` of a
``serve_open_loop`` run's observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    slot_steps = sum(1 for r in obs["requests"]
                     for i, t in enumerate(r["token_times"])
                     if i and lo <= t < hi)
    elements = (float(slot_steps) * s["gdn_layers"] * s["gdn_value_heads"]
                * s["gdn_key_dim"] * s["gdn_value_dim"])
    return 7.0 * elements, 2.0 * s["gdn_state_bytes"] * elements
