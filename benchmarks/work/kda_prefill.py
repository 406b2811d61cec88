"""The work of the delta-rule prefills in the traced window of a serving run,
whatever implements them: a request of ``prompt_len`` n passes, on every
delta-rule layer and head, n positions through the recurrence, each 7 FLOPs an
element of the head's ``[keys, values]`` state (the decay, the prediction's
multiply-add, the rank-one write's multiply-add, the read-out's multiply-add):
the recurrence's own count, which every chunked form exceeds. Bytes: a
position's ``q``, ``k``, ``v`` and ``o`` once at 2 bytes an element and its log
decay ``g`` once at 4, and a head's state read and written once a token block
(``_TOKEN_BLOCK`` positions, the program's ``prompt_block``) in float32. Not
counted, so that it reads as loss: the bucket's padding, the chunked form's
extra FLOPs (scores, the solve), float32 operands and the passes they take.

A request counts only if its ``admitted`` and its ``first_token`` both lie
inside ``trace_span``: a prefill the window cuts counts NOTHING while its
kernel time still counts, so the share can read low and never high
(``work/mla_prefill.py``'s rule). Reads ``shapes`` (``kda_layers``,
``kda_heads``, ``kda_key_dim``, ``kda_value_dim``), ``requests`` and
``trace_span`` of a ``serve_open_loop`` run's observations."""

_TOKEN_BLOCK = 2048     # deepspeed_tpu.models.solar_kda.SolarKdaConfig


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    positions = blocks = 0
    for r in obs["requests"]:
        if r["admitted"] is None or r["first_token"] is None or \
                not (lo <= r["admitted"] and r["first_token"] < hi):
            continue
        positions += r["prompt_len"]
        blocks += -(-r["prompt_len"] // _TOKEN_BLOCK)
    heads = float(s["kda_layers"] * s["kda_heads"])
    dk, dv = s["kda_key_dim"], s["kda_value_dim"]
    return (positions * heads * 7.0 * dk * dv,
            positions * heads * (2.0 * (2 * dk + 2 * dv) + 4.0 * dk)
            + blocks * heads * 2.0 * 4.0 * dk * dv)
