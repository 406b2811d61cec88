"""The USEFUL work of the softmax grouped-query layers' prefills in the traced
window of a serving run, whatever implements them: a request of ``prompt_len``
n attends, in every softmax layer and query head, its ``n (n + 1) / 2``
(query, key) pairs, each ``2 x head_dim`` FLOPs for the score and ``2 x
head_dim`` for the weighted sum (128 and 128 at Solar-Open2-250B's sizes). Not
counted, so that it reads as loss: the bucket's padding and the masked half
of a block the diagonal crosses. Bytes: the key and the value rows of every
key-value head read once a token block (``head_dim`` elements of 2 bytes each;
a token block ``_TOKEN_BLOCK`` positions, the program's ``prompt_block``),
queries and results left out: under 1% of the least time, FLOPs bound it.

A request counts only if its ``admitted`` and its ``first_token`` both lie
inside ``trace_span``: a prefill the window cuts counts NOTHING while its
kernel time still counts, so the share can read low and never high
(``work/mla_prefill.py``'s rule). Reads ``shapes`` (``attn_layers``,
``heads``, ``kv_heads``, ``head_dim``), ``requests`` and ``trace_span`` of a
``serve_open_loop`` run's observations."""

_TOKEN_BLOCK = 2048     # deepspeed_tpu.models.solar_kda.SolarKdaConfig


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    pairs = rows = 0
    for r in obs["requests"]:
        if r["admitted"] is None or r["first_token"] is None or \
                not (lo <= r["admitted"] and r["first_token"] < hi):
            continue
        n = r["prompt_len"]
        pairs += n * (n + 1) // 2
        # token block i reads the rows [0, end of block)
        blocks = -(-n // _TOKEN_BLOCK)
        rows += sum(min((i + 1) * _TOKEN_BLOCK, n) for i in range(blocks))
    layers, dh = float(s["attn_layers"]), s["head_dim"]
    return (pairs * layers * s["heads"] * 2.0 * (dh + dh),
            rows * layers * s["kv_heads"] * 2.0 * dh * 2.0)
