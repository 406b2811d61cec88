"""The USEFUL work of the latent-attention prefills in the traced window of a
serving run, whatever implements them: a request of ``prompt_len`` n attends,
in every layer and head, its ``n (n + 1) / 2`` (query, key) pairs, each
``2 x head_dim`` FLOPs for the score and ``2 x v_head_dim`` for the weighted
sum (192 and 128 at Sarvam-105B's decompressed sizes). Not counted, so that
it reads as loss: the bucket's padding, the masked half of a block the
diagonal crosses, and the up-projection of a key block's latent rows to keys
and values, which every token block behind it repeats. Bytes: the latent rows
read once a token block (``cache_row_dim`` elements of 2 bytes; a token block
``_TOKEN_BLOCK`` positions, the program's ``prompt_block``), queries and
results left out: under 0.1% of the least time, FLOPs bound it.

A request counts only if its ``admitted`` and its ``first_token`` both lie
inside ``trace_span``: a prefill the window cuts counts NOTHING while its
kernel time still counts, so the share can read low and never high. Reads
``shapes`` (``layers``, ``heads``, ``head_dim``, ``v_head_dim``,
``cache_row_dim``), ``requests`` and ``trace_span`` of a ``serve_open_loop``
run's observations."""

_TOKEN_BLOCK = 2048     # deepspeed_tpu.models.sarvam_mla.SarvamMlaConfig


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
    per_head = float(s["layers"] * s["heads"])
    return (pairs * per_head * 2.0 * (s["head_dim"] + s["v_head_dim"]),
            rows * float(s["layers"]) * s["cache_row_dim"] * 2.0)
