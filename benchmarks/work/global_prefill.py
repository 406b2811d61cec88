"""The USEFUL work of the global (full-attention) layers' prefills in the
traced window of a serving run of a model whose keys and values differ in
width and whose global layers have a key-value head count of their own
(``work/gqa_prefill.py``'s rule at two widths): a request of ``prompt_len`` n
attends, in every global layer and query head, its ``n (n + 1) / 2`` (query,
key) pairs, each ``2 x head_dim`` FLOPs for the score and ``2 x v_head_dim``
for the weighted sum (192 and 128 at MiMo-V2.5's sizes). Not counted, so that
it reads as loss: the bucket's padding, the masked half of a block the
diagonal crosses, the lanes a key row is padded to. Bytes: the key and the
value rows of every key-value head read once a token block (``head_dim +
v_head_dim`` live elements of 2 bytes; a token block ``_TOKEN_BLOCK``
positions, the program's ``prompt_block``), queries and results left out.

A request counts only if its ``admitted`` and its ``first_token`` both lie
inside ``trace_span``: a prefill the window cuts counts NOTHING while its
kernel time still counts, so the share can read low and never high. Reads
``shapes`` (``global_layers``, ``heads``, ``global_kv_heads``, ``head_dim``,
``v_head_dim``), ``requests`` and ``trace_span`` of a ``serve_open_loop``
run's observations."""

_TOKEN_BLOCK = 2048     # deepspeed_tpu.models.mimo_v2.MimoV2Config


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
    layers = float(s["global_layers"])
    width = s["head_dim"] + s["v_head_dim"]
    return (pairs * layers * s["heads"] * 2.0 * width,
            rows * layers * s["global_kv_heads"] * width * 2.0)
