"""The USEFUL work of the EVA prompt blocks in the traced window of a serving
run, whatever implements them: a request of ``prompt_len`` n passes ``ceil(n
/ W)`` blocks of a window; block ``i`` holds ``r = min(W, n - i W)`` real
positions, which attend, in every layer and head, their ``r (r + 1) / 2``
causal (query, key) pairs inside the block and all ``r x i W / c`` (query,
summary) pairs, 4 FLOPs an element of ``head_dim`` a pair (the score's
multiply-add and the weighted sum's). Not counted, so that it reads as loss:
the bucket's padding, the masked half of a tile the diagonal crosses, the
pooling of the block's chunks. Bytes: the block's own queries, keys and
values and its result once, and the visible summary rows' keys and values
once a block, 2 bytes an element; FLOPs bound it.

A request counts only if its ``admitted`` and its ``first_token`` both lie
inside ``trace_span``: a prefill the window cuts counts NOTHING while its
kernel time still counts, so the share can read low and never high. Reads
``shapes`` (``layers``, ``heads``, ``head_dim``, ``window``, ``chunk``),
``requests`` and ``trace_span`` of a ``serve_open_loop`` run's
observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    w, c = s["window"], s["chunk"]
    pairs = rows = 0
    for r in obs["requests"]:
        if r["admitted"] is None or r["first_token"] is None or \
                not (lo <= r["admitted"] and r["first_token"] < hi):
            continue
        n = r["prompt_len"]
        for i in range(-(-n // w)):
            real = min(w, n - i * w)
            seen = i * (w // c)
            pairs += real * (real + 1) // 2 + real * seen
            rows += 4 * real + 2 * seen
    per_head = float(s["layers"] * s["heads"] * s["head_dim"])
    return 4.0 * pairs * per_head, 2.0 * rows * per_head
