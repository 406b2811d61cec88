"""The work the fused decode step needed in the traced window of a serving run
of a model with rows on its global layers and rings on its sliding ones: a
token committed at decode step i of a request attended ``n = prompt_len + i``
cached positions on each global layer and the last ``min(n, window)`` of them
on each sliding one. A row is the LIVE elements of its key and its value, ``kv
heads x (head_dim + v_head_dim)`` of 2 bytes by the layer's kind (lanes a key
row is padded to are not counted and read as loss); a query head does ``2 x
head_dim`` FLOPs for a row's score and ``2 x v_head_dim`` for its share of the
weighted sum. Not counted: the new token's own row, the walk's rounding of a
slot's rows to chunks of 128, idle slots.

Reads ``shapes`` (``heads``, ``head_dim``, ``window``, ``global_layers``,
``sliding_layers``, and optionally ``v_head_dim``, ``global_kv_heads``,
``sliding_kv_heads``: a family with one head count states ``kv_heads``
alone), ``requests`` and ``trace_span`` of a ``serve_open_loop`` run's
observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    window = s["window"]
    rows = ring = 0
    for r in obs["requests"]:
        for i, t in enumerate(r["token_times"]):
            if i and lo <= t < hi:
                n = r["prompt_len"] + i
                rows += n
                ring += min(n, window)
    dk = s["head_dim"]
    width = dk + s.get("v_head_dim", dk)
    g_rows, s_rows = float(s["global_layers"] * rows), \
        float(s["sliding_layers"] * ring)
    g_kv = s.get("global_kv_heads", s["kv_heads"])
    s_kv = s.get("sliding_kv_heads", s["kv_heads"])
    return ((g_rows + s_rows) * s["heads"] * 2.0 * width,
            (g_rows * g_kv + s_rows * s_kv) * width * 2.0)
