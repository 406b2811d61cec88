"""The work the absorbed latent-attention decode step needed in the traced
window of a serving run: a token committed at decode step i > 0 of a request
read ``prompt_len + i`` cached rows in every layer, each row ONCE
(``cache_row_dim`` elements of 2 bytes: it is key and value at once), and met
every head with ``2 x cache_row_dim`` FLOPs for the score and ``2 x latent``
for the weighted sum. A kernel that fetches rows padded to whole lanes, or
rows of a slot that is not decoding, reads lower, as it should. Reads
``shapes`` (``layers``, ``heads``, ``cache_row_dim``, ``latent``),
``requests`` and ``trace_span`` of a ``serve_open_loop`` run's
observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    rows = float(sum(r["prompt_len"] + i for r in obs["requests"]
                     for i, t in enumerate(r["token_times"])
                     if i and lo <= t < hi)) * s["layers"]
    return (rows * s["heads"] * 2.0 * (s["cache_row_dim"] + s["latent"]),
            rows * s["cache_row_dim"] * 2.0)
