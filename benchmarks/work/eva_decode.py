"""The work the one-token EVA step needed in the traced window of a serving
run. A token committed at decode step i > 0 of a request inside the window
was one active slot of one decode iteration, at context ``p = prompt_len + i
- 1`` (the tokens its slot held): on each layer it attended the ``(p mod W) +
1`` rows of its window up to its own and the ``(W / c) floor(p / W)`` summary
rows of the windows before, ``heads x head_dim`` elements a row of keys and
as many of values, each read once at 2 bytes and met with 4 FLOPs an element
(the score's multiply-add, the weighted sum's), and wrote two rows (its own,
its chunk's summary) of keys and of values. That is what the mathematics
needs whatever implements it: slots idle in an iteration needed nothing, rows
a DMA rounds up to and the pooling's 16 rows are not counted. Reads
``shapes`` (``layers``, ``heads``, ``head_dim``, ``window``, ``chunk``),
``requests`` and ``trace_span`` of a ``serve_open_loop`` run's
observations."""


def work(obs):
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    w, c = s["window"], s["chunk"]
    rows = steps = 0
    for r in obs["requests"]:
        for i, t in enumerate(r["token_times"]):
            if i and lo <= t < hi:
                p = r["prompt_len"] + i - 1
                rows += p % w + 1 + (w // c) * (p // w)
                steps += 1
    elements = float(s["layers"] * s["heads"] * s["head_dim"])
    return (4.0 * rows * elements,
            2.0 * 2.0 * (rows + 2 * steps) * elements)
