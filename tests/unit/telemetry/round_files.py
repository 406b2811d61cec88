"""Five synthetic per-round bench files for the bench_trajectory tests.

Same top-level keys as the files ``bench.py``'s driver used to leave at the
root (``n``, ``cmd``, ``rc``, ``tail``, ``parsed``); the numbers are made up
so that the series show each flag the script can raise: a headline with a
full five-point series, a metric that appears in the last round only, a
serving series that starts in round 2 and improves in round 5, and one
regression.
"""

import json

_PARSED = [
    {"metric": "train_tokens_per_sec", "value": 1000.0, "unit": "tokens/sec"},
    {"metric": "train_tokens_per_sec", "value": 1200.0, "unit": "tokens/sec",
     "mfu": 0.40,
     "serving": {"bf16": {"decode_ms_per_token": 1.20,
                          "batch8_decode_tokens_per_sec": 2000.0}}},
    {"metric": "train_tokens_per_sec", "value": 1210.0, "unit": "tokens/sec",
     "mfu": 0.40,
     "serving": {"bf16": {"decode_ms_per_token": 1.10,
                          "batch8_decode_tokens_per_sec": 2100.0}}},
    {"metric": "train_tokens_per_sec", "value": 1190.0, "unit": "tokens/sec",
     "mfu": 0.41,
     "serving": {"bf16": {"decode_ms_per_token": 0.60,
                          "batch8_decode_tokens_per_sec": 4000.0}}},
    {"metric": "train_tokens_per_sec", "value": 1250.0, "unit": "tokens/sec",
     "mfu": 0.30,                                     # the one regression
     "serving": {"bf16": {"decode_ms_per_token": 0.45,
                          "batch8_decode_tokens_per_sec": 8000.0}},
     "train_774m": {"tokens_per_sec": 160.0, "mfu_vs_attainable": 0.45}},
]


def write_round_files(directory):
    """Write BENCH_r01..r05.json under ``directory``; return their paths."""
    paths = []
    for n, parsed in enumerate(_PARSED, start=1):
        p = directory / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps({"n": n, "cmd": "python bench.py", "rc": 0,
                                 "tail": json.dumps(parsed),
                                 "parsed": parsed}))
        paths.append(str(p))
    return paths
