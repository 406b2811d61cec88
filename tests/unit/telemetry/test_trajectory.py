"""scripts/bench_trajectory.py tests (ISSUE 11 satellite) — run against
five per-round bench files written into tmp_path (round_files.py: the
shape of the data the script exists to read), plus synthetic series for
the flagging logic."""

import importlib.util
import json
import os

import pytest

from tests.unit.telemetry.round_files import write_round_files

pytestmark = [pytest.mark.tracing, pytest.mark.observability,
              pytest.mark.quick]

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


def _mod():
    spec = importlib.util.spec_from_file_location(
        "bench_trajectory", os.path.join(ROOT, "scripts",
                                         "bench_trajectory.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.fixture
def round_files(tmp_path):
    return write_round_files(tmp_path)


def test_flatten_numeric_leaves_only():
    m = _mod()
    flat = m.flatten({"a": {"b": 1, "c": "text", "d": True},
                      "e": 2.5, "f": {"g": {"h": 3}}})
    assert flat == {"a.b": 1.0, "e": 2.5, "f.g.h": 3.0}


def test_checked_in_rounds_collate(round_files):
    m = _mod()
    rounds = m.load_rounds(round_files)
    labels = [lbl for lbl, _ in rounds]
    assert labels == ["r01", "r02", "r03", "r04", "r05"]
    t = m.trend(rounds)
    # the headline metric has a full 5-point series
    assert list(t["value"]["series"]) == labels
    assert t["value"]["series"]["r05"] == pytest.approx(1250.0)
    # the 774M MFU line appeared in r05 only
    assert t["train_774m.mfu_vs_attainable"]["flag"] == "new"
    # serving bf16 decode series spans r02..r05 and r05 improved
    s = t["serving.bf16.batch8_decode_tokens_per_sec"]
    assert list(s["series"]) == ["r02", "r03", "r04", "r05"]
    assert s["flag"] == "improvement" and s["delta_pct"] > 10


def test_direction_heuristic_and_threshold():
    m = _mod()
    assert m.lower_is_better("serving.bf16.decode_ms_per_token")
    assert m.lower_is_better("serving.ttft_p99")
    assert m.lower_is_better("observability.train.overhead_pct")
    assert not m.lower_is_better("train_774m.tokens_per_sec")
    rounds = [("r01", {"lat_ms": 10.0, "tput": 100.0, "quiet": 5.0}),
              ("r02", {"lat_ms": 13.0, "tput": 80.0, "quiet": 5.2})]
    t = m.trend(rounds, threshold=0.10)
    assert t["lat_ms"]["flag"] == "regression"       # latency up 30%
    assert t["tput"]["flag"] == "regression"         # throughput down 20%
    assert t["quiet"]["flag"] == "stable"            # 4% < threshold
    # a wider threshold absorbs both moves
    t = m.trend(rounds, threshold=0.50)
    assert t["lat_ms"]["flag"] == "stable"
    assert t["tput"]["flag"] == "stable"


def test_gone_and_full_append(tmp_path, round_files):
    m = _mod()
    rounds = [("r01", {"a": 1.0, "b": 2.0}), ("r02", {"a": 1.0})]
    t = m.trend(rounds)
    assert t["b"]["flag"] == "gone"
    # --full appends a fresh bench JSON as the newest point
    full = tmp_path / "full.json"
    full.write_text(json.dumps({"value": 100.0, "nested": {"x": 1}}))
    loaded = m.load_rounds(round_files, full=str(full))
    assert loaded[-1][0] == "full"
    assert loaded[-1][1]["value"] == 100.0


def test_cli_json_output(capsys, round_files):
    m = _mod()
    rc = m.main(["--json"] + round_files)
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rounds"] == ["r01", "r02", "r03", "r04", "r05"]
    assert "value" in out["metrics"]


def test_cli_table_output(capsys, round_files):
    m = _mod()
    rc = m.main(round_files + ["--flagged"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "bench trajectory" in out and "5 rounds" in out
