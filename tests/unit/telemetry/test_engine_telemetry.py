"""Training-engine telemetry instrumentation (ISSUE 3 tentpole):
per-step registry updates, fence-sampled device metrics, JSONL snapshots,
monitor_interval decoupling, checkpoint-save events, destroy() shutdown
hooks (comms summary + sink close)."""

import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from simple_model import SimpleModel, random_batch  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu import telemetry  # noqa: E402
from deepspeed_tpu.utils import groups  # noqa: E402

pytestmark = [pytest.mark.observability, pytest.mark.quick]


def _engine(tmp_path=None, **overrides):
    groups.reset()
    telemetry.reset_registry()
    config = {
        "train_batch_size": 8,
        "steps_per_print": 0,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    }
    config.update(overrides)
    engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(), config=config)
    return engine


def _step(engine, i=0):
    batch = random_batch(8, seed=i)
    stacked = jax.tree_util.tree_map(lambda x: x[None], batch)
    return engine.train_batch_from_stacked(stacked)


def test_per_step_metrics_and_fence(tmp_path):
    path = str(tmp_path / "run.jsonl")
    engine = _engine(telemetry={"sync_interval": 2, "jsonl_path": path})
    for i in range(5):
        _step(engine, i)
    reg = telemetry.get_registry()
    assert engine.telemetry is reg
    snap = reg.snapshot()
    assert snap["counters"]["train/steps"] == 5
    assert snap["histograms"]["train/step_wall_ms"]["count"] == 5
    # fences fired (steps 1, 2, 4): device-truth gauges are populated
    assert "train/grad_norm" in snap["gauges"]
    assert "train/loss" in snap["gauges"]
    assert snap["gauges"].get("train/device_step_time_ms", 0) > 0
    engine.destroy()
    recs = telemetry.read_jsonl(path)
    snaps = [r for r in recs if r["kind"] == "snapshot"]
    assert len(snaps) >= 3                     # fence flushes + destroy
    assert snaps[-1]["metrics"]["counters"]["train/steps"] == 5


def test_telemetry_disabled_is_bare(tmp_path):
    # an enabled engine that nobody destroyed still has the global registry
    # subscribed to the compile log (ISSUE 42); it follows its own work
    # alone, so what a disabled engine compiles beside it leaves no entry/*
    log = telemetry.compile_log()
    forgotten = _engine(telemetry={"sync_interval": 0})
    _step(forgotten)
    assert forgotten._compile_sub in log._subs
    traced = log.totals["entry/traces"]
    engine = _engine(telemetry={"enabled": False})    # clears the registry
    for i in range(2):
        _step(engine, i)
    assert engine.telemetry is None and engine._compile_sub is None
    assert log.totals["entry/traces"] > traced        # it did compile
    assert telemetry.get_registry().snapshot()["counters"] == {}
    engine.destroy()                           # no sink, no comms: no-op
    forgotten.destroy()
    assert forgotten._compile_sub is None


def test_monitor_interval_decouples_from_steps_per_print(tmp_path):
    """steps_per_print=100 would have gated monitor writes to step 100
    under the legacy coupling; monitor_interval=2 must fire at 2 and 4."""
    out = str(tmp_path / "csv")
    engine = _engine(
        steps_per_print=100,
        monitor_interval=2,
        csv_monitor={"enabled": True, "output_path": out,
                     "job_name": "job"},
    )
    assert engine.config.monitor_interval == 2
    for i in range(4):
        _step(engine, i)
    csv = os.path.join(out, "job", "Train_Samples_train_loss.csv")
    assert os.path.exists(csv)
    with open(csv) as f:
        rows = [line.split(",")[0] for line in f.read().splitlines()[1:]]
    assert rows == ["2", "4"]


def test_monitor_interval_default_keeps_legacy_coupling(tmp_path):
    out = str(tmp_path / "csv")
    engine = _engine(
        steps_per_print=3,
        csv_monitor={"enabled": True, "output_path": out,
                     "job_name": "job"},
    )
    assert engine.config.monitor_interval == 0
    for i in range(4):
        _step(engine, i)
    csv = os.path.join(out, "job", "Train_Samples_train_loss.csv")
    with open(csv) as f:
        rows = [line.split(",")[0] for line in f.read().splitlines()[1:]]
    assert rows == ["3"]                       # steps_per_print cadence


def test_checkpoint_save_and_load_events(tmp_path):
    engine = _engine()
    _step(engine)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    reg = telemetry.get_registry()
    assert reg.counter("checkpoint/saves").value == 1
    engine.load_checkpoint(str(tmp_path / "ckpt"))
    assert reg.counter("checkpoint/loads").value == 1


def test_destroy_emits_comms_summary_when_enabled(monkeypatch):
    engine = _engine(comms_logger={"enabled": True})
    calls = []
    import deepspeed_tpu.comm as dist

    monkeypatch.setattr(dist, "log_summary",
                        lambda *a, **k: calls.append(1) or "")
    engine.destroy()
    assert calls == [1]

    engine2 = _engine()                        # comms logging off
    calls.clear()
    monkeypatch.setattr(dist, "log_summary",
                        lambda *a, **k: calls.append(1) or "")
    engine2.destroy()
    assert calls == []


def test_comm_log_summary_reports_recorded_ops():
    """Satellite: comm.log_summary() renders what CommsLogger accumulated
    (records were previously appended but never reported)."""
    import deepspeed_tpu.comm as dist

    dist.comms_logger.comms_dict.clear()
    dist.configure(enabled=True, prof_all=True)
    try:
        dist.all_reduce(np.ones((4,), np.float32))
        out = dist.log_summary()
    finally:
        dist.configure(enabled=False)
        dist.comms_logger.comms_dict.clear()
    assert "all_reduce" in out
    assert "Comm. Op" in out                   # header rendered
