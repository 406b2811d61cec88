"""Traffic kind ``train_job``: one training job through
``deepspeed_tpu.initialize`` and ``train_batch_from_stacked``.

The traffic file gives the job (rows a step, sequence length, micro-batch,
accumulation steps, mesh), the model's options and the engine's config. Fresh
learnable rows are drawn from ``--seed`` for every step, on the host, while
the previous step runs on the device.

The window: steps are begun while less than ``--seconds`` have passed since
the first; the window closes when the last of them is fenced. Every step ends
inside it, so the rate is all tokens over all the time, with no whole step
rounded away at the edge.

``correct``: the largest |engine logit - reference logit| over the checked
rows stays inside the mix's ``check.logit_tol`` (``check.why`` says where the
number came from), the reference is finite and no step's loss is not. Of a
configuration this file reads ``family`` alone: sizes come from the family's
``shapes()``, and the reference takes its own hyper-parameters from the
configuration's dict.
"""
from __future__ import annotations

import math
import time
from typing import Mapping

import numpy as np

from benchmarks import flops, harness, traffic_gen

WARMUP_STEPS = 3   # compile, the engine's second trace of the step, one steady


def _rehearsal(job: Mapping, options: Mapping):
    """Sandbox sizes for ``rehearse=True``: tiny, dense, one device."""
    return (dict(job, rows_per_step=2, seq_len=32, micro_batch=1, gas=2,
                 mesh={"dp": 1, "tp": 1}, check_rows=1, trace_steps=1),
            dict(options, attn_impl="dense", remat=False, loss_chunk=0))


def run(cell: Mapping, *, seed: int, seconds: float, trace: bool,
        clock0: float, rehearse: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    traffic, cfg = cell["traffic_file"], cell["config_file"]
    family = harness.module("families", cfg["family"])
    reference = harness.module("reference", cfg["family"])
    job, options = traffic["job"], traffic["model_options"]
    logit_tol = traffic["check"]["logit_tol"]
    if rehearse:
        cfg = family.tiny(cfg)
        job, options = _rehearsal(job, options)
    shapes = family.shapes(cfg)
    guard = harness.device_guard(cell["chips"], rehearse=rehearse)
    t_import = time.perf_counter()

    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.utils import groups

    dp, tp = job["mesh"]["dp"], job["mesh"]["tp"]
    n_dev = dp * tp
    if n_dev != (1 if rehearse else cell["chips"]):
        raise ValueError(f"mesh {job['mesh']} is not {cell['chips']} chip(s)")
    micro, gas, seq = job["micro_batch"], job["gas"], job["seq_len"]
    rows = micro * gas * dp
    if rows != job["rows_per_step"]:
        raise ValueError(f"micro {micro} x gas {gas} x dp {dp} is not "
                         f"{job['rows_per_step']} rows a step")
    seed31 = traffic_gen.fold_seed(seed)
    conf = dict(traffic["engine_config"], train_batch_size=rows,
                train_micro_batch_size_per_gpu=micro,
                gradient_accumulation_steps=gas, seed=seed31)
    if tp > 1:
        conf["tensor_parallel"] = {"tp_size": tp}
    model = family.build_model(cfg, options)
    groups.reset()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=DeepSpeedConfig(conf, world_size=n_dev),
        topology=build_topology(devices=guard["devices"][:n_dev], dp=dp,
                                tp=tp))
    vocab = shapes["vocab"]
    data_rng = np.random.RandomState(traffic_gen.fold_seed(seed, 4))

    def make_batch():
        return traffic_gen.arith_rows(data_rng, vocab, (gas, micro * dp, seq))

    for _ in range(WARMUP_STEPS):
        jax.block_until_ready(engine.train_batch_from_stacked(make_batch()))
    t_warm = time.perf_counter()

    # ---- correct: the engine's logits against the plain reference, on the
    # engine's own weights, before the window (counts in setup_s)
    check_rng = np.random.RandomState(traffic_gen.fold_seed(seed, 5))
    ids = traffic_gen.arith_rows(check_rng, vocab,
                                 (job["check_rows"], seq))["input_ids"]
    params = engine.state.params
    eng_logits = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
        params, jnp.asarray(ids)).astype(jnp.float32)
    ref_fn = jax.jit(lambda p, x: reference.forward_logits(p, x, cfg))
    logit_err = 0.0
    for r in range(ids.shape[0]):  # a row at a time: the reference is float32
        ref = ref_fn(params, jnp.asarray(ids[r:r + 1]))
        logit_err = max(logit_err, float(jnp.max(jnp.abs(ref[0] - eng_logits[r]))))
        del ref
    finite_ref = math.isfinite(logit_err)
    del eng_logits, ref_fn
    t_check = time.perf_counter()

    # ---- the window
    step_fn = engine._compiled_train_step
    programs_before = step_fn._cache_size()
    losses = []
    batch = make_batch()
    t0 = time.perf_counter()
    setup_s = t0 - clock0
    while time.perf_counter() - t0 < seconds:
        losses.append(engine.train_batch_from_stacked(batch))
        batch = make_batch()            # host work under the running step
        if len(losses) > 1:             # at most two steps queued
            jax.block_until_ready(losses[-2])
    jax.block_until_ready(losses[-1])
    window_s = time.perf_counter() - t0
    compiles = step_fn._cache_size() - programs_before
    loss_values = [float(x) for x in jax.device_get(losses)]
    steps = len(loss_values)
    failed = sum(1 for x in loss_values if not math.isfinite(x))
    tokens = steps * rows * seq

    # ---- the traced steps, after the window and apart from it
    reduced = None
    if trace:
        with harness.profile_if(True) as prof:
            prof.start()
            prof.open_window()
            last = None
            for _ in range(job["trace_steps"]):
                last = engine.train_batch_from_stacked(batch)
                batch = make_batch()
            jax.block_until_ready(last)
            prof.close_window()
            reduced = prof.reduce()

    rate = tokens / window_s
    flops_tok = flops.train_flops_per_token(shapes, seq)
    mfu = rate * flops_tok / (n_dev * guard["peak"]["bf16_tflops"] * 1e12)
    peak_bytes = harness.memory_peak_bytes(guard["devices"][:n_dev])
    # whatever the engine counted (its telemetry is on unless the mix's
    # engine_config turns it off; this file turns nothing on or off)
    registry = getattr(engine, "telemetry", None)
    counters = {} if registry is None else registry.snapshot()["counters"]
    engine.destroy()
    return {
        "correct": bool(finite_ref and logit_err <= logit_tol
                        and failed == 0),
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "device": dict(guard["device"], memory_peak_bytes=peak_bytes),
        "notes": {
            "steps": steps, "window_s": window_s,
            "mean_step_s": window_s / steps,
            "loss_first_last": [loss_values[0], loss_values[-1]],
            "logit_max_abs_err": logit_err, "logit_tol": logit_tol,
            "flops_per_token": flops_tok, "model_flops_utilisation": mfu,
            "setup_parts_s": {"import_and_guard": t_import - clock0,
                              "build_and_warmup": t_warm - t_import,
                              "reference_check": t_check - t_warm,
                              "first_batch": t0 - t_check},
        },
        "observations": {
            "counters": {**counters, "compiles_in_window": compiles},
            "trace": reduced, "peak": guard["peak"], "shapes": shapes,
            "train": {"rows_per_device_step": micro * gas, "seq_len": seq,
                      "traced_steps": job["trace_steps"]},
        },
    }
