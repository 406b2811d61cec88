"""The per-layer metrics that read the program's phase spans, its host
annotations and its kernel names: each new reader on a hand-made span list
and trace, and the two properties of the program those readers lean on (a
``name=`` on every Pallas kernel; scopes that change no operation).

One module, as tests/unit/benchmarks/test_benchmark.py: no subprocess, no
TPU topology, nothing at module level that loads libtpu.
"""
import ast
import collections
import contextlib
import os
import re

import numpy as np
import pytest

from benchmarks import harness, trace_reduce

OPS_DIR = os.path.join(harness.ROOT, "deepspeed_tpu", "ops")


def _read(metric, obs):
    spec = harness.load_json("layer_metrics", metric + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _span(name, start, end):
    return {"name": name, "start": start, "end": end}


# ------------------------------------------------------------ span readers
def _iterations():
    """Three iterations on the engine's clock, in seconds. The first admits
    two requests (prefills of 5 and 3 ms inside a 10 ms schedule), the
    second schedules for 1 ms with nothing to admit, the third admits one
    (a 4 ms prefill inside 4.5 ms). Decode phases: upload 0.5, launch 0.25,
    fetch 8, commit 0.25 ms, twice; the third has no decode."""
    spans = []
    for t0, sched, prefills, decodes in [
            (0.000, 0.010, [(0.001, 0.006), (0.0065, 0.0095)], True),
            (0.100, 0.001, [], True),
            (0.200, 0.0045, [(0.2003, 0.2043)], False)]:
        t = t0 + sched
        spans.append(_span("iter_schedule", t0, t))
        spans += [_span("prefill_chunk", a, b) for a, b in prefills]
        if decodes:
            for name, d in [("iter_upload", 0.0005), ("iter_launch", 0.00025),
                            ("iter_fetch", 0.008), ("iter_commit", 0.00025)]:
                spans.append(_span(name, t, t + d))
                t += d
        spans.append(_span("iteration", t0, t))
    spans.append(_span("iter_schedule", 0.3, None))      # still open: skipped
    return spans


def test_span_readers_on_hand_made_iterations():
    obs = {"spans": _iterations()}
    # schedule less the prefills inside it: 10 - 8 = 2, 1 - 0 = 1 and
    # 4.5 - 4 = 0.5 ms; the median is 1
    assert _read("sched.schedule_host_ms", obs) == pytest.approx(1.0)
    # 95th percentile of 10, 1 and 4.5 ms by linear interpolation
    assert _read("sched.iter_schedule_p95_ms", obs) == pytest.approx(
        float(np.percentile([10.0, 1.0, 4.5], 95)))
    assert _read("step.prefill_chunk_ms", obs) == pytest.approx(4.0)
    assert _read("step.upload_host_ms", obs) == pytest.approx(0.5)
    assert _read("step.launch_host_ms", obs) == pytest.approx(0.25)
    assert _read("step.fetch_wait_ms", obs) == pytest.approx(8.0)
    assert _read("step.commit_host_ms", obs) == pytest.approx(0.25)


def test_span_self_time_counts_overlapping_and_straddling_children_once():
    reader = harness.module("readers", "span_self_median")
    params = {"span": "outer", "children": ["a", "b"]}
    spans = [_span("outer", 1.0, 2.0),
             _span("a", 0.9, 1.2),        # straddles the start: 0.2 inside
             _span("b", 1.1, 1.3),        # overlaps a: 1.0..1.3 covered once
             _span("a", 1.8, 2.5),        # straddles the end: 0.2 inside
             _span("c", 1.4, 1.6),        # not a child name
             _span("a", 3.0, 4.0)]        # outside
    assert reader.read(params, {"spans": spans}) == pytest.approx(500.0)
    assert reader.read(params, {"spans": spans[1:]}) is None
    assert reader.read({"span": "outer", "children": []},
                       {"spans": spans}) == pytest.approx(1000.0)


# ----------------------------------------------------------- trace readers
def _serving_trace():
    """One device, a 10 s window. Three decode iterations: the program
    runs 1.0..1.8, 3.0..3.8 and 5.0..5.8 as a fusion, the decode kernel and
    a fusion that reads the kernel's result; a prefill runs 2.5..3.0."""
    kernel = ("%dstpu_decode_step.7 = (bf16[32,20,64]) custom-call(bf16[] "
              "%p), custom_call_target=\"tpu_custom_call\"")
    user = ("%fusion.9 = bf16[32,1,1280] fusion(bf16[32,20,64] "
            "%dstpu_decode_step.7), kind=kLoop")
    ops = []
    for t in (1.0, 3.0, 5.0):
        ops += [("%fusion.1 = bf16[32,1,3840] fusion()", t, t + 0.2),
                (kernel, t + 0.2, t + 0.7), (user, t + 0.7, t + 0.8)]
    ops.append(("%fusion.5 = bf16[1,256,1280] fusion()", 2.5, 3.0))
    return trace_reduce.Trace({0: ops}, [("bench/window", 0.0, 10.0)],
                              (0.0, 10.0))


def test_trace_readers_on_a_hand_made_trace():
    obs = {"trace": _serving_trace()}
    # the kernel ran 3 x 0.5 s of 2.9 s busy; the fusion that names the
    # kernel as its operand is not the kernel
    assert _read("kernel.decode_attn_share", obs) == pytest.approx(
        100.0 * 1.5 / 2.9)
    assert _read("kernel.flash_share", obs) is None
    assert _read("device.idle_share.serve", obs) == pytest.approx(71.0)


def test_flash_share_finds_the_kernels_under_any_wrapper():
    ops = [("%dstpu_flash_fwd.3 = (bf16[40,1024,64]) custom-call()", 0, 2),
           ("%dstpu_flash_bwd_dkv.4 = (bf16[40,1024,64]) custom-call()", 2, 3),
           ("%checkpoint_dstpu_flash_bwd_dq.1 = bf16[40,1024,64] "
            "custom-call()", 3, 4),
           ("%fusion.2 = bf16[2,1024,1280] fusion(bf16[40,1024,64] "
            "%dstpu_flash_bwd_dq.1)", 4, 8)]
    tr = trace_reduce.Trace({0: ops, 1: ops}, [("bench/window", 0, 10)],
                            (0.0, 10.0))
    assert _read("kernel.flash_share", {"trace": tr}) == pytest.approx(50.0)


def test_new_readers_return_nothing_without_observations():
    for metric in ("sched.schedule_host_ms", "sched.iter_schedule_p95_ms",
                   "step.prefill_chunk_ms", "step.upload_host_ms",
                   "step.launch_host_ms", "step.fetch_wait_ms",
                   "step.commit_host_ms", "kernel.decode_attn_share",
                   "kernel.flash_share"):
        assert _read(metric, {}) is None, metric
        assert _read(metric, {"spans": [], "trace": None}) is None, metric
        empty = trace_reduce.Trace({}, [("bench/window", 0, 1)], (0.0, 1.0))
        assert _read(metric, {"spans": [], "trace": empty}) is None, metric


# ------------------------------------------------- what the readers lean on
def _pallas_calls():
    """Every ``pallas_call(...)`` of ``deepspeed_tpu/ops``, called through a
    module (``pl.pallas_call``) or by its bare name: (file, n-th call in it,
    the call's node). Parsed, never imported."""
    found = []
    for fname in sorted(os.listdir(OPS_DIR)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(OPS_DIR, fname)) as f:
            tree = ast.parse(f.read())
        calls = sorted((n for n in ast.walk(tree)
                        if isinstance(n, ast.Call)
                        and getattr(n.func, "attr", getattr(n.func, "id", None))
                        == "pallas_call"),
                       key=lambda n: n.lineno)
        found += [(fname, i, n) for i, n in enumerate(calls)]
    return found


PALLAS_CALLS = _pallas_calls()


@pytest.mark.parametrize("fname,i,call", PALLAS_CALLS,
                         ids=[f"{f}-{i}" for f, i, _ in PALLAS_CALLS])
def test_every_pallas_call_has_a_stable_name(fname, i, call):
    """The device trace names a kernel's event after ``name=``; without it
    the compiler names it after whatever wraps the call."""
    names = [k.value for k in call.keywords if k.arg == "name"]
    assert len(names) == 1, f"{fname}:{call.lineno} has no name="
    assert isinstance(names[0], ast.Constant), f"{fname}:{call.lineno}"
    assert re.match(r"^dstpu_[a-z0-9_]+$", names[0].value), names[0].value


def test_pallas_names_are_distinct_and_cover_the_kernels_the_cells_run():
    """A new Pallas call needs a ``name=`` of its own and nothing else here:
    the count is a lower bound (nine through ``pl.`` and one by the bare
    name when this was written), and each share metric's pattern is held to
    its kernels among ALL the names, whatever is added."""
    names = [k.value.value for _, _, c in PALLAS_CALLS for k in c.keywords
             if k.arg == "name"]
    assert len(names) == len(set(names)) == len(PALLAS_CALLS) >= 10
    assert {"dstpu_flash_fwd", "dstpu_flash_bwd_dq", "dstpu_flash_bwd_dkv",
            "dstpu_decode_step", "dstpu_block_decode_step"} <= set(names)
    assert "dstpu_ssm_update" in names      # the call by its bare name
    # what each share metric's pattern finds among the names
    for metric, want in [
            ("kernel.flash_share", {"dstpu_flash_fwd", "dstpu_flash_bwd_dq",
                                    "dstpu_flash_bwd_dkv"}),
            ("kernel.decode_attn_share", {"dstpu_decode_step"}),
            ("kernel.ssm_update_share", {"dstpu_ssm_update"})]:
        rx = re.compile(harness.load_json(
            "layer_metrics", metric + ".json")["params"]["pattern"])
        assert {n for n in names if rx.search(f"%{n}.3 = x")} == want


def _opcode_counts(lowered_text):
    return collections.Counter(re.findall(r"\b(?:stablehlo|func|sdy|mhlo)"
                                          r"\.[a-z_]+\b", lowered_text))


def test_scopes_of_the_fused_train_step_change_no_operation(monkeypatch):
    """``jax.named_scope`` writes metadata only: the train step lowers to
    the same operations, opcode by opcode, with the scopes and with
    ``named_scope`` replaced by a context manager that does nothing."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.utils import groups

    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    groups.reset()
    cfg = GPT2Config.tiny()
    conf = {"train_batch_size": 4, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2, "gradient_clipping": 1.0,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 0}
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg), config=DeepSpeedConfig(conf, world_size=1),
        topology=build_topology(devices=jax.devices()[:1], dp=1, tp=1))
    ids = np.zeros((2, 2, 16), np.int32)
    batch = {"input_ids": ids, "labels": ids}
    lr = jnp.asarray(1e-3, jnp.float32)
    rng = jax.random.PRNGKey(0)

    seen = []
    real = jax.named_scope

    @contextlib.contextmanager
    def recording(name):
        seen.append(name)
        with real(name):
            yield

    def lowered():
        step = engine._build_train_step(batch)
        return step.lower(engine.state, batch, lr, rng).as_text(
            debug_info=True)

    monkeypatch.setattr(jax, "named_scope", recording)
    scoped = lowered()
    assert {"dstpu_fwd_bwd", "dstpu_accumulate",
            "dstpu_optimizer"} <= set(seen)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lowered()
    engine.destroy()
    assert _opcode_counts(scoped) == _opcode_counts(bare)
    assert sum(_opcode_counts(scoped).values()) > 100
    # and the scopes do reach the operations' metadata
    assert "dstpu_optimizer" in scoped and "dstpu_optimizer" not in bare
    assert "dstpu_fwd_bwd" in scoped and "dstpu_accumulate" in scoped


def _served_configs():
    """One configuration a family among the cells a ``ServingEngine``
    serves, by ``BENCHMARK.json``: a family that a later cell brings is
    lowered here too."""
    bench, seen = harness.benchmark_json(), {}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        if cell["traffic_file"]["kind"] == "serve_open_loop":
            seen.setdefault(cell["config_file"]["family"], w["config"])
    return sorted(seen.items())


SERVED = _served_configs()


class _Recorded:
    """A jitted program that notes each call: itself and its operands, as
    shapes (a donated operand is gone after the call)."""

    def __init__(self, program, calls):
        self.program, self.calls = program, calls

    def __call__(self, *args):
        import jax

        self.calls.append((self.program, jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.result_type(x)),
            args)))
        return self.program(*args)

    def __getattr__(self, name):        # ``_cache_size`` and the like
        return getattr(self.program, name)


@pytest.mark.parametrize("family_name,config", SERVED,
                         ids=[f for f, _ in SERVED])
def test_serving_programs_carry_their_scopes(family_name, config, monkeypatch):
    """The decode and prefill programs lowered with the operands
    ``ServingEngine`` itself passes them at warm-up, whatever those are (the
    model's state tree leaf by leaf today): the scopes the trace readers
    lean on are in the lowered text. The test fixes no signature."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils import groups

    family = harness.module("families", family_name)
    cfg = family.tiny(harness.load_json("configs", config + ".json"))
    groups.reset()
    eng = deepspeed_tpu.init_inference(family.build_model(cfg, {}),
                                       dtype="fp32", max_out_tokens=64)
    calls = {"slot_decode_program": [], "slot_prefill_program": []}
    for method, noted in calls.items():
        monkeypatch.setattr(
            eng, method, lambda *a, _real=getattr(eng, method), _noted=noted,
            **kw: _Recorded(_real(*a, **kw), _noted))
    ServingEngine(eng, num_slots=4, max_len=64, buckets=(16, 32),
                  tenants=False).warmup()
    for method, scope in [("slot_decode_program", "dstpu_decode"),
                          ("slot_prefill_program", "dstpu_prefill")]:
        assert calls[method], method
        program, operands = calls[method][0]
        text = program.lower(*operands).as_text(debug_info=True)
        assert scope in text, (family_name, method)
