"""ZeRO-3 states its parameter gather where a parameter is used
(``models/base.gathered``): a layer's slice inside the rematerialised block
of the layer scan (``models/stack.wrapped_block``, the one walk every
decoder shares), the embedding and the head at their use. On the CPU's
virtual devices: what the compiled train step moves between devices, and
that the mathematics is stage 0's."""

import contextlib
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.topology import build_topology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.zero.partition import stating_param_use
from deepspeed_tpu.utils import groups

VOCAB, SEQ, HIDDEN, LAYERS = 256, 32, 64, 3


def _gpt2(remat_policy="dots_no_batch"):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=VOCAB, max_seq_len=SEQ, num_layers=LAYERS,
                     hidden_size=HIDDEN, num_heads=4, loss_chunk=16)
    return GPT2Model(cfg, compute_dtype=jnp.float32, remat=True,
                     remat_policy=remat_policy)


def _llama():
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)
    return LlamaModel(cfg, compute_dtype=jnp.float32, remat=True,
                      remat_policy="dots_no_batch")


def _transformer():
    from deepspeed_tpu.models.transformer import DecoderConfig, DecoderModel

    cfg = DecoderConfig(vocab_size=VOCAB, max_seq_len=SEQ, num_layers=LAYERS,
                        hidden_size=HIDDEN, num_heads=4, mlp_dim=4 * HIDDEN)
    return DecoderModel(cfg, compute_dtype=jnp.float32, remat=True,
                        remat_policy="dots_no_batch")


def _gpt_moe():
    from deepspeed_tpu.models.gpt_moe import GPTMoEConfig, GPTMoEModel

    cfg = GPTMoEConfig.tiny(vocab_size=VOCAB, max_seq_len=SEQ)
    return GPTMoEModel(cfg, compute_dtype=jnp.float32)


def _granite():
    from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                     GraniteHybridModel)

    cfg = GraniteHybridConfig.tiny(    # two stacks, walked as four runs
        vocab_size=VOCAB, max_seq_len=SEQ,
        layer_types=("mamba", "mamba", "attention", "mamba", "attention"))
    return GraniteHybridModel(cfg, compute_dtype=jnp.float32, remat=True,
                              remat_policy="dots_no_batch")


MODELS = {"gpt2": _gpt2, "llama": _llama, "transformer": _transformer,
          "gpt_moe": _gpt_moe, "granite": _granite}


def _engine(model, stage, dp, tp=1):
    groups.reset()
    conf = {"train_batch_size": 4 * dp, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2, "steps_per_print": 0,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage,
                                  "stage3_param_persistence_threshold": 0}}
    if tp > 1:
        conf["tensor_parallel"] = {"tp_size": tp}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=DeepSpeedConfig(conf, world_size=dp * tp),
        topology=build_topology(dp * tp, dp=dp, tp=tp))
    return engine


def _batch(dp):
    ids = np.random.RandomState(0).randint(
        0, VOCAB, size=(2, 2 * dp, SEQ + 1)).astype(np.int32)
    return {"input_ids": ids[..., :-1], "labels": ids[..., 1:]}


def _train(engine, batch, steps=2):
    losses = [float(engine.train_batch_from_stacked(batch))
              for _ in range(steps)]
    return losses, jax.device_get(engine.state.params)


def _step_text(engine, batch):
    """The compiled text of the engine's fused train step."""
    engine._build_train_step(batch)
    placed = jax.device_put(batch, engine._gas_batch_shardings(batch))
    return engine._compiled_train_step.lower(
        engine.state, placed, jnp.asarray(1e-3, jnp.float32),
        jax.random.PRNGKey(0), None, None).compile().as_text()


def _collectives(text, kind):
    """Result shapes of the ``kind`` collectives in a compiled text."""
    return re.findall(r"= \(?(\w+\[[\d,]*\])[^=]*? " + kind + r"(?:-start)?\(",
                      text)


def _assert_same_training(got, want):
    (losses, params), (ref_losses, ref_params) = got, want
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6),
        params, ref_params)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_stage3_gathers_weights_and_moves_no_activation(name):
    batch = _batch(4)
    reference = _train(_engine(MODELS[name](), 0, 4), batch)
    engine = _engine(MODELS[name](), 3, 4)
    text = _step_text(engine, batch)
    assert not _collectives(text, "all-to-all"), \
        "the partitioner reshards activations around a weight shard"
    gathered = {tuple(int(d) for d in s[s.index("[") + 1:-1].split(",")
                      if d) for s in _collectives(text, "all-gather")}
    stacks = ("mamba", "attention") if name == "granite" else ("blocks",)
    # a stack's matrices; gpt_moe keeps a list of layers, whose matrices
    # count as slices of a stack of two
    lift = (2,) if name == "gpt_moe" else ()
    stacked = [lift + leaf.shape for stack in stacks
               for leaf in jax.tree_util.tree_leaves(
                   engine._params_shape[stack]) if len(lift + leaf.shape) == 3]
    assert stacked
    # never a whole stack: the gather is stated on a layer's slice
    assert not {s for s in stacked if s[0] > 1} & gathered, gathered
    # a layer's whole weight, gathered inside the layer scan: the stacked
    # leaf's shape without (or with a unit) layer dimension
    whole = {s[1:] for s in stacked}
    gathered |= {g[1:] for g in gathered if g[:1] == (1,)}
    assert whole <= gathered, (whole, gathered)
    _assert_same_training(_train(engine, batch), reference)


def test_stage3_with_tensor_parallel_keeps_model_axis():
    """data=2 x model=2: the gather takes 'data' out and leaves 'model'."""
    batch = _batch(2)
    reference = _train(_engine(_gpt2(), 0, 2, tp=2), batch)
    engine = _engine(_gpt2(), 3, 2, tp=2)
    use = engine._param_use
    assert use.compute["blocks"]["qkv_w"] == jax.sharding.PartitionSpec(
        None, "data", "model")
    assert use.gathered["blocks"]["qkv_w"] == jax.sharding.PartitionSpec(
        None, None, "model")
    # (no all-to-all assertion here: on this backend the partitioner
    # reduce-scatters a weight gradient over 'data' as all-to-all + add)
    shapes = _collectives(_step_text(engine, batch), "all-gather")
    # the gathered qkv weight is whole over 'data', halved over 'model'
    assert any(s.endswith(f"[{HIDDEN},{3 * HIDDEN // 2}]")
               or s.endswith(f"[1,{HIDDEN},{3 * HIDDEN // 2}]")
               for s in shapes), shapes
    assert not any(s.endswith(f"[{HIDDEN},{3 * HIDDEN}]") for s in shapes)
    _assert_same_training(_train(engine, batch), reference)


def test_what_lies_outside_the_stacks_is_named_by_the_model():
    """``gathered_top`` knows no stack's name: a model whose stacks are not
    called ``blocks`` names them, and they are left alone."""
    from deepspeed_tpu.models.base import gathered_top

    model = _granite()
    engine = _engine(model, 3, 4)
    params = engine._params_shape
    with stating_param_use(engine._param_use):
        jaxpr = jax.make_jaxpr(
            lambda p: gathered_top(p, "mamba", "attention"))(params)
        everything = jax.make_jaxpr(gathered_top)(params)
    assert set(gathered_top(params, "mamba", "attention")) == \
        {"embed", "final_norm"}
    # a gather for the embedding and one for the final norm (the threshold
    # is 0), and one more for every leaf of the stacks when they go unnamed
    assert str(jaxpr).count("sharding_constraint") == 2
    assert str(everything).count("sharding_constraint") > 2 + \
        len(jax.tree_util.tree_leaves(params["attention"]))


@pytest.mark.parametrize("name", ["gpt2", "llama"])
@pytest.mark.parametrize("stage,dp", [(0, 4), (1, 4), (2, 4), (3, 1)])
def test_helper_is_the_identity_below_stage3_and_on_one_device(stage, dp,
                                                               name):
    """Nothing is sharded for compute, so the engine states no gather and a
    model traces to the jaxpr it has outside any engine: GPT-2, which names
    the leaf its block uses first (``wrapped_block``'s ``first``), carries
    nothing, and LLaMA, which names none, is not asked."""
    model = MODELS[name]()
    engine = _engine(model, stage, dp)
    assert engine._param_use is None
    params = engine._params_shape
    batch = {k: jnp.zeros((2, SEQ), jnp.int32)
             for k in ("input_ids", "labels")}

    def loss(p):
        return model.apply(p, batch, train=True)[0]

    bare = jax.make_jaxpr(jax.grad(loss))(params)
    with stating_param_use(engine._param_use):
        stated = jax.make_jaxpr(jax.grad(loss))(params)
    assert str(stated) == str(bare)


def test_helper_states_gathers_only_under_a_stage3_plan():
    """The same model, traced under a stage-3 plan on data=4, constrains
    its weights; outside the scope it does not."""
    model = _gpt2()
    engine = _engine(model, 3, 4)
    assert engine._param_use is not None
    params = engine._params_shape
    batch = {k: jnp.zeros((8, SEQ), jnp.int32)
             for k in ("input_ids", "labels")}

    def loss():   # a new function each time: jax keeps a trace by function
        return lambda p: model.apply(p, batch, train=True)[0]

    assert "sharding_constraint" not in str(jax.make_jaxpr(loss())(params))
    with stating_param_use(engine._param_use):
        stated = str(jax.make_jaxpr(loss())(params))
    assert "sharding_constraint" in stated and "custom_vjp_call" in stated
    assert "sharding_constraint" not in str(jax.make_jaxpr(loss())(params))


def test_one_model_object_under_two_engines():
    """jax keeps a traced block (``jax.checkpoint``, ``lax.scan``) by its
    function; a model whose block was traced for a stage-0 engine must
    still state its gathers for a stage-3 engine, and the reverse."""
    model, batch = _gpt2(), _batch(4)
    whole = f"[{HIDDEN},{4 * HIDDEN}]"
    first = _collectives(_step_text(_engine(model, 0, 4), batch), "all-gather")
    assert not [s for s in first if s.endswith(whole)], first
    stage3 = _collectives(_step_text(_engine(model, 3, 4), batch),
                          "all-gather")
    assert [s for s in stage3 if s.endswith(whole)], stage3
    again = _collectives(_step_text(_engine(model, 0, 4), batch), "all-gather")
    assert not [s for s in again if s.endswith(whole)], again


# ---- the leaf a block uses first, gathered a layer ahead (GPT-2's qkv_w)
QKV = f"f32[{HIDDEN},{3 * HIDDEN}]"


def _zero_counters():
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    return (reg.counter("zero/traced_prefetched_gather").value,
            reg.counter("zero/traced_gather").value)


def _every_gather_in_place():
    """The walk as it is where no leaf is carried: ``first`` is stated and
    the plan is taken to gather nothing ahead."""
    return mock.patch("deepspeed_tpu.models.stack.gathers",
                      lambda *path: False)


@functools.lru_cache(maxsize=None)
def _compiled_step(carried: bool, remat_policy="dots_no_batch"):
    """(text, temporaries in bytes) of GPT-2's stage-3 step on data=4,
    with ``qkv_w`` carried a layer ahead or every gather in place."""
    batch = _batch(4)
    with contextlib.nullcontext() if carried else _every_gather_in_place():
        engine = _engine(_gpt2(remat_policy), 3, 4)
        engine._build_train_step(batch)
        placed = jax.device_put(batch, engine._gas_batch_shardings(batch))
        compiled = engine._compiled_train_step.lower(
            engine.state, placed, jnp.asarray(1e-3, jnp.float32),
            jax.random.PRNGKey(0), None, None).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_first_leaf_a_layer_ahead_is_the_same_mathematics():
    """Loss and every gradient of a micro-step with ``qkv_w`` gathered a
    layer ahead equal those with every gather in place."""
    engine, batch = _engine(_gpt2(), 3, 4), _batch(4)
    micro = jax.device_put({k: v[0] for k, v in batch.items()},
                           engine._batch_shardings(
                               {k: v[0] for k, v in batch.items()}))

    def loss_and_grads():   # a new function each time: a new trace
        return jax.jit(lambda p, b: engine._micro_loss_and_grads(
            p, b, 1.0, jax.random.PRNGKey(0))[:2])

    before = _zero_counters()
    ahead = jax.device_get(loss_and_grads()(engine.state.params, micro))
    assert _zero_counters()[0] > before[0], "nothing was carried"
    with _every_gather_in_place():
        before = _zero_counters()
        in_place = jax.device_get(
            loss_and_grads()(engine.state.params, micro))
        assert _zero_counters()[0] == before[0]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
        ahead, in_place)
    assert float(np.abs(in_place[1]["blocks"]["qkv_w"]).max()) > 0


def _computations(text):
    """name -> instruction lines of each computation of a compiled text."""
    out, name = {}, None
    for line in text.split("\n"):
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name and line.strip():
            out[name].append(line)
    return out


def _qkv_gathers(text, where):
    """(computation, result name) of the all-gathers of a whole ``qkv_w``
    whose ``op_name`` holds ``where``."""
    return [(comp, re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1))
            for comp, lines in _computations(text).items() for line in lines
            if re.search(re.escape(QKV) + r"\S* all-gather(-start)?\(", line)
            and where in re.search(r'op_name="([^"]*)"', line).group(1)]


def test_first_leaf_leaves_the_forward_body_through_the_carry():
    """The compiled step gathers ``qkv_w`` in the forward layer loop and no
    matmul of that body reads the result: it goes out with the carry, for
    the next layer. The backward loop is the one of a leaf gathered in
    place: it gathers the layer's own slice, once, as it did."""
    text, _ = _compiled_step(True)
    forward = _qkv_gathers(text, "/jvp()/while/body/")
    assert len(forward) == 1, forward
    comp, gather = forward[0]
    lines = _computations(text)[comp]
    reached, grew = {gather}, True
    while grew:     # everything in the body computed from the gather
        grew = False
        for line in lines:
            name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
            operands = set(re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[1]))
            if name not in reached and operands & reached:
                reached.add(name)
                grew = True
                assert "dot" not in line.split(" = ", 1)[1].split("(")[0] \
                    and "dot_general" not in line, line
                if line.lstrip().startswith("ROOT"):
                    assert " tuple(" in line, line
    assert any(line.lstrip().startswith("ROOT") and re.match(
        r"\s*ROOT %([\w.\-]+) = ", line).group(1) in reached
        for line in lines), "the gathered leaf does not reach the carry"
    assert len(_qkv_gathers(text, "transpose(jvp())")) == 1
    # the control: with every gather in place the forward loop's one gather
    # feeds that body's first matmul, and the backward loop is the same
    in_place, _ = _compiled_step(False)
    assert len(_qkv_gathers(in_place, "transpose(jvp())")) == 1
    assert len(_qkv_gathers(in_place, "/jvp()/while/body/")) == 1


@pytest.mark.parametrize("remat_policy,leaves", [("dots_no_batch", 3),
                                                 ("nothing", LAYERS + 3)])
def test_the_carry_saves_no_more_than_one_leaf_a_layer(remat_policy, leaves):
    """What the step holds in temporaries beyond the walk with every gather
    in place. Where remat keeps the product that reads the leaf
    (``dots_no_batch``): the leaf in flight and the one being handed over,
    and nothing for each layer. Where the product is recomputed: the
    carried leaf saved for every layer besides, and still nothing like a
    whole layer, whose four matrices would be four times that."""
    leaf = HIDDEN * 3 * HIDDEN * 4
    layer = (4 + 2 * 4) * HIDDEN * HIDDEN * 4
    grown = _compiled_step(True, remat_policy)[1] \
        - _compiled_step(False, remat_policy)[1]
    assert 0 < grown <= leaves * leaf < LAYERS * layer, grown


def test_counters_say_what_was_gathered_where():
    """One trace of the step under the plan: one leaf of a block is stated a
    layer ahead and the block's three other matrices in place (the scan's
    body is traced once, not once a layer); with no plan, nothing counts."""
    groups.reset()
    conf = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2, "steps_per_print": 0,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            # the four matrices of a block and the embedding, no vector
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 10000}}
    model = _gpt2()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=DeepSpeedConfig(conf, world_size=4),
        topology=build_topology(4, dp=4, tp=1))
    use, params = engine._param_use, engine._params_shape
    sharded = {k for k, v in use.compute["blocks"].items()
               if v != use.gathered["blocks"][k]}
    assert sharded == {"qkv_w", "attn_out_w", "mlp_fc_w", "mlp_out_w"}
    top = sum(use.compute[k] != use.gathered[k] for k in use.compute
              if k != "blocks")
    batch = {k: jnp.zeros((8, SEQ), jnp.int32)
             for k in ("input_ids", "labels")}

    def grad():
        return jax.grad(lambda p: model.apply(p, batch, train=True)[0])

    before = _zero_counters()
    jax.make_jaxpr(grad())(params)
    assert _zero_counters() == before
    with stating_param_use(use):
        jax.make_jaxpr(grad())(params)
    ahead, in_place = np.subtract(_zero_counters(), before)
    # the embedding is gathered where it is read and again for the head
    assert (ahead, in_place - 2 * top) == (1, 3)


@pytest.mark.parametrize("name", ["llama", "transformer", "granite"])
def test_a_family_that_states_no_first_leaf_is_not_asked(name):
    """Under a stage-3 plan a family whose ``wrapped_block`` names no first
    leaf never consults the plan for one and carries nothing."""
    model = MODELS[name]()
    engine = _engine(model, 3, 4)
    batch = {k: jnp.zeros((8, SEQ), jnp.int32)
             for k in ("input_ids", "labels")}
    before = _zero_counters()
    with mock.patch("deepspeed_tpu.models.stack.gathers",
                    side_effect=AssertionError("asked")), \
            stating_param_use(engine._param_use):
        jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, batch, train=True)[0]))(
                engine._params_shape)
    ahead, in_place = np.subtract(_zero_counters(), before)
    assert ahead == 0 and in_place > 0
