"""The hybrid model's decode step two ways: every Mamba layer's mixer folded
into the one kernel (``ops/ssm.mamba_step``, interpret mode here) against the
split route the CPU takes by itself (``causal_conv``, the ``jnp`` update, gate
and norm as XLA operations), through ``forward_with_cache`` at a tiny size
whose widths fold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                                 GraniteHybridConfig,
                                                 GraniteHybridModel)
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.telemetry.registry import get_registry

pytestmark = pytest.mark.quick

SLOTS, PROMPT = 3, 6
TOL = dict(rtol=1e-4, atol=1e-6)    # logits of a random model are near 0.01


def _model(d_state=128):
    return GraniteHybridModel(GraniteHybridConfig(
        vocab_size=512, max_seq_len=32, hidden_size=64, num_heads=4,
        num_kv_heads=2, intermediate_size=128, mamba_n_heads=4,
        mamba_d_head=32, mamba_d_state=d_state, mamba_chunk_size=8,
        layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA)),
        compute_dtype=jnp.float32)


def _counts():
    reg = get_registry()
    return (reg.counter("ssm/traced_folded_step").value,
            reg.counter("ssm/traced_split_step").value)


def _decode(model, params, monkeypatch, folded):
    """A prompt a slot, then three decode steps, slot 1 switched off in the
    second: the logits of each step and the cache after each."""
    if folded:      # the route is the backend's; the test steers it
        monkeypatch.setattr(ssm, "default_route", lambda: "pallas")
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 512, (SLOTS, PROMPT + 3)), jnp.int32)
    step = jax.jit(model.forward_with_cache)
    cache = model.init_cache(SLOTS, 32, dtype=jnp.float32)
    cache["index"] = jnp.zeros((SLOTS,), jnp.int32)
    cache["valid_len"] = jnp.full((SLOTS,), PROMPT, jnp.int32)
    _, cache = step(params, ids[:, :PROMPT], cache)
    out = []
    for i, active in enumerate(([1, 1, 1], [1, 0, 1], [1, 1, 1])):
        cache["valid_len"] = jnp.asarray(active, jnp.int32)
        logits, cache = step(params, ids[:, PROMPT + i:PROMPT + i + 1], cache)
        out.append((np.asarray(logits), jax.tree_util.tree_map(np.asarray,
                                                               cache)))
    return out


def test_three_decode_steps_folded_equal_the_split_route(monkeypatch):
    model = _model()
    c = model.config
    assert ssm.step_folds(c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                          c.mamba_n_groups)
    params = model.init(jax.random.PRNGKey(0))
    before = _counts()
    split = _decode(model, params, monkeypatch, folded=False)
    between = _counts()
    folded = _decode(model, params, monkeypatch, folded=True)
    after = _counts()
    # one decode trace each, three Mamba layers in two runs: a run's layer
    # is traced once
    assert (between[0] - before[0], between[1] - before[1]) == (0, 2)
    assert (after[0] - between[0], after[1] - between[1]) == (2, 0)
    for (logits, cache), (logits_ref, cache_ref) in zip(folded, split):
        np.testing.assert_allclose(logits, logits_ref, **TOL)
        for name in ("k", "v", "ssm", "conv"):
            np.testing.assert_allclose(cache[name], cache_ref[name],
                                       rtol=1e-5, atol=1e-5, err_msg=name)
    # the slot switched off in the second step kept state and tail, bit for
    # bit, and moved again in the third
    for name in ("ssm", "conv"):
        first, second, third = (cache[name] for _, cache in folded)
        np.testing.assert_array_equal(second[:, 1], first[:, 1])
        assert (second[:, 0] != first[:, 0]).any()
        assert (third[:, 1] != second[:, 1]).any()


def test_widths_that_do_not_fold_run_split_on_the_kernel_route(monkeypatch):
    """A state of 16 is no row of lanes: the layer runs as before, the update
    alone in the kernel, and says so in the registry."""
    model = _model(d_state=16)
    c = model.config
    assert not ssm.step_folds(c.mamba_n_heads, c.mamba_d_head,
                              c.mamba_d_state, c.mamba_n_groups)
    assert model.init_cache(SLOTS, 32)["conv"].shape == \
        (3, SLOTS, 3, c.conv_dim)
    params = model.init(jax.random.PRNGKey(1))
    split = _decode(model, params, monkeypatch, folded=False)
    before = _counts()
    kernel = _decode(model, params, monkeypatch, folded=True)
    after = _counts()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 2)
    for (logits, cache), (logits_ref, cache_ref) in zip(kernel, split):
        np.testing.assert_allclose(logits, logits_ref, **TOL)
        np.testing.assert_allclose(cache["ssm"], cache_ref["ssm"],
                                   rtol=1e-5, atol=1e-5)


def test_a_stack_of_single_sublayers_folds_at_two_groups(monkeypatch):
    """``models/mamba.mixer`` under the other family that calls it:
    Nemotron-H's layers are ONE sublayer each and its Mamba-2 has several
    groups of heads (a head reads its group's ``B`` and ``C``, the gated norm
    runs a group). Folded at two groups the three decode steps are the split
    route's, the expert layers between them carry no leaf, and the registry
    says that a step of several groups folded."""
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    model = NemotronHModel(NemotronHConfig.tiny(
        hybrid_override_pattern="MEM*E", mamba_n_heads=16, mamba_n_groups=2,
        mamba_d_state=128, max_seq_len=32), compute_dtype=jnp.float32)
    c = model.config
    assert ssm.step_folds(c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                          c.mamba_n_groups)
    assert not ssm.step_folds(c.mamba_n_heads, c.mamba_d_head,
                              c.mamba_d_state, 4)   # a group is half a row
    grouped = get_registry().counter("ssm/traced_step_folded_groups")
    params = model.init(jax.random.PRNGKey(2))
    split = _decode(model, params, monkeypatch, folded=False)
    before = (*_counts(), grouped.value)
    folded = _decode(model, params, monkeypatch, folded=True)
    after = (*_counts(), grouped.value)
    # one decode trace, two Mamba layers, each a run of its own
    assert tuple(a - b for a, b in zip(after, before)) == (2, 0, 2)
    for (logits, cache), (logits_ref, cache_ref) in zip(folded, split):
        np.testing.assert_allclose(logits, logits_ref, rtol=1e-4, atol=1e-5)
        for name in ("k", "v", "ssm", "conv"):
            np.testing.assert_allclose(cache[name], cache_ref[name],
                                       rtol=1e-5, atol=1e-5, err_msg=name)
        assert cache["step_counters"].shape == (7,)
    first, second, third = (cache["ssm"] for _, cache in folded)
    np.testing.assert_array_equal(second[:, 1], first[:, 1])
    assert (third[:, 1] != second[:, 1]).any()
