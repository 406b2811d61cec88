"""What LongCat-Flash's expert layer adds to models/moe_ffn.py and
moe/grouped.py, at tiny size in float32 against the plain reference
(``benchmarks/reference/longcat_flash.py``): the softmax route against a plain
top-k, the 32 shares' routed parts plus the identity term ONCE against the
uncut layer, the identity experts' edge cases (a token whose choices are all
zero-compute experts, a token with none), the step's counters, and the stack
the tiny model states. The program against the reference end to end (full
forward, prefill then decode through the slot cache) is held in
tests/unit/benchmarks/test_longcat_flash.py."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import longcat_flash as reference
from deepspeed_tpu.models.longcat_flash import LongcatFlashConfig, LongcatFlashModel
from deepspeed_tpu.models.moe_ffn import SPARSE, STEP_COUNTERS, ffn
from deepspeed_tpu.moe.grouped import softmax_topk_route

pytestmark = pytest.mark.quick

TOL = dict(rtol=1e-4, atol=1e-5)
REAL, ZERO, K, D = 32, 8, 4, 64
EXPERTS = ("expert_gate", "expert_up", "expert_down")
# the uncut layer under the published keys, as the reference reads them
CFG = {"n_routed_experts": REAL, "zero_expert_num": ZERO, "moe_topk": K,
       "routed_scaling_factor": 6.0}


@pytest.fixture(scope="module")
def layer():
    """One expert layer with all 32 real experts held, 8 identity experts
    behind them, 4 choices a token: its leaves, the ``pair`` stack the
    reference reads, and 2 x 9 tokens."""
    whole = LongcatFlashModel(
        LongcatFlashConfig.tiny(num_layers=1, n_routed_experts=REAL,
                                zero_experts=ZERO, num_experts_per_tok=K),
        compute_dtype=jnp.float32)
    pair = whole.init(jax.random.PRNGKey(5))["pair"]
    # experts that weigh as much as the identity term (drawn at 0.02 they
    # would add a thousandth of it)
    pair = dict(pair, **{n: pair[n] * 8.0 for n in EXPERTS})
    blk = jax.tree_util.tree_map(lambda a: a[0], pair)
    z = jnp.asarray(np.random.RandomState(2).randn(2, 9, D), jnp.float32)
    return blk, pair, z


def _ffn(blk, z, held, bias=None, valid=None):
    c = types.SimpleNamespace(
        num_experts_per_tok=K, routed_scaling_factor=6.0,
        norm_topk_prob=False, held=held, scoring_func="softmax",
        zero_experts=ZERO)
    first, count = held
    share = dict(blk, **{n: blk[n][first:first + count] for n in EXPERTS})
    if bias is not None:
        share["select_bias"] = bias
    with jax.default_matmul_precision("highest"):
        y, counts = ffn(z, share, SPARSE, valid, c)
    return y, dict(zip(STEP_COUNTERS, (int(n) for n in counts)))


def _reference(pair, z, bias=None):
    if bias is not None:
        pair = dict(pair, select_bias=bias[None])
    with jax.default_matmul_precision("highest"):
        return reference._moe(z, pair, 0, CFG)


def test_the_tiny_model_is_the_stated_stack():
    model = LongcatFlashModel(LongcatFlashConfig.tiny(held=(0, 2)),
                              compute_dtype=jnp.float32)
    c = model.config
    assert (c.num_experts, c.held, c.q_head_dim, c.row_width) == \
        (24, (0, 2), 24, 128)
    assert (c.q_scale, c.kv_scale, c.score_scale) == \
        (2.0, 2 ** 0.5, 24 ** -0.5)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params()
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    # two stacks: the 2 L sublayers, and what a double layer holds once
    assert params["sub"]["wq_a"].shape == (4, 64, 16)
    assert params["sub"]["w_gate"].shape == (4, 64, 128)
    assert params["pair"]["wkv_b0"].shape == params["pair"]["wkv_b1"].shape \
        == (2, 32, 4 * 32)
    assert params["pair"]["router"].shape == (2, 64, 24)
    assert params["pair"]["expert_gate"].shape == (2, 2, 64, 32)
    assert not [k for k in params["pair"] if k.startswith("shared_")]
    # the cache: one leaf, two rows of it a double layer
    cache = jax.eval_shape(lambda: model.init_cache(3, 64))
    assert cache["latent"].shape == (4, 3, 64, 128)
    assert model.slot_state_keys == model.row_state_keys == ("latent",)
    # the published widths: 768 router outputs, scales 2.0 and 12 ** 0.5
    wide = LongcatFlashConfig()
    assert (wide.num_experts, wide.row_width, wide.q_scale) == (768, 640, 2.0)
    assert wide.kv_scale == pytest.approx(3.4641, abs=1e-4)
    assert wide.score_scale == 192 ** -0.5
    with pytest.raises(ValueError, match="real experts"):
        LongcatFlashConfig(held=(504, 16))


@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
def test_the_softmax_route_against_a_plain_top_k(bias):
    """Probabilities over ALL outputs in float32, the bias picks and does not
    weigh, no normalisation over the chosen, times the scale."""
    rng = np.random.RandomState(3)
    x = rng.randn(11, D).astype(np.float32)
    w = (rng.randn(D, REAL + ZERO) * 0.3).astype(np.float32)
    b = (rng.randn(REAL + ZERO) * (0.05 if bias else 0.0)).astype(np.float32)
    got = softmax_topk_route(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                             K, scale=6.0)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-(p + b), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(got.experts), -1),
                                  np.sort(order, -1))
    np.testing.assert_allclose(
        np.asarray(got.weights),
        6.0 * np.take_along_axis(p, np.asarray(got.experts), -1), rtol=1e-5)
    assert got.weights.dtype == jnp.float32
    assert (np.asarray(got.weights).sum(-1) < 6.0).all()   # not normalised


def test_the_32_shares_and_the_identity_term_once_add_up_to_the_uncut_layer(
        layer):
    """The expert layer with all 32 real experts held against the uncut
    reference, and against the sum of the 32 shares ``(r, 1)``'s routed parts
    with the identity term counted ONCE: what ties one chip's share to the
    model (moe/grouped.py leaves the exchange out)."""
    blk, pair, z = layer
    want = _reference(pair, z)
    uncut, counts = _ffn(blk, z, (0, REAL))
    np.testing.assert_allclose(uncut, want, **TOL)
    pairs = 2 * 9 * K
    assert counts["moe_assignments"] == pairs
    assert counts["moe_assignments_held"] + counts["moe_assignments_zero"] \
        == pairs
    assert 0 < counts["moe_assignments_zero"] < pairs
    # the identity term alone: a share whose expert weights are zero
    none = dict(blk, **{n: jnp.zeros_like(blk[n]) for n in EXPERTS})
    identity = _ffn(none, z, (0, 1))[0]
    assert float(jnp.abs(identity).max()) > 0.1
    shares = [_ffn(blk, z, (r, 1)) for r in range(REAL)]
    total = identity + sum(y - identity for y, _ in shares)
    assert float(jnp.abs(uncut - identity).max()) > 0.1   # experts do add
    np.testing.assert_allclose(total, want, **TOL)
    assert sum(n["moe_assignments_held"] for _, n in shares) == \
        counts["moe_assignments_held"]
    # every share counts the same identity pairs: they are computed where
    # the token is
    assert {n["moe_assignments_zero"] for _, n in shares} == \
        {counts["moe_assignments_zero"]}


@pytest.mark.parametrize("favoured", ["identity", "real"])
def test_a_token_whose_choices_are_all_of_one_kind(layer, favoured):
    """With the selection bias on the identity experts every token's four
    choices are zero-compute: the layer gives ``6 * sum(p chosen) * z`` and
    touches no expert. With it on the real experts no token gets an identity
    term. The bias picks and does not weigh, so the reference gives the same
    with the same bias."""
    blk, pair, z = layer
    zero = np.arange(REAL + ZERO) >= REAL
    bias = jnp.asarray(np.where(zero == (favoured == "identity"), 10.0, 0.0),
                       jnp.float32)
    y, counts = _ffn(blk, z, (0, REAL), bias=bias)
    np.testing.assert_allclose(y, _reference(pair, z, bias), **TOL)
    pairs = 2 * 9 * K
    if favoured == "identity":
        with jax.default_matmul_precision("highest"):
            p = jax.nn.softmax(z @ blk["router"], axis=-1)
        # the four largest of the eight identity experts' probabilities
        top = jnp.sort(p[..., REAL:], axis=-1)[..., -K:].sum(-1)
        np.testing.assert_allclose(y, 6.0 * top[..., None] * z, **TOL)
        assert (counts["moe_experts_touched"], counts["moe_assignments_held"],
                counts["moe_assignments_zero"]) == (0, 0, pairs)
    else:
        none = dict(blk, **{n: jnp.zeros_like(blk[n]) for n in EXPERTS})
        assert not np.asarray(_ffn(none, z, (0, REAL), bias=bias)[0]).any()
        assert (counts["moe_assignments_held"],
                counts["moe_assignments_zero"]) == (pairs, 0)


def test_padding_is_routed_nowhere_and_counted_nowhere(layer):
    blk, _, z = layer
    valid = jnp.arange(9)[None, :] < jnp.asarray([5, 0])[:, None]
    y, counts = _ffn(blk, z, (0, 2), valid=valid)
    assert not np.asarray(y[0, 5:]).any() and not np.asarray(y[1]).any()
    assert counts["moe_assignments"] == 5 * K
    assert counts["moe_assignments_held"] + counts["moe_assignments_zero"] \
        <= 5 * K
    np.testing.assert_allclose(y[0, :5], _ffn(blk, z, (0, 2))[0][0, :5],
                               **TOL)
