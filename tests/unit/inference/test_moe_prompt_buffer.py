"""A prompt block's expert layer through each branch of
``moe/grouped.held_experts``' switch (no pair held, the compact sorted
buffer, the worst-case one) against the layer without a switch, bit for bit,
under both routers; the sizes the compact buffer takes; and the shares of a
crowded layer against the uncut one."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.moe_ffn import PROMPT_COUNTERS, SPARSE, STEP_COUNTERS, ffn
from deepspeed_tpu.moe.grouped import (compact_rows, held_experts, sigmoid_topk_route,
                                       softmax_topk_route)
from deepspeed_tpu.telemetry.registry import get_registry

pytestmark = pytest.mark.quick

# 256 tokens, 4 experts a token, 4 held of a router 32 wide: 128 pairs are
# expected here, the compact buffer has 256 rows and the full one 1,024
N, D, M, K, WIDTH, COUNT = 256, 16, 8, 4, 32, 4
ROUTERS = {
    # (route, zero-compute experts among the router's outputs)
    "sigmoid": (lambda x, w, b: sigmoid_topk_route(x, w, b, K, scale=2.5), 0),
    "softmax_zero_experts": (
        lambda x, w, b: softmax_topk_route(x, w, b, K, scale=6.0), 8)}


def _layer(seed: int, zero: int):
    rng = np.random.RandomState(seed)
    real = WIDTH - zero
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, WIDTH) * 0.5, jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(real, D, M) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(real, M, D) * 0.3, jnp.float32)
    return x, router, (wg, wu, wd)


def _share(weights, first):
    return tuple(w[first:first + COUNT] for w in weights)


def test_the_compact_buffer_follows_the_share_held():
    assert compact_rows(N, K, COUNT, WIDTH) == 256
    # a 2,048-token block of the three cells that hold an eighth, of the one
    # that holds 16 of 768 outputs, and K-EXAONE's 4,096-token prompt whole
    assert compact_rows(2048, 8, 16, 128) == 4096
    assert compact_rows(2048, 8, 40, 320) == 4096
    assert compact_rows(2048, 12, 16, 768) == 1024
    assert compact_rows(4096, 8, 16, 128) == 8192
    # a decode-sized block: a row tile at least, which is no smaller than
    # its worst case, so there is nothing to switch between
    assert compact_rows(16, 8, 16, 128) == 128 >= 16 * 8


@pytest.mark.parametrize("case", ["compact", "spilled", "empty"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_every_branch_gives_the_layer_without_a_switch(router, case):
    """(a) seeded routing: the compact buffer; (b) a selection bias that
    sends every real token's four pairs to the four experts held: 820 pairs,
    the full buffer, nothing dropped; (c) no token real: zeros. Result and
    counts are those of the call that is not told the router's width (the
    worst-case buffer alone, its rows weighed where they lie: a decode
    step's form, and a prompt's before PR 53), bit for bit."""
    route, zero = ROUTERS[router]
    x, w_router, weights = _layer(7, zero)
    bias = jnp.zeros((WIDTH,), jnp.float32)
    valid = jnp.arange(N) % 5 != 0
    if case == "spilled":
        bias = bias.at[:COUNT].set(100.0)
    if case == "empty":
        valid = jnp.zeros((N,), bool)
    reg = get_registry()
    before = [reg.counter("moe/traced_prompt_" + n).value
              for n in ("compact", "full")]

    @jax.jit
    def both(x, w_router, bias, weights, valid):
        routing = route(x, w_router, bias)
        share = _share(weights, 0)
        return (held_experts(x, routing, *share, (0, COUNT), valid=valid,
                             n_experts=WIDTH),
                held_experts(x, routing, *share, (0, COUNT), valid=valid))

    (y, counts), (want, want_counts) = both(x, w_router, bias, weights, valid)
    after = [reg.counter("moe/traced_prompt_" + n).value
             for n in ("compact", "full")]
    # the program that carries the switch says so once; the other call is
    # not a prompt's and says nothing
    assert [a - b for a, b in zip(after, before)] == [1, 0]
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    assert tuple(map(int, counts[:4])) == tuple(map(int, want_counts[:4]))
    held = int(counts.assignments_held)
    n_valid = int(valid.sum())
    assert int(counts.assignments) == n_valid * K
    if case == "compact":
        assert 0 < held <= 256 and int(counts.spilled) == 0
        assert np.asarray(y).any()
    elif case == "spilled":
        assert held == n_valid * K > 256 and int(counts.spilled) == 1
        assert np.asarray(y)[np.asarray(valid)].all(-1).all()
    else:
        assert held == 0 and int(counts.spilled) == 0
        assert not np.asarray(y).any()
    assert int(want_counts.spilled) == 0
    assert not np.asarray(y)[~np.asarray(valid)].any()


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_the_shares_of_a_crowded_layer_add_up_to_the_uncut_one(router):
    """Through ``models/moe_ffn.ffn`` as a prompt block: a selection bias
    crowds two experts of the first share, which spills to the full buffer
    while the others run compact or hold nothing; added up (the zero-compute
    experts' term counted once) they are the layer that holds every real
    expert, and the block's counters say which branch each took."""
    route, zero = ROUTERS[router]
    real = WIDTH - zero
    x, w_router, weights = _layer(11, zero)
    bias = jnp.zeros((WIDTH,), jnp.float32).at[:2].set(100.0) \
        .at[COUNT:2 * COUNT].set(-100.0)
    z = x.reshape(1, N, D)

    def config(held):
        return types.SimpleNamespace(
            num_experts_per_tok=K, routed_scaling_factor=2.5 if not zero
            else 6.0, norm_topk_prob=True, held=held, zero_experts=zero,
            scoring_func="softmax" if zero else "sigmoid")

    @jax.jit
    def layer(z, w_router, bias, weights):
        def blk(first, count):
            names = ("expert_gate", "expert_up", "expert_down")
            return dict(zip(names, (w[first:first + count] for w in weights)),
                        router=w_router, select_bias=bias)

        with jax.default_matmul_precision("highest"):
            uncut = ffn(z, blk(0, real), SPARSE, None, config((0, real)))
            shares = [ffn(z, blk(f, COUNT), SPARSE, None, config((f, COUNT)))
                      for f in range(0, real, COUNT)]
        return uncut, shares

    (uncut, uncut_counts), shares = layer(z, w_router, bias, weights)
    identity = 0.0
    if zero:
        with jax.default_matmul_precision("highest"):
            routing = route(x, w_router, bias)
        identity = (jnp.where(routing.experts >= real, routing.weights, 0.0)
                    .sum(-1, keepdims=True) * x).reshape(z.shape)
    total = sum(y for y, _ in shares) - (len(shares) - 1) * identity
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    names = STEP_COUNTERS + PROMPT_COUNTERS
    counted = [dict(zip(names, map(int, c))) for _, c in shares]
    assert all(c["moe_prompt_blocks"] == 1 for c in counted)
    # the crowded share: two pairs of every token at least; the share the
    # bias bars: none; the other two of a token's k spread over the rest
    assert counted[0]["moe_assignments_held"] >= N * 2 > 256
    assert [c["moe_prompt_blocks_spilled"] for c in counted] == \
        [1] + [0] * (len(shares) - 1)
    assert [c["moe_prompt_blocks_empty"] for c in counted] == \
        [0, 1] + [0] * (len(shares) - 2)
    assert sum(c["moe_assignments_held"] for c in counted) == \
        dict(zip(names, map(int, uncut_counts)))["moe_assignments_held"]
