"""The softmax grouped-query layer's prompt attention as one Pallas call
(ops/gqa_prefill.py) in interpret mode against the ``lax`` loop
(``ops/attention.blocked_prompt_attention``) and against dense
``multihead_attention`` over repeated key-value heads: head size 128 (the
kernel takes whole lanes only), token blocks of two query tiles over key
blocks of a tile's size, so that tiles are skipped, masked and walked whole;
and ``SolarKdaModel._gqa_layer``'s choice between the two routes, which the
two ``gqa/*`` counters tell."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.moe_ffn import zero_counts
from deepspeed_tpu.ops import gqa_prefill
from deepspeed_tpu.ops.attention import (blocked_prompt_attention,
                                         multihead_attention)

pytestmark = pytest.mark.quick

T, BK, TILE, S, LAYERS, HKV, D = 32, 16, 16, 96, 2, 2, 128
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(autouse=True)
def two_tiles_a_token_block(monkeypatch):
    """And two chunks a head's tile, so that a masked tile's second chunk
    sees its own rows' diagonal."""
    monkeypatch.setattr(gqa_prefill, "_QUERY_TILE", TILE)
    monkeypatch.setattr(gqa_prefill, "_CHUNK_ROWS", TILE // 2)


def _operands(b, rep, seed, s_max=S):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    return (f(b, T, HKV * rep, D), f(LAYERS, b, HKV, s_max, D),
            f(LAYERS, b, HKV, s_max, D))


def _positions(first, b):
    """``[B, T]``: each row's consecutive positions from its first on."""
    return jnp.broadcast_to(jnp.asarray(first), (b,))[:, None] \
        + jnp.arange(T)[None]


def _dense(q, k_layer, v_layer, first):
    """Every query head against its key-value head's rows, one softmax."""
    b, rep = q.shape[0], q.shape[2] // k_layer.shape[1]
    live = jnp.arange(k_layer.shape[2])[None, None, :] \
        <= _positions(first, b)[:, :, None]
    rows = lambda a: jnp.repeat(a.transpose(0, 2, 1, 3), rep, 2)  # noqa: E731
    return multihead_attention(q, rows(k_layer), rows(v_layer), causal=False,
                               mask=live[:, None])


# (first position(s), real positions of the block or None, batch rows, rep)
CASES = {
    "one-token-block": ([0], None, 1, 8),
    # each bucket's block count: 1, 2, 3 token blocks of a 96-row allocation,
    # the last one's key blocks wholly below the diagonal but the last two
    "three-token-blocks": ([0, T, 2 * T], None, 1, 8),
    # behind 24 cached rows: a tile's diagonal crosses two key blocks
    "continued-at-idx": ([24], None, 1, 8),
    "shorter-than-its-bucket": ([T], 20, 1, 8),
    "a-dead-block": ([T], 0, 1, 8),
    "batch-of-two": ([np.asarray([8, 40])], np.asarray([T, 9]), 2, 8),
    "one-query-head-a-key-value-head": ([0, 2 * T], None, 1, 1),
    "rep-1-shorter": ([T], 7, 2, 1),
    "chunks-of-a-whole-tile": ([8], 20, 1, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_against_the_loop_and_dense_attention(case, monkeypatch):
    firsts, valid, b, rep = CASES[case]
    if case == "chunks-of-a-whole-tile":  # one pair of matmuls a head's tile
        monkeypatch.setattr(gqa_prefill, "_CHUNK_ROWS", TILE)
    q, k_full, v_full = _operands(b, rep, len(case))
    for first in firsts:
        with jax.default_matmul_precision("highest"):
            out = jax.jit(lambda q, k, v: gqa_prefill.gqa_prefill(
                q, k, v, jnp.asarray(1), first, valid, key_block=BK,
                interpret=True))(q, k_full, v_full)
            want = blocked_prompt_attention(
                q, k_full[1], v_full[1], _positions(first, b), key_block=BK)
            dense = _dense(q, k_full[1], v_full[1], first)
        np.testing.assert_allclose(want, dense, **TOL)
        real = np.broadcast_to(T if valid is None else np.asarray(valid),
                               (b,))
        for row in range(b):
            # tiles with a real position: the loop's numbers; the others
            # were never visited and are zeros
            live = -(-int(real[row]) // TILE) * TILE
            np.testing.assert_allclose(out[row, :live], want[row, :live],
                                       **TOL)
            assert not np.asarray(out[row, live:]).any()


def test_a_dead_tile_touches_no_key():
    """Rows past the last live tile's reach may hold anything (a slot's last
    tenant, NaN): they are neither fetched into a product nor masked."""
    q, k_full, v_full = _operands(1, 8, 3)
    reach = T + TILE                       # 20 real positions: one live tile
    k_full = k_full.at[:, :, :, reach:].set(jnp.nan)
    v_full = v_full.at[:, :, :, reach:].set(jnp.nan)
    out = gqa_prefill.gqa_prefill(q, k_full, v_full, 0, T, 12, key_block=BK,
                                  interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    assert np.asarray(out[0, :TILE]).any() and not np.asarray(
        out[0, TILE:]).any()


@pytest.mark.parametrize("shape,fits", [
    # (rows, row width, head size, key block, token block, heads, kv heads)
    ((16384, 128, 128, 512, 2048, 64, 8), True),   # the cell's 16k bucket
    ((2048, 128, 128, 512, 2048, 64, 8), True),    # and its smallest
    ((4096, 128, 128, 512, 2048, 64, 64), True),   # rep 1
    ((4096, 256, 256, 512, 2048, 16, 8), True),    # two rows of lanes
    ((4096, 128, 64, 512, 2048, 64, 8), False),    # packed rows (two a lane row)
    ((4096, 64, 64, 512, 2048, 64, 8), False),     # half a row of lanes
    ((4000, 128, 128, 512, 2048, 64, 8), False),   # no whole key blocks
    ((4096, 128, 128, 24, 2048, 64, 8), False),    # no whole sublane tiles
    ((4096, 128, 128, 512, 2000, 64, 8), False),   # no whole query tiles
    ((4096, 128, 128, 512, 192, 64, 8), False),    # a tile of no whole chunks
    ((4096, 128, 128, 512, 2048, 60, 8), False),   # heads in no whole groups
    ((64, 16, 16, 8, 16, 4, 2), False)],           # a tiny model
    ids=str)
def test_supports_says_from_shapes_what_routes(shape, fits, monkeypatch):
    monkeypatch.undo()                     # the program's own query tile
    assert gqa_prefill.supports(*shape) is fits
    assert gqa_prefill.query_tile(2048) == gqa_prefill._QUERY_TILE
    assert gqa_prefill._QUERY_TILE % gqa_prefill._CHUNK_ROWS == 0


# ----------------------------------------------------------- the model's route
def _counters():
    from deepspeed_tpu.telemetry.registry import get_registry

    c = get_registry().snapshot()["counters"]
    return (c.get("gqa/traced_prefill_kernel", 0),
            c.get("gqa/traced_blocked_block", 0))


def _layer(head_dim=D, key_block=BK, s_max=S, t=T, packed=False):
    """``SolarKdaModel._gqa_layer`` on one layer's weights over a cache of
    two layers, a prompt block continued at position 8 whose last 12
    positions are padding -> ``run() -> (x, counted)``."""
    import dataclasses

    from deepspeed_tpu.models.solar_kda import SolarKdaConfig, SolarKdaModel
    from deepspeed_tpu.ops.attention import alloc_kv_cache

    c = dataclasses.replace(SolarKdaConfig.tiny(key_block=key_block),
                            head_dim=head_dim)
    model = SolarKdaModel(c, compute_dtype=jnp.float32)
    blk = jax.tree_util.tree_map(
        lambda a: a[0], model.init(jax.random.PRNGKey(2))["gqa"])
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(1, t, c.hidden_size), jnp.float32)
    if packed:      # the persistent slot cache's form at head size 64
        kc, vc = (alloc_kv_cache(2, 2, c.num_kv_heads, s_max, head_dim,
                                 jnp.float32) for _ in range(2))
        assert kc.shape[4] == 2 * head_dim
        x = jnp.concatenate([x, x])
    else:
        kc = jnp.asarray(rng.randn(2, 1, c.num_kv_heads, s_max, head_dim),
                         jnp.float32)
        vc = kc[::-1] * 0.5
    counts = zero_counts(t)

    def run():
        before = _counters()
        with jax.default_matmul_precision("highest"):
            out, _ = model._gqa_layer(x, blk, (kc, vc, counts), 1,
                                      jnp.asarray(8), jnp.asarray([t - 12]
                                                                  * len(x)))
        return out, tuple(a - b for a, b in zip(_counters(), before))

    return run


@pytest.fixture
def kernel_route(monkeypatch):
    """The model picks the kernel where ``jax.default_backend()`` says tpu
    (steered here, not through an option of the program); the call itself
    runs in the Pallas interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gqa_prefill, "gqa_prefill", functools.partial(
        gqa_prefill.gqa_prefill, interpret=True))


def test_the_model_takes_the_kernel_for_a_cached_prompt_block_on_a_tpu(
        kernel_route, monkeypatch):
    run = _layer()
    out, counted = run()
    assert counted == (1, 0)
    monkeypatch.undo()                    # a CPU: the loop
    want, counted = run()
    assert counted == (0, 1)
    live = -(-(T - 12) // TILE) * TILE
    # past the live tiles the attention is zeros, before them the loop's
    np.testing.assert_allclose(np.asarray(out[0, :live]),
                               np.asarray(want[0, :live]), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("why,kw", [
    ("a tiny model's head size", {"head_dim": 16}),
    ("rows that are no whole key blocks", {"s_max": 104, "key_block": 8,
                                           "head_dim": 16}),
    ("a key block of no whole sublane tiles", {"key_block": 8}),
    ("a token block of no whole query tiles", {"t": 24}),
])
def test_every_refusal_of_supports_routes_to_the_loop(kernel_route, why, kw):
    """On a TPU too: the kernel's counter stays, the loop's counts."""
    _, counted = _layer(**kw)()
    assert counted == (0, 1), why


def test_packed_rows_and_one_token_never_reach_either_route(kernel_route):
    """Rows packed two a lane row (head size 64 in a persistent slot cache)
    and a one-token step go through ``cached_attention`` as before."""
    _, counted = _layer(head_dim=64, s_max=128, packed=True)()
    assert counted == (0, 0)
