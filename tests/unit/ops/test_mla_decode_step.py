"""The fused absorbed decode step of latent attention
(ops/mla_decode_step.py) in interpret mode against a plain einsum over the
same leaf: ragged lengths that end inside, at the edge of and past a chunk of
128, a slot of length 0, inactive slots whose rows must come back bit for bit,
the new token's row written at the length and nowhere else, the same under
every plan ``(bg, cs)`` of the walk, the plan a geometry takes, and the
trace-time counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_step import decode_rows_fetched, slot_walk
from deepspeed_tpu.ops.mla_decode_step import (_walk_plan, count_form,
                                               fused_mla_decode_step,
                                               supports)

pytestmark = pytest.mark.quick

L, S, W, WV, H = 2, 512, 256, 128, 8
SCALE = 0.11


def _operands(b, seed=0, s=S):
    rng = np.random.RandomState(seed)
    latent = rng.randn(L, b, s, W).astype(np.float32)
    latent[..., 200:] = 0.0          # the zero lanes behind latent and key
    q = rng.randn(b, H, W).astype(np.float32)
    q[..., 200:] = 0.0
    row = rng.randn(b, W).astype(np.float32)
    row[..., 200:] = 0.0
    return jnp.asarray(q), jnp.asarray(latent), jnp.asarray(row)


def _einsum_route(q, latent, row, layer, idx):
    """What models/sarvam_mla.py does where the kernel does not run."""
    b = q.shape[0]
    latent = latent.at[layer, jnp.arange(b), idx].set(row)
    rows = latent[layer]
    s = jnp.einsum("bhw,bsw->bhs", q, rows) * SCALE
    live = jnp.arange(rows.shape[1])[None, None, :] <= idx[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, rows[..., :WV]), latent


CASES = {
    "ragged": ([0, 1, 127, 128, 129, 300, 511, 255], [1] * 8),
    "inactive": ([40, 500, 0, 130, 7, 256, 384, 99], [1, 0, 1, 1, 0, 1, 0, 1]),
    "one-active": ([17, 400, 3, 260], [0, 1, 0, 0]),
    "none-active": ([5, 6, 7, 8], [0, 0, 0, 0]),
    "odd-batch": ([129, 64, 300], [1, 1, 1]),
}


def _check_against_the_einsum_route(lengths, active, layer, s=S, seed=0,
                                    **plan):
    b = len(lengths)
    q, latent, row = _operands(b, seed=seed, s=s)
    idx = jnp.asarray(lengths, jnp.int32)
    act = jnp.asarray(active, bool)
    with jax.default_matmul_precision("highest"):
        want_u, want_latent = _einsum_route(q, latent, row, layer, idx)
        u, out = jax.jit(
            lambda *a: fused_mla_decode_step(
                *a, value_width=WV, scale=SCALE,
                active=slot_walk(idx, act), interpret=True, **plan))(
            q, latent, row, layer, idx)
    for i in range(b):
        if active[i]:
            np.testing.assert_allclose(u[i], want_u[i], rtol=2e-5, atol=2e-5)
            # the new row at the slot's length, every other row untouched
            np.testing.assert_array_equal(out[:, i], want_latent[:, i])
        else:
            assert not np.asarray(u[i]).any()
            # an inactive slot's rows: bit-identical before and after
            np.testing.assert_array_equal(out[:, i], latent[:, i])


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("layer", [0, 1])
def test_kernel_against_the_einsum_route(case, layer):
    lengths, active = CASES[case]
    _check_against_the_einsum_route(lengths, active, layer, seed=len(case))


# slots of 1,024 rows: two loop steps of 512, four of 256, eight of 128.
# Lengths that are no multiple of a DMA or of a step, 0 and ``s_max - 1``;
# inactive slots whose stale lengths are longer than every live one; one live
# slot of 16; all 16 live
S2 = 1024
PLAN_CASES = {
    "tails": ([1, 127, 129, 511, 513, S2 - 1, 0, 640], [1] * 8),
    "stale-inactive": ([1000, 30, S2 - 1, 200, 900, 129, 800, 513],
                       [0, 1, 0, 1, 0, 1, 0, 1]),
    "one-of-16": ([900, 511, 3, 260] * 4, [0] * 9 + [1] + [0] * 6),
    "all-16": ([S2 - 1, 0, 1, 127, 128, 129, 255, 256, 257, 511, 512, 513,
                640, 767, 900, 1000], [1] * 16),
}
PLANS = {"by-geometry": {}, "4x128": dict(bg=4, cs=128),
         "2x256": dict(bg=2, cs=256), "1x512": dict(bg=1, cs=512),
         "4x256": dict(bg=4, cs=256), "1x1024": dict(bg=1, cs=1024)}


@pytest.mark.parametrize("case", list(PLAN_CASES))
@pytest.mark.parametrize("plan", list(PLANS))
def test_every_plan_against_the_einsum_route(plan, case):
    lengths, active = PLAN_CASES[case]
    _check_against_the_einsum_route(lengths, active, 1, s=S2,
                                    seed=len(case), **PLANS[plan])


@pytest.mark.parametrize("s,lengths,active,plan", [
    pytest.param(4096, [4095, 513, 0, 2000, 127, 3000], [1, 1, 1, 0, 1, 1],
                 (2, 256), id="4k-rows"),
    pytest.param(16384, [16383, 1025, 9000], [1, 1, 0], (1, 1024),
                 id="16k-rows"),
])
def test_rows_that_take_the_long_step_by_their_geometry(s, lengths, active,
                                                        plan):
    """The plan taken from the shapes alone: steps of 256 rows at 4,096 rows
    a slot, two slots a group of a batch that four do not divide; steps of
    1,024 rows of one slot at 16,384."""
    assert _walk_plan(len(lengths), s, H) == plan
    _check_against_the_einsum_route(lengths, active, 0, s=s)


@pytest.mark.parametrize("geometry,plan", [
    # (slots, rows a slot, heads) of the three cells' calls, then of none
    pytest.param((16, 16384, 64), (1, 1024), id="sarvam-105b"),
    pytest.param((32, 4096, 64), (4, 256), id="longcat-flash-chat"),
    pytest.param((64, 4096, 64), (4, 256), id="gigachat3.5"),
    pytest.param((32, 8192, 64), (2, 512), id="8k-rows"),
    pytest.param((16, 32768, 64), (1, 1024), id="32k-rows"),
    pytest.param((16, 16384, 128), (1, 512), id="128-heads"),
    pytest.param((8, 2048, 64), (4, 128), id="short-rows"),
    pytest.param((6, 1024, 64), (2, 128), id="short-rows-of-6"),
    pytest.param((3, 4096, 64), (1, 256), id="4k-rows-of-3"),
])
def test_the_plan_a_geometry_takes(geometry, plan):
    """The docstring's table: ``_walk_plan`` reads slots, rows a slot and
    heads, and nothing else."""
    assert _walk_plan(*geometry) == plan


def test_an_active_mask_and_a_walk_are_the_same_call():
    q, latent, row = _operands(4)
    idx = jnp.asarray([3, 200, 130, 0], jnp.int32)
    act = jnp.asarray([1, 0, 1, 1], bool)
    by_mask = fused_mla_decode_step(q, latent, row, 1, idx, value_width=WV,
                                    scale=SCALE, active=act, interpret=True)
    by_walk = fused_mla_decode_step(q, latent, row, 1, idx, value_width=WV,
                                    scale=SCALE, active=slot_walk(idx, act),
                                    interpret=True)
    for a, b in zip(by_mask, by_walk):
        np.testing.assert_array_equal(a, b)


def test_shapes_the_walk_streams():
    assert supports(16384, 640) and supports(128, 128)
    assert not supports(16384, 576)      # 4.5 tiles of lanes
    assert not supports(100, 640)
    # the host's bookkeeping of what the walk fetches is the sibling's
    assert decode_rows_fetched([0, 1, 128, 129]) == 0 + 128 + 128 + 256
    with pytest.raises(AssertionError):
        q, latent, row = _operands(2)
        fused_mla_decode_step(q, latent[:, :, :100], row, 0,
                              jnp.zeros((2,), jnp.int32), value_width=WV,
                              scale=SCALE, interpret=True)


@pytest.mark.parametrize("b,s,long_step", [
    pytest.param(16, 16384, True, id="sarvam-105b"),
    pytest.param(8, 1024, False, id="short-rows"),
])
def test_the_counters_say_which_walk_was_traced(b, s, long_step):
    """``mla/traced_walk_long`` and ``mla/traced_walk_128`` in the global
    registry, both there after one traced call (shapes only: nothing runs)."""
    from deepspeed_tpu.telemetry.registry import get_registry

    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    reg = get_registry()
    names = ["mla/traced_walk_128", "mla/traced_walk_long"]
    was = reg.snapshot()["counters"]
    before = [was.get(n, 0) for n in names]
    jax.eval_shape(
        lambda q, latent, row, idx: fused_mla_decode_step(
            q, latent, row, jnp.int32(0), idx, value_width=512, scale=0.1,
            interpret=True),
        sds(b, 64, 640), sds(1, b, s, 640), sds(b, 640),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    counters = reg.snapshot()["counters"]
    assert set(names) <= set(counters)
    assert [counters[n] - c for n, c in zip(names, before)] \
        == [int(not long_step), int(long_step)]


def test_the_counters_say_which_form_was_traced():
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    count_form(True)
    before = dict(reg.snapshot()["counters"])
    assert {"mla/traced_absorbed_step",
            "mla/traced_decompressed_block"} <= set(before)
    count_form(False)
    count_form(False)
    after = reg.snapshot()["counters"]
    assert after["mla/traced_decompressed_block"] == \
        before["mla/traced_decompressed_block"] + 2
    assert after["mla/traced_absorbed_step"] == \
        before["mla/traced_absorbed_step"]
