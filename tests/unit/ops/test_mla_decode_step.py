"""The fused absorbed decode step of latent attention
(ops/mla_decode_step.py) in interpret mode against a plain einsum over the
same leaf: ragged lengths that end inside, at the edge of and past a chunk of
128, a slot of length 0, inactive slots whose rows must come back bit for bit,
the new token's row written at the length and nowhere else, and the
trace-time counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_step import decode_rows_fetched, slot_walk
from deepspeed_tpu.ops.mla_decode_step import (count_form,
                                               fused_mla_decode_step,
                                               supports)

pytestmark = pytest.mark.quick

L, S, W, WV, H = 2, 512, 256, 128, 8
SCALE = 0.11


def _operands(b, seed=0):
    rng = np.random.RandomState(seed)
    latent = rng.randn(L, b, S, W).astype(np.float32)
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
    live = jnp.arange(S)[None, None, :] <= idx[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, rows[..., :WV]), latent


CASES = {
    "ragged": ([0, 1, 127, 128, 129, 300, 511, 255], [1] * 8),
    "inactive": ([40, 500, 0, 130, 7, 256, 384, 99], [1, 0, 1, 1, 0, 1, 0, 1]),
    "one-active": ([17, 400, 3, 260], [0, 1, 0, 0]),
    "none-active": ([5, 6, 7, 8], [0, 0, 0, 0]),
    "odd-batch": ([129, 64, 300], [1, 1, 1]),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("layer", [0, 1])
def test_kernel_against_the_einsum_route(case, layer):
    lengths, active = CASES[case]
    b = len(lengths)
    q, latent, row = _operands(b, seed=len(case))
    idx = jnp.asarray(lengths, jnp.int32)
    act = jnp.asarray(active, bool)
    with jax.default_matmul_precision("highest"):
        want_u, want_latent = _einsum_route(q, latent, row, layer, idx)
        u, out = jax.jit(
            lambda *a: fused_mla_decode_step(
                *a, value_width=WV, scale=SCALE,
                active=slot_walk(idx, act), interpret=True))(
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
