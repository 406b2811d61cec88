"""``models/stack.prompt_walk`` against a plain Python loop over token blocks,
on a toy family whose layers show everything the walk hands them: the leaf
takes each real position's embedding at its cache position, the counters the
real positions and the calls, and ``x`` depends on ``idx`` and on whether a
``slot_walk`` came."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.stack import prompt_walk

pytestmark = pytest.mark.quick

B, D, VOCAB, S_MAX, PB, INDEX = 3, 4, 11, 64, 4, 5


def _layers(x, leaves, counts, idx, valid, slot_walk):
    """``leaf[b, idx + j] = x[b, j]`` for the real ``j``; ``counts`` += (real
    positions, 1); ``x`` out is ``2x + idx`` (+ 100 under a ``slot_walk``)."""
    (leaf,) = leaves
    t = x.shape[1]
    real = jnp.arange(t)[None, :] < (
        jnp.full((x.shape[0], 1), t) if valid is None else valid[:, None])
    rows = jax.lax.dynamic_slice_in_dim(leaf, idx, t, 1)
    leaf = jax.lax.dynamic_update_slice_in_dim(
        leaf, jnp.where(real[..., None], x, rows), idx, 1)
    counts = counts + jnp.stack([real.sum(), 1]).astype(jnp.int32)
    return (2 * x + idx + (0 if slot_walk is None else 100), (leaf,), counts)


def _reference(embed, ids, leaf, cache, pb):
    """The same walk as a Python loop, in numpy where the walk itself is."""
    t = ids.shape[1]
    valid = cache.get("valid_len")
    if valid is not None:
        valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))
    counts = jnp.zeros((2,), jnp.int32)
    if t > pb and t % pb == 0:
        xs = []
        for i in range(t // pb):
            x, (leaf,), counts = _layers(
                embed[ids[:, i * pb:(i + 1) * pb]], (leaf,), counts,
                cache["index"] + i * pb,
                None if valid is None else jnp.clip(valid - i * pb, 0, pb),
                None)
            xs.append(np.asarray(x))
        x = np.concatenate(xs, axis=1)
    else:
        x, (leaf,), counts = _layers(embed[ids], (leaf,), counts,
                                     cache["index"], valid,
                                     cache.get("slot_walk"))
        x = np.asarray(x)
    if t > 1 and valid is not None:
        x = np.stack([x[r, max(int(v) - 1, 0)] for r, v in
                      enumerate(np.asarray(valid))])[:, None]
    return x, np.asarray(leaf), np.asarray(counts)


@pytest.mark.parametrize("valid", [
    None, 7, (12, 5, 0)], ids=["no-valid", "scalar", "a-row-of-padding"])
@pytest.mark.parametrize("t, blocks", [
    (12, 3), (8, 2), (10, 1), (4, 1), (3, 1), (1, 1)],
    ids=["three-blocks", "two-blocks", "does-not-divide", "one-block",
         "short", "one-token"])
def test_prompt_walk_is_the_loop_over_token_blocks(t, blocks, valid):
    rng = np.random.default_rng(t)
    embed = jnp.asarray(rng.normal(size=(VOCAB, D)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, VOCAB, size=(B, t)), jnp.int32)
    leaf = jnp.zeros((B, S_MAX, D), jnp.float32)
    cache = {"index": jnp.asarray(INDEX, jnp.int32),
             "slot_walk": jnp.arange(B)}
    if valid is not None:
        cache["valid_len"] = jnp.minimum(jnp.asarray(valid, jnp.int32), t)
    x, (got_leaf,), counts = jax.jit(
        lambda ids, leaf, cache: prompt_walk(
            _layers, embed, ids, (leaf,), jnp.zeros((2,), jnp.int32), cache,
            PB))(ids, leaf, cache)
    want_x, want_leaf, want_counts = _reference(embed, ids, leaf, cache, PB)
    np.testing.assert_array_equal(np.asarray(x), want_x)
    np.testing.assert_array_equal(np.asarray(got_leaf), want_leaf)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    # the route taken: a call a token block
    assert int(counts[1]) == blocks
    assert x.shape == (B, 1 if valid is not None and t > 1 else t, D)


def test_a_token_block_sees_its_own_share_of_valid():
    """Each block's ``valid`` is clipped to the block: row 0 fills three
    blocks, row 1 one and a position, row 2 none, and the leaf keeps what was
    there behind each row's length."""
    embed = jnp.ones((VOCAB, D), jnp.float32)
    ids = jnp.zeros((B, 12), jnp.int32)
    leaf = jnp.full((B, S_MAX, D), -1.0)
    cache = {"index": jnp.asarray(0, jnp.int32),
             "valid_len": jnp.asarray([12, 5, 0], jnp.int32)}
    _, (leaf,), counts = prompt_walk(
        _layers, embed, ids, (leaf,), jnp.zeros((2,), jnp.int32), cache, PB)
    np.testing.assert_array_equal(
        np.asarray((leaf[:, :, 0] == 1).sum(axis=1)), [12, 5, 0])
    np.testing.assert_array_equal(np.asarray(counts), [17, 3])
