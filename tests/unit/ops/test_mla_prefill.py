"""The decompressed prompt attention of latent attention as one Pallas call
(ops/mla_prefill.py) in interpret mode, reached as the model reaches it
(``SarvamMlaModel._prompt_attention`` with the backend read as a TPU), against
the ``lax`` loop of the same method and against dense ``multihead_attention``
on decompressed heads: tiny widths with the cell's ratios (nope 2 x rope, the
row padded to whole lanes), token blocks of two query tiles of two chunks over
key blocks of a tile's size, so that tiles are skipped, masked and walked
whole and a masked tile's second chunk sees its own rows' diagonal; and the
chunked walk against one chunk a tile, bit for bit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.sarvam_mla import SarvamMlaConfig, SarvamMlaModel
from deepspeed_tpu.ops import mla_prefill
from deepspeed_tpu.ops.attention import multihead_attention

pytestmark = pytest.mark.quick

T, BK, TILE, S, LAYERS = 32, 16, 16, 96, 3


def _model(**kw):
    kw.setdefault("prompt_block", T)
    kw.setdefault("key_block", BK)
    return SarvamMlaModel(SarvamMlaConfig.tiny(**kw),
                          compute_dtype=jnp.float32)


def _operands(model, b, seed, s_max=S):
    c = model.config
    rng = np.random.RandomState(seed)
    h, n, rope, r = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                     c.kv_lora_rank)
    latent = rng.randn(LAYERS, b, s_max, c.row_width).astype(np.float32)
    latent[..., r + rope:] = 0.0          # the zero lanes behind the key
    wkv_b = 0.2 * rng.randn(2, r, h * (n + c.v_head_dim)).astype(np.float32)
    return (jnp.asarray(rng.randn(b, T, h, n).astype(np.float32)),
            jnp.asarray(rng.randn(b, T, h, rope).astype(np.float32)),
            jnp.asarray(latent), jnp.asarray(wkv_b))


def _dense(model, q_nope, q_rope, latent, wkv_b, layer, first):
    """Every head's keys and values decompressed, one softmax a row."""
    c = model.config
    b, t, h, n = q_nope.shape
    r, rope = c.kv_lora_rank, c.qk_rope_head_dim
    rows = latent[layer]
    kv = jnp.einsum("bsc,che->bshe", rows[..., :r],
                    wkv_b.reshape(r, h, n + c.v_head_dim))
    keys = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(rows[:, :, None, r:r + rope],
                                       (b, rows.shape[1], h, rope))], -1)
    live = jnp.arange(rows.shape[1])[None, None, :] \
        <= _positions(first, b)[:, :, None]
    return multihead_attention(
        jnp.concatenate([q_nope, q_rope], -1), keys, kv[..., n:],
        causal=False, mask=live[:, None], scale=c.score_scale)


def _positions(first, b):
    """``[B, T]``: each row's consecutive positions from its first on."""
    return jnp.broadcast_to(jnp.asarray(first), (b,))[:, None] \
        + jnp.arange(T)[None]


@pytest.fixture
def kernel_route(monkeypatch):
    """The model picks the kernel where ``jax.default_backend()`` says tpu
    (steered here, not through an option of the program); the call itself
    runs in the Pallas interpreter, two query tiles a token block, two chunks
    a tile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mla_prefill, "_QUERY_TILE", TILE)
    monkeypatch.setattr(mla_prefill, "_CHUNK_ROWS", TILE // 2)
    monkeypatch.setattr(mla_prefill, "mla_prefill", functools.partial(
        mla_prefill.mla_prefill, interpret=True))


def _counters():
    from deepspeed_tpu.telemetry.registry import get_registry

    c = get_registry().snapshot()["counters"]
    return (c.get("mla/traced_prefill_kernel", 0),
            c.get("mla/traced_decompressed_block", 0))


# (first position(s), real positions of the block or None, batch rows)
CASES = {
    "one-token-block": ([0], None, 1),
    "three-token-blocks": ([0, T, 2 * T], None, 1),
    # behind 24 cached rows: a tile's diagonal crosses two key blocks
    "continued-at-idx": ([24], None, 1),
    "shorter-than-its-bucket": ([T], 20, 1),
    "a-dead-block": ([T], 0, 1),
    "batch-of-two": ([np.asarray([8, 40])], np.asarray([T, 9]), 2),
    # positions 21 to 52: no chunk starts at a multiple of its rows, and the
    # diagonal crosses inside the chunks of both tiles
    "a-diagonal-inside-a-chunk": ([21], None, 1),
    # a tile smaller than a chunk is one chunk whole
    "a-tile-smaller-than-a-chunk": ([8], 20, 1),
}
CHUNK_ROWS = {"a-tile-smaller-than-a-chunk": 4 * TILE}
# a row meets the same key blocks in the same order however its tile is cut
BIT_FOR_BIT = {c + "-chunked-as-whole-bit-for-bit": c
               for c in ("three-token-blocks", "batch-of-two")}


@pytest.mark.parametrize(
    "case", list(CASES) + list(BIT_FOR_BIT) + ["refused-by-shape"])
def test_kernel_against_the_loop_and_dense_attention(kernel_route, case,
                                                     monkeypatch):
    if case == "refused-by-shape":
        # 100 cached rows are no whole key blocks: the loop, and its counter
        model = _model()
        q_nope, q_rope, latent, wkv_b = _operands(model, 1, 7, s_max=100)
        assert not mla_prefill.supports(100, model.config.row_width, BK, T)
        before = _counters()
        with jax.default_matmul_precision("highest"):
            out = model._prompt_attention(q_nope, q_rope, latent, 1,
                                          _positions(0, 1),
                                          {"wkv_b": wkv_b[0]})
            want = _dense(model, q_nope, q_rope, latent, wkv_b[0], 1, 0)
        assert _counters() == (before[0], before[1] + 1)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        return
    firsts, valid, b = CASES[BIT_FOR_BIT.get(case, case)]
    model = _model()
    q_nope, q_rope, latent, wkv_b = _operands(model, b, len(case))
    # the walk hands the stack whole, the layer beside it (models/base.py)
    blk = {"wkv_b": {"__whole__": wkv_b, "__layer__": jnp.asarray(1)}}

    def kernel(first, chunk_rows, **compiler_options):
        monkeypatch.setattr(mla_prefill, "_CHUNK_ROWS", chunk_rows)
        before = _counters()
        out = jax.jit(lambda *a: model._prompt_attention(
            *a, 2, _positions(first, b), blk, valid)).lower(
            q_nope, q_rope, latent).compile(compiler_options)(
            q_nope, q_rope, latent)
        assert _counters() == (before[0] + 1, before[1])
        return out

    for first in firsts:
        with jax.default_matmul_precision("highest"):
            if case in BIT_FOR_BIT:
                # the kernel's arithmetic is the same; XLA:CPU's is not: it
                # contracts ``l * corr + sum`` to a fused multiply-add in one
                # of the two programs and not in the other, a last bit in a
                # row whose maximum moved. At level 0 LLVM forms none
                whole, chunked = (
                    kernel(first, rows, xla_backend_optimization_level=0)
                    for rows in (TILE, TILE // 2))
                np.testing.assert_array_equal(chunked, whole)
                continue
            out = kernel(first, CHUNK_ROWS.get(case, TILE // 2))
            with pytest.MonkeyPatch.context() as loop:
                loop.setattr(mla_prefill, "supports", lambda *a: False)
                want = model._prompt_attention(
                    q_nope, q_rope, latent, 2, _positions(first, b), blk,
                    valid)
            dense = _dense(model, q_nope, q_rope, latent, wkv_b[1], 2, first)
        np.testing.assert_allclose(want, dense, rtol=2e-5, atol=2e-5)
        real = np.broadcast_to(T if valid is None else np.asarray(valid),
                               (b,))
        for row in range(b):
            # tiles with a real position: the loop's numbers; the others
            # were never visited and are zeros
            live = -(-int(real[row]) // TILE) * TILE
            np.testing.assert_allclose(out[row, :live], want[row, :live],
                                       rtol=2e-5, atol=2e-5)
            assert not np.asarray(out[row, live:]).any()


def test_shapes_the_kernel_takes():
    # the cell: 16 slots x 16,384 rows of 640 lanes, token blocks of 2,048
    assert mla_prefill.supports(16384, 640, 512, 2048)
    assert mla_prefill.supports(2048, 640, 512, 2048)
    assert mla_prefill.query_tile(2048) == 512
    assert not mla_prefill.supports(16384, 576, 512, 2048)   # 4.5 lane tiles
    assert not mla_prefill.supports(16000, 640, 512, 2048)   # 31.25 blocks
    assert not mla_prefill.supports(16384, 640, 512, 2000)   # 3.9 tiles
    assert not mla_prefill.supports(128, 640, 128, 24)       # generate()'s
    with pytest.raises(AssertionError):
        model = _model()
        q_nope, q_rope, latent, wkv_b = _operands(model, 1, 0, s_max=100)
        mla_prefill.mla_prefill(q_nope, q_rope, latent, wkv_b[0], 0, 0,
                                latent_width=32, scale=1.0, key_block=BK,
                                interpret=True)


def test_a_prefill_through_the_stack_takes_the_kernel(kernel_route):
    """``forward_with_cache`` as the slot prefill program calls it: a bucket
    of two token blocks, a true length inside the second; the logits at the
    last real position and the cached rows below the length are the loop's."""
    model = _model()
    params = model.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 2 * T), 0, 512)

    def prefill(params, ids):
        cache = model.init_cache(1, 2 * T, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(T + 5)
        logits, cache = model.forward_with_cache(params, ids, cache)
        return logits, cache["latent"]

    before = _counters()
    with jax.default_matmul_precision("highest"):
        logits, latent = jax.jit(prefill)(params, ids)
        # two runs of layers, each traced once inside the token-block scan
        assert _counters() == (before[0] + 2, before[1])
        with pytest.MonkeyPatch.context() as loop:
            loop.setattr(mla_prefill, "supports", lambda *a: False)
            want_logits, want_latent = jax.jit(prefill)(params, ids)
    assert logits.shape == (1, 1, 512)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(latent[:, :, :T + 5], want_latent[:, :, :T + 5],
                               rtol=1e-4, atol=1e-4)
