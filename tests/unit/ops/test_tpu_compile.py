"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler installed here compiles for a chip that
is *described* (``jax.experimental.topologies``), and refuses what the chip's
compiler would refuse — misaligned slices, too much VMEM, a kernel GSPMD
cannot partition. Interpret mode, which every other kernel test uses, sees
none of that. Nothing runs, so these say nothing about results or speed.

This is the ONLY file that describes a topology, and it does so inside a
fixture: only the process that is given this file may load the TPU library
(see /opt/skills/guides/on-chip-measurement, section 2). The kernel cases
assert ``tpu_custom_call`` in the compiled text, i.e. the kernel is really
there; one case reads the text for an uninitialised buffer, and the serve
cells' whole decode steps are read for copies of a layer's weights.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled text"
    return text


def _flash_loss(q, k, v):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    # blocks and walk as the shapes choose them: what the cells run
    out = flash_attention(q, k, v, True, None, None, None, False)
    return jnp.sum(out.astype(jnp.float32))


# (B, T, H, Dh): GPT-2 125M at chip_smoke's micro-batch; LLaMA-7B widths
# (one row a tile); the train cells' micro-batches, gpt2-large's 40 rows and
# gpt2-xl's 50 (25 heads: rows pair across the batch boundary); the longest
# sequence whose K and V stay resident in VMEM, 8 MiB of it double-buffered;
# and one that walks them in chunks
FLASH_SHAPES = [(8, 1024, 12, 64), (2, 2048, 32, 128), (2, 1024, 20, 64),
                (2, 1024, 25, 64), (1, 8192, 8, 64), (1, 16384, 4, 64)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward(one_chip, shape):
    q = _sds(one_chip, shape)
    _compiled_text(_flash_loss, q, q, q)


def _flash_backward_kernels(text):
    """The backward kernels of a compiled text, by the names their call
    sites give them."""
    return set(re.findall(r"dstpu_flash_bwd_(?:dq|dkv)", text))


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_backward(one_chip, shape):
    """A row that is one grid step (sequence 2,048 and under: the first four
    shapes, the train cells' ``(2, 1024, 20, 64)`` and ``(2, 1024, 25,
    64)`` among them) lowers ONE backward kernel, the ``dstpu_flash_bwd_dkv``
    site with dq as its third result; ``(2, 2048, 32, 128)``, sixteen blocks
    at 128 lanes, is the largest such row and compiles under the scoped VMEM
    default. The two long rows keep the dq and the dk/dv kernel."""
    q = _sds(one_chip, shape)
    text = _compiled_text(jax.grad(_flash_loss, argnums=(0, 1, 2)), q, q, q)
    assert _flash_backward_kernels(text) == (
        {"dstpu_flash_bwd_dkv"} if shape[1] <= 2048
        else {"dstpu_flash_bwd_dq", "dstpu_flash_bwd_dkv"})


# (L, B, Hq, Hkv, S, Dh): 125M serving at 8 slots (token-pair packed cache);
# LLaMA-7B widths, batch 1
DECODE_SHAPES = [(12, 8, 12, 12, 1024, 64), (32, 1, 32, 32, 2048, 128)]


def _decode_operands(sh, l, b, hq, hkv, s, dh, *, block_size=None):
    from deepspeed_tpu.ops.attention import kv_pack_factor

    pair = kv_pack_factor(dh)
    if block_size is None:            # slot-paged: [L, B, Hkv, S/pair, Dh*pair]
        cache = _sds(sh, (l, b, hkv, s // pair, dh * pair))
    else:                             # block pool, +1 garbage block
        n = b * (s // block_size) + 1
        cache = _sds(sh, (l, n, hkv, block_size // pair, dh * pair))
    return (_sds(sh, (b, 1, hq, dh)), cache, cache,
            _sds(sh, (b, 1, hkv, dh)), _sds(sh, (b, 1, hkv, dh)),
            _sds(sh, (), jnp.int32), _sds(sh, (b,), jnp.int32))


@pytest.mark.parametrize("dims", DECODE_SHAPES, ids=str)
def test_fused_decode_step(one_chip, dims):
    from deepspeed_tpu.ops.decode_step import fused_decode_step

    fn = functools.partial(fused_decode_step, interpret=False)
    _compiled_text(fn, *_decode_operands(one_chip, *dims))


# the serve cells' slot caches: gpt2-large, 32 slots x 1024 (MHA), and the
# hybrid's four attention layers, 64 slots x 2048 (4 query heads a KV head)
SLOT_DECODE_SHAPES = [(36, 32, 20, 20, 1024, 64), (4, 64, 32, 8, 2048, 64)]


@pytest.mark.parametrize("dims", SLOT_DECODE_SHAPES, ids=str)
def test_fused_decode_step_per_slot_active(one_chip, dims):
    """The per-slot walk with the active mask, as the decode program calls
    it: the walk order made outside the kernel, per-row chunk DMAs, the
    dynamic loops over groups and active slots."""
    from deepspeed_tpu.ops.decode_step import fused_decode_step, slot_walk

    def fn(q, k, v, kn, vn, layer, idx, active):
        return fused_decode_step(q, k, v, kn, vn, layer, idx,
                                 active=slot_walk(idx, active),
                                 interpret=False)

    ops = _decode_operands(one_chip, *dims)
    _compiled_text(fn, *ops, _sds(one_chip, (dims[1],), jnp.bool_))


# K-EXAONE's share (head size 128, 8 query heads a KV head, 32 slots): the
# global layer's rows at 4096 and the four sliding layers' rings of 128
WIDE_GQA_SHAPES = [(1, 32, 64, 8, 4096, 128, False),
                   (4, 32, 64, 8, 128, 128, True)]


@pytest.mark.parametrize("dims", WIDE_GQA_SHAPES, ids=str)
def test_fused_decode_step_head_128_rep_8_and_ring(one_chip, dims):
    """The per-slot walk at head size 128 and ``rep`` 8, and on a ring
    (``ring=True``: the write row apart from the length, the written row
    left out of the walk)."""
    from deepspeed_tpu.ops.decode_step import fused_decode_step, slot_walk

    *dims, ring = dims

    def fn(q, k, v, kn, vn, layer, idx, active):
        return fused_decode_step(q, k, v, kn, vn, layer, idx,
                                 active=slot_walk(idx, active), ring=ring,
                                 interpret=False)

    ops = _decode_operands(one_chip, *dims)
    _compiled_text(fn, *ops, _sds(one_chip, (dims[1],), jnp.bool_))


@pytest.mark.parametrize("l,b,s,plan", [
    pytest.param(5, 16, 16384, (1, 1024), id="sarvam-105b"),
    pytest.param(8, 32, 4096, (4, 256), id="longcat-flash-chat"),
])
def test_fused_mla_decode_step(one_chip, l, b, s, plan):
    """The absorbed latent-attention step at the Sarvam-105B cell's shapes
    (five layers, 16 slots x 16,384 rows of 640 lanes (512 + 64, padded), 64
    heads) and at LongCat-Flash's (eight attention sublayers, 32 slots x
    4,096), each under the plan its geometry takes since PR 60 (a loop step
    of 1,024 rows of one slot in eight DMAs; 256 rows of four slots in two).
    The call asks for ``_VMEM_LIMIT`` of fast memory (``_compiler_params``)
    and the compiler refuses a kernel that needs more. The walk order is made
    outside the kernel, as the decode program calls it."""
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.ops.mla_decode_step import (_walk_plan,
                                                   fused_mla_decode_step)

    w, h = 640, 64
    assert _walk_plan(b, s, h) == plan

    def fn(q, latent, row, layer, idx, active):
        return fused_mla_decode_step(
            q, latent, row, layer, idx, value_width=512, scale=0.1,
            active=slot_walk(idx, active), interpret=False)

    text = _compiled_text(
        fn, _sds(one_chip, (b, h, w)), _sds(one_chip, (l, b, s, w)),
        _sds(one_chip, (b, w)), _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.bool_))
    assert "dstpu_mla_decode_step" in text


def test_fused_eva_decode_step(one_chip):
    """The EVA step at the EvaByte cell's shapes: eight layers, 12 slots, 32
    heads of 128, a window of 2,048 rows beside 2,048 summary rows a slot (a
    chunk of 16, 32,768 positions), a loop step of 256 rows of one slot in
    two DMAs from either leaf, the two rows written in place through a
    16-row and an 8-row window. The call asks for ``_VMEM_LIMIT`` of fast
    memory and the compiler refuses a kernel that needs more."""
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.ops.eva import fused_eva_decode_step, supports_step

    l, b, h, w, d, c, rows = 8, 12, 32, 2048, 128, 16, 2048
    assert supports_step(h, d, w, c, rows)

    def fn(q, kw, vw, ks, vs, kn, vn, phi, mu, layer, pos, active):
        return fused_eva_decode_step(
            q, kw, vw, ks, vs, kn, vn, phi, mu, layer, pos, chunk=c,
            scale=d ** -0.5, active=slot_walk(pos, active), interpret=False)

    new, head = _sds(one_chip, (b, h, d)), _sds(one_chip, (h, d), jnp.float32)
    win, summ = (_sds(one_chip, (l, b, h, n, d)) for n in (w, rows))
    text = _compiled_text(
        fn, new, win, win, summ, summ, new, new, head, head,
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_))
    assert "dstpu_eva_decode_step" in text


@pytest.mark.parametrize("rows", [256, 2048], ids=["bucket-4096",
                                                   "bucket-32768"])
def test_eva_prefill_kernel(one_chip, rows):
    """One prompt block of the EvaByte cell: 2,048 queries at 32 heads of 128
    against the summary rows of a bucket's own cache (the smallest bucket's
    256, the largest's 2,048) and against itself, the layer and the visible
    rows traced, as the prefill's scan over blocks calls it."""
    from deepspeed_tpu.ops.eva import fused_eva_prompt_block, supports_prompt

    l, t, h, d = 8, 2048, 32, 128
    assert supports_prompt(t, d, rows)

    def fn(q, k, v, ks, vs, layer, visible):
        return fused_eva_prompt_block(q, k, v, ks, vs, layer, visible,
                                      scale=d ** -0.5, interpret=False)

    blk, summ = _sds(one_chip, (1, t, h, d)), _sds(one_chip,
                                                   (l, 1, h, rows, d))
    text = _compiled_text(fn, blk, blk, blk, summ, summ,
                          _sds(one_chip, (), jnp.int32),
                          _sds(one_chip, (1,), jnp.int32))
    assert "dstpu_eva_prefill" in text


# (cache layers, rows of the bucket, token block): the Sarvam-105B cell's
# largest bucket; LongCat-Flash's smallest that takes the kernel (its 256
# bucket is no whole key block of 512 and takes the loop), one tile of four
# chunks; and a tile of 64 rows, smaller than a chunk and so one chunk whole
# (no cell's: the shape ``min(tq, _CHUNK_ROWS)`` is there for)
@pytest.mark.parametrize("l,s,t", [(5, 16384, 2048), (8, 512, 512),
                                   (8, 64, 64)], ids=str)
def test_mla_prefill_kernel(one_chip, l, s, t):
    """The decompressed prompt attention at the two latent-attention cells'
    widths: a token block at 64 heads of 128 + 64 / 128 against the cached
    rows of 640 lanes in key blocks of 512 (one key block where the bucket
    is smaller), ``wkv_b`` as the layer-stacked leaf it is fetched from; the
    first position, the valid length and the layers traced, as the prefill's
    scan over token blocks calls it."""
    from deepspeed_tpu.ops.mla_prefill import mla_prefill

    b, w, h = 1, 640, 64

    def fn(q_nope, q_rope, latent, wkv_b, layer, first, valid, w_layer):
        return mla_prefill(q_nope, q_rope, latent, wkv_b, layer, first, valid,
                           latent_width=512, scale=0.1, key_block=min(s, 512),
                           w_layer=w_layer, interpret=False)

    scalar = _sds(one_chip, (), jnp.int32)
    text = _compiled_text(
        fn, _sds(one_chip, (b, t, h, 128)), _sds(one_chip, (b, t, h, 64)),
        _sds(one_chip, (l, b, s, w)), _sds(one_chip, (4, 512, h * 256)),
        scalar, scalar, scalar, scalar)
    assert "dstpu_mla_prefill" in text


def test_kda_update_kernel(one_chip):
    """``dstpu_kda_update`` at the Solar-Open2 cell's shapes: 16 slots, three
    delta-rule layers of 64 heads of 128 x 128 float32 state, four taps; a
    grid cell 16 heads of an active slot, the state and the tails in place
    (no temporary of the state's or the tails' size)."""
    from deepspeed_tpu.ops import kda
    from deepspeed_tpu.ops.ssm import slot_order

    b, l, h, d, taps = 16, 3, 64, 128, 4
    f32 = jnp.float32

    def fn(qkv, g_pre, beta, gate, state, tail, conv_w, a_log, dt_bias,
           o_norm, layer, active):
        weights = kda.fold_weights({"conv_w": conv_w, "A_log": a_log,
                                    "dt_bias": dt_bias, "o_norm": o_norm}, h)
        return kda.kda_step(qkv, g_pre, beta, gate, state, tail, layer,
                            weights, slot_order(active), active, eps=1e-5,
                            interpret=False)

    sds = functools.partial(_sds, one_chip)
    compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(
        sds((b, 3 * h * d)), sds((b, h * d)), sds((b, h), f32),
        sds((b, h * d)), sds((l, b, h, d, d), f32),
        sds((l, b) + kda.tail_shape(taps, h, d)),
        sds((l, taps, 3 * h * d), f32), sds((l, h), f32),
        sds((l, h * d), f32), sds((l, d), f32), sds((), jnp.int32),
        sds((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dstpu_kda_update" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_gdn_update_kernel(one_chip):
    """``dstpu_gdn_update`` at the GigaChat 3.5 cell's shapes: 64 slots, four
    delta-rule layers of 32 key and 64 value heads of 128 x 128 float32
    state, four taps; a grid cell 32 value heads of an active slot (8 MB of
    state blocks in VMEM), a slot's ``q | k | v`` and tails one block a slot,
    the state and the tails in place (no temporary of either's size)."""
    from deepspeed_tpu.ops import gdn
    from deepspeed_tpu.ops.ssm import slot_order

    b, l, hk, hv, d, taps = 64, 4, 32, 64, 128, 4
    rows = gdn.conv_rows(hk, hv)
    f32 = jnp.float32

    def fn(qkv, ab, gate, state, tail, conv_w, a_log, dt_bias, o_norm, layer,
           active):
        weights = gdn.fold_weights({"conv_w": conv_w, "A_log": a_log,
                                    "dt_bias": dt_bias, "o_norm": o_norm},
                                   hk, hv)
        return gdn.gdn_step(qkv, ab, gate, state, tail, layer, weights,
                            slot_order(active), active, eps=1e-6,
                            gate_scale=2.0, interpret=False)

    sds = functools.partial(_sds, one_chip)
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((b, rows * d)), sds((b, 2, hv)), sds((b, hv * d)),
        sds((l, b, hv, d, d), f32),
        sds((l, b) + gdn.tail_shape(taps, hk, hv, d)),
        sds((l, taps, rows * d), f32), sds((l, hv), f32), sds((l, hv), f32),
        sds((l, d), f32), sds((), jnp.int32), sds((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dstpu_gdn_update" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_kda_prefill_kernel(one_chip):
    """``dstpu_kda_prefill`` at the Solar-Open2 cell's shapes: a token block
    of 2,048 positions, 64 heads of 128 keys and values, chunks of 64; the
    convolution's result ``q | k | v`` and the two gates in bf16 as the mixer
    holds them, ``beta`` float32, the layer's small weights folded."""
    from deepspeed_tpu.ops import kda

    t, h, d = 2048, 64, 128
    assert kda.supports_prefill(t, h, d, d, 64)
    sds = functools.partial(_sds, one_chip)
    f32 = jnp.float32

    def fn(act, g_pre, beta, gate_pre, heads, o_norm, state, length):
        return kda.kda_prefill(act, g_pre, beta, gate_pre,
                               {"heads": heads, "o_norm": o_norm}, state,
                               chunk=64, eps=1e-5, length=length,
                               interpret=False)

    compiled = jax.jit(fn).lower(
        sds((1, t, 3 * h * d)), sds((1, t, h * d)), sds((1, t, h), f32),
        sds((1, t, h * d)), sds((2, h, d), f32), sds((1, d), f32),
        sds((1, h, d, d), f32), sds((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dstpu_kda_prefill" in text
    # nothing beside the call: every operand is read where it lies
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("rows", [2048, 16384], ids=str)
def test_gqa_prefill_kernel(one_chip, rows):
    """``dstpu_gqa_prefill`` at the Solar-Open2 cell's shapes: a token block
    of 2,048 at 64 query over 8 key-value heads of 128 against the smallest
    and the largest bucket's rows in key blocks of 512, the cache leaves
    whole; the layer, the first position and the valid length traced, as the
    prefill's scan over token blocks calls it."""
    from deepspeed_tpu.ops import gqa_prefill

    t, hq, hkv, d = 2048, 64, 8, 128
    assert gqa_prefill.supports(rows, d, d, 512, t, hq, hkv)
    fn = functools.partial(gqa_prefill.gqa_prefill, key_block=512,
                           interpret=False)
    scalar = _sds(one_chip, (), jnp.int32)
    leaf = _sds(one_chip, (1, 1, hkv, rows, d))
    text = _compiled_text(fn, _sds(one_chip, (1, t, hq, d)), leaf, leaf,
                          scalar, scalar, scalar)
    assert "dstpu_gqa_prefill" in text


# MiMo-V2.5's share (16 slots): the two global layers' rows at 16,384 with 4
# key-value heads of 16 query heads, and the five sliding layers' rings of
# 128 with 8 heads of 8 and a sink; a key row is 192 live lanes of 256
TWO_WIDTH_SHAPES = [(2, 16, 64, 4, 16384, False), (5, 16, 64, 8, 128, True)]


def _two_width_operands(sh, l, b, hq, hkv, s, dk, dv):
    """``_decode_operands`` with keys ``dk`` and values ``dv`` wide, and the
    active mask."""
    return (_sds(sh, (b, 1, hq, dk)), _sds(sh, (l, b, hkv, s, dk)),
            _sds(sh, (l, b, hkv, s, dv)), _sds(sh, (b, 1, hkv, dk)),
            _sds(sh, (b, 1, hkv, dv)), _sds(sh, (), jnp.int32),
            _sds(sh, (b,), jnp.int32), _sds(sh, (b,), jnp.bool_))


@pytest.mark.parametrize("dims", TWO_WIDTH_SHAPES, ids=str)
def test_fused_decode_step_keys_256_values_128_and_a_sink(one_chip, dims):
    """Both per-slot walks with a key leaf 256 lanes wide and a value leaf
    of 128 (Mosaic refuses a DMA slice of 192 lanes: the HBM tiling pads the
    row to 256 whatever the leaf says), ``rep`` 16 and 8, the ring with the
    heads' sink as the running softmax's first state."""
    from deepspeed_tpu.ops.attention import key_row_width
    from deepspeed_tpu.ops.decode_step import fused_decode_step, slot_walk

    l, b, hq, hkv, s, ring = dims
    dk, dv = key_row_width(192), 128

    def fn(q, k, v, kn, vn, layer, idx, active, sink):
        return fused_decode_step(q, k, v, kn, vn, layer, idx,
                                 active=slot_walk(idx, active), ring=ring,
                                 sink=sink if ring else None,
                                 scale=192 ** -0.5, interpret=False)

    text = _compiled_text(
        fn, *_two_width_operands(one_chip, l, b, hq, hkv, s, dk, dv),
        _sds(one_chip, (hq,), jnp.float32))
    assert "dstpu_decode_step" in text


def test_long_step_at_mimos_rows_fits_the_scoped_vmem(one_chip):
    """The plan of MiMo's global layers (16 slots x 16,384, 4 heads, keys of
    256 lanes and values of 128) is the long step, and the kernel it names
    compiles for the described chip inside the scoped VMEM limit: its chunk
    buffers are 3 MB of the 40, a group of four's (the widest plan the table
    measured) 12 MB."""
    from deepspeed_tpu.ops import decode_step

    l, b, hq, hkv, s, dk, dv = 2, 16, 64, 4, 16384, 256, 128
    bg, cs = decode_step._slot_plan(b, hkv, s, dk, 2, dv=dv, hq=hq)
    assert cs > decode_step._SLOT_CHUNK
    assert 2 * bg * hkv * cs * (dk + dv) * 2 <= decode_step._SLOT_BUFFERS

    for plan in (None, {"bg": 4, "cs": cs}):
        def fn(q, k, v, kn, vn, layer, idx, active):
            return decode_step.fused_decode_step(
                q, k, v, kn, vn, layer, idx,
                active=decode_step.slot_walk(idx, active), plan=plan,
                scale=192 ** -0.5, interpret=False)

        text = _compiled_text(
            fn, *_two_width_operands(one_chip, l, b, hq, hkv, s, dk, dv))
        assert "dstpu_decode_step" in text


def test_gqa_prefill_kernel_keys_256_values_128(one_chip):
    """``dstpu_gqa_prefill`` at MiMo-V2.5's global layers: 64 query over 4
    key-value heads (16 query heads a cell), keys of 256 lanes and values of
    128, against the largest bucket's rows. The token block is 256 and not
    the cell's 2,048: a tile of two chunks a head is the same lowering with
    32 unrolled chunks for 64 a tile of 512 (12 s of Mosaic for 37; the
    cell's own blocks compiled and ran on the chip, PERF.md, PR 55)."""
    from deepspeed_tpu.ops import gqa_prefill

    t, hq, hkv, dk, dv, rows = 256, 64, 4, 256, 128, 16384
    assert gqa_prefill.supports(rows, dk, dk, 512, 2048, hq, hkv, dv)
    assert gqa_prefill.supports(rows, dk, dk, 512, t, hq, hkv, dv)
    assert not gqa_prefill.supports(rows, 192, 192, 512, t, hq, hkv, dv)
    fn = functools.partial(gqa_prefill.gqa_prefill, key_block=512,
                           scale=192 ** -0.5, interpret=False)
    scalar = _sds(one_chip, (), jnp.int32)
    text = _compiled_text(fn, _sds(one_chip, (1, t, hq, dk)),
                          _sds(one_chip, (2, 1, hkv, rows, dk)),
                          _sds(one_chip, (2, 1, hkv, rows, dv)),
                          scalar, scalar, scalar)
    assert "dstpu_gqa_prefill" in text


def _lowered_text(fn, *args):
    """The program as jax hands it to the TPU compiler: a Mosaic kernel is
    whole in it (its module is serialized at lowering), and nothing is
    compiled, which is nine tenths of such a case's time."""
    text = jax.jit(fn).lower(*args).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowered text"
    return text


def _kernel_digests(text):
    """A digest of each Mosaic kernel in a lowered or compiled program: its
    module printed WITHOUT source locations (the serialized body carries
    files and lines, which move with every edit above a kernel)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    out = []
    # the quotes of the lowered text's backend_config are escaped (\22)
    for body in re.findall(r'body(?:"|\\22): ?(?:"|\\22)([\w+/=]+)', text):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=False)
        out.append(hashlib.sha256(asm.encode()).hexdigest()[:16])
    return out


# (L, B, Hq, Hkv, S, Dh, ring) of the four older attention families' decode
# steps and the kernel each traced to at PR 54 (the parent of the PR that
# gave the step two widths and a sink). PR 58 recorded two anew: K-EXAONE's
# 4,096 rows and Solar's 16,384 are walked a loop step of 512 rows, a row a
# group; the walks at 1,024 and 2,048 rows and the rings are PR 54's still
ONE_WIDTH_KERNELS = {
    "gpt2-large": ((36, 32, 20, 20, 1024, 64, False), "f179d10f202a1411"),
    "granite-4.0-h-micro": ((4, 64, 32, 8, 2048, 64, False),
                            "fb4841169344c797"),
    "k-exaone.rows": ((1, 32, 64, 8, 4096, 128, False), "103597fa4ee57595"),
    "k-exaone.ring": ((4, 32, 64, 8, 128, 128, True), "3ccde5c38a1a3b00"),
    "solar-open2-250b": ((1, 16, 64, 8, 16384, 128, False),
                         "d5750f9021028bf1"),
}


@pytest.mark.parametrize("family", list(ONE_WIDTH_KERNELS))
def test_one_width_and_no_sink_trace_to_the_kernel_they_did(one_chip, family):
    """For keys and values of one width and no sink, ``fused_decode_step``
    lowers to the kernel it lowered to before it knew of two: operation for
    operation the same module (PERF.md, PR 55: the families' whole decode
    and prefill programs compiled to the parent's HLO). A PR that changes the
    walk on purpose records the new digests here."""
    from deepspeed_tpu.ops.decode_step import fused_decode_step, slot_walk

    (*dims, ring), want = ONE_WIDTH_KERNELS[family]

    def fn(q, k, v, kn, vn, layer, idx, active):
        return fused_decode_step(q, k, v, kn, vn, layer, idx,
                                 active=slot_walk(idx, active), ring=ring,
                                 interpret=False)

    ops = _decode_operands(one_chip, *dims)
    text = _lowered_text(fn, *ops, _sds(one_chip, (dims[1],), jnp.bool_))
    assert _kernel_digests(text) == [want]


def test_solars_prompt_kernel_is_the_one_it_was(one_chip):
    from deepspeed_tpu.ops import gqa_prefill

    fn = functools.partial(gqa_prefill.gqa_prefill, key_block=512,
                           interpret=False)
    scalar = _sds(one_chip, (), jnp.int32)
    leaf = _sds(one_chip, (1, 1, 8, 16384, 128))
    text = _lowered_text(fn, _sds(one_chip, (1, 2048, 64, 128)), leaf, leaf,
                         scalar, scalar, scalar)
    assert _kernel_digests(text) == ["51ef2f4fd73336aa"]


def _folded_mamba_step(sharding, l, b, h, p, n, g, k=4):
    """``ops/ssm.mamba_step`` between a stack's ``in_proj`` and ``out_proj``,
    the small weights folded inside the program as a decode step makes them
    -> ``(fn, operands)``."""
    from deepspeed_tpu.ops import ssm

    f32 = jnp.float32
    d_in, c = h * p, h * p + 2 * g * n
    stack = {"conv_w": _sds(sharding, (l, k, c), f32),
             "conv_b": _sds(sharding, (l, c), f32),
             "gate_norm": _sds(sharding, (l, d_in), f32),
             **{name: _sds(sharding, (l, h), f32)
                for name in ("dt_bias", "A_log", "D")}}

    def fn(stack, zx, state, conv, layer, active):
        return ssm.mamba_step(zx, state, conv, layer,
                              ssm.fold_weights(stack, p),
                              ssm.slot_order(active), active, eps=1e-5,
                              interpret=False)

    return fn, (stack, _sds(sharding, (b, 2 * d_in + 2 * g * n + h)),
                _sds(sharding, (l, b, h, p, n), f32),
                _sds(sharding, (l, b) + ssm.conv_tail_shape(k, d_in,
                                                            2 * g * n)),
                _sds(sharding, (), jnp.int32),
                _sds(sharding, (b,), jnp.bool_))


def test_folded_mamba_step_at_one_group_is_the_kernel_it_was(one_chip):
    """granite-4.0-h-micro's folded step (36 layers, 64 slots, 64 heads of 64,
    one group): operation for operation the module it lowered to before the
    call knew of groups (PR 64's tree gives the same digest). A PR that
    changes the step on purpose records the new digest here."""
    fn, ops = _folded_mamba_step(one_chip, 36, 64, 64, 64, 128, 1)
    assert _kernel_digests(_lowered_text(fn, *ops)) == ["60051eab88159733"]


def test_folded_mamba_step_at_eight_groups(one_chip):
    """Nemotron 3 Super's Mamba-2 layer (128 heads of 64, state 128, EIGHT
    groups, 64 slots): a cell holds a slot's 4 MB of state in and out, twice
    each for the pipeline, past the default 16 MB of scoped VMEM, so the call
    states its own limit; a group's 8 rows of lanes at a time. It compiles,
    in place: no temporary of the state's size."""
    fn, ops = _folded_mamba_step(one_chip, 5, 64, 128, 64, 128, 8)
    lowered = jax.jit(fn, donate_argnums=(2, 3)).lower(*ops)
    assert "dstpu_ssm_update" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("tokens", [32, 4096], ids=["decode", "prefill"])
def test_held_experts_grouped_matmul(one_chip, fused_routes, tokens):
    """The expert layer at K-EXAONE's widths, 16 of 128 experts held,
    against the whole layer-stacked weights: no copy of a layer's experts
    (1.2 GB) is made to slice them. A decode step's 32 slots are ONE call,
    ``dstpu_moe_experts_decode`` (ops/moe_experts.py), with no grouped
    matmul of XLA's, no sort of the step's pairs and no conditional; a
    prompt of 4,096 tokens is told the router's width and carries the
    switch over ``jax.lax.ragged_dot`` (XLA's own kernel on a TPU): three
    calls in each branch that multiplies, 8,192 sorted rows in the compact
    one, and no float32 buffer of the worst case's 32,768 rows (805 MB)
    anywhere, nor one in bfloat16 outside the full branch but the gather
    back to a token's eight pairs."""
    from deepspeed_tpu.moe.grouped import (compact_rows, held_experts,
                                           sigmoid_topk_route)

    d, m, e, held, layers, k = 6144, 2048, 128, 16, 4, 8
    prompt = tokens > 32

    def fn(x, router, bias, wg, wu, wd, layer):
        routing = sigmoid_topk_route(x, router, bias, k, scale=2.5)
        whole = [{"__whole__": w, "__layer__": layer} for w in (wg, wu, wd)]
        return held_experts(x, routing, *whole, (0, held),
                            n_experts=e if prompt else None)

    text = _compiled_text(
        fn, _sds(one_chip, (tokens, d)), _sds(one_chip, (d, e)),
        _sds(one_chip, (e,)), _sds(one_chip, (layers, held, d, m)),
        _sds(one_chip, (layers, held, d, m)),
        _sds(one_chip, (layers, held, m, d)), _sds(one_chip, (), jnp.int32))
    # the stacks reach the kernels as they lie: nothing of a layer's size
    assert not re.search(r"= bf16\[16,(6144,2048|2048,6144)\]", text)
    if prompt:
        assert compact_rows(tokens, k, held, e) == 8192
        assert _assert_prompt_buffer_is_compact(text, tokens * k, d, 1) == \
            {8192}
        return
    assert len(re.findall(r"custom-call\(.*dstpu_moe_experts_decode",
                          text)) == 1
    assert not re.search(r"%ragged-dot-(?!metadata)[\w.\-]* = ", text)
    assert " conditional(" not in text
    # the router's top-k is the step's one sort: none of its pairs
    assert all("/top_k" in line for line in text.splitlines()
               if " sort(" in line)


# (d, m, matrices, N, held, swiglu_limit): Nemotron's latent experts,
# GigaChat's, K-EXAONE's and LongCat's, Sarvam's and MiMo's, Solar's; the
# widest of them at the most rows the route sends to the call (FREE_ROWS)
EXPERT_STEPS = [(1024, 2688, 2, 64, 128, None), (7168, 2048, 3, 64, 16, 10.0),
                (6144, 2048, 3, 32, 16, None), (4096, 2048, 3, 16, 16, None),
                (4096, 1280, 3, 16, 40, None), (7168, 2048, 3, 128, 16, 10.0)]


@pytest.mark.parametrize("dims", EXPERT_STEPS, ids=str)
def test_moe_experts_decode(one_chip, fused_routes, dims):
    """A decode step's routed experts at every expert cell's widths, slots
    and held experts: the one call compiles under the VMEM limit it states,
    two buffers of every matrix's tile, the accumulator and a tile's float32
    results together, and the route is the one the shapes choose."""
    from deepspeed_tpu.moe.grouped import Routing, held_experts
    from deepspeed_tpu.ops import moe_experts

    d, m, mats, n, held, limit = dims
    assert moe_experts.default_route(n, d, m) == "fused"
    tile = moe_experts.block_m(d, m, mats, 2)
    assert m % tile == 0 and tile % 128 == 0
    assert 2 * mats * d * tile * 2 <= moe_experts._TILE_BUFFERS

    def fn(x, experts, weights, wu, wd, *wg):
        return held_experts(x, Routing(experts, weights), *(wg or (None,)),
                            wu, wd, (0, held), limit=limit)

    k = 8
    text = _compiled_text(
        fn, _sds(one_chip, (n, d)), _sds(one_chip, (n, k), jnp.int32),
        _sds(one_chip, (n, k), jnp.float32), _sds(one_chip, (held, d, m)),
        _sds(one_chip, (held, m, d)),
        *[_sds(one_chip, (held, d, m))] * (mats - 2))
    assert "dstpu_moe_experts_decode" in text


def test_window_prefill_scores_are_a_band(one_chip):
    """A 4096-token prompt block on a sliding layer: query blocks of the
    window against two blocks of keys, not ``[64, 4096, 4096]`` scores."""
    from deepspeed_tpu.ops.attention import window_cached_attention

    b, t, hq, hkv, w, dh = 1, 4096, 64, 8, 128, 128
    ring = _sds(one_chip, (4, b, hkv, w, dh))
    new = _sds(one_chip, (b, t, hkv, dh))

    def fn(q, kr, vr, kn, vn, layer, length):
        return window_cached_attention(q, kr, vr, kn, vn, layer, 0,
                                       valid=length)

    compiled = jax.jit(fn).lower(
        _sds(one_chip, (b, t, hq, dh)), ring, ring, new, new,
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (), jnp.int32)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    assert "4096,4096" not in compiled.as_text()


@pytest.mark.parametrize("dims", DECODE_SHAPES, ids=str)
def test_fused_block_decode_step(one_chip, dims):
    from deepspeed_tpu.ops.decode_step import fused_block_decode_step

    l, b, hq, hkv, s, dh = dims
    block_size = 128
    table = _sds(one_chip, (b, s // block_size), jnp.int32)
    fn = functools.partial(fused_block_decode_step, interpret=False)
    _compiled_text(fn, *_decode_operands(one_chip, *dims,
                                         block_size=block_size), table)


def test_int8_matmul_dma(one_chip):
    from deepspeed_tpu.ops.int8_matmul import int8_matmul_dma

    d, e = 768, 3072                  # 125M's MLP up-projection, 8 decode rows
    fn = functools.partial(int8_matmul_dma, interpret=False)
    _compiled_text(fn, _sds(one_chip, (8, d)), _sds(one_chip, (d, e), jnp.int8),
                   _sds(one_chip, (1, e), jnp.float32))


def test_flash_decode_gqa(one_chip):
    from deepspeed_tpu.ops.flash_decode import flash_decode

    b, hq, hkv, s, dh = 8, 32, 4, 2048, 128     # wide GQA: rep 8
    cache = _sds(one_chip, (b, hkv, s, dh))
    fn = functools.partial(flash_decode, interpret=False)
    _compiled_text(fn, _sds(one_chip, (b, 1, hq, dh)), cache, cache,
                   _sds(one_chip, (), jnp.int32))


@pytest.fixture
def fused_routes(monkeypatch):
    """The program picks its fused routes (``sp_attention``'s interpret mode
    and strict vma checking, the models' fused decode step) from
    ``jax.default_backend()``, which still says cpu here, so the test steers
    it (the guide's rule: steer in the test, not through an option of the
    program)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def mesh_2x2(topo, fused_routes):
    """data=2 x model=2 over the four described chips, installed as the
    initialised topology."""
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.utils import groups

    topology = build_topology(tp=2, devices=list(topo.devices))
    groups.initialize(topology)
    yield topology.mesh
    groups.reset()


def _mesh_flash_loss(q, k, v):
    from deepspeed_tpu.models.base import sp_attention

    return jnp.sum(sp_attention("flash", q, k, v).astype(jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_partitions_under_mesh(mesh_2x2, grad):
    """Bare, the kernel fails here with "Mosaic kernels cannot be
    automatically partitioned"; ``sp_attention`` wraps it in shard_map.
    A device's share, ``(4, 1024, 6, 64)``, is a row of one grid step: its
    backward is the one fused kernel, whose three results carry the
    operands' varying axes out of the shard_map."""
    from deepspeed_tpu.parallel.topology import BATCH_AXES, MODEL_AXIS

    sharding = NamedSharding(mesh_2x2, P(BATCH_AXES, None, MODEL_AXIS, None))
    q = _sds(sharding, (8, 1024, 12, 64))
    fn = jax.grad(_mesh_flash_loss, argnums=(0, 1, 2)) if grad \
        else _mesh_flash_loss
    text = _compiled_text(fn, q, q, q)
    assert _flash_backward_kernels(text) == (
        {"dstpu_flash_bwd_dkv"} if grad else set())


def test_in_program_kv_cache_is_zero_filled(one_chip):
    """generate()'s prefill allocates its KV cache inside the program and
    fills only the prompt's rows. On the v5e the compiler had replaced the
    zeros by an uninitialised ``AllocateBuffer``; the masked tail then held
    NaN bit patterns and every logit came out NaN (PR 23, found on the
    chip). 125M widths, two layers, batch 1, 24 of 128 rows written."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config(num_layers=2))

    def prefill(params, ids):
        cache = model.init_cache(1, 128, dtype=BF16)
        logits, cache = model.forward_with_cache(params, ids, cache)
        return logits[:, -1], cache["k"], cache["v"]

    params = jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    text = jax.jit(prefill).lower(
        params, _sds(one_chip, (1, 24), jnp.int32)).compile().as_text()
    uninitialised = [ln for ln in text.splitlines()
                     if "AllocateBuffer" in ln and "bf16[2,1,12,128,64]" in ln]
    assert not uninitialised, uninitialised[0][:200]


def _outside_fusions(text):
    """``(computation, result type, opcode, line)`` of every
    instruction of the compiled text that sits outside fused computations:
    the entry, the loops' bodies and what else is called as it stands; and
    the fused computations' bodies by name."""
    bodies, name = {}, None
    for line in re.sub(r"/\*.*?\*/", "", text).splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            bodies[name].append(line)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    found = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*)", line)
            if not m:
                continue
            rest = m.group(1)
            if rest.startswith("("):        # a tuple: to its closing bracket
                depth = 0
                for end, ch in enumerate(rest):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                kind, rest = rest[:end + 1], rest[end + 1:].lstrip()
            else:
                kind, rest = rest.split(" ", 1)
            found.append((comp, kind, rest.split("(", 1)[0], line))
    return found, bodies


def _sizes(dims):
    """A shape as its dimensions above 1, sorted: what a layer's slice of a
    stacked leaf keeps through ``[1, d, e]``, a squeeze, a transposing
    bitcast."""
    return tuple(sorted(int(d) for d in dims if d and int(d) > 1))


def _has_matmul(bodies, line):
    """Whether the fused computation a ``fusion`` line calls holds a matmul."""
    body = "\n".join(bodies[re.search(r"calls=%([\w.\-]+)", line).group(1)])
    return " convolution(" in body or " dot(" in body


def _weight_sized_copies(text, leaves):
    """Synchronous operations outside fused computations that write a
    result of a weight leaf's size to HBM and are no matmul: a ``copy``,
    ``slice``, ``dynamic-slice``, or a ``fusion`` with no ``convolution`` /
    ``dot`` in it. ``leaves``: ``{(dtype, _sizes of one layer)}``. A result
    in the compiler's fast memory (``S(1)`` in its layout) passes, and so do
    the asynchronous prefetches (``copy-start`` / ``-done``, ``slice-start``
    / ``-done``): they run beside the matmuls. -> the offending lines."""
    found, bodies = _outside_fusions(text)
    out = []
    for comp, kind, opcode, line in found:
        if opcode not in ("copy", "slice", "dynamic-slice", "fusion"):
            continue
        if opcode == "fusion" and _has_matmul(bodies, line):
            continue
        for dtype, dims, layout in re.findall(
                r"(\w+)\[([\d,]*)\](\{[^}]*\})?", kind):
            if (dtype, _sizes(dims.split(","))) in leaves \
                    and "S(1)" not in layout:
                out.append(f"{comp}: {line.strip()[:240]}")
                break
    return out


def _exaone_cell():
    from deepspeed_tpu.models.exaone_moe import (DENSE, GLOBAL, SLIDING,
                                                 SPARSE, ExaoneMoeConfig,
                                                 ExaoneMoeModel)

    # the cell's five layers: runs of one (dense), two, one (global), one
    return ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=19200, max_seq_len=4096, held=(0, 16),
        layer_types=(SLIDING, SLIDING, SLIDING, GLOBAL, SLIDING),
        mlp_layer_types=(DENSE,) + (SPARSE,) * 4)), 32, 4096


def _granite_cell():
    from deepspeed_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                                     GraniteHybridConfig,
                                                     GraniteHybridModel)

    # of 40 layers a Mamba run of two and two attention layers, each a run
    # of one as all four of the cell's are
    return GraniteHybridModel(GraniteHybridConfig(
        max_seq_len=2048,
        layer_types=(ATTENTION, MAMBA, MAMBA, ATTENTION))), 64, 2048


def _gpt2_large_cell():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    # of 36 layers four: one loop, as the cell's
    return GPT2Model(GPT2Config(num_layers=4, hidden_size=1280,
                                num_heads=20)), 32, 1024


def _sarvam_cell():
    from deepspeed_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                                 SarvamMlaModel)

    # the cell's dense layer and two of its four sparse ones (a loop run)
    return SarvamMlaModel(SarvamMlaConfig(
        vocab_size=32768, max_seq_len=16384, num_layers=3,
        held=(0, 16))), 16, 16384


def _longcat_cell():
    from deepspeed_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                    LongcatFlashModel)

    # two of the cell's four double layers: one loop, as the cell's
    return LongcatFlashModel(LongcatFlashConfig(
        vocab_size=16384, max_seq_len=4096, num_layers=2,
        held=(0, 16))), 32, 4096


def _solar_cell():
    from deepspeed_tpu.models.solar_kda import SolarKdaConfig, SolarKdaModel

    # the cell's one period: the softmax layer and a run of three
    return SolarKdaModel(SolarKdaConfig(
        vocab_size=24576, max_seq_len=16384, held=(0, 40))), 16, 16384


def _mimo_cell():
    from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model

    # the cell's seven layers: a dense global one, a run of four sparse
    # sliding ones, a sparse global one, a sparse sliding one
    return MimoV2Model(MimoV2Config(
        vocab_size=19072, max_seq_len=16384, held=(0, 16),
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1))), 16, 16384


def _gigachat_cell():
    from deepspeed_tpu.models.gigachat35 import (GigaChat35Config,
                                                 GigaChat35Model)

    # the cell's five layers: the dense delta-rule layer, the latent layer,
    # a run of three sparse delta-rule layers
    return GigaChat35Model(GigaChat35Config(
        vocab_size=16032, max_seq_len=4096, num_layers=5,
        full_attention_layers=(1,), first_k_dense=1, held=(0, 16))), 64, 4096


def _evabyte_cell():
    from deepspeed_tpu.models.evabyte import EvaByteConfig, EvaByteModel

    # the cell's widths at two of its eight layers (one kind: a loop run)
    return EvaByteModel(EvaByteConfig(num_layers=2)), 12, 32768


def _nemotron_cell():
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    # the cell whole: one period of eleven layers, every run one layer
    return NemotronHModel(NemotronHConfig(
        vocab_size=32768, max_seq_len=4096, held=(0, 128))), 64, 4096


def _weights(model, sharding):
    """The model's parameters as the serving engine holds them: bf16, as
    shapes on the described chip; and one layer's sizes of every stacked
    matrix (projections, MLPs, a layer's experts)."""
    params = jax.tree_util.tree_map(
        lambda s: _sds(sharding, s.shape),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    leaves = {("bf16", _sizes(s.shape[1:]))
              for s in jax.tree_util.tree_leaves(params) if len(s.shape) >= 3}
    return params, leaves


def _assert_copies_no_weight(compiled, leaves):
    copies = _weight_sized_copies(compiled.as_text(), leaves)
    assert not copies, "\n".join(copies)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


def _lower_decode_step(cell, sharding):
    """A cell's decode step (``InferenceEngine.slot_decode_program``'s call of
    the model: one token a slot, per-slot lengths, the slot walk), lowered
    for the described chip -> ``(lowered, the state's shapes, _weights'
    leaves)``."""
    from deepspeed_tpu.ops.decode_step import slot_walk

    model, slots, max_len = cell()
    params, leaves = _weights(model, sharding)
    state = jax.eval_shape(
        lambda: model.init_cache(slots, max_len, dtype=BF16))
    del state["index"]
    state = jax.tree_util.tree_map(
        lambda s: _sds(sharding, s.shape, s.dtype), state)

    def step(params, state, lengths, tokens, active):
        cache = dict(state, index=lengths, valid_len=active.astype(jnp.int32),
                     slot_walk=slot_walk(lengths, active))
        logits, cache = model.forward_with_cache(params, tokens[:, None],
                                                 cache)
        return logits[:, -1], {k: cache[k] for k in state}

    per_slot = _sds(sharding, (slots,), jnp.int32)
    return jax.jit(step, donate_argnums=1).lower(
        params, state, per_slot, per_slot,
        _sds(sharding, (slots,), jnp.bool_)), state, leaves


@pytest.mark.parametrize("cell", [_exaone_cell, _granite_cell,
                                  _gpt2_large_cell, _sarvam_cell,
                                  _solar_cell, _longcat_cell, _mimo_cell,
                                  _gigachat_cell, _evabyte_cell],
                         ids=["k-exaone", "granite-4.0-h-micro",
                              "gpt2-large", "sarvam-105b",
                              "solar-open2-250b", "longcat-flash-chat",
                              "mimo-v2.5", "gigachat3.5-432b-a28b",
                              "evabyte"])
def test_decode_step_copies_no_weight(one_chip, fused_routes, cell):
    """The serve cells' decode step (``InferenceEngine.slot_decode_program``'s
    call of the model: one token a slot, per-slot lengths, the slot walk) at
    the cells' widths and slots, depth cut for compile time with a loop run
    and a one-layer run each kept: every weight reaches its matmul where it
    lies. K-EXAONE's block once fused its projections with the per-head norm
    behind them, and a layer's ``wq``, ``wk``, ``wv`` were sliced out of the
    stack and transposed, ``wo`` copied, every step: 232 MB of temporaries,
    ``constant_dynamic-slice_fusion`` on the chip's trace, 0.87 ms of a step
    of 7.4 (PERF.md, PR 41; ``models/base.project_heads``). No step holds a
    conditional: what a prompt block's expert layer chooses between (PR 53)
    is not a decode step's to choose."""
    lowered, state, leaves = _lower_decode_step(cell, one_chip)
    compiled = lowered.compile()
    _assert_copies_no_weight(compiled, leaves)
    # one token a slot: the expert layers are the one call that streams the
    # touched experts (ops/moe_experts.py), and the switch a prompt block
    # carries (moe/grouped.held_experts) has no part in the step, nor has
    # any other conditional, nor XLA's grouped matmul
    assert " conditional(" not in compiled.as_text()
    assert "ragged-dot" not in compiled.as_text()
    if cell in (_exaone_cell, _sarvam_cell, _solar_cell, _longcat_cell,
                _mimo_cell, _gigachat_cell):
        assert "dstpu_moe_experts_decode" in compiled.as_text()
    if "conv" in state:
        _assert_mamba_runs_are_folded(compiled.as_text(), state["conv"])
    if cell is _mimo_cell:
        # the fused step on all seven layers, rows and rings: no einsum over
        # the cache's 16,384 rows (the only matmuls that wide are the dense
        # FFN's 16,384 columns)
        text = compiled.as_text()
        assert text.count("dstpu_decode_step") >= 4      # one a run of layers
        assert not [line for line in text.splitlines()
                    if re.search(r"= \w+\[[\d,]*\b16384,(256|128)\]", line)
                    and (" dot(" in line or " convolution(" in line)]
    if "gdn" in state:
        # one folded call a run of delta-rule layers, the state in place,
        # beside the latent layer's absorbed step in the one program; every
        # run is folded: no update out of XLA's own operations
        text = compiled.as_text()
        assert text.count("dstpu_gdn_update") >= 2
        assert text.count("dstpu_mla_decode_step") >= 1
    if "k_sum" in state:
        # the one fused call over both leaves, in place: no einsum over a
        # leaf's 2,048 rows, no scatter into one
        text = compiled.as_text()
        assert text.count("dstpu_eva_decode_step") >= 1
        assert " scatter(" not in text
    if "kda" in state:
        # one folded call the delta-rule run's layer, the state in place; the
        # prompt block's kernel has no part in a one-token step
        assert compiled.as_text().count("dstpu_kda_update") >= 1
        assert "dstpu_kda_prefill" not in compiled.as_text()


def test_nemotrons_decode_step_fits_the_chip_and_folds(one_chip, fused_routes):
    """The new cell's decode step at its own size (eleven layers, 128 of 512
    experts held, 64 slots of 4,096 rows): arguments and temporaries fit a
    v5e's 16 GB with room for the prefill programs; every Mamba layer is the
    one folded call at eight groups with the state and the tail in place; no
    weight is copied; and the step holds no conditional and no grouped
    matmul of XLA's: each of the five sparse layers is the one call that
    streams the touched experts of its 128, as every family's step is."""
    lowered, state, leaves = _lower_decode_step(_nemotron_cell, one_chip)
    compiled = lowered.compile()
    _assert_copies_no_weight(compiled, leaves)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.5e9
    text = compiled.as_text()
    # every run is ONE layer, so the runs' loops are unrolled into the entry
    # computation: five folded calls there, the slot order made once, and no
    # operation outside a kernel that writes a result of the state's or the
    # tail's size (both leaves are updated in place)
    found, _ = _outside_fusions(text)
    kernels = [line for _, _, opcode, line in found
               if opcode == "custom-call" and "dstpu_ssm_update" in line]
    assert len(kernels) == 5, kernels
    assert len([kind for _, kind, opcode, _ in found
                if opcode == "sort" and "pred[" in kind]) == 1
    in_place = {("f32", _sizes(state["ssm"].shape)),
                ("bf16", _sizes(state["conv"].shape))}
    for _, kind, opcode, line in found:
        if opcode in ("fusion", "copy", "slice", "dynamic-slice"):
            sized = {(d, _sizes(dims.split(","))) for d, dims in
                     re.findall(r"(\w+)\[([\d,]*)\]", kind)}
            assert not in_place & sized, line.strip()[:240]
    assert " conditional(" not in text
    assert "ragged-dot" not in text
    assert len([line for _, _, opcode, line in found
                if opcode == "custom-call"
                and "dstpu_moe_experts_decode" in line]) == 5
    # the one attention layer takes the fused step over a request's own rows
    assert "dstpu_decode_step" in text


def _assert_mamba_runs_are_folded(text, conv):
    """The hybrid's decode step: between ``in_proj`` and ``out_proj`` a Mamba
    layer is the one kernel (``ops/ssm.mamba_step``). Each Mamba run's loop
    body holds one ``dstpu_ssm_update`` and no ``sort`` (the slot order is the
    step's, made in the entry computation), writes no result of the
    convolution leaf's size outside the kernel (the leaf is updated in place:
    no transposing copy in, no update-slice out), and keeps at most 10
    fusions without a matmul: the two residual norms' five, ``in_proj``'s
    row padded to whole rows of lanes, the mask on ``y``. 30 before PR 44,
    with a ``slice``, two ``copy`` and a ``sort``: 1,224 small operations a
    step of 36 layers, 2.4 ms of it on the chip (PERF.md, PR 44)."""
    found, bodies = _outside_fusions(text)
    runs = {comp for comp, _, opcode, line in found
            if opcode == "custom-call" and "dstpu_ssm_update" in line}
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    assert runs and entry not in runs
    leaf = ("bf16", _sizes(conv.shape))
    for run in runs:
        ops = [(kind, opcode, line) for comp, kind, opcode, line in found
               if comp == run]
        kernels = [line for _, opcode, line in ops if opcode == "custom-call"]
        assert len(kernels) == 1, kernels
        assert not [line for _, opcode, line in ops if opcode == "sort"]
        small = []
        for kind, opcode, line in ops:
            if opcode == "fusion" and not _has_matmul(bodies, line):
                small.append(line.strip()[:160])
            if opcode in ("fusion", "copy", "slice", "dynamic-slice"):
                sized = {(d, _sizes(dims.split(","))) for d, dims in
                         re.findall(r"(\w+)\[([\d,]*)\]", kind)}
                assert leaf not in sized, line.strip()[:240]
        assert len(small) <= 10, "\n".join(small)
    sorts = [kind for comp, kind, opcode, _ in found
             if comp == entry and opcode == "sort" and "pred[" in kind]
    assert len(sorts) == 1, sorts    # the active mask's, once a step


def _compile_prefill(model, params, sharding, bucket):
    """``slot_prefill_program``'s call of the model, compiled: one row of
    ``bucket`` positions, a cache of its own, the true length."""
    def prefill(params, ids, length):
        cache = model.init_cache(1, bucket, dtype=BF16)
        cache["valid_len"] = length
        logits, cache = model.forward_with_cache(params, ids, cache)
        return logits, cache

    return jax.jit(prefill).lower(
        params, _sds(sharding, (1, bucket), jnp.int32),
        _sds(sharding, (), jnp.int32)).compile()


def test_prefill_copies_no_weight(one_chip, fused_routes):
    """K-EXAONE's bucket-256 prefill (``slot_prefill_program``'s call of the
    model: one row, a cache of its own, the true length) walks the same
    block: no weight is copied there either."""
    model, _, _ = _exaone_cell()
    params, leaves = _weights(model, one_chip)

    compiled = _compile_prefill(model, params, one_chip, 256)
    _assert_copies_no_weight(compiled, leaves)


def _assert_prompt_buffer_is_compact(text, rows, width, switches):
    """A prompt program's expert layers (``moe/grouped.held_experts`` told
    the router's width): ``switches`` conditionals outside fused
    computations, one a run of sparse layers, each with a branch that holds
    nothing of the layer, a compact and a full one of three grouped matmuls;
    of the worst case's ``[rows, width]`` no float32 buffer is left in the
    program, and in bfloat16 the full branch's alone and, in the compact
    one, the gather back to a token's ``k`` pairs. -> the compact branches'
    row counts."""
    found, _ = _outside_fusions(text)

    def matmuls(branch):
        return [kind for comp, kind, opcode, line in found
                if comp == branch and opcode == "custom-call"
                and re.search(r"%ragged-dot-(?!metadata)[\w.\-]* = ", line)]

    conds = [line for _, _, opcode, line in found if opcode == "conditional"]
    assert len(conds) == switches, conds
    worst = [(comp, kind) for comp, kind, _, _ in found
             if re.match(rf"(f32|bf16)\[{rows},{width}\]", kind)]
    assert all(kind.startswith("bf16") for _, kind in worst), worst
    compact_rows, inside = set(), set()
    for line in conds:
        empty, compact, full = re.search(
            r"branch_computations=\{([^}]*)\}", line).group(1).replace(
                "%", "").split(", ")
        assert not matmuls(empty) and not [
            kind for comp, kind, opcode, _ in found
            if comp == empty and opcode == "fusion"]
        assert len(matmuls(compact)) == len(matmuls(full)) == 3
        assert all(kind.startswith(f"bf16[{rows},")
                   for kind in matmuls(full)), matmuls(full)
        compact_rows.add(int(re.match(r"bf16\[(\d+),",
                                      matmuls(compact)[0]).group(1)))
        assert sum(comp == compact for comp, _ in worst) == 1, worst
        inside |= {compact, full}
    assert all(comp in inside for comp, _ in worst), worst
    return compact_rows


def test_sarvam_prefill_keeps_scores_on_chip(one_chip, fused_routes):
    """Sarvam-105B's 4,096-bucket prefill (``slot_prefill_program``'s call of
    the model: two token blocks of 2,048 through the stack, a cache of its
    own, the true length): the prompt attention is the one kernel a layer
    run, no weight is copied (``wkv_b`` reaches the kernel as the stack it
    lies in: as a layer's slice it was written out, 16.8 MB a layer a token
    block) and no float32 buffer of a score block's size ``[64, 2048, 512]``
    is a temporary of the program. The ``lax`` loop wrote three such buffers a
    key block and read them back: 0.84 s of a busy 2.22 s (PERF.md, PR 47).
    The sparse run's expert layer sorts a token block's pairs into 4,096 rows
    unless more are routed here (PR 53)."""
    model, _, _ = _sarvam_cell()
    params, leaves = _weights(model, one_chip)
    # the expert layer's sorted rows, prompt_block x 8 a token block, are
    # [16384, 4096] like the dense layer's w_down, and are gathered
    leaves = leaves - {("bf16", (4096, 16384))}
    # a token block's activations [2048, 4096], which the expert layer's
    # switch takes and gives, are the shared expert's matrices' size
    leaves = leaves - {("bf16", (2048, 4096))}

    compiled = _compile_prefill(model, params, one_chip, 4096)
    text = compiled.as_text()
    copies = _weight_sized_copies(text, leaves)
    assert not copies, "\n".join(copies)
    found, _ = _outside_fusions(text)
    kernels = [line for _, _, opcode, line in found
               if opcode == "custom-call" and "dstpu_mla_prefill" in line]
    assert len(kernels) == 2, kernels       # the dense run's and the sparse
    # a key block's scores, and the loop's float32 accumulator of all heads
    walked = {(64, 512, 2048), (64, 128, 2048)}
    big = [line.strip()[:200] for _, kind, _, line in found
           for dims in re.findall(r"f32\[([\d,]*)\]", kind)
           if _sizes(dims.split(",")) in walked]
    assert not big, "\n".join(big)
    # the expert layers' sorted buffer: 4,096 of a token block's 16,384 rows
    assert _assert_prompt_buffer_is_compact(text, 16384, 4096, 1) == {4096}
    # 402 MiB here; 613 with the expert layer's float32 passes over the worst
    # case's rows (PR 52), the loop's program 662
    assert compiled.memory_analysis().temp_size_in_bytes < 420 * 2 ** 20


@functools.lru_cache(maxsize=None)
def _solar_prefill(sharding, bucket):
    """Solar-Open2's prefill program of a bucket, compiled once a process
    (under ``fused_routes``: both its readers ask for the fixture)."""
    model, _, _ = _solar_cell()
    params, _ = _weights(model, sharding)
    return _compile_prefill(model, params, sharding, bucket)


@pytest.mark.parametrize("bucket,temp_mb", [(2048, 345), (16384, 620)])
def test_solar_prefill_keeps_a_chunk_on_chip(one_chip, fused_routes, bucket,
                                             temp_mb):
    """Solar-Open2's prefill (``slot_prefill_program``'s call of the model: a
    cache of its own, the true length; the 16,384 bucket walks eight token
    blocks of 2,048): the delta-rule run's prompt block is the one kernel
    ``dstpu_kda_prefill``. Gone with the chunked form: the unit triangular
    solve's expander (``InvertDiagBlocksLowerTriangular`` and its loops), the
    chunk's pairwise-decay scores ``[32, 64, 64, 64]`` as a variadic
    reduction's results, and every chunk operand transposed to ``[.., 64, 64,
    128]``. The softmax layer's prompt block is the one kernel
    ``dstpu_gqa_prefill`` over the cache leaves where they lie: no float32
    score buffer ``[.., 2048, 512]`` of the ``lax`` loop is left, and in the
    16,384 bucket nothing outside the kernel copies or slices a layer's key
    or value rows (in the 2,048 bucket the block's own new rows, turned for
    the write, are as many). Both runs' expert layers sort a token block's
    pairs into 4,096 rows unless more are routed here. The program's
    temporaries (``temp_mb``: 327 and 595 MiB) are below what they were with
    the float32 operands XLA made for the delta-rule kernel (372 and 652,
    PERF.md, PR 64) and with the expert layer's float32 passes over the worst
    case's 16,384 rows (537 and 805, PR 49; the chunked form's were 619 and
    1,009)."""
    compiled = _solar_prefill(one_chip, bucket)
    text = compiled.as_text()
    found, _ = _outside_fusions(text)
    for name in ("dstpu_kda_prefill", "dstpu_gqa_prefill"):
        kernels = [line for _, _, opcode, line in found
                   if opcode == "custom-call" and name in line
                   and "tpu_custom_call" in line]
        # the run of three layers is a loop, the softmax layer is one
        assert len(kernels) == 1, (name, kernels)
    scores = [line.strip()[:200] for _, kind, _, line in found
              for dims in re.findall(r"f32\[([\d,]*)\]", kind)
              if dims.split(",")[-2:] == ["2048", "512"]]
    assert not scores, "\n".join(scores)
    if bucket > 2048:
        # the two leaves start as one broadcast zero and its copy, once a
        # program (whichever of them the compiler keeps in fast memory)
        rows = [line for line in _weight_sized_copies(
            text, {("bf16", (8, 128, bucket))})
            if " copy(%broadcast." not in line]
        assert not rows, "\n".join(rows)
    assert "triangular" not in text.lower() and "cholesky" not in text.lower()
    chunked = [line.strip()[:200] for _, kind, _, line in found
               for dims in re.findall(r"f32\[([\d,]*)\]", kind)
               if _sizes(dims.split(","))[-3:] == (64, 64, 128)
               or _sizes(dims.split(",")) == (32, 64, 64, 64)]
    assert not chunked, "\n".join(chunked)
    assert _assert_prompt_buffer_is_compact(text, 16384, 4096, 2) == {4096}
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb * 2 ** 20


def test_solar_prompt_block_rounds_nothing_to_float32_around_its_kernel(
        one_chip, fused_routes):
    """The 2,048 bucket's delta-rule run: ``dstpu_kda_prefill`` takes the
    convolution's result and the two gates in bf16 as they are and hands the
    output matmul its bf16 operand. Between the convolution and ``wo``
    nothing outside the call writes a float32 array of a token block's
    elements: before PR 64 the two L2 norms, the log decay, ``beta`` spread
    over 128 lanes and the kernel's own result were five of them, 64 MB each
    a layer a token block, with three ``reshape f32[1,2048,8192]`` and two
    copies ``f32[256,8,64,128]`` between layouts (PERF.md, PR 50's trace)."""
    text = _solar_prefill(one_chip, 2048).as_text()
    found, bodies = _outside_fusions(text)
    runs = {comp for comp, _, opcode, line in found
            if opcode == "custom-call" and "dstpu_kda_prefill" in line}
    assert len(runs) == 1, runs
    block = ((2048, 8192), (64, 128, 2048), (8, 64, 128, 256))
    ops = [(kind, opcode, line) for comp, kind, opcode, line in found
           if comp in runs]
    (call, line), = [(kind, line) for kind, opcode, line in ops
                     if opcode == "custom-call"
                     and "dstpu_kda_prefill" in line]
    wide = [line.strip()[:200] for kind, opcode, line in ops
            if opcode not in ("custom-call", "parameter", "tuple",
                              "get-tuple-element")
            for dims in re.findall(r"f32\[([\d,]*)\]", kind)
            if _sizes(dims.split(",")) in block]
    assert not wide, "\n".join(wide)
    # the call's own result: the stream's dtype where it is a token block
    # wide, float32 for the state alone
    assert "bf16[1,2048,8192]" in call and "f32[1,64,128,128]" in call
    assert not [d for d in re.findall(r"f32\[([\d,]*)\]", call)
                if _sizes(d.split(",")) in block]
    # its operands, by the lines that make them: the convolution's result
    # whole, three times (no split), the gates as the low-rank matmuls leave
    # them
    made = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)",
                           "\n".join(line for _, _, line in ops), re.M))
    operands = re.search(r"custom-call\(([^)]*)\)", line).group(1).split(", ")
    assert operands[1] == operands[2] == operands[3], operands[:4]
    assert made[operands[1]].startswith("bf16[1,2048,24576]")
    assert all(made[name].startswith("bf16[1,2048,8192]")
               for name in operands[4:6]), operands


def _program_digest(text):
    """A digest of a LOWERED program: its text with every Mosaic kernel's
    serialized module replaced by :func:`_kernel_digests`' (the bodies carry
    source lines), as jax hands it to the TPU compiler."""
    import hashlib

    kernels = _kernel_digests(text)
    bare = re.sub(r'(body(?:"|\\22): ?(?:"|\\22))[\w+/=]+', r"\1", text)
    return hashlib.sha256(
        (bare + " ".join(kernels)).encode()).hexdigest()[:16], kernels


def test_solars_decode_program_is_the_one_it_was(one_chip, fused_routes):
    """The cell's decode step (one token a slot, 16 slots of 16,384 rows, the
    folded ``dstpu_kda_update`` and the per-slot walk) lowers to the program
    it lowered to at PR 67, which made the expert layers of the step ONE
    call each on purpose (``dstpu_moe_experts_decode``: the softmax layer's
    and the delta-rule run's, in the program's order); the two kernels it had
    are the ones of PR 63 (``bceee5ce4f1dad16`` then, which PR 64, the PR
    that moved the prompt kernel's boundary, left as it was). A PR that
    changes the step on purpose records the new digest here."""
    text = _lower_decode_step(_solar_cell, one_chip)[0].as_text()
    # the per-slot walk (ONE_WIDTH_KERNELS' own), the softmax layer's
    # experts, ``dstpu_kda_update``, the delta-rule run's experts
    assert _program_digest(text) == ("32b706b6c16dd54e", [
        "d5750f9021028bf1", "4fea0099f4601fe2", "4fe8519fbb6a26b5",
        "7efad80a37eb6151"])


def test_longcat_prefill_sorts_the_pairs_it_holds(one_chip, fused_routes):
    """LongCat-Flash-Chat's 2,048-bucket prefill (``slot_prefill_program``'s
    call of the model: the prompt whole, a cache of its own, the true
    length): 16 of the router's 768 outputs are held, so of a prompt's 24,576
    pairs about 512 are routed here and the sorted buffer has 1,024 rows; no
    weight is copied. 1,112 MiB of temporaries (1,399 with the float32
    ``[24576, 6144]`` passes, 604 MB each, PERF.md, PR 52)."""
    model, _, _ = _longcat_cell()
    params, leaves = _weights(model, one_chip)

    compiled = _compile_prefill(model, params, one_chip, 2048)
    text = compiled.as_text()
    copies = _weight_sized_copies(text, leaves)
    assert not copies, "\n".join(copies)
    assert _assert_prompt_buffer_is_compact(text, 24576, 6144, 1) == {1024}
    assert compiled.memory_analysis().temp_size_in_bytes < 1150 * 2 ** 20


@pytest.fixture
def mesh_data4(topo):
    """data=4 over the four described chips, as the initialised topology."""
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.utils import groups

    topology = build_topology(devices=list(topo.devices))
    groups.initialize(topology)
    yield topology
    groups.reset()


def test_zero3_layer_scan_gathers_weights_per_layer(mesh_data4):
    """GPT-2 XL widths under ZeRO-3 on data=4, ``dots_no_batch`` remat, one
    row of 512 a chip, as ``_micro_loss_and_grads`` builds it. Unstated, the
    partitioner kept the weight shards in place and moved the activations of
    every matmul (all-to-all, 56.8% of the step on the chip, PR 25); stated
    inside the rematerialised block (``models/base.gathered``) it gathers a
    layer's weights, and the scan saves none of them whole."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.zero.partition import (PartitionPlan,
                                                      stating_param_use)

    d, mesh = 1600, mesh_data4.mesh
    plan = PartitionPlan(topology=mesh_data4, zero_stage=3)

    def compiled(layers):
        model = GPT2Model(GPT2Config(num_layers=layers, hidden_size=d,
                                     num_heads=25, loss_chunk=512),
                          remat=True, remat_policy="dots_no_batch")
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        axes = model.logical_axes()
        use = plan.param_use(shapes, axes)
        grad_sh = plan.shardings(plan.grad_specs(shapes, axes))

        def loss_and_grads(params, batch):
            def loss(p):
                with stating_param_use(use):
                    return model.apply(p, batch, train=True)[0]

            value, grads = jax.value_and_grad(loss)(params)
            return value, jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads, grad_sh)

        params = jax.tree_util.tree_map(
            lambda s, sh: _sds(sh, s.shape), shapes,
            plan.shardings(plan.compute_specs(shapes, axes)))
        ids = _sds(NamedSharding(mesh, plan.batch_spec(2)), (4, 512),
                   jnp.int32)
        return jax.jit(loss_and_grads).lower(
            params, {"input_ids": ids, "labels": ids}).compile()

    shallow, deep = compiled(2), compiled(6)
    text = deep.as_text()
    lines = text.splitlines()
    # one all-to-all stays, outside the scan and cheaper than what it
    # replaces: the embedding lookup's transpose sends each chip the
    # quarter of the features it scatter-adds into its shard of wte's
    # gradient (3 MB here, against a reduce-scatter of the 160 MB table)
    moved = [ln for ln in lines if re.search(r" all-to-all(-start)?\(", ln)]
    assert not [ln for ln in moved if "/while/" in ln], moved[0][:300]
    assert all("scatter-add" in ln for ln in moved), moved
    for whole in ("[1600,4800]", "[1600,6400]", "[6400,1600]", "[1600,1600]"):
        assert any("all-gather" in ln and whole in ln.split(" all-gather")[0]
                   for ln in lines), f"no all-gather of {whole}"
    # a residual of gathered weights would be a stack of them, whole
    for stack in ("[6,1600,6400]", "[6,6400,1600]", "[6,1600,4800]"):
        assert stack not in text, f"the scan saves {stack}"
    layer_bytes = 12 * d * d * 2          # one layer's weights, gathered
    grown = (deep.memory_analysis().temp_size_in_bytes
             - shallow.memory_analysis().temp_size_in_bytes) / 4
    assert grown < 0.75 * layer_bytes, (grown, layer_bytes)
