"""How a model's layers are stacked and walked: the one place.

A model file keeps its configuration, its parameter tree, its embedding, its
head and its *block functions*. What is not the model's lives here: the
training walk (:func:`wrapped_block`, :func:`walk`), the cached walk of
prefill and decode (:func:`cached_walk`), the walk of a long prompt a token
block at a time (:func:`prompt_walk`), and the key-value cache tree with
the cache a step returns (:func:`kv_cache`, :func:`next_cache`). A stack is
a dict of leaves with a leading ``layer`` dimension under one key of the
params tree (``"blocks"``; the hybrid model has two).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import gathered, gathers, layer_view
from deepspeed_tpu.ops.attention import alloc_kv_cache
from deepspeed_tpu.runtime.activation_checkpointing import checkpoint_policy
from deepspeed_tpu.telemetry.registry import get_registry


@jax.custom_vjp
def _together(x, coming):
    """``(x, coming)`` handed over as one: neither before the other is
    there."""
    return jax.lax.optimization_barrier((x, coming))


# the order is the forward pass's alone, and nothing is differentiated
# through ``coming``
_together.defvjp(lambda x, coming: (_together(x, coming), None),
                 lambda _, g: (g[0], None))


def wrapped_block(block, stack: str, remat: bool = False,
                  remat_policy: Optional[str] = None,
                  first: Optional[str] = None):
    """``block(x, blk, *args) -> x`` as the training walk runs it on one
    layer's slice ``blk`` of the stack found under ``params[stack]``.

    ZeRO-3 gathers the layer's weights inside what remat wraps, so the
    backward pass gathers them again and the scan saves no whole weight.
    ``first`` names the leaf of the stack a block uses first, where the
    model states one (and reads it through ``base.qdot``). Gathered in its
    place that leaf has nothing of its own layer to wait under, so where the
    plan shards it the FORWARD pass gathers it a layer ahead and carries it
    (:func:`walk`); the backward pass is the one of a leaf gathered in
    place (``base.Brought``). What that costs: the leaf in flight, and,
    only where remat recomputes the product that reads the leaf instead of
    keeping it (``dots_no_batch`` keeps it), the carried leaf saved whole
    for every layer (GPT-2 XL's ``qkv_w``: 48 x 15.4 MB, 0.74 GB a chip).

    The result is called as ``fn(x, blk, *args)``, and by a walk that
    carries ``first`` as ``fn(x, blk, *args, ahead=whole)``: ``whole`` is
    this layer's ``first`` as the layer before brought it, and stands in
    the place of ``blk[first]`` gathered. ``fn.first_leaf`` keeps ``(stack,
    first)`` for the walk, or None.

    Call this inside the model's ``forward_hidden``, once a trace: jax keeps
    a traced block (``jax.checkpoint``, ``lax.scan``) by its function, and a
    function that outlived its trace would replay the gathers, or their
    absence, of whichever engine traced first (``base.gathered``)."""

    def fn(x, blk, *args, ahead=None):
        brought = None if ahead is None else {first: ahead}
        return block(x, gathered(blk, stack, stacked=True, brought=brought),
                     *args)

    if remat:
        fn = jax.checkpoint(fn, policy=checkpoint_policy(remat_policy))
    fn.first_leaf = None if first is None else (stack, first)
    return fn


def walk(block, x, stack, *args, xs=(), run=None, first_leaf=None):
    """``x`` through the layers of ``stack`` (its stacked leaves):
    ``block(x, blk, *xs_of_the_layer, *args)`` for each, ``block`` from
    :func:`wrapped_block`. The stack is the scan's input, so the layers'
    gradients come back stacked.

    ``run=(first, count)`` walks that sub-range of the stack and indexes it
    by layer number instead: a slice of the stack as the scan's input would
    be a copy of it (the hybrid model's runs of equal layers).

    ``first_leaf``: the wrapped block's, where ``block`` is a layer function
    of the model's around it and passes ``ahead`` on to it (GPT-2's, for its
    dropout key and layer drop). Where it names a leaf that the plan
    shards (``base.gathers``), that leaf rides the scan's carry whole:
    layer 0's is gathered before the scan, and the body gathers layer
    i+1's (read by layer number: shifted by a layer as the scan's input it
    would be a copy of the whole stacked leaf) and hands it over together
    with layer i's output. Handed over apart, the gather has no reader in
    the body and the compiler gives it no matmul to run beside: it takes a
    body's gathers in the order of their readers, one at a time, each
    beside the matmul before its reader (the compiled step for a v5e,
    PERF.md, PR 56). Nothing is differentiated through the carry: the
    gradient goes to the layer's own slice in the layer's own backward,
    which gathers that slice in place (``base.Brought``). The last
    iteration gathers the last layer's leaf once more and nobody reads it:
    a body that is the same for every layer costs one transfer in
    ``count`` more, a last layer outside the scan a second lowering of the
    block. Where nothing is to be carried the program is the one without
    it."""
    first_leaf = first_leaf or getattr(block, "first_leaf", None)
    if first_leaf is not None and gathers(*first_leaf):
        assert run is None, "no family that walks by layer number states " \
            "a first leaf: carry it in this form when one does"
        name, key = first_leaf
        leaf = jax.lax.stop_gradient(stack[key])
        last = leaf.shape[0] - 1
        get_registry().counter("zero/traced_prefetched_gather").inc()

        def fetch(layer):
            shard = jax.lax.dynamic_index_in_dim(leaf, layer, 0,
                                                 keepdims=False)
            return gathered({key: shard}, name, stacked=True,
                            ahead=True)[key]

        def body(carry, layer_in):
            x, whole = carry
            blk, layer, per_layer = layer_in
            coming = fetch(jnp.minimum(layer + 1, last))
            x = block(x, blk, *per_layer, *args, ahead=whole)
            # with the output's first leaf alone: what else a model carries
            # (GPT-2's dropout key) stays dead code where it was
            out, tree = jax.tree_util.tree_flatten(x)
            out[0], coming = _together(out[0], coming)
            return (tree.unflatten(out), coming), None

        return jax.lax.scan(body, (x, fetch(0)),
                            (stack, jnp.arange(last + 1), xs))[0][0]
    if run is None:
        def body(x, layer_in):
            blk, per_layer = layer_in
            return block(x, blk, *per_layer, *args), None

        return jax.lax.scan(body, x, (stack, xs))[0]
    first, count = run

    def body(x, layer):
        return block(x, layer_view(stack, layer), *args), None

    return jax.lax.scan(body, x, first + jnp.arange(count))[0]


def cached_walk(block, x, stack, state, idx, *args, count: int,
                first: int = 0):
    """Prefill (``T > 1``) or decode (``T == 1``) through ``count`` layers of
    ``stack`` from layer ``first``: ``block(x, blk, state, layer, idx, *args)
    -> (x, state)``. ``state`` is a tuple of FULL stacked per-layer leaves
    (``[L, B, ...]`` key-value rows, recurrent state); a block reads and
    writes only its layer's slice.

    The state rides the scan's CARRY (per-layer slice writes XLA keeps in
    place), not its inputs and outputs: that form copied the entire cache
    every step and dominated decode latency. The layers are indexed by the
    carried counter, not fed as the scan's input: ``layer_view`` keeps int8
    weight dicts whole so ``qdot``'s kernel DMA-slices the layer in-kernel
    (a host-side slice of an int8 operand copies the weight every step)."""

    def body(carry, _):
        x, *state, layer = carry
        x, state = block(x, layer_view(stack, layer), tuple(state), layer,
                         idx, *args)
        return (x, *state, layer + 1), None

    (x, *state, _), _ = jax.lax.scan(
        body, (x, *state, jnp.full((), first, jnp.int32)), None, length=count)
    return x, tuple(state)


def prompt_walk(layers, embed, input_ids, leaves: tuple, counts, cache,
                prompt_block: int):
    """The ids of a step (a prompt block, ``T > 1``, or one token a row)
    through all of a family's layers against its cache: ``layers(x, leaves,
    counts, idx, valid, slot_walk) -> (x, leaves, counts)`` is the family's
    walk over its runs of equal layers, ``leaves`` its cache's stacked leaves
    and ``counts`` its step counters. Of ``cache`` this reads ``index``,
    ``valid_len`` (scalar or ``[B]``: how many of the positions are real for
    each row) and ``slot_walk`` (the decode program's walk order).

    A prompt of a whole number (> 1) of ``prompt_block`` passes the stack a
    token block at a time, leaves and counts carried, ``valid`` clipped to
    the block and no ``slot_walk``: what a layer holds for all its positions
    at once (an expert buffer's rows, a block's scores) is then a block's and
    not the prompt's. Any other length passes whole. Where ``valid_len`` is
    given, a prompt's ``x`` comes back as each row's last real position
    alone, ``[B, 1, D]``."""
    b, t = input_ids.shape
    idx, valid, pb = cache["index"], cache.get("valid_len"), prompt_block
    if valid is not None:
        valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
    if t > pb and t % pb == 0:
        def block(carry, i):
            *leaves, counts = carry
            ids = jax.lax.dynamic_slice_in_dim(input_ids, i * pb, pb, 1)
            x, leaves, counts = layers(
                embed[ids], tuple(leaves), counts, idx + i * pb,
                None if valid is None else jnp.clip(valid - i * pb, 0, pb),
                None)
            return (*leaves, counts), x

        (*leaves, counts), xs = jax.lax.scan(
            block, (*leaves, counts), jnp.arange(t // pb))
        x, leaves = xs.transpose(1, 0, 2, 3).reshape(b, t, -1), tuple(leaves)
    else:
        x, leaves, counts = layers(embed[input_ids], leaves, counts, idx,
                                   valid, cache.get("slot_walk"))
    if t > 1 and valid is not None:
        x = jnp.take_along_axis(
            x, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)
    return x, leaves, counts


def kv_cache(layers: int, batch: int, kv_heads: int, max_len: int,
             head_dim: int, dtype, packed: bool = True,
             v_head_dim: Optional[int] = None):
    """The static-shape key-value cache tree ``{"k", "v", "index"}``:
    stacked head-major ``[L, B, Hkv, S, Dh]``, token-pair packed for
    ``Dh < 128`` unless the model's decode always takes the einsum path
    (``packed=False``; ops/attention.alloc_kv_cache), and a scalar index.
    ``v_head_dim``: the value rows' width where it is not the keys' (the
    leaves are then unpacked, each of its own last dimension). A model with
    other per-layer state adds its leaves to this tree."""
    if v_head_dim not in (None, head_dim):
        packed = False
    return {"k": alloc_kv_cache(layers, batch, kv_heads, max_len, head_dim,
                                dtype, packed=packed),
            "v": alloc_kv_cache(layers, batch, kv_heads, max_len,
                                v_head_dim or head_dim, dtype, packed=packed),
            "index": jnp.zeros((), jnp.int32)}


def next_cache(cache, t: int, **state):
    """The cache a step over ``t`` positions returns: the carried leaves,
    the index moved on (a scalar, or a per-slot ``[B]`` vector under
    continuous batching), and the block table of a block-paged pool passed
    through when there is one."""
    out = dict(state, index=cache["index"] + t)
    if cache.get("block_table") is not None:
        out["block_table"] = cache["block_table"]
    return out
