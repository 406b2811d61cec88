"""How a model's layers are stacked and walked: the one place.

A model file keeps its configuration, its parameter tree and its *block
functions*. What is not the model's lives here: the training walk
(:func:`wrapped_block`, :func:`walk`), the cached walk of prefill and decode
(:func:`cached_walk`), the walk of a long prompt a token block at a time
(:func:`prompt_walk`), the key-value cache tree with the cache a step returns
(:func:`kv_cache`, :func:`next_cache`), the runs of equal layers of a stack of
mixed ones (:func:`runs_of`), and the frame of a decoder around all of them
(:class:`StackedDecoder`: the embedding, the walks over the runs, the head,
the loss, the cached step). A stack is a dict of leaves with a leading
``layer`` dimension under one key of the params tree (``"blocks"``; a model of
mixed layers has one a kind).
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Hashable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import moe_ffn
from deepspeed_tpu.models.base import (cross_entropy_loss, gathered, gathered_top, gathers, layer_view, rms_norm,
                                       whole_leaves)
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


def runs_of(layer_kinds: Sequence[Hashable],
            kinds: Dict[Hashable, Tuple[str, Tuple[str, ...]]]):
    """The runs of equal kind among ``layer_kinds`` (each layer's kind, in
    stack order) as ``(kind, first index in the kind's stack, first index in
    the kind's cache leaves, count)``. ``kinds[kind]`` is ``(stack, leaves)``:
    the params tree's stack the kind's layers are stored in, and the cache
    leaves they are counted in. Kinds that share a stack are numbered in it
    together, and so are kinds that share their leaves."""
    out, in_stack, in_leaves = [], collections.Counter(), collections.Counter()
    for kind in layer_kinds:
        stack, leaves = kinds[kind]
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, in_stack[stack], in_leaves[leaves], 1])
        in_stack[stack] += 1
        in_leaves[leaves] += 1
    return tuple(tuple(r) for r in out)


class StackedDecoder:
    """A causal LM as a stack of layers of one or several kinds: ModelSpec
    (``batch = {"input_ids": [B, T], "labels": [B, T]}``) and what the
    inference and serving engines ask of a model. A family states

    - ``stacks``: the params tree's layer stacks;
    - ``kinds``: a layer kind -> ``(its stack, the cache leaves its layers
      read and write)`` (:func:`runs_of`), and :meth:`layer_kinds`;
    - ``whole``: the leaves of a stack that a walk hands whole
      (``base.whole_leaves``);
    - :meth:`_block_of`: the block function of a kind;
    - ``init``, ``logical_axes``, ``init_cache``, ``num_params``,
      ``flops_per_token``, and of the attributes below those that are not the
      defaults.

    It reads ``self.config`` for ``eps`` and ``prompt_block``. The params tree
    holds ``embed [V, d]``, ``final_norm [d]`` and ``lm_head [d, V]`` beside
    the stacks. Where a family differs it overrides the method."""

    stacks: Tuple[str, ...] = ()
    kinds: Dict[Hashable, Tuple[str, Tuple[str, ...]]] = {}
    whole: Tuple[str, ...] = ()

    # ---- what the engines read of a model (models/base.slot_state_keys and
    # the like, inference/engine.py, serving/engine.py, serving/kv_slots.py),
    # and ``state_dtype``, which a family's own ``init_cache`` reads
    supports_weight_quant = False
    # the per-slot state leaves of ``init_cache`` (all but the index), in the
    # order the serving programs take them as operands
    slot_state_keys: Tuple[str, ...] = ("k", "v")
    # of them: the leaves of TOKEN ROWS, which grow with the request (leaves
    # that are neither these nor named below are fixed-size recurrent state);
    row_state_keys: Tuple[str, ...] = ("k", "v")
    # rings of a sliding window's last positions, keys' first, values' last;
    window_state_keys: Tuple[str, ...] = ()
    # a window that starts over, and the summary rows behind it
    restart_window_keys: Tuple[str, ...] = ()
    summary_state_keys: Tuple[str, ...] = ()
    # recurrent state adds thousands of small terms to a slowly decaying sum:
    # float32 whatever the compute dtype
    state_dtype = jnp.float32
    # what a decode step and a prompt block count on the device, as the
    # returned cache carries them (``step_counters``, ``prompt_counts``), and
    # how the serving engine's registry takes them: the expert layer's
    step_counters: Tuple[str, ...] = moe_ffn.STEP_COUNTERS
    prompt_counters: Tuple[str, ...] = moe_ffn.PROMPT_COUNTERS
    record_step_counters = staticmethod(moe_ffn.record_step_counters)
    record_prompt_counters = staticmethod(moe_ffn.record_prompt_counters)

    def __init__(self, config, compute_dtype=jnp.bfloat16,
                 param_dtype=jnp.float32, remat: bool = False,
                 remat_policy: Optional[str] = None):
        self.config = config
        self.compute_dtype = compute_dtype
        # what init() draws the matrices in: float32 master weights for
        # training, the checkpoint's bfloat16 where only serving follows
        self.param_dtype = param_dtype
        self.remat = remat
        self.remat_policy = remat_policy

    def fused_row_walk(self, state, num_slots: int) -> bool:
        """Whether a slot cache of these leaves routes a decode step to a
        fused call that walks a request's own rows: asked of a model whose
        cache has no ``k`` (serving/kv_slots.py, which works it out itself
        for one that has). No: what the engine counts by a request's length
        (``serving/decode_rows_*``) is then not what a step moves."""
        return False

    # --------------------------------------------------------------- layers
    def layer_kinds(self) -> Sequence[Hashable]:
        """Each layer's kind, in stack order."""
        raise NotImplementedError

    def runs(self):
        return runs_of(self.layer_kinds(), self.kinds)

    def _block_of(self, kind, shift: int, walk_, step):
        """The block of a layer of ``kind`` as a walk calls it: ``block(x,
        blk, state, layer, idx, valid) -> (x, state)``. ``state``: ``None``
        (no cache), or the kind's cache leaves and the step's counters, the
        layer's own at ``layer + shift`` (the stack's index of a layer, and
        what its leaves' index is ahead of it); ``valid [B]``: the real
        positions a row; ``walk_``: the decode program's ``slot_walk``;
        ``step``: :meth:`_decode_step`'s."""
        raise NotImplementedError

    def _decode_step(self, params, valid, b: int):
        """What the recurrent layers of one decode step (one token a slot, a
        cache) share, made once a step and not once a layer. A family
        without such layers makes nothing."""
        return None

    def _stack(self, params, kind):
        """The stacked layers of ``kind`` as a walk takes them."""
        return whole_leaves(params[self.kinds[kind][0]], *self.whole)

    def _embed(self, params, input_ids):
        return params["embed"].astype(self.compute_dtype)[input_ids]

    def _norm(self, x, w):
        """The norm on the stream behind the last layer."""
        return rms_norm(x, w, self.config.eps)

    # -------------------------------------------------------------- forward
    def forward_hidden(self, params, input_ids, *, rngs=None,
                       train: bool = False):
        top = gathered_top(params, *self.stacks)
        x = self._embed(top, input_ids)
        for kind, first, _, count in self.runs():
            block = self._block_of(kind, 0, None, None)
            block_fn = wrapped_block(
                lambda x, blk, block=block: block(x, blk, None, None, None,
                                                  None)[0],
                self.kinds[kind][0], self.remat, self.remat_policy)
            x = walk(block_fn, x, self._stack(params, kind),
                     run=(first, count))
        return self._norm(x, top["final_norm"])

    def logits(self, params, hidden):
        return jnp.einsum("btd,dv->btv", hidden,
                          params["lm_head"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs,
                                     train=train)
        head = gathered_top(params, *self.stacks)
        loss, n = cross_entropy_loss(self.logits(head, hidden),
                                     batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # ------------------------------------------------------- inference path
    def _layers(self, params, x, leaves, counts, idx, valid, walk_):
        """``x`` through the stack against the cache's ``leaves`` (in
        ``slot_state_keys`` order) -> ``(x, leaves, counts)``: a run's block
        carries its kind's leaves and the counters, whatever those are."""
        held = dict(zip(self.slot_state_keys, leaves))
        b, t = x.shape[:2]
        step = self._decode_step(params, valid, b) if t == 1 else None
        for kind, first, at, count in self.runs():
            names = self.kinds[kind][1]
            x, (*state, counts) = cached_walk(
                self._block_of(kind, at - first, walk_, step), x,
                self._stack(params, kind), (*(held[n] for n in names), counts),
                idx, valid, first=first, count=count)
            held.update(zip(names, state))
        return x, tuple(held[n] for n in self.slot_state_keys), counts

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T > 1) or decode (T == 1) against the cache tree.
        ``cache["index"]`` is a scalar or a per-slot ``[B]`` vector;
        ``cache["valid_len"]`` (scalar or ``[B]``) how many of the block's
        positions are real for each row: recurrent state and a ring stop
        there (a row with 0 valid positions keeps its state), a position
        that is not real is routed to no expert, and the token rows it
        writes lie behind the length and are dead; ``cache["slot_walk"]`` the
        decode program's walk order for the fused decode steps. A prompt of
        a whole number (> 1) of ``prompt_block`` passes the stack a token
        block at a time (:func:`prompt_walk`); with ``valid_len`` a prompt's
        logits are those of each row's last real position alone, ``[B, 1,
        V]``. The returned cache carries ``step_counters``
        (models/moe_ffn.STEP_COUNTERS), summed over the sparse layers."""
        t = input_ids.shape[1]
        x, leaves, counts = prompt_walk(
            functools.partial(self._layers, params),
            params["embed"].astype(self.compute_dtype), input_ids,
            tuple(cache[k] for k in self.slot_state_keys),
            moe_ffn.zero_counts(t, len(self.step_counters)), cache,
            self.config.prompt_block)
        hidden = self._norm(x, params["final_norm"])
        out = next_cache(cache, t, **dict(zip(self.slot_state_keys, leaves)))
        out.update(moe_ffn.carried_counts(cache, counts,
                                          len(self.step_counters)))
        return self.logits(params, hidden), out
