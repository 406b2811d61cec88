"""Slot-paged persistent per-request state for continuous batching.

The vLLM PagedAttention idea specialized to XLA's static-shape world: a
persistent per-slot state TREE whose BATCH dimension is the page table. The
model declares the tree (``model.init_cache``; the leaves' names and operand
order are its ``slot_state_keys``, ``("k", "v")`` where it declares none).
Five kinds of state can live in it side by side; a model says which of its
leaves hold token rows (``row_state_keys``, models/base.py; ``("k", "v")``
where it declares none) and which are rings (``window_state_keys``), and
every other leaf is recurrent:

  * **key-value rows** ``k``, ``v``: ``[L, B_slots, Hkv, S_max/pair,
    Dh*pair]`` stacked caches (ops/attention.alloc_kv_cache layout —
    head-major, token-pair packed for Dh < 128) over the model's attention
    layers. They grow with the request. The geometry is each LEAF's: ``v``
    may be of another last dimension than ``k`` (models/mimo_v2.py: keys
    192 wide in rows of 256 lanes, values 128; unpacked then), and a
    model's rings may have other heads than its rows. A per-slot
    ``lengths`` int32 vector
    replaces the single scalar cache position, so the fused decode kernel
    (ops/decode_step.py) streams only each ACTIVE slot's valid prefix and
    the einsum path masks per row. Rows behind a slot's length are dead:
    bucket padding lands there (and, on the einsum path, an inactive slot's
    write) and is overwritten; a freed slot keeps its stale length until
    the next prefill resets it.
  * **latent rows** (a leaf the model names in ``row_state_keys`` that is
    neither ``k`` nor ``v``; models/sarvam_mla.py's ``latent`` ``[L, B_slots,
    S_max, W]``): ONE row a token a layer that all heads share, the
    compressed key-value latent with the rotated key behind it. Like
    key-value rows it grows with the request, is addressed by token rows, is
    written as a prefix by prefill and a row a step by decode, and rows
    behind a slot's length are dead; unlike them it is no key or value of a
    head, so what addresses the cache by rows of ``k`` / ``v`` PAIRS (prefix
    reuse in the block pool, speculative verify, swap, ``kv_dtype``) is
    refused for it at construction (serving/engine.py; ROADMAP R2 (c)). Its
    decode walk is the model's own fused step (ops/mla_decode_step.py), and
    whether this allocation routes to it is asked of the model
    (``fused_row_walk``).
  * **recurrent state** (every other leaf, ``[L', B_slots, ...]``; a
    state-space model's ``ssm`` and ``conv``): fixed size, no rows, nothing
    to hide a write behind. Prefill writes it at the request's true length,
    decode moves it for active slots only, and what addresses the cache by
    token rows (prefix reuse, speculative verify, swap) does not apply to
    it: ``recurrent_keys`` names these leaves and the engine refuses those
    options while there are any.

  * **window rows** (leaves the model names in ``window_state_keys``; a
    sliding-window layer's ``k_win``, ``v_win`` ``[L'', B_slots, Hkv, W,
    Dh]``): a ring of the last ``W`` positions, position ``p`` at row ``p %
    W``, so its size does not grow with ``max_len``. Prefill writes the last
    ``W`` real positions, decode overwrites the row that leaves the window.
    Like recurrent state it is not addressed by token rows, so it counts
    among ``recurrent_keys`` and the same options are refused.

  * **summary rows beside a window that starts over** (leaves the model
    names in ``summary_state_keys`` and ``restart_window_keys``;
    models/evabyte.py's ``k_sum``, ``v_sum`` ``[L, B, H, S_max / c, Dh]`` and
    ``k_win``, ``v_win`` ``[L, B, H, W, Dh]``): state that grows SLOWER than
    the request, one row for every ``c`` positions (chunk ``j`` at row ``j``),
    and becomes visible a window at a time: at length ``n`` the ``(W / c)
    floor(n / W)`` rows of the closed windows. The window beside it is no
    ring: position ``p`` lies at row ``p % W`` and the live rows are ``0 ..
    n % W``; the rows behind belong to the window before and are not read.
    Both are bounded functions of the slot's one ``lengths`` entry
    (:meth:`SlotKVCache.live_rows`), so a freed slot's rows need no clearing:
    a shorter request that takes the slot reads what it wrote itself and
    nothing else. Prefill writes whole windows (its buckets are multiples of
    ``W``: serving/engine.py refuses others), decode writes one row of each
    kind a step for active slots. Neither is addressed by token rows: they
    count among ``recurrent_keys`` and the same options are refused.

A finished request's slot is reused by the next admission with ZERO cache
reshaping — the prefill program overwrites the slot's prefix rows
(ops/attention.write_slot_rows) and its whole recurrent row, and resets
its length.

Memory model: the tree is allocated ONCE at serving-engine construction
for the worst case (``num_slots`` sequences of ``max_len`` tokens) and
never grows, shrinks, or reallocates — for key-value rows 2 * L * B * Hkv *
S_max * Dh * itemsize bytes of HBM, the same footprint a static batch of the
same shape would pin, but shared by an unbounded request stream. There is no
fragmentation because pages are whole slots; the cost of that simplicity
is internal padding (a short request holds a full slot row) — the
iteration-level scheduler keeps slots hot, which is where the throughput
win lives (ISSUE 2: 4-4.8x batch-8 aggregate).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from deepspeed_tpu.models.base import recurrent_state_keys, row_state_keys, slot_state_keys
from deepspeed_tpu.ops.attention import kv_pack_factor
from deepspeed_tpu.ops.decode_step import supports
from deepspeed_tpu.serving.errors import EngineConfigError


class SlotKVCache:
    """Owns the persistent per-slot state tree + per-slot lengths.

    The leaves are exposed (``state``, ``lengths``; ``k`` and ``v`` by
    name where the model has them) so the jitted serving programs can take them as (donated)
    operands; after every program call the engine stores the returned
    arrays back via :meth:`update` — the host never mutates them in place.
    """

    def __init__(self, model, num_slots: int, max_len: int, dtype=None):
        if num_slots < 1:
            raise EngineConfigError(f"num_slots must be >= 1, got {num_slots}")
        base = model.init_cache(num_slots, max_len, dtype=dtype)
        self.keys = slot_state_keys(model)
        self.state = {name: base[name] for name in self.keys}
        self.lengths = jnp.zeros((num_slots,), jnp.int32)
        self.num_slots = num_slots
        self.max_len = max_len
        self.row_keys = row_state_keys(model)
        # key-value rows: the pack factor the persistent allocation chose
        # (routes the decode path, see ops/attention.alloc_kv_cache), and
        # whether that allocation is one the fused decode step streams on a
        # TPU (the shapes' part of ops/attention.cached_attention's route):
        # then a decode step fetches what ops/decode_step's walk fetches. A
        # model with row leaves of its own has no pack factor and says
        # itself whether its fused step walks this allocation
        self.window_layers = self.window = 0
        self.fused_window_walk = False
        # summary rows beside a window that starts over: the window, and the
        # positions a summary row pools (the leaf holds a row a chunk of the
        # whole windows that ``max_len`` reaches into)
        self.restart_window = self.summary_chunk = 0
        summaries = getattr(model, "summary_state_keys", ())
        if summaries:
            w = self.state[model.restart_window_keys[0]].shape[3]
            self.restart_window = w
            self.summary_chunk = (-(-max_len // w) * w
                                  // self.state[summaries[0]].shape[3])
        if "k" not in self.state:
            self.pair = 1
            self.fused_walk = bool(model.fused_row_walk(self.state, num_slots))
            return
        # the geometry is each leaf's: ``k`` and ``v`` may differ in their
        # last dimension (keys wider than values, a key row padded to whole
        # lane tiles), a ring's heads need not be the rows'. ``head_dim`` is
        # the model's unpadded key width: only a leaf of packed token pairs
        # is a multiple of it, any other row is as wide as the leaf says
        head_dim = model.config.head_dim
        hkv, rows, width = self.k.shape[2:]
        self.pair = width // head_dim if width % head_dim == 0 else 1
        dk, dv = width // self.pair, self.v.shape[4] // self.pair
        self.fused_walk = (
            num_slots >= 2 and self.pair == kv_pack_factor(dk)
            and supports(hkv, hkv, rows * self.pair, dk, dv))
        # ring leaves (a sliding-window layer's last ``window`` positions):
        # their layers, the window, and whether the fused step walks them
        # too (ops/attention.window_cached_attention's route)
        # (the model names the keys' ring first, the values' last)
        rings = [self.state[n] for n in
                 getattr(model, "window_state_keys", ())]
        if rings:
            ring = rings[0]
            self.window_layers, self.window = ring.shape[0], ring.shape[3]
            self.fused_window_walk = (
                num_slots >= 2 and ring.shape[4] % 128 == 0
                and supports(ring.shape[2], ring.shape[2], self.window,
                             ring.shape[4], rings[-1].shape[4]))

    @property
    def k(self):
        return self.state["k"]

    @property
    def v(self):
        return self.state["v"]

    @property
    def recurrent_keys(self) -> Tuple[str, ...]:
        """Leaves that are not token rows: state that no row addresses."""
        return recurrent_state_keys(self.keys, self.row_keys)

    def live_rows(self, length: int) -> Tuple[int, int]:
        """``(window rows, summary rows)`` of a slot that holds ``length``
        tokens, where the model keeps summary rows beside a window that
        starts over: what its next step may read, whatever an earlier
        request left in the rows behind."""
        w, c = self.restart_window, self.summary_chunk
        return length % w, (w // c) * (length // w)

    # ------------------------------------------------------------- carry
    def carry(self) -> Tuple:
        """(*state leaves in the model's order, lengths): the operands of
        a serving program call; ``(k, v, lengths)`` for a key-value-only
        model."""
        return (*(self.state[name] for name in self.keys), self.lengths)

    def update(self, *carry) -> None:
        """Adopt a serving program's returned carry."""
        *leaves, self.lengths = carry
        self.state = dict(zip(self.keys, leaves, strict=True))

    # ------------------------------------------------------------ sizing
    def capacity_for(self, prompt_len: int, max_new_tokens: int,
                     lookahead: int = 0) -> bool:
        """Whether one slot can hold the request end to end (prompt plus
        every generated token; the decode step writes token i at row
        prompt_len + i, so the last write lands at row
        prompt_len + max_new_tokens - 1).

        ``lookahead`` reserves extra rows for speculative decoding
        (ISSUE 4): the verify step writes ALL k draft candidates' K/V
        BEFORE acceptance, so the worst-case final verify (length at
        prompt_len + max_new_tokens - 1, k-token draft) touches row
        prompt_len + max_new_tokens - 1 + k. Without the reserve a
        near-full slot would overflow max_len (pinned by the boundary
        test in tests/unit/serving/test_kv_slots.py)."""
        return prompt_len + max_new_tokens + lookahead <= self.max_len

    def hbm_bytes(self) -> int:
        """Bytes of every leaf: a slot bills its recurrent state too."""
        return int(sum(a.size * a.dtype.itemsize
                       for a in self.state.values()))

    def __repr__(self):
        leaves = ", ".join(f"{n}{list(a.shape)}"
                           for n, a in self.state.items())
        return (f"SlotKVCache(slots={self.num_slots}, max_len={self.max_len}, "
                f"pair={self.pair}, state=[{leaves}], "
                f"hbm={self.hbm_bytes() / 1e6:.1f}MB)")
