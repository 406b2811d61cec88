"""EvaByte style decoder (HF ``evabyte``, ``attention_class`` ``eva``): a
byte-level model whose every layer attends with EVA (ops/eva.py): exactly and
causally inside the query's own aligned window of ``window_size`` positions,
and to one pooled key and value for each ``chunk_size`` positions of every
window before it, under one softmax. The rest of the block is the ``llama``
family's, with norms that add a unit offset, float32 residual sums and
logits, and a head that predicts ``num_pred_heads`` bytes ahead.

    N(x; w) = x / rms(x) * (1 + w)
    h = x + Attn(N(x; w1)) Wo;  y = h + (silu(u Wg) * (u Wu)) Wd,  u = N(h; w2)
    q_t, k_t, v_t: heads of u_t Wq, u_t Wk, u_t Wv; q, k rotated (rotate-half)
    chunk j:  a = softmax_m(s phi_h . k_m);  ksum_j = sum a k + mu_h;
              vsum_j = sum a v
    query t in window w: keys m in [W w, t] and summaries j in [0, w W / c),
              one float32 softmax
    logits_i = N(x; wf) Whead[:, V i : V i + V]      (head i: the byte t + 1 + i)

What it brings that no other model here has: per-slot state that is neither a
row a token nor fixed. ``k_win``, ``v_win`` ``[L, B, H, W, Dh]`` are a window
that STARTS OVER (position ``p`` at row ``p mod W``, live rows ``0 .. p mod
W``), ``k_sum``, ``v_sum`` ``[L, B, H, S_max / c, Dh]`` grow a row in ``c``
positions and become visible a window at a time (serving/kv_slots.py). A
prompt passes the stack in blocks of ``W`` (``prompt_block`` = ``window_size``,
models/stack.prompt_walk): the block is the attention's own unit, and what a
finished block leaves behind, ``W / c`` summary rows, is what later blocks
read. The serving path computes and samples head 0.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cache_positions, gathered_top, merge_heads, project_heads, qdot, rms_norm
from deepspeed_tpu.models.stack import StackedDecoder, cached_walk, next_cache, prompt_walk, walk, wrapped_block
from deepspeed_tpu.ops import eva
from deepspeed_tpu.ops.rotary import apply_rotary_half

# what a decode step counts on the device, from its own lengths and active
# mask, summed over the layers: rows the tokens attend (their window's and the
# visible summaries), rows the fused step brings for them, and the summaries'
# part of the first
STEP_COUNTERS = ("eva_rows_live", "eva_rows_fetched", "eva_summary_rows_live")


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320
    max_seq_len: int = 32768
    num_layers: int = 32
    hidden_size: int = 4096
    num_heads: int = 32
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 1e5
    eps: float = 1e-5
    init_std: float = 0.01275
    has_position_table = False    # rotation is computed, nothing is indexed

    def __post_init__(self):
        if self.hidden_size % self.num_heads:
            raise ValueError("heads must divide hidden_size")
        if self.chunk_size < 1 or self.window_size % self.chunk_size:
            raise ValueError(f"chunk_size={self.chunk_size} does not divide "
                             f"window_size={self.window_size}")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads must be >= 1")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def prompt_block(self) -> int:
        """Positions of a prompt that pass the stack at once: a window."""
        return self.window_size

    @classmethod
    def tiny(cls, **kw):
        sizes = dict(vocab_size=320, max_seq_len=128, num_layers=2,
                     hidden_size=64, num_heads=4, intermediate_size=128,
                     window_size=32, chunk_size=4, num_pred_heads=8)
        return cls(**{**sizes, **kw})


class EvaByteModel(StackedDecoder):
    """One stack of layers of one kind, walked as the scan's input; of the
    decoder's frame (models/stack.StackedDecoder) the constructor, the loss
    and the engines' contract: the stream, the norms, the head and what a step
    counts are this family's own."""

    stacks = ("blocks",)
    # per-slot state, in operand order. None is a row a token: the serving
    # engine refuses prefix reuse, speculation, swap and kv_dtype by this
    # list, and what it counts by a request's length
    # (``serving/decode_rows_*``) is not what a step moves here: the step
    # counts its own (:data:`STEP_COUNTERS`)
    slot_state_keys = ("k_win", "v_win", "k_sum", "v_sum")
    row_state_keys = ()
    # the window that starts over, and the summary rows behind it
    # (serving/kv_slots.py)
    restart_window_keys = ("k_win", "v_win")
    summary_state_keys = ("k_sum", "v_sum")
    step_counters = STEP_COUNTERS
    prompt_counters = ()

    @staticmethod
    def record_step_counters(telemetry, counts) -> None:
        """A decode step's vector into the serving engine's registry, and
        with it which way the programs' layers were traced
        (``eva/traced_*``)."""
        for name, n in zip(STEP_COUNTERS, counts):
            telemetry.counter("serving/" + name).inc(int(n))
        eva.record_traced(telemetry)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, l, m, h, dh = (c.hidden_size, c.num_layers, c.intermediate_size,
                          c.num_heads, c.head_dim)
        pd = self.param_dtype
        k = jax.random.split(rng, 14)
        init = jax.nn.initializers.normal(c.init_std)
        # a norm's ``w`` at half the scale it is added to, the pooling's
        # direction and offset at the keys' own scale: a program that reads
        # the scale as ``w``, pools by a mean or drops ``mu`` is far from the
        # reference, not a rounding away

        def offset(key, shape):
            return 0.5 * jax.random.normal(key, shape, pd)

        def clipped(key, shape):
            return jnp.clip(jax.random.normal(key, shape, pd), -1.0, 1.0)

        return {
            "embed": jax.random.normal(k[0], (c.vocab_size, d), pd),
            "blocks": {
                "attn_norm": offset(k[1], (l, d)),
                "wq": init(k[2], (l, d, d), pd),
                "wk": init(k[3], (l, d, d), pd),
                "wv": init(k[4], (l, d, d), pd),
                "wo": init(k[5], (l, d, d), pd),
                "phi": clipped(k[6], (l, h, dh)),
                "mu": clipped(k[7], (l, h, dh)),
                "mlp_norm": offset(k[8], (l, d)),
                "w_gate": init(k[9], (l, d, m), pd),
                "w_up": init(k[10], (l, d, m), pd),
                "w_down": init(k[11], (l, m, d), pd),
            },
            "final_norm": offset(k[12], (d,)),
            "lm_head": init(k[13], (d, c.num_pred_heads * c.vocab_size), pd),
        }

    def logical_axes(self):
        return {
            "embed": ("vocab_in", "hidden"),
            "blocks": {
                "attn_norm": ("layer", "hidden"),
                "wq": ("layer", "hidden", "heads"),
                "wk": ("layer", "hidden", "heads"),
                "wv": ("layer", "hidden", "heads"),
                "wo": ("layer", "heads", "hidden"),
                "phi": ("layer", None, None),
                "mu": ("layer", None, None),
                "mlp_norm": ("layer", "hidden"),
                "w_gate": ("layer", "hidden", "mlp"),
                "w_up": ("layer", "hidden", "mlp"),
                "w_down": ("layer", "mlp", "hidden"),
            },
            "final_norm": ("hidden",),
            "lm_head": ("hidden", "vocab"),
        }

    # --------------------------------------------------------------- layers
    def _norm(self, x, w):
        """``x / rms(x) * (1 + w)`` of the float32 stream, for a matmul."""
        return rms_norm(x, 1.0 + w.astype(jnp.float32), self.config.eps
                        ).astype(self.compute_dtype)

    def _plain_attention(self, q, k_, v_, phi, mu):
        """A whole sequence with no cache (training, a full forward): the
        summaries of all chunks at once, then a window of queries at a time
        against them and itself."""
        c = self.config
        b, t, h, dh = q.shape
        w, per_window = c.window_size, c.window_size // c.chunk_size
        scale = dh ** -0.5
        if t <= w:      # one window: no summary is visible
            none = jnp.zeros((1, b, h, per_window, dh), q.dtype)
            return eva.eva_prompt_block(q, k_, v_, none, none, 0, 0,
                                        scale=scale)
        n = -(-t // w)
        pad = [(0, 0), (0, n * w - t), (0, 0), (0, 0)]
        q, k_, v_ = (jnp.pad(a, pad) for a in (q, k_, v_))
        ksum, vsum = eva.pool_chunks(
            k_.transpose(0, 2, 1, 3), v_.transpose(0, 2, 1, 3), phi, mu,
            chunk=c.chunk_size, scale=scale)
        ksum, vsum = ksum.astype(q.dtype)[None], vsum.astype(q.dtype)[None]

        def window(i):
            qi, ki, vi = (jax.lax.dynamic_slice_in_dim(a, i * w, w, 1)
                          for a in (q, k_, v_))
            return eva.eva_prompt_block(qi, ki, vi, ksum, vsum, 0,
                                        i * per_window, scale=scale)

        out = jax.lax.map(window, jnp.arange(n))          # [n, B, W, H, Dh]
        return out.transpose(1, 0, 2, 3, 4).reshape(b, n * w, h, dh)[:, :t]

    def _cached_attention(self, q, k_, v_, leaves, layer, idx, valid, walk_,
                          phi, mu):
        """One layer against its leaves ``(k_win, v_win, k_sum, v_sum)`` ->
        ``(out, leaves)``: one token a row (the step), or a block of at most
        a window that starts one (every row at the same first position: a
        batch-1 prefill, ``generate()``'s uniform batch)."""
        c = self.config
        b, t, h, dh = q.shape
        w, chunk = c.window_size, c.chunk_size
        scale = dh ** -0.5
        if t == 1:
            active = walk_
            if active is None and valid is not None:
                active = valid > 0
            attn, *leaves = eva.eva_decode_step(
                q[:, 0], *leaves, k_[:, 0], v_[:, 0], phi, mu, layer,
                jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,)),
                chunk=chunk, scale=scale, active=active)
            return attn[:, None], tuple(leaves)
        k_win, v_win, k_sum, v_sum = leaves
        first = jnp.asarray(idx, jnp.int32).reshape(-1)[0]
        out = eva.eva_prompt_block(q, k_, v_, k_sum, v_sum, layer,
                                   (w // chunk) * (first // w), scale=scale)
        # what the block leaves behind: its chunks' summaries (a chunk the
        # prompt does not fill is pooled anew by every step that adds to it,
        # ops/eva.py, so what padding makes of it here is never read) ...
        rows = -(-t // chunk) * chunk
        kh, vh = (jnp.pad(a.transpose(0, 2, 1, 3),
                          [(0, 0), (0, 0), (0, rows - t), (0, 0)])
                  for a in (k_, v_))
        ksum, vsum = eva.pool_chunks(kh, vh, phi, mu, chunk=chunk,
                                     scale=scale)
        at = (layer, 0, 0, first // chunk, 0)
        k_sum = jax.lax.dynamic_update_slice(
            k_sum, ksum.astype(k_sum.dtype)[None], at)
        v_sum = jax.lax.dynamic_update_slice(
            v_sum, vsum.astype(v_sum.dtype)[None], at)
        # ... and, if it holds a real position, its rows as the window's: the
        # prompt's last such block is the window the steps go on in (rows
        # behind the prompt's end are dead until a step writes them)
        real = jnp.ones((b,), bool) if valid is None else valid > 0

        def rows_of(leaf, new):
            old = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
            new = jax.lax.dynamic_update_slice(
                old, new[:, :, :t].astype(leaf.dtype), (0, 0, 0, 0))
            return jax.lax.dynamic_update_index_in_dim(
                leaf, jnp.where(real[:, None, None, None], new, old), layer,
                0)

        return out, (rows_of(k_win, kh), rows_of(v_win, vh), k_sum, v_sum)

    def _block(self, x, blk, state, layer, idx, valid, walk_):
        """One layer -> ``(x, state)``; the stream ``x`` is float32."""
        c = self.config
        t = x.shape[1]
        h, dh = c.num_heads, c.head_dim
        y = self._norm(x, blk["attn_norm"])
        pos = cache_positions(0 if idx is None else idx, t)
        q = apply_rotary_half(project_heads(y, blk["wq"], h, dh), pos,
                              c.rope_theta)
        k_ = apply_rotary_half(project_heads(y, blk["wk"], h, dh), pos,
                               c.rope_theta)
        v_ = project_heads(y, blk["wv"], h, dh)
        if state is None:
            out = self._plain_attention(q, k_, v_, blk["phi"], blk["mu"])
        else:
            out, state = self._cached_attention(
                q, k_, v_, state, layer, idx, valid, walk_, blk["phi"],
                blk["mu"])
        x = x + merge_heads(out.astype(y.dtype), blk["wo"]
                            ).astype(jnp.float32)
        u = self._norm(x, blk["mlp_norm"])
        gate = jax.nn.silu(qdot("btd,dm->btm", u, blk["w_gate"]))
        up = qdot("btd,dm->btm", u, blk["w_up"])
        return x + qdot("btm,md->btd", gate * up, blk["w_down"]
                        ).astype(jnp.float32), state

    # -------------------------------------------------------------- forward
    def forward_hidden(self, params, input_ids, *, rngs=None,
                       train: bool = False):
        top = gathered_top(params, "blocks")
        x = top["embed"].astype(jnp.float32)[input_ids]
        block_fn = wrapped_block(
            lambda x, blk: self._block(x, blk, None, None, None, None,
                                       None)[0],
            "blocks", self.remat, self.remat_policy)
        x = walk(block_fn, x, params["blocks"])
        return self._norm(x, top["final_norm"])

    def all_logits(self, params, hidden):
        """Every head's float32 logits ``[B, T, num_pred_heads, V]``: head
        ``i`` (columns ``V i .. V i + V - 1``) predicts the byte at ``t + 1
        + i``."""
        c = self.config
        b, t, _ = hidden.shape
        return jnp.einsum(
            "btd,dv->btv", hidden, params["lm_head"].astype(hidden.dtype),
            preferred_element_type=jnp.float32
        ).reshape(b, t, c.num_pred_heads, c.vocab_size)

    def logits(self, params, hidden):
        """Head 0's float32 logits ``[B, T, V]``: the next byte, the head
        that is served."""
        v = self.config.vocab_size
        return jnp.einsum(
            "btd,dv->btv", hidden,
            params["lm_head"][:, :v].astype(hidden.dtype),
            preferred_element_type=jnp.float32)

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``k_win``, ``v_win``: ``window_size`` rows whatever ``max_len``
        is; ``k_sum``, ``v_sum``: a row a chunk of the whole windows that
        ``max_len`` positions reach into; the index."""
        c = self.config
        dtype = dtype or self.compute_dtype
        lead = (c.num_layers, batch_size, c.num_heads)
        rows = -(-max_len // c.window_size) * (c.window_size // c.chunk_size)
        # (a buffer a leaf: a serving program donates each)
        return {"k_win": jnp.zeros(lead + (c.window_size, c.head_dim), dtype),
                "v_win": jnp.zeros(lead + (c.window_size, c.head_dim), dtype),
                "k_sum": jnp.zeros(lead + (rows, c.head_dim), dtype),
                "v_sum": jnp.zeros(lead + (rows, c.head_dim), dtype),
                "index": jnp.zeros((), jnp.int32)}

    def _layers(self, params, x, leaves, counts, idx, valid, walk_):
        x, leaves = cached_walk(self._block, x, params["blocks"], leaves,
                                idx, valid, walk_,
                                count=self.config.num_layers)
        return x, leaves, counts

    def forward_with_cache(self, params, input_ids, cache):
        """A prompt (T > 1) or one token a row against the cache tree.
        ``cache["index"]`` is a scalar or a per-slot ``[B]`` vector;
        ``cache["valid_len"]`` (scalar or ``[B]``) how many of the positions
        are real for each row; ``cache["slot_walk"]`` the decode program's
        walk order. A prompt starts a window and is at most one window or a
        whole number of them, passed a window at a time
        (models/stack.prompt_walk); with ``valid_len`` its logits are those
        of each row's last real position alone, ``[B, 1, V]``. The logits are
        head 0's, float32. The returned cache carries ``step_counters``
        (:data:`STEP_COUNTERS`)."""
        c = self.config
        b, t = input_ids.shape
        w = c.window_size
        if t > w and t % w:
            raise ValueError(
                f"a prompt of {t} positions is no whole number of windows of "
                f"{w}: pad it and say valid_len")
        x, leaves, _ = prompt_walk(
            functools.partial(self._layers, params),
            params["embed"].astype(jnp.float32), input_ids,
            tuple(cache[k] for k in self.slot_state_keys), None, cache, w)
        hidden = self._norm(x, params["final_norm"])
        out = next_cache(cache, t, **dict(zip(self.slot_state_keys, leaves)))
        counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
        if t == 1:
            pos = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32),
                                   (b,))
            valid = cache.get("valid_len")
            live = jnp.ones((b,), bool) if valid is None else \
                jnp.broadcast_to(jnp.asarray(valid), (b,)) > 0
            rows, summaries = eva.live_rows(pos, w, c.chunk_size)
            counts = c.num_layers * jnp.stack([
                jnp.sum(jnp.where(live, n, 0)) for n in (
                    rows + summaries,
                    eva.rows_fetched(pos, w, c.chunk_size), summaries)
            ]).astype(jnp.int32)
        out["step_counters"] = counts
        return self.logits(params, hidden), out

    def num_params(self) -> int:
        c = self.config
        d = c.hidden_size
        layer = (4 * d * d + 2 * c.num_heads * c.head_dim
                 + 3 * d * c.intermediate_size + 2 * d)
        return (c.num_layers * layer + c.vocab_size * d
                + d * c.num_pred_heads * c.vocab_size + d)

    def flops_per_token(self) -> float:
        return 6.0 * self.num_params()
