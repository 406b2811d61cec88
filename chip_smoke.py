#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that deepspeed_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, with
GPT-2 125M whole (12 layers, 768 wide, 12 heads, vocabulary 50257,
sequence 1024; random weights from ``--seed``):

  python chip_smoke.py             one TPU chip: train phase, then serve phase
  python chip_smoke.py --chips 4   four chips: the mesh phase and its
                                   one-device comparison, and no other phase
  python chip_smoke.py --rehearse  sandbox only: tiny shapes, any backend,
                                   kernels interpreted; never a pass

train  ``deepspeed_tpu.initialize`` (bf16, AdamW, clipping, ZeRO-0,
       attn_impl="flash", micro-batch 8 x 1024) + ``train_batch_from_stacked``
       on one learnable batch; step-1 loss checked against attn_impl="dense".
serve  ``deepspeed_tpu.init_inference`` once, then ``ServingEngine`` twice
       (slot-paged cache, then the block-paged prefix cache) against
       ``InferenceEngine.generate`` as the greedy reference.
mesh   (--chips 4) ZeRO-3 + tensor-parallel 2 on a data=2 x model=2 mesh
       against the same steps on a one-device topology.

Everything runs in this one process; the script starts no child, catches
nothing (any exception is a non-zero exit), and fails at its top unless JAX
found a TPU. The last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

Numbers printed here are smoke observations (host clock around
``block_until_ready``), not benchmark results.
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances, stated once and printed with what was observed.
LOSS_RTOL_BF16 = 2e-2      # step-1 loss, flash kernel vs dense einsum, bf16
MESH_LOSS_RTOL = 2e-2      # step-1 loss, 2x2 mesh vs one device
GREEDY_LOGIT_TOL = 0.0625  # a served token may sit this far below the best
                           # logit of the reference forward (4 bf16 steps at
                           # logits of 2 to 4; random weights tie often)
# mean share of each request's greedy tokens that match before the first
# divergence: the two cache managers run the same kernels and buckets;
# generate() pads differently (batch-1 einsum route), and one bf16 tie at a
# request's first token costs that request's whole share
SERVE_MIN_AGREE_MANAGERS = 0.9
SERVE_MIN_AGREE_GENERATE = 0.5
MEM_SPREAD_MAX = 4.0       # max/min bytes_in_use across the mesh's devices


class SmokeFailure(AssertionError):
    """A check of this script failed."""


def _say(**kw):
    print(json.dumps(kw, sort_keys=True), flush=True)


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _arith_batch(rng, vocab, shape):
    """Learnable data (the verify skill's arithmetic sequences mod vocab):
    row r is start_r + stride_r * t — random tokens would sit at ln(V)."""
    import numpy as np

    *lead, t = shape
    start = rng.randint(0, vocab, size=(*lead, 1))
    stride = rng.randint(1, 8, size=(*lead, 1))
    ids = (start + stride * np.arange(t + 1)) % vocab
    ids = ids.astype(np.int32)
    return {"input_ids": ids[..., :-1], "labels": ids[..., 1:]}


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.soft = []          # device/kernel findings a rehearsal only reports

    # ---------------------------------------------------------------- checks
    def hard_or_report(self, cond, msg):
        """Device-present and kernel-present checks: enforced on the chip,
        reported and remembered in a rehearsal."""
        if cond:
            return
        if self.rehearse:
            self.soft.append(msg)
            _say(rehearsal_would_fail=msg)
        else:
            raise SmokeFailure(msg)

    def check_kernel(self, what, text, extra=()):
        found = "tpu_custom_call" in text
        _say(check="kernel_in_compiled_text", program=what, found=found)
        self.hard_or_report(found, f"{what}: no tpu_custom_call in the "
                            "compiled text (einsum or interpreter route)")
        for group in extra:
            hit = [w for w in group if w in text]
            _say(check="collective_in_compiled_text", program=what,
                 any_of=list(group), found=hit)
            _require(hit, f"{what}: none of {group} in the compiled text")

    # --------------------------------------------------------------- configs
    def model_cfg(self):
        from deepspeed_tpu.models.gpt2 import GPT2Config

        if self.rehearse:
            return GPT2Config(vocab_size=512, max_seq_len=256, num_layers=2,
                              hidden_size=128, num_heads=2)
        return GPT2Config.gpt2_125m()

    def train_config(self, *, micro, gas, dp=1, stage=0, tp=1):
        cfg = {
            "train_batch_size": micro * gas * dp,
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": gas,
            "bf16": {"enabled": True},
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "gradient_clipping": 1.0,
            "zero_optimization": {"stage": stage},
            "steps_per_print": 0,
            "seed": self.args.seed,
        }
        if tp > 1:
            cfg["tensor_parallel"] = {"tp_size": tp}
        return cfg

    def _train_steps(self, engine, batches, label):
        """Run the batches through ``train_batch_from_stacked``; host clock
        around block_until_ready. Returns (losses, compiled_text)."""
        import jax
        import jax.numpy as jnp

        losses, secs = [], []
        for b in batches:
            t0 = time.perf_counter()
            loss = engine.train_batch_from_stacked(b)
            jax.block_until_ready(loss)
            secs.append(time.perf_counter() - t0)
            losses.append(float(loss))
        # lower + compile the engine's own jitted step once more and read it
        dev_batch = jax.device_put(batches[0],
                                   engine._gas_batch_shardings(batches[0]))
        t0 = time.perf_counter()
        text = engine._compiled_train_step.lower(
            engine.state, dev_batch, jnp.zeros((), jnp.float32),
            jax.random.PRNGKey(0), None, None).compile().as_text()
        _say(phase=label, losses=losses,
             train_step_programs=engine._compiled_train_step._cache_size(),
             first_step_s_with_compile=round(secs[0], 3),
             step_s=[round(s, 4) for s in secs[1:]],
             relower_s=round(time.perf_counter() - t0, 3))
        _require(all(l == l and abs(l) != float("inf") for l in losses),
                 f"{label}: non-finite loss in {losses}")
        if len(losses) > 1:
            _require(losses[-1] < losses[0],
                     f"{label}: loss did not fall: {losses}")
        return losses, text

    # ----------------------------------------------------------------- train
    def phase_train(self):
        import numpy as np

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Model
        from deepspeed_tpu.utils import groups

        cfg = self.model_cfg()
        micro, gas = (2, 2) if self.rehearse else (8, 2)
        seq = cfg.max_seq_len
        steps = 3 if self.rehearse else self.args.steps
        # the same stacked batch every step: in a handful of steps only
        # descent on a fixed batch shows (new rows each step stayed at ln V
        # for 6 steps on the chip, PR 23)
        rng = np.random.RandomState(self.args.seed)
        batches = [_arith_batch(rng, cfg.vocab_size, (gas, micro, seq))] * steps
        conf = self.train_config(micro=micro, gas=gas)

        # the same first step under the dense einsum, same seed => same
        # weights. remat=True changes no forward value; without it the dense
        # scores at micro-batch 8 need 16.3 GB of the chip's 15.75 (v5e
        # compiler, this PR) where the flash step needs 13.2.
        groups.reset()
        dense, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg, attn_impl="dense", remat=True), config=conf)
        dense_losses, _ = self._train_steps(dense, batches[:1], "train_dense")
        dense.destroy()
        del dense           # built, run and dropped before the next engine
        gc.collect()

        groups.reset()
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg, attn_impl="flash"), config=conf)
        losses, text = self._train_steps(engine, batches, "train_flash")
        self.check_kernel("train_step", text)
        rel = abs(losses[0] - dense_losses[0]) / abs(dense_losses[0])
        _say(check="flash_vs_dense_step1_loss", flash=losses[0],
             dense=dense_losses[0], rel_diff=rel, rtol=LOSS_RTOL_BF16)
        _require(rel <= LOSS_RTOL_BF16,
                 f"step-1 loss flash {losses[0]} vs dense {dense_losses[0]}: "
                 f"rel diff {rel} > {LOSS_RTOL_BF16}")
        engine.destroy()

    # ----------------------------------------------------------------- serve
    def phase_serve(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Model
        from deepspeed_tpu.serving import Request, ServingEngine
        from deepspeed_tpu.utils import groups

        cfg = self.model_cfg()
        # block_size 128: at Dh=64 the pool is token-pair packed only when
        # a block is 128-aligned (ops/attention.alloc_kv_cache), and only a
        # packed pool routes to fused_block_decode_step; the default 16
        # takes gather + einsum.
        block_size = 128
        if self.rehearse:
            slots, max_len, buckets = 4, 256, (16, 64)
            prompt_lens, new_choices = (5, 12, 40, 12, 5, 40), (4, 8, 6)
        else:
            slots, max_len, buckets = 8, 1024, (128, 512)
            prompt_lens = (24, 100, 200, 400, 100, 24, 400, 200, 24, 100)
            new_choices = (8, 16, 32)
        rng = np.random.RandomState(self.args.seed + 1)
        prompts = [_arith_batch(rng, cfg.vocab_size, (n,))["input_ids"]
                   for n in prompt_lens]
        max_new = [new_choices[i % len(new_choices)]
                   for i in range(len(prompts))]

        groups.reset()
        t0 = time.perf_counter()
        engine = deepspeed_tpu.init_inference(
            GPT2Model(cfg), dtype="fp32" if self.rehearse else "bf16",
            max_out_tokens=max_len, seed=self.args.seed)
        jax.block_until_ready(engine.params)
        _say(phase="serve_init", init_s=round(time.perf_counter() - t0, 3))

        # greedy reference: generate(), one prompt at a time (batch 1 takes
        # the einsum decode route, so this is kernel vs einsum)
        t0 = time.perf_counter()
        ref = []
        for p, n in zip(prompts, max_new):
            out = engine.generate(p[None, :], max_new_tokens=max(new_choices))
            ref.append([int(t) for t in out[0, len(p):len(p) + n]])
        _say(phase="serve_reference_generate",
             seconds_with_compile=round(time.perf_counter() - t0, 3))

        outs = {}
        for name, paged in (("slot_paged", False), ("block_paged", True)):
            srv = ServingEngine(engine, num_slots=slots, max_len=max_len,
                                buckets=buckets, prefix_cache=paged,
                                block_size=block_size)
            t0 = time.perf_counter()
            srv.warmup()
            warm_s = time.perf_counter() - t0
            sizes_warm = srv.program_cache_sizes()
            reqs = [Request(rid=i, prompt=[int(t) for t in p],
                            max_new_tokens=n)
                    for i, (p, n) in enumerate(zip(prompts, max_new))]
            t0 = time.perf_counter()
            results = srv.run(reqs, warmup=False)
            run_s = time.perf_counter() - t0
            sizes = srv.program_cache_sizes()
            by_rid = {r.rid: r for r in results}
            _require(sorted(by_rid) == list(range(len(reqs))),
                     f"{name}: finished {sorted(by_rid)} of {len(reqs)}")
            for i, n in enumerate(max_new):
                _require(len(by_rid[i].tokens) == n,
                         f"{name}: request {i} has {len(by_rid[i].tokens)} "
                         f"tokens, wanted {n} ({by_rid[i].finish_reason})")
            _require(sizes == sizes_warm and set(sizes.values()) == {1},
                     f"{name}: a program compiled after warmup(): "
                     f"{sizes_warm} -> {sizes}")
            text = srv._program_map()["decode"].lower(
                *srv._program_shapes["decode"]).compile().as_text()
            self.check_kernel(f"decode_step[{name}]", text)
            outs[name] = [by_rid[i].tokens for i in range(len(reqs))]
            _say(phase=f"serve_{name}", warmup_s_with_compile=round(warm_s, 3),
                 run_s=round(run_s, 3), requests=len(reqs), tokens=sum(max_new),
                 programs=sizes, peak_bytes_in_use=_peak_bytes(jax.devices()[0]))
            del srv         # built, run and dropped before the next
            gc.collect()

        # every served token is the reference forward's argmax, up to bf16
        # ties: teacher-force each request's own tokens through
        # engine.forward (no cache, dense attention) and look at how far
        # below the row's best logit the served token sits
        pad = max(new_choices)
        for name in ("slot_paged", "block_paged"):
            worst, exact, total = 0.0, 0, 0
            for p, toks in zip(prompts, outs[name]):
                seq = np.concatenate([p, toks, np.zeros(pad - len(toks))])
                logits = engine.forward(seq[None].astype(np.int32))
                rows = logits[0, len(p) - 1:len(p) - 1 + len(toks)]
                rows = rows.astype(jnp.float32)
                gap = rows.max(-1) - jnp.take_along_axis(
                    rows, jnp.asarray(toks)[:, None], -1)[:, 0]
                _require(bool(jnp.all(jnp.isfinite(rows))),
                         f"{name}: non-finite reference logits")
                worst = max(worst, float(gap.max()))
                exact += int((gap == 0).sum())
                total += len(toks)
            _say(check="served_tokens_vs_reference_forward", engine=name,
                 worst_logit_gap=worst, exact_argmax=exact, of=total,
                 tol=GREEDY_LOGIT_TOL)
            _require(worst <= GREEDY_LOGIT_TOL,
                     f"{name}: a served token sits {worst} below the "
                     f"reference forward's best logit (> {GREEDY_LOGIT_TOL})")

        def agree(a, b):
            """Mean over requests of the matching-prefix share, and the
            number of requests that match whole."""
            shares, whole = [], 0
            for x, y in zip(a, b):
                k = 0
                while k < len(x) and x[k] == y[k]:
                    k += 1
                shares.append(k / len(x))
                whole += k == len(x)
            return sum(shares) / len(shares), whole

        for a, b, floor in (
                ("slot_paged", "generate", SERVE_MIN_AGREE_GENERATE),
                ("block_paged", "generate", SERVE_MIN_AGREE_GENERATE),
                ("slot_paged", "block_paged", SERVE_MIN_AGREE_MANAGERS)):
            share, whole = agree(outs[a], ref if b == "generate" else outs[b])
            _say(check="greedy_agreement", a=a, b=b, prefix_share=share,
                 whole_requests=whole, of=len(prompts), min=floor)
            _require(share >= floor,
                     f"greedy tokens {a} vs {b}: prefix share {share} < {floor}")

    # ------------------------------------------------------------------ mesh
    def phase_mesh(self):
        import jax
        import numpy as np

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Model
        from deepspeed_tpu.parallel.topology import build_topology
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.utils import groups

        cfg = self.model_cfg()
        micro, gas, dp, tp = (2, 1, 2, 2) if self.rehearse else (8, 1, 2, 2)
        seq = cfg.max_seq_len
        steps = 3 if self.rehearse else self.args.steps
        rng = np.random.RandomState(self.args.seed)
        # [1, global, T] for the mesh; the same rows as [dp, micro, T] for
        # the one-device run (gas = dp there: same global batch per step).
        # One batch, repeated, as in the train phase.
        flat = _arith_batch(rng, cfg.vocab_size, (micro * dp, seq))
        mesh_batches = [{k: v[None] for k, v in flat.items()}] * steps
        one_batches = [{k: v.reshape(dp, micro, seq)
                        for k, v in flat.items()}] * steps

        groups.reset()
        topo = build_topology(world_size=4, tp=tp)
        engine, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg, attn_impl="flash"),
            config=self.train_config(micro=micro, gas=gas, dp=dp, stage=3,
                                     tp=tp),
            topology=topo)
        _say(phase="mesh_topology",
             mesh=dict(zip(topo.get_axis_names(), topo.mesh_shape)))
        losses, text = self._train_steps(engine, mesh_batches, "mesh_zero3_tp2")
        self.check_kernel("mesh_train_step", text,
                          extra=(("all-gather",),
                                 ("reduce-scatter", "all-reduce")))

        # the state is really spread: no big parameter whole on one device,
        # and every device holds memory of the same order
        threshold = engine.config.zero_config.param_persistence_threshold
        whole = []
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                engine.state.params):
            shard = leaf.addressable_shards[0].data
            if leaf.size > threshold and shard.size >= leaf.size:
                whole.append(jax.tree_util.keystr(path))
        example = engine.state.params["blocks"]["mlp_fc_w"].sharding.spec
        _say(check="params_sharded", persistence_threshold=threshold,
             whole_on_one_device=whole, mlp_fc_w_spec=str(example))
        _require(not whole, f"parameters above the persistence threshold "
                 f"live whole on a device: {whole}")
        used = [(d.memory_stats() or {}).get("bytes_in_use")
                for d in topo.mesh.devices.flat]
        _say(check="bytes_in_use_per_device", bytes=used,
             max_over_min=MEM_SPREAD_MAX)
        if all(u is None for u in used):
            self.hard_or_report(False, "this backend reports no memory_stats")
        else:
            _require(min(used) > 0 and max(used) / min(used) <= MEM_SPREAD_MAX,
                     f"device memory is not spread evenly: {used}")
        engine.destroy()
        del engine
        gc.collect()

        # world_size=1: the config would otherwise size data parallelism from
        # all four devices of the process, not from the topology handed in
        groups.reset()
        one, *_ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg, attn_impl="flash"),
            config=DeepSpeedConfig(self.train_config(micro=micro, gas=dp),
                                   world_size=1),
            topology=build_topology(devices=jax.devices()[:1]))
        one_losses, _ = self._train_steps(one, one_batches, "mesh_one_device")
        rel = abs(losses[0] - one_losses[0]) / abs(one_losses[0])
        _say(check="mesh_vs_one_device_step1_loss", mesh=losses[0],
             one_device=one_losses[0], rel_diff=rel, rtol=MESH_LOSS_RTOL,
             mesh_losses=losses, one_device_losses=one_losses)
        _require(rel <= MESH_LOSS_RTOL,
                 f"step-1 loss mesh {losses[0]} vs one device "
                 f"{one_losses[0]}: rel diff {rel} > {MESH_LOSS_RTOL}")
        one.destroy()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, data and prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve phases; 4: the mesh phase and its "
                         "one-device comparison only")
    ap.add_argument("--steps", type=int, default=6,
                    help="training steps per engine")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny shapes, any backend, "
                         "interpreted kernels; the last line is never a pass")
    args = ap.parse_args()

    # Compile cache: JAX's own variable wins; otherwise one fixed path in the
    # checkout (the path is part of the cache key). A rehearsal runs on
    # XLA:CPU, where a cache hit on subgroup collectives deadlocks: none.
    if args.rehearse:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(_ROOT, ".jax_cache"))

    import deepspeed_tpu  # noqa: F401  (fails here where the program is absent)
    import jax

    from deepspeed_tpu.accelerator import get_accelerator

    smoke = Smoke(args)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    acc = get_accelerator()
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    # the device is really there: before any work and before any line on
    # stdout, so that a run without a chip prints no result at all
    smoke.hard_or_report(device["platform"] == "tpu",
                         f"JAX found no TPU: {device}")
    smoke.hard_or_report(acc.name() == "tpu",
                         f"get_accelerator() is {acc.name()!r}, not 'tpu'")
    smoke.hard_or_report(
        acc.peak_tflops() is not None and acc.peak_hbm_gbps() is not None,
        f"no peak table entry for device kind {device['kind']!r}")
    _say(jax=jax.__version__, jaxlib=__import__("jaxlib").__version__,
         libtpu=libtpu, device=device, accelerator=acc.name(),
         peak_tflops=acc.peak_tflops(), peak_hbm_gbps=acc.peak_hbm_gbps(),
         compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
         rehearse=args.rehearse, seed=args.seed)
    _require(len(devs) == args.chips,
             f"--chips {args.chips} but JAX sees {len(devs)} device(s)")

    t0 = time.perf_counter()
    if args.chips == 4:
        smoke.phase_mesh()
    else:
        smoke.phase_train()
        smoke.phase_serve()
    _say(total_s=round(time.perf_counter() - t0, 1),
         peak_bytes_in_use=[_peak_bytes(d) for d in devs])

    if args.rehearse:
        print(json.dumps({"ok": False, "reason": "rehearsal, not a chip run",
                          "rehearsal_passed": True,
                          "would_fail_on_chip": smoke.soft,
                          "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
