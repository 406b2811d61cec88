"""1-bit compressed collectives + optimizers.

Mirrors the reference's onebit tests (tests/onebit/test_nccl_backend.py:
compressed_allreduce correctness vs exact allreduce; tests/unit/runtime/
half_precision/onebit/test_onebit.py: optimizer convergence) on the
8-device CPU mesh via shard_map.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.ops.onebit import (
    OnebitAdam,
    OnebitLamb,
    ZeroOneAdam,
    compressed_allreduce,
)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


class TestCompressedAllreduce:
    def test_single_round_approximates_mean(self):
        mesh = _mesh()
        rng = np.random.RandomState(0)
        x = rng.randn(8, 1000).astype(np.float32)

        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),) * 3,
                           out_specs=(P("data"),) * 3)
        def run(xs, we, se):
            out, w2, s2 = compressed_allreduce(
                xs[0], we[0], se[0], "data")
            return out[None], w2[None], s2[None]

        zeros = np.zeros_like(x)
        out, _, _ = run(x, zeros, zeros)
        exact = x.mean(axis=0)
        out = np.asarray(out)
        for r in range(8):
            np.testing.assert_array_equal(out[r], out[0])  # consensus
        # sign compression is lossy but must correlate strongly with the mean
        corr = np.corrcoef(out[0], exact)[0, 1]
        assert corr > 0.5, f"corr={corr}"

    def test_error_feedback_preserves_signal_over_rounds(self):
        """With error feedback, the ACCUMULATED compressed sum tracks the
        accumulated true mean (the 1-bit convergence argument)."""
        mesh = _mesh()
        rng = np.random.RandomState(1)
        numel = 512

        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),) * 3,
                           out_specs=(P("data"),) * 3)
        def run(xs, we, se):
            out, w2, s2 = compressed_allreduce(xs[0], we[0], se[0], "data")
            return out[None], w2[None], s2[None]

        we = np.zeros((8, numel), np.float32)
        se = np.zeros((8, numel), np.float32)
        acc_comp = np.zeros(numel)
        acc_true = np.zeros(numel)
        for _ in range(30):
            x = rng.randn(8, numel).astype(np.float32)
            out, we, se = run(x, we, se)
            we, se = np.asarray(we), np.asarray(se)
            acc_comp += np.asarray(out)[0]
            acc_true += x.mean(axis=0)
        # residual error is bounded by the CURRENT error feedback, not by the
        # number of rounds — relative deviation of the running sums shrinks
        rel = np.linalg.norm(acc_comp - acc_true) / np.linalg.norm(acc_true)
        assert rel < 0.6, f"relative accumulated error {rel}"


def _dp_train(opt, steps=150, lr=0.05):
    """Data-parallel toy regression under shard_map: each device computes
    LOCAL grads on its batch shard; the optimizer handles all comm.

    Error-feedback state is PER-DEVICE (never replicated): worker/server
    errors carry a leading device dim sharded over 'data'; everything else
    is replicated consensus (compressed sync outputs are identical on all
    devices, so no pmean is needed)."""
    mesh = _mesh()
    rng = np.random.RandomState(0)
    w_true = rng.randn(16).astype(np.float32)
    # nonzero init: LAMB's trust ratio needs a weight norm to scale against
    params = {"w": jnp.asarray(rng.randn(16) * 0.5, jnp.float32)}
    state = opt.init(params)
    # per-device error carriers: [n_dev, ...]
    stack8 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (8,) + a.shape), t)
    we, se = stack8(state.worker_error), stack8(state.server_error)
    state = state._replace(worker_error=None, server_error=None)

    rep = jax.tree_util.tree_map(lambda _: P(), state)
    dev = jax.tree_util.tree_map(lambda _: P("data"), we)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), rep, dev, dev, P("data"), P("data")),
        out_specs=(P(), rep, dev, dev),
        # params/moments are consensus by construction (the compressed sync
        # ends in an allgather reconstruction identical on every device),
        # which vma typing cannot prove statically
        check_vma=False)
    def step(params, state, we, se, xb, yb):
        pred = xb[0] @ params["w"]
        g = {"w": 2 * xb[0].T @ (pred - yb[0]) / xb.shape[1]}
        drop0 = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        inner = state._replace(worker_error=drop0(we), server_error=drop0(se))
        new_p, new_s = opt.step(params, g, inner, lr, axis_name="data")
        add0 = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        return (new_p, new_s._replace(worker_error=None, server_error=None),
                add0(new_s.worker_error), add0(new_s.server_error))

    losses = []
    for i in range(steps):
        x = rng.randn(8, 16, 16).astype(np.float32)
        y = np.einsum("dbi,i->db", x, w_true).astype(np.float32)
        params, state, we, se = step(params, state, we, se, x, y)
        losses.append(float(np.linalg.norm(np.asarray(params["w"]) - w_true)))
    return losses


class TestOnebitOptimizers:
    def test_onebit_adam_converges_dp(self):
        losses = _dp_train(OnebitAdam(lr=0.05, freeze_step=10))
        assert losses[-1] < 0.25 * losses[0], f"{losses[0]} -> {losses[-1]}"

    def test_onebit_lamb_converges_dp(self):
        # LAMB's trust-ratio clamp is conservative on this toy problem;
        # monotone convergence is the property under test
        losses = _dp_train(OnebitLamb(lr=0.05, freeze_step=10))
        assert losses[-1] < 0.55 * losses[0], f"{losses[0]} -> {losses[-1]}"

    def test_zero_one_adam_converges_dp(self):
        losses = _dp_train(ZeroOneAdam(lr=0.02, var_freeze_step=50,
                                       var_update_scaler=4))
        assert losses[-1] < 0.4 * losses[0], f"{losses[0]} -> {losses[-1]}"

    def test_warmup_matches_exact_adam(self):
        """During warmup (exact comm, both moments live) OnebitAdam must be
        bit-close to FusedAdam."""
        from deepspeed_tpu.ops.adam import FusedAdam

        rng = np.random.RandomState(2)
        params = {"w": jnp.asarray(rng.randn(64), jnp.float32)}
        ob = OnebitAdam(lr=1e-2, freeze_step=1000)
        fa = FusedAdam(lr=1e-2, weight_decay=0.0)
        sob, sfa = ob.init(params), fa.init(params)
        pob = pfa = params
        for _ in range(5):
            g = {"w": jnp.asarray(rng.randn(64), jnp.float32)}
            pob, sob = ob.step(pob, g, sob, 1e-2)
            pfa, sfa = fa.step(pfa, g, sfa, 1e-2)
        np.testing.assert_allclose(np.asarray(pob["w"]), np.asarray(pfa["w"]),
                                   rtol=1e-5, atol=1e-6)

    def test_registry(self):
        from deepspeed_tpu.ops.adam import build_optimizer

        assert isinstance(build_optimizer("OneBitAdam", {"lr": 1e-3}), OnebitAdam)
        assert isinstance(build_optimizer("OneBitLamb", {}), OnebitLamb)
        assert isinstance(build_optimizer("ZeroOneAdam", {}), ZeroOneAdam)
