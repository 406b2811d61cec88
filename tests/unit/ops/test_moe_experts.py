"""A decode step's routed experts as one Pallas call
(``ops/moe_experts.experts_decode``, interpret mode here) against
``moe/grouped.held_experts``' ``ragged_dot`` branch and against a plain
float32 reference, and the rule that chooses between the two routes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.grouped import Routing, held_experts
from deepspeed_tpu.ops import moe_experts
from deepspeed_tpu.telemetry.registry import get_registry

D, M, E, K = 128, 256, 16, 3


def _reference(x, routing, wg, wu, wd, held, valid, limit):
    """Every held expert on every token in float32, weighed by the token's
    weight where it chose the expert."""
    first, count = held
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    local = routing.experts - first
    chose = (local[:, :, None] == jnp.arange(count)) & valid[:, None, None]
    weights = jnp.where(chose, routing.weights[:, :, None], 0.0).sum(1)
    x = jnp.where(valid[:, None], f32(x), 0.0)
    up = jnp.einsum("nd,edm->nem", x, f32(wu))
    if wg is None:
        h = jnp.square(jax.nn.relu(up))
    else:
        gate = jnp.einsum("nd,edm->nem", x, f32(wg))
        if limit is not None:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        h = jax.nn.silu(gate) * up
    return jnp.einsum("ne,nem,emd->nd", weights, h, f32(wd))


# name -> what differs from a gated layer of 8 held experts (4..11 of 16), 12
# slots, all valid
CASES = {
    "gated": {},
    "swiglu-limit-binds": {"limit": 0.25},
    "relu2": {"gated": False},
    "layer-stacked": {"layer": 2},
    "held-from-0": {"held": (0, 8)},
    "valid-mask": {"valid": "some"},
    "no-pair-held": {"experts": "outside"},
    "one-expert": {"experts": "one"},
    "rows-no-multiple-of-8": {"n": 11},
    "two-tiles": {"tile_buffers": 2 * 3 * D * 128 * 2},
    "float32": {"dtype": jnp.float32},
}


@pytest.mark.parametrize("case", CASES, ids=str)
def test_the_fused_step_is_the_grouped_one(case, monkeypatch):
    spec = CASES[case]
    n, dtype = spec.get("n", 12), spec.get("dtype", jnp.bfloat16)
    held, limit = spec.get("held", (4, 8)), spec.get("limit")
    first, count = held
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    x = jax.random.normal(keys[0], (n, D), jnp.float32)
    if spec.get("experts") == "one":
        experts = jnp.tile(jnp.array([[first + 5, 0, E - 1]], jnp.int32),
                           (n, 1))
    else:
        lo, hi = (0, first) if spec.get("experts") == "outside" else (0, E)
        experts = jax.vmap(lambda k: jax.random.permutation(k, hi - lo)[:K])(
            jax.random.split(keys[1], n)).astype(jnp.int32) + lo
    routing = Routing(experts, jax.random.uniform(keys[2], (n, K)) + 0.1)
    valid = jnp.arange(n) % 3 != 1 if spec.get("valid") else None
    if valid is not None:
        # what an idle slot's row holds must reach no other row, and not its
        # own result
        x = jnp.where(valid[:, None], x, jnp.nan)
    x = x.astype(dtype)

    def matrices(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    layer, layers = spec.get("layer"), 4
    lead = (count,) if layer is None else (layers, count)
    wg = matrices(keys[3], *lead, D, M) if spec.get("gated", True) else None
    wu, wd = matrices(keys[4], *lead, D, M), matrices(keys[5], *lead, M, D)
    if "tile_buffers" in spec:
        monkeypatch.setattr(moe_experts, "_TILE_BUFFERS", spec["tile_buffers"])
        assert moe_experts.block_m(D, M, 3, 2) == 128

    def run(route):
        monkeypatch.setattr(moe_experts, "default_route", lambda *a: route)

        def fn(x, routing, valid, at, *w):
            if layer is not None:
                w = [u if u is None else {"__whole__": u, "__layer__": at}
                     for u in w]
            return held_experts(x, routing, *w, held, valid=valid,
                                limit=limit)

        reg = get_registry()
        names = ["moe/traced_decode_" + r for r in ("grouped", "fused")]
        before = [reg.counter(c).value for c in names]
        out = jax.jit(fn)(x, routing, valid, jnp.int32(layer or 0), wg, wu,
                          wd)
        after = [reg.counter(c).value for c in names]
        assert [a - b for a, b in zip(after, before)] == \
            [int(route == r) for r in ("grouped", "fused")]
        return out

    (fused, counts), (grouped, counts_grouped) = run("fused"), run("grouped")
    for field in counts._fields:
        assert int(getattr(counts, field)) == \
            int(getattr(counts_grouped, field)), field
    assert int(counts.streamed) == int(counts.touched)
    everyone = jnp.ones((n,), bool) if valid is None else valid
    pick = (lambda w: w) if layer is None else (lambda w: w[layer])
    want = np.asarray(_reference(
        x, routing, None if wg is None else pick(wg), pick(wu), pick(wd),
        held, everyone, limit))
    fused, grouped = (np.asarray(y, np.float32) for y in (fused, grouped))
    assert fused.shape == (n, D) and np.isfinite(fused).all()
    if spec.get("experts") == "outside":
        assert int(counts.touched) == 0 and not fused.any()
    elif spec.get("experts") == "one":
        assert int(counts.touched) == 1 and fused.any()
    if valid is not None:
        assert not fused[~np.asarray(valid)].any()
    # bf16: the reference is float32 throughout, the fused call rounds h and
    # the sum once each, the grouped branch every matmul's result
    scale = np.abs(want).max() + 1e-6
    tol = 2e-5 if dtype == jnp.float32 else 1.5e-2
    assert np.abs(fused - want).max() <= tol * scale
    assert np.abs(fused - grouped).max() <= 2 * tol * scale
    assert np.abs(fused - want).max() <= np.abs(grouped - want).max() \
        + 0.25 * tol * scale


@pytest.mark.parametrize("tiles", [1, 2])
def test_the_call_streams_touched_experts_alone(tiles, monkeypatch):
    """An expert with no pair is not read: its matrices hold NaN and the
    result is finite, with a matrix in one tile and walked in two."""
    n, count = 8, 6
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (n, D), jnp.float32)
    sizes = jnp.array([0, 3, 0, 0, 5, 0], jnp.int32)
    weights = jnp.zeros((n, count)).at[:3, 1].set(0.5).at[3:, 4].set(1.5)
    wu = jax.random.normal(keys[1], (count, D, M)) * D ** -0.5
    wd = jax.random.normal(keys[2], (count, M, D)) * M ** -0.5
    dead = (sizes == 0)[:, None, None]

    def act(u):
        return jnp.square(jax.nn.relu(u))

    want = jnp.einsum("ne,nem,emd->nd", weights, act(jnp.einsum(
        "nd,edm->nem", x, wu)), wd)
    monkeypatch.setattr(moe_experts, "_TILE_BUFFERS",
                        2 * 2 * D * (M // tiles) * 4)
    assert moe_experts.block_m(D, M, 2, 4) == M // tiles
    got = moe_experts.experts_decode(
        x, weights, sizes, None, jnp.where(dead, jnp.nan, wu),
        jnp.where(dead, jnp.nan, wd), act=act, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_route_is_the_shapes_and_the_chips(monkeypatch):
    """Off the chip a decode step keeps ``ragged_dot``; on it the call takes
    every cell's step and leaves more rows than ride free, and widths that
    are no whole rows of lanes, to the grouped branch."""
    assert moe_experts.default_route(64, 1024, 2688) == "grouped"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for n, d, m in [(64, 1024, 2688), (64, 7168, 2048), (32, 6144, 2048),
                    (16, 4096, 2048), (16, 4096, 1280), (1, 4096, 2048)]:
        assert moe_experts.default_route(n, d, m) == "fused", (n, d, m)
    assert moe_experts.FREE_ROWS == 128
    assert moe_experts.default_route(moe_experts.FREE_ROWS + 1, 1024,
                                     2688) == "grouped"
    assert moe_experts.default_route(8, 64, 32) == "grouped"
    # a tile's two buffers a matrix fit the stated budget at every cell
    for d, m, mats, tile in [(1024, 2688, 2, 896), (7168, 2048, 3, 128),
                             (6144, 2048, 3, 128), (4096, 2048, 3, 256),
                             (4096, 1280, 3, 256)]:
        assert moe_experts.block_m(d, m, mats, 2) == tile
