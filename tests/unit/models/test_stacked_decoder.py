"""``models/stack.runs_of`` and the contract between a decoder family and the
engines (``models/stack.StackedDecoder``), over the families of
``deepspeed_tpu/models/`` at the benchmark's own configurations. Nothing here
compiles: configurations, ``runs()`` and ``jax.eval_shape`` of ``init_cache``.
"""

import importlib

import jax
import pytest

import deepspeed_tpu
from benchmarks import harness
from deepspeed_tpu.models.base import row_state_keys, slot_state_keys
from deepspeed_tpu.models.stack import StackedDecoder, runs_of

pytestmark = pytest.mark.quick

# family -> (the benchmark's configuration, ``runs()`` as the family's own
# loop returned it before there was one function (PR 62's tree, at the cell's
# size), how that shape reads in ``runs_of``'s ``(kind, first of the stack,
# first of the cache leaves, count)``)
MIXED = {
    "granite_hybrid": (
        "granite-4.0-h-micro",
        (("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9),
         ("attention", 1, 1), ("mamba", 14, 9), ("attention", 2, 1),
         ("mamba", 23, 9), ("attention", 3, 1), ("mamba", 32, 4)),
        lambda kind, first, at, n: (kind, first, n)),
    "exaone_moe": (
        "k-exaone-236b-a23b",
        (("dense", "sliding_attention", 0, 0, 1),
         ("sparse", "sliding_attention", 0, 1, 2),
         ("sparse", "full_attention", 2, 0, 1),
         ("sparse", "sliding_attention", 3, 3, 1)),
        lambda kind, first, at, n: (*kind, first, at, n)),
    "sarvam_mla": (
        "sarvam-105b", (("dense", 0, 1), ("sparse", 1, 4)),
        lambda kind, first, at, n: (kind, at, n)),
    "solar_kda": (
        "solar-open2-250b", (("gqa", 0, 1), ("kda", 0, 3)),
        lambda kind, first, at, n: (kind, first, n)),
    "mimo_v2": (
        "mimo-v2.5",
        (("dense", "global", 0, 0, 1), ("sparse", "sliding", 0, 0, 4),
         ("sparse", "global", 0, 1, 1), ("sparse", "sliding", 4, 4, 1)),
        lambda kind, first, at, n: (*kind, first, at, n)),
    "gigachat35": (
        "gigachat3.5-432b-a28b",
        (("gdn_dense", 0, 0, 1), ("mla_sparse", 0, 0, 1),
         ("gdn_sparse", 0, 1, 3)),
        lambda kind, first, at, n: (kind, first, at, n)),
    # no parent loop: the family was written against ``runs_of`` (PR 65).
    # Eleven runs of ONE layer, an expert layer numbered in its stack and
    # among the layers that hold no leaf
    "nemotron_h": (
        "nemotron-3-super-120b-a12b",
        tuple((kind, i, i, 1) for kind, i in (
            ("mamba", 0), ("moe", 0), ("mamba", 1), ("moe", 1), ("mamba", 2),
            ("moe", 2), ("mamba", 3), ("moe", 3), ("mamba", 4),
            ("attention", 0), ("moe", 4))),
        lambda kind, first, at, n: (kind, first, at, n)),
}
ALL = dict({name: cell for name, (cell, _, _) in MIXED.items()},
           longcat_flash="longcat-flash-chat", evabyte="evabyte")


def _model(family: str):
    build = importlib.import_module(f"benchmarks.families.{family}")
    return build.build_model(
        harness.load_json("configs", ALL[family] + ".json"), {})


@pytest.mark.parametrize("family", list(MIXED))
def test_runs_of_gives_the_runs_the_family_walked(family):
    _, parent, as_parent = MIXED[family]
    model = _model(family)
    runs = runs_of(model.layer_kinds(), model.kinds)
    assert model.runs() == runs
    assert tuple(as_parent(*run) for run in runs) == parent


@pytest.mark.parametrize("family", list(MIXED) + ["longcat_flash"])
def test_a_run_starts_where_its_stack_and_its_leaves_stand(family):
    model = _model(family)
    kinds, seen = model.layer_kinds(), 0
    assert len(kinds) == model.config.num_layers
    for kind, first, at, count in model.runs():
        stack, leaves = model.kinds[kind]
        assert set(kinds[seen:seen + count]) == {kind}
        assert first == sum(model.kinds[k][0] == stack for k in kinds[:seen])
        assert at == sum(model.kinds[k][1] == leaves for k in kinds[:seen])
        assert stack in model.stacks
        assert set(leaves) <= set(model.slot_state_keys)
        seen += count
    assert seen == len(kinds)


def test_runs_of_numbers_shared_stacks_and_shared_leaves_together():
    kinds = {"a": ("s", ("x",)), "b": ("s", ("y",)), "c": ("t", ("x",))}
    assert runs_of("aabcca", kinds) == (
        ("a", 0, 0, 2), ("b", 2, 0, 1), ("c", 0, 2, 2), ("a", 3, 4, 1))
    assert runs_of((), kinds) == ()


@pytest.mark.parametrize("family", list(ALL))
def test_a_family_states_the_leaves_of_its_cache(family):
    model = _model(family)
    assert isinstance(model, StackedDecoder)
    cache = jax.eval_shape(lambda: model.init_cache(2, 4096))
    keys = model.slot_state_keys
    assert set(keys) == set(cache) - {"index"}
    assert slot_state_keys(model) == keys
    assert row_state_keys(model) == model.row_state_keys
    for name in ("row_state_keys", "window_state_keys", "summary_state_keys",
                 "restart_window_keys"):
        assert set(getattr(model, name)) <= set(keys), name
    # token rows are what ``max_len`` sizes, and nothing else is
    longer = jax.eval_shape(lambda: model.init_cache(2, 8192))
    grown = {k for k in keys if longer[k].shape != cache[k].shape}
    assert grown == set(model.row_state_keys) | set(model.summary_state_keys)
    assert bool(model.summary_state_keys) == bool(model.restart_window_keys)
    assert callable(model.record_step_counters)


def test_an_older_jax_is_refused_by_name():
    deepspeed_tpu.require_jax(jax.__version__)
    deepspeed_tpu.require_jax("0.10.2.dev20260101")
    deepspeed_tpu.require_jax("1.0.0")
    assert issubclass(deepspeed_tpu.UnsupportedJaxError, ImportError)
    for old in ("0.8.2", "0.6.0", "0.4.35"):
        with pytest.raises(deepspeed_tpu.UnsupportedJaxError, match=old):
            deepspeed_tpu.require_jax(old)
