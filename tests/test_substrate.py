"""Substrate tests: data pipeline, checkpointing, optimizers, HLO analysis,
and the end-to-end train step (single device)."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointing
from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCHS, get_config
from repro.data.pipeline import make_pipeline
from repro.core.exchange import null_exchange_state
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.steps import make_train_step
from repro.models.model import build
from repro.optim import optimizers as opt

KEY = jax.random.PRNGKey(0)


def test_pipeline_deterministic_and_restartable():
    cfg = get_config("tinyllama-1.1b").reduced()
    shape = ShapeConfig("t", 32, 4, "train")
    p1 = make_pipeline(cfg, shape, seed=7)
    b1 = [next(p1) for _ in range(3)]
    p2 = make_pipeline(cfg, shape, seed=7)
    p2.restore({"step": 2, "seed": 7})
    b2 = next(p2)
    np.testing.assert_array_equal(np.asarray(b1[2]["tokens"]), np.asarray(b2["tokens"]))
    # labels are inputs shifted by one
    np.testing.assert_array_equal(
        np.asarray(b1[0]["tokens"])[:, 1:], np.asarray(b1[0]["labels"])[:, :-1]
    )


def test_pipeline_learnable_structure():
    """The synthetic stream has predictable structure (not uniform noise)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    shape = ShapeConfig("t", 128, 8, "train")
    batch = next(make_pipeline(cfg, shape, seed=0))
    toks = np.asarray(batch["tokens"])
    V = cfg.vocab_size
    det = (toks[:, 1:-1] * 31 + toks[:, :-2] * 17 + 7) % V
    match = (det == toks[:, 2:]).mean()
    assert match > 0.6, match  # ~85% deterministic transitions


def test_checkpoint_roundtrip():
    cfg = get_config("gemma-2b").reduced()
    model = build(cfg)
    params = model.init(KEY)
    ocfg = opt.OptimizerConfig(name="extra_adam")
    state = opt.init_state(ocfg, params)
    with tempfile.TemporaryDirectory() as d:
        checkpointing.save(d, 3, {"params": params, "opt_state": state})
        assert checkpointing.latest_step(d) == 3
        step, trees = checkpointing.restore(
            d, {"params": params, "opt_state": state}
        )
    assert step == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(trees["params"]),
        jax.tree_util.tree_leaves(params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["adam", "extra_adam", "optimistic_adam"])
def test_train_step_reduces_loss(name):
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build(cfg)
    params = model.init(KEY)
    ocfg = opt.OptimizerConfig(name=name, lr=3e-3)
    state = opt.init_state(ocfg, params)
    step = jax.jit(make_train_step(model, ocfg))
    ex_state = null_exchange_state()
    shape = ShapeConfig("t", 64, 8, "train")
    pipe = make_pipeline(cfg, shape, seed=1)
    losses = []
    batch = next(pipe)  # single repeated batch: loss must drop fast
    for i in range(30):
        params, state, ex_state, m = step(
            params, state, ex_state, batch, jax.random.fold_in(KEY, i)
        )
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (name, losses[0], losses[-1])


def test_hlo_analysis_loop_multiplier():
    hlo = """
HloModule test

%cond.1 (arg: (s32[], f32[8])) -> pred[] {
  %gte = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(10)
  ROOT %cmp = pred[] compare(%gte, %c), direction=LT
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %t = (s32[], f32[8]) tuple(%iv, %ar)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %init = (s32[], f32[8]) tuple(%zero, %p)
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  %ag = f32[32]{0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
    r = analyze_collectives(hlo)
    # all-reduce inside the x10 loop: 8 floats * 4 bytes * 10 = 320
    assert r["payload_bytes_by_kind"]["all-reduce"] == 320.0
    assert r["count_by_kind"]["all-reduce"] == 10.0
    # all-gather outside the loop: 32 floats * 4B = 128
    assert r["payload_bytes_by_kind"]["all-gather"] == 128.0
    # wire estimates: AR 2*(3/4)*320 = 480; AG (3/4)*128 = 96
    assert abs(r["wire_bytes_by_kind"]["all-reduce"] - 480.0) < 1e-6
    assert abs(r["wire_bytes_by_kind"]["all-gather"] - 96.0) < 1e-6


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_full_configs_keep_their_dtype_and_reduced_is_float32(arch):
    # the launchers run a config's own dtype; only the CPU smoke variant
    # pins float32
    cfg = get_config(arch)
    assert cfg.dtype == "bfloat16"
    assert cfg.reduced().dtype == "float32"


def test_compilation_cache_dir_follows_env_else_fixed(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache.cache_dir() == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # with the variable set jax reads it itself: the directory is the
    # variable's and nothing is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))

    def refuse(path):
        raise AssertionError(f"cache dir set in code: {path}")

    monkeypatch.setattr(compilation_cache, "set_cache_dir", refuse)
    assert cache.enable_compilation_cache() == str(tmp_path / "c")
    assert os.path.isdir(tmp_path / "c")
