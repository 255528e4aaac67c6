"""The unified Exchange API (repro.core.exchange).

Covers the redesign's contracts:

* bit-exact parity of ``Exchange.pmean`` with the legacy
  ``compressed_pmean`` across the full (bits, mode, use_pallas) grid;
* the unbiasedness contract ``E[compress(v)] = v`` for every registered
  compressor of the UNBIASED tier (the contractive tier's properties live
  in tests/test_compressor_contracts.py);
* the ``use_pallas``/kernel-flag forwarding regression: a train step
  built with ``use_pallas=True`` actually routes through the fused Pallas
  kernels (the pre-redesign ``make_train_step`` dropped the flags on the
  floor, making the fused pipeline unreachable from training) —
  trace-inspect evidence;
* a QAda-scheduled Exchange running end-to-end inside ``make_train_step``
  with level updates visible in the threaded ExchangeState;
* the per-step ``wire_bytes`` metric equalling the trace-time wire
  recorder (single-device here; the 8-device assertion lives in
  tests/_multidev_train_metrics.py via test_multidevice.py).
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import repro.core.exchange as exchange_mod
from repro.core.exchange import (
    ExchangeConfig,
    ExchangeState,
    make_exchange,
    null_exchange_state,
    registered_compressors,
)
from repro.core.quantization import QuantConfig, uniform_levels

N = 3000  # not a bucket multiple — exercises padding
KEY = jax.random.PRNGKey(11)


def _one_dev_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _contract_config(name: str) -> ExchangeConfig:
    """A representative config per registered compressor."""
    if name == "qgenx":
        return ExchangeConfig(
            compressor="qgenx",
            quant=QuantConfig(num_levels=15, bucket_size=256, q_norm=math.inf),
        )
    if name == "layerwise":
        return ExchangeConfig(
            compressor="layerwise",
            quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
            layerwise_threshold=1024,
        )
    if name == "randk":
        return ExchangeConfig(compressor="randk", rand_frac=0.25)
    if name == "ef-randk":
        return ExchangeConfig(compressor="ef-randk", rand_frac=0.25)
    if name == "ef21-topk":
        return ExchangeConfig(compressor="ef21-topk", ef_topk_frac=0.25)
    return ExchangeConfig(compressor=name)


def _unbiased_compressors() -> tuple:
    """Registry entries under the unbiased contract tier — the only ones
    the E[compress(v)] = v properties apply to (the contractive tier has
    its own harness: tests/test_compressor_contracts.py)."""
    from repro.core.exchange import get_compressor

    return tuple(n for n in registered_compressors()
                 if get_compressor(n).contract == "unbiased")


# ---------------------------------------------------------------------------
# Parity with the legacy path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("mode", ["gather", "two_phase"])
@pytest.mark.parametrize("bits", [8, 4])
def test_exchange_matches_legacy_compressed_pmean(bits, mode, use_pallas):
    """Full grid: the qgenx compressor is bit-exact with the pre-Exchange
    flat path (the retired compressed_pmean wrapper == _qgenx_pmean)."""
    quant = QuantConfig(
        num_levels=5 if bits == 4 else 15, q_norm=math.inf,
        bucket_size=256, bits=bits,
    )
    mesh = _one_dev_mesh()
    x = jax.random.normal(jax.random.PRNGKey(3), (N,), jnp.float32)

    ex = make_exchange(ExchangeConfig(
        compressor="qgenx", quant=quant, mode=mode, axis_name="data",
        use_pallas=use_pallas,
    ))
    state = ex.init_state()

    @jax.jit
    def run_new(xl, key):
        def f(a, k):
            mean, _ = ex.pmean(a, state, k)
            return mean

        return shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                         check_vma=False)(xl, key)

    levels = uniform_levels(quant.num_levels)

    @jax.jit
    def run_legacy(xl, key):
        f = functools.partial(
            exchange_mod._qgenx_pmean, axis_name="data", levels=levels,
            cfg=quant, mode=mode, use_pallas=use_pallas,
        )
        return shard_map(lambda a, k: f(a, key=k), mesh=mesh,
                         in_specs=(P(), P()), out_specs=P(),
                         check_vma=False)(xl, key)

    got = np.asarray(run_new(x, KEY))
    want = np.asarray(run_legacy(x, KEY))
    assert got.shape == want.shape == (N,)
    np.testing.assert_array_equal(got, want)


def test_pmean_tree_matches_legacy_tree():
    def compressed_pmean_tree(tl, axis_name, levels, k, quant, mode):
        # pre-plan reference: naive concatenate + flat exchange (the
        # retired compressed_pmean_tree wrapper, inlined)
        leaves, treedef = jax.tree_util.tree_flatten(tl)
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves]
        )
        mean = exchange_mod._qgenx_pmean(flat, axis_name, levels, k, quant, mode)
        outs, off = [], 0
        for l in leaves:
            outs.append(mean[off: off + l.size].reshape(l.shape))
            off += l.size
        return jax.tree_util.tree_unflatten(treedef, outs)

    quant = QuantConfig(num_levels=15, bucket_size=256, q_norm=math.inf)
    mesh = _one_dev_mesh()
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32),
        "b": jax.random.normal(jax.random.PRNGKey(1), (77,), jnp.float32),
    }
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant,
                                      mode="two_phase", axis_name="data"))
    state = ex.init_state()
    levels = uniform_levels(quant.num_levels)

    @jax.jit
    def run(t, key):
        def f(tl, k):
            new, _ = ex.pmean_tree(tl, state, k)
            old = compressed_pmean_tree(tl, "data", levels, k, quant,
                                        mode="two_phase")
            return new, old

        return shard_map(f, mesh=mesh, in_specs=({"w": P(), "b": P()}, P()),
                         out_specs=({"w": P(), "b": P()},) * 2,
                         check_vma=False)(t, key)

    new, old = run(tree, KEY)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(new[k]), np.asarray(old[k]))


# ---------------------------------------------------------------------------
# Unbiasedness contract — every registered compressor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", _unbiased_compressors())
def test_compressor_unbiasedness_contract(name):
    """E[compress(v)] = v for every unbiased-tier compressor (the
    property Theorem 1 and the whole rate analysis rest on)."""
    ex = make_exchange(_contract_config(name))
    state = ex.init_state()
    d, trials = 2000, 1024
    v = jax.random.normal(jax.random.PRNGKey(0), (d,), jnp.float32)

    keys = jax.random.split(jax.random.PRNGKey(1), trials)
    outs = jax.vmap(lambda k: ex.compress(v, state, k))(keys)
    est = np.asarray(jnp.mean(outs, axis=0))
    # per-coordinate MC error scales with the compressor's variance —
    # normalize by the empirical std so the tolerance is principled
    std = np.asarray(jnp.std(outs, axis=0))
    err = np.abs(est - np.asarray(v))
    tol = 5.0 * std / math.sqrt(trials) + 1e-6
    frac_bad = float(np.mean(err > tol))
    assert frac_bad < 0.01, (name, frac_bad, err.max())


@pytest.mark.parametrize("name", _unbiased_compressors())
def test_compressor_pmean_replicated_and_unbiased_1dev(name):
    """pmean on a 1-device mesh: shape-preserving and unbiased vs x."""
    ex = make_exchange(dataclasses.replace(
        _contract_config(name), mode="gather", axis_name="data"))
    state = ex.init_state()
    mesh = _one_dev_mesh()
    x = jax.random.normal(jax.random.PRNGKey(5), (N,), jnp.float32)

    trials = 256

    @jax.jit
    def run(xl, keys):
        def f(a, ks):
            def one(_, k):
                mean, st = ex.pmean(a, state, k)
                return None, (mean, st.step)

            _, (means, steps) = jax.lax.scan(one, None, ks)
            return means, steps

        return shard_map(f, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P()), check_vma=False)(xl, keys)

    outs, steps = run(x, jax.random.split(jax.random.PRNGKey(6), trials))
    assert int(np.asarray(steps)[-1]) == 1  # state threading: 1 call counted
    est = np.asarray(jnp.mean(outs, axis=0))
    err_avg = float(np.mean(np.abs(est - np.asarray(x))))
    err_one = float(np.mean(np.abs(np.asarray(outs[0]) - np.asarray(x))))
    # unbiased => the trial-average converges to x (error shrinks ~1/sqrt(T),
    # i.e. 16x at T=256; a biased exchange would plateau at its bias)
    assert err_avg < err_one / 4.0 + 1e-4, (name, err_avg, err_one)


# ---------------------------------------------------------------------------
# Kernel-flag forwarding regression (the PR-1 fused pipeline must be
# reachable from make_train_step)
# ---------------------------------------------------------------------------


def _tiny_train_setup(ex_cfg):
    from repro.configs.registry import get_config
    from repro.launch.steps import make_train_step
    from repro.models.model import build
    from repro.optim import optimizers as opt

    cfg = get_config("tinyllama-1.1b").reduced()
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="extra_adam", lr=1e-3)
    opt_state = opt.init_state(opt_cfg, params)
    mesh = _one_dev_mesh()
    step = make_train_step(model, opt_cfg, exchange=ex_cfg, mesh=mesh)
    ex = make_exchange(ex_cfg) if ex_cfg is not None else None
    ex_state = ex.init_state() if ex is not None else null_exchange_state()
    batch = {
        "tokens": jnp.zeros((4, 16), jnp.int32),
        "labels": jnp.zeros((4, 16), jnp.int32),
    }
    return step, params, opt_state, ex_state, batch, mesh


@pytest.mark.parametrize("use_pallas", [True, False])
def test_train_step_forwards_use_pallas(use_pallas):
    """Regression for the dropped-kwargs bug (launch/steps.py pre-redesign):
    with use_pallas=True the traced train step must contain the fused
    Pallas exchange kernels; with False it must not."""
    ex_cfg = ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=15, bucket_size=256),
        mode="gather", axis_name="data", use_pallas=use_pallas,
    )
    step, params, opt_state, ex_state, batch, mesh = _tiny_train_setup(ex_cfg)
    with mesh:
        jaxpr = jax.make_jaxpr(step)(
            params, opt_state, ex_state, batch, jax.random.PRNGKey(1)
        )
    text = str(jaxpr)
    assert ("pallas_call" in text) == use_pallas, (
        "fused kernels unreachable from make_train_step"
        if use_pallas else "pallas kernels present without use_pallas"
    )


def test_train_step_pallas_executes_fused_kernels():
    """The use_pallas=True train step doesn't just trace — it runs (1-dev
    mesh; interpret mode), and its wire metric matches the recorder."""
    ex_cfg = ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=15, bucket_size=256),
        mode="gather", axis_name="data", use_pallas=True,
    )
    step, params, opt_state, ex_state, batch, mesh = _tiny_train_setup(ex_cfg)
    exchange_mod.wire_trace_start()
    with mesh:
        params, opt_state, ex_state, metrics = jax.jit(step)(
            params, opt_state, ex_state, batch, jax.random.PRNGKey(1)
        )
    rec = exchange_mod.wire_trace_stop()
    assert np.isfinite(float(metrics["loss"]))
    assert int(ex_state.step) == 2  # extra_adam: both exchanges ran
    assert rec, "no collective operands recorded — exchange did not run"
    assert sum(b for _, b in rec) == float(metrics["wire_bytes"])


# ---------------------------------------------------------------------------
# QAda-scheduled Exchange end-to-end in make_train_step
# ---------------------------------------------------------------------------


def test_qada_schedule_updates_levels_in_train_step():
    """Adaptive levels at model scale: the ExchangeState threaded through
    the train step carries QAda sufficient statistics and a refreshed
    level table (previously only reachable in the toy VI loop)."""
    quant = QuantConfig(num_levels=15, bucket_size=256)
    ex_cfg = ExchangeConfig(
        compressor="qgenx", quant=quant, mode="two_phase", axis_name="data",
        level_schedule="qada", level_update_every=2,
    )
    step, params, opt_state, ex_state, batch, mesh = _tiny_train_setup(ex_cfg)
    uniform = np.asarray(uniform_levels(quant.num_levels))
    assert np.allclose(np.asarray(ex_state.levels), uniform)

    jitted = jax.jit(step)
    with mesh:
        for i in range(2):  # 2 steps x 2 exchanges -> 2 QAda refreshes
            params, opt_state, ex_state, metrics = jitted(
                params, opt_state, ex_state, batch, jax.random.PRNGKey(i)
            )
    assert int(ex_state.step) == 4
    moved = np.asarray(ex_state.levels)
    assert moved.shape == uniform.shape
    assert not np.allclose(moved, uniform, atol=1e-4), (
        "QAda schedule produced no visible level update in ExchangeState"
    )
    # still a valid level table
    assert moved[0] == 0.0 and moved[-1] == 1.0
    assert np.all(np.diff(moved) > 0)


def test_qada_cadence_under_sync_every_counts_exchange_calls():
    """QAda x sync_every, the pinned decision (DESIGN.md §1.5): the
    histogram accumulates ONLY on sync steps (the exchanged gradients are
    the population the quantizer sees; local steps pay no collective),
    and the refresh cadence counts EXCHANGE CALLS, not optimizer steps —
    so sync_every=K stretches a refresh period K× in wall-clock."""
    quant = QuantConfig(num_levels=15, bucket_size=256)
    ex_cfg = ExchangeConfig(
        compressor="qgenx", quant=quant, mode="two_phase", axis_name="data",
        level_schedule="qada", level_update_every=2, sync_every=2,
    )
    step, params, opt_state, ex_state, batch, mesh = _tiny_train_setup(ex_cfg)
    uniform = np.asarray(uniform_levels(quant.num_levels))

    states = []
    jitted = jax.jit(step)
    with mesh:
        for i in range(4):
            params, opt_state, ex_state, _ = jitted(
                params, opt_state, ex_state, batch, jax.random.PRNGKey(i)
            )
            states.append(ex_state)

    # local steps (t=0, 2): the exchange state is untouched — no exchange,
    # no histogram accumulation, no counter bump
    assert int(states[0].step) == 0
    assert np.allclose(np.asarray(states[0].levels), uniform)
    assert float(np.sum(np.asarray(states[0].hist))) == 0.0
    assert int(states[2].step) == int(states[1].step)
    np.testing.assert_array_equal(np.asarray(states[2].hist),
                                  np.asarray(states[1].hist))
    # sync steps (t=1, 3): 2 exchange calls each; with level_update_every=2
    # the refresh fires on the 2nd call of each sync step — after 4
    # optimizer steps the table has moved (2 refreshes, cadence = calls)
    assert int(states[1].step) == 2
    assert int(states[3].step) == 4
    assert not np.allclose(np.asarray(states[3].levels), uniform, atol=1e-4)


def test_leafwise_allreduce_fallback_unbiased_and_counted():
    """The partial-manual-mesh fallback (DEQ-then-psum): same expected
    mean as the all-gather leafwise path, f32 operand recorded, and the
    analytic wire accounting says 4 B/coordinate."""
    import repro.core.exchange as exchange_mod

    quant = QuantConfig(num_levels=15, bucket_size=256)
    mk = lambda fb: make_exchange(ExchangeConfig(  # noqa: E731
        compressor="qgenx", quant=quant, mode="leafwise", axis_name="data",
        allreduce_fallback=fb,
    ))
    ex_gather, ex_fb = mk(False), mk(True)
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (8, 256),
                                   jnp.float32)}
    mesh = _one_dev_mesh()

    outs = {}
    for tag, ex in (("gather", ex_gather), ("fallback", ex_fb)):
        exchange_mod.wire_trace_start()

        @jax.jit
        def run(t, key, ex=ex):
            def f(tl, k):
                mean, st = ex.pmean_tree(tl, ex.init_state(), k)
                return mean

            return shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False)(t, key)

        outs[tag] = run(tree, KEY)
        rec = exchange_mod.wire_trace_stop()
        recorded = sum(b for _, b in rec)
        assert recorded == ex.wire_bytes_tree(tree, 1), (tag, rec)
        if tag == "fallback":
            assert any(n == "leaf_fallback" for n, _ in rec), rec
            assert recorded == 4.0 * tree["w"].size  # f32 operand, honest

    # 1 device, same key -> same quantization draw: the fallback's local
    # DEQ equals the gather path's dequantized own payload exactly
    np.testing.assert_allclose(np.asarray(outs["gather"]["w"]),
                               np.asarray(outs["fallback"]["w"]),
                               rtol=1e-6, atol=1e-7)


def test_qada_refreshes_both_layerwise_tables():
    """The layerwise compressor carries two level tables; a QAda refresh
    must move both (the low-bit table quantizes the dominant group)."""
    ex = make_exchange(ExchangeConfig(
        compressor="layerwise",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
        layerwise_threshold=1024, mode="gather", axis_name="data",
        level_schedule="qada", level_update_every=1,
    ))
    state = ex.init_state()
    mesh = _one_dev_mesh()
    x = jax.random.normal(jax.random.PRNGKey(7), (N,), jnp.float32)

    @jax.jit
    def run(xl, key):
        def f(a, k):
            _, st = ex.pmean(a, state, k)
            return st

        return shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                         check_vma=False)(xl, key)

    st = run(x, KEY)
    assert int(st.step) == 1
    assert not np.allclose(np.asarray(st.levels),
                           np.asarray(state.levels), atol=1e-4)
    assert not np.allclose(np.asarray(st.levels_lo),
                           np.asarray(state.levels_lo), atol=1e-4)


@pytest.mark.parametrize("name", ["layerwise", "randk", "ef21-topk",
                                  "ef-randk"])
def test_leafwise_without_a_leafwise_path_is_loud(name):
    """Compressors without a sharding-preserving per-leaf exchange must
    reject mode='leafwise' instead of silently flat-concatenating."""
    with pytest.raises(ValueError, match="leafwise"):
        make_exchange(dataclasses.replace(
            _contract_config(name), mode="leafwise"))


# ---------------------------------------------------------------------------
# Wire metric == trace recorder (single-device; 8-dev in test_multidevice)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,qada", [
    ("gather", False), ("two_phase", False), ("leafwise", False),
    ("two_phase", True),  # the qada hist psum is collective traffic too
])
def test_wire_metric_matches_recorder_1dev(mode, qada):
    ex_cfg = ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
        mode=mode, axis_name="data",
        level_schedule="qada" if qada else "fixed",
        level_update_every=2 if qada else 0,
    )
    step, params, opt_state, ex_state, batch, mesh = _tiny_train_setup(ex_cfg)
    exchange_mod.wire_trace_start()
    with mesh:
        out = jax.jit(step)(
            params, opt_state, ex_state, batch, jax.random.PRNGKey(0)
        )
    rec = exchange_mod.wire_trace_stop()
    assert sum(b for _, b in rec) == float(out[3]["wire_bytes"]), (mode, rec)


# ---------------------------------------------------------------------------
# Config/registry hygiene
# ---------------------------------------------------------------------------


def test_registry_has_scenario_diversity():
    names = registered_compressors()
    assert {"none", "qgenx", "randk", "layerwise",
            "ef21-topk", "ef-randk"} <= set(names)


def test_unknown_compressor_error_names_contract_tiers():
    """Satellite fix: the registry error lists every entry WITH its
    contract tier, so the caller knows what each alternative promises."""
    with pytest.raises(ValueError, match=r"'ef21-topk' \(contractive\)"):
        make_exchange(ExchangeConfig(compressor="nope"))
    with pytest.raises(ValueError, match=r"'qgenx' \(unbiased\)"):
        make_exchange(ExchangeConfig(compressor="nope"))


def test_ef_rejects_recenter_and_mask():
    """EF + recenter is rejected at build time; EF + participation mask
    at trace time — both name the contractive contract."""
    with pytest.raises(ValueError, match="contractive contract"):
        make_exchange(ExchangeConfig(compressor="ef21-topk",
                                     recenter_every=4))
    ex = make_exchange(ExchangeConfig(compressor="ef-randk"))
    st = ex.init_state()
    with pytest.raises(ValueError, match="partial-participation"):
        ex.pmean(jnp.zeros((8,)), st, jax.random.PRNGKey(0),
                 mask=jnp.float32(1.0))


def test_unknown_compressor_is_loud():
    with pytest.raises(ValueError, match="unknown compressor"):
        make_exchange(ExchangeConfig(compressor="nope"))


def test_qgenx_requires_quant():
    with pytest.raises(ValueError, match="requires ExchangeConfig.quant"):
        make_exchange(ExchangeConfig(compressor="qgenx", quant=None))


def test_qada_requires_update_period():
    with pytest.raises(ValueError, match="level_update_every"):
        ExchangeConfig(level_schedule="qada")


def test_exchange_state_is_pytree():
    st = null_exchange_state()
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 6  # levels, levels_lo, hist, step, error, pending
    st2 = jax.tree_util.tree_map(lambda x: x, st)
    assert isinstance(st2, ExchangeState)


def test_ef_error_memory_sizing():
    """init_state sizes the error slot from (template, num_workers) for
    contractive compressors; unbiased ones keep the [1] placeholder."""
    tree = {"a": jnp.zeros((4, 6)), "b": jnp.zeros((10,))}
    ex = make_exchange(ExchangeConfig(compressor="ef21-topk"))
    st = ex.init_state(template=tree, num_workers=8)
    assert st.error.shape == (8, 34)
    assert ex.init_state().error.shape == (1,)  # placeholder without args
    exq = make_exchange(_contract_config("randk"))
    assert exq.init_state(template=tree, num_workers=8).error.shape == (1,)


def test_train_cli_one_device_compression_builds_exchange(capsys,
                                                           monkeypatch,
                                                           tmp_path):
    """--compression int8 on ONE device is honoured: the launcher builds
    the quantized exchange over the 1-device mesh (not a silent fp32
    run), routes it through the Pallas kernels, and every step line
    reports wire > 0."""
    from repro.launch import train

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.device_count() == 1
    loss = train.main([
        "--arch", "tinyllama-1.1b", "--reduced", "--steps", "2",
        "--batch", "2", "--seq", "16", "--compression", "int8",
        "--use-pallas",
    ])
    out = capsys.readouterr().out
    assert np.isfinite(loss)
    assert "[train] exchange: compressor=qgenx" in out
    assert "use_pallas=True" in out
    wires = [float(w) for w in re.findall(r"wire=(\S+)B", out)]
    assert len(wires) == 2 and all(w > 0 for w in wires), out
