"""Model-scale adaptive Q-GenX optimizer + the sync_every local-update regime.

Pins the method-engine contracts:

* the model-scale optimizer (:mod:`repro.optim.qgenx`) runs the SAME
  adaptive step-size rule AND the same recursion algebra
  (:mod:`repro.core.methods`) as the toy VI loop — literally the same
  functions, and bit-identical trajectories on the same oracle sequence
  for EVERY method (de and optda; anchored at X_1 = 0, where the two
  recursions coincide exactly);
* ``--method optda`` pays exactly ONE oracle call per step (counted at
  trace time — each counted call is one forward+backward in the jaxpr)
  and carries the exchanged half-step feedback in the ``prev_half``
  state slot; ``method=de`` keeps the 4-slot state pytree unchanged;
* ``ExchangeConfig.sync_every`` gates the exchange: ``sync_every=1`` is
  byte-identical to the PR 2 path (params + wire_bytes, no cond in the
  jaxpr), K>1 moves bytes only on sync steps, with the trace-time
  recorder agreeing with the metric (8-device version in
  tests/_multidev_sync_exchange.py via test_multidevice.py);
* ``ExchangeConfig.recenter_every`` re-centers the drifted iterates
  through the compressor on schedule, with the bytes counted by the same
  metric/recorder (8-device version in tests/_multidev_recenter.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.exchange as exchange_mod
import repro.core.extragradient as eg
from repro.configs.registry import get_config
from repro.core.exchange import ExchangeConfig, make_exchange
from repro.core.quantization import QuantConfig
from repro.launch.steps import make_train_step
from repro.models.model import build
from repro.optim import optimizers as opt
from repro.optim import qgenx as qgenx_opt

KEY = jax.random.PRNGKey(7)


def _one_dev_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _reduced_model():
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    return build(cfg)


def _batch(key, batch=4, seq=16, vocab=256):
    toks = jax.random.randint(key, (batch, seq), 0, vocab)
    return {"tokens": toks, "labels": toks}


# ---------------------------------------------------------------------------
# The gamma rule is shared, not copied
# ---------------------------------------------------------------------------


def test_adaptive_gamma_is_the_same_function():
    """optim.qgenx calls core.extragradient.adaptive_gamma itself — the
    two implementations cannot drift apart."""
    assert qgenx_opt.adaptive_gamma is eg.adaptive_gamma
    assert eg._gamma is eg.adaptive_gamma  # toy loop alias


def test_adaptive_gamma_values():
    # gamma_1 = scale * K (sum_sq = 0); halves when 1 + sum_sq quadruples
    assert float(eg.adaptive_gamma(jnp.float32(0.0), 4, 1.0)) == 4.0
    g1 = float(eg.adaptive_gamma(jnp.float32(3.0), 8, 0.5))
    assert np.isclose(g1, 0.5 * 8 / 2.0)


def test_gamma_rule_bit_identical_to_toy_loop():
    """Drive the toy VI loop and the model-scale optimizer on the SAME
    oracle sequence (K=1, no compression, X_1 = 0 — where the toy's
    origin-anchored recursion and the optimizer's X_1-anchored recursion
    coincide): iterates AND the adaptive gamma sequence must be
    bit-identical."""
    d, T, scale = 64, 12, 0.37
    x0 = jnp.zeros((d,), jnp.float32)

    # elementwise oracle (no reductions -> bit-stable under the toy's vmap)
    def oracle(z, k):
        return 0.8 * z + 0.3 * jax.random.normal(k, z.shape, jnp.float32)

    toy_cfg = eg.QGenXConfig(variant="de", num_workers=1, gamma_scale=scale)
    toy = eg.qgenx_init(x0, toy_cfg)

    opt_cfg = opt.OptimizerConfig(name="qgenx", gamma_scale=scale,
                                  grad_clip=0.0)
    params = {"w": x0}
    st = opt.init_state(opt_cfg, params)
    assert isinstance(st, qgenx_opt.QGenXOptState)

    keys = jax.random.split(KEY, T)
    for t in range(T):
        toy = eg.qgenx_step(toy, oracle, keys[t], toy_cfg)

        # replicate the toy's exact key discipline (5-way split, per-worker
        # oracle keys) so both sides see the same oracle draws
        _, _, k_o1, k_o2, _ = jax.random.split(keys[t], 5)
        v1 = oracle(params["w"], jax.random.split(k_o1, 1)[0])
        half = qgenx_opt.extrapolate(opt_cfg, params, st, {"w": v1}, 1)
        v2 = oracle(half["w"], jax.random.split(k_o2, 1)[0])
        sq = qgenx_opt.local_sq_diff({"w": v1}, {"w": v2})
        params, st = qgenx_opt.commit(opt_cfg, params, st, {"w": v2}, sq, 1)

        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      np.asarray(toy.x)), t
        np.testing.assert_array_equal(np.asarray(st.sum_sq),
                                      np.asarray(toy.sum_sq))
        # same sufficient statistic + same function = same gamma, bitwise
        np.testing.assert_array_equal(
            np.asarray(eg.adaptive_gamma(st.sum_sq, 1, scale)),
            np.asarray(eg.adaptive_gamma(toy.sum_sq, 1, scale)),
        )


def test_optda_bit_identical_to_toy_loop():
    """The one-call optimistic schedule: drive the toy optda recursion and
    the model-scale optimizer on the SAME oracle sequence (K=1, no
    compression, X_1 = 0) — iterates, sum_sq and the carried prev_half
    must be bit-identical."""
    d, T, scale = 64, 12, 0.37
    x0 = jnp.zeros((d,), jnp.float32)

    def oracle(z, k):
        return 0.8 * z + 0.3 * jax.random.normal(k, z.shape, jnp.float32)

    toy_cfg = eg.QGenXConfig(variant="optda", num_workers=1, gamma_scale=scale)
    toy = eg.qgenx_init(x0, toy_cfg)

    opt_cfg = opt.OptimizerConfig(name="qgenx", method="optda",
                                  gamma_scale=scale, grad_clip=0.0)
    params = {"w": x0}
    st = opt.init_state(opt_cfg, params)
    assert st.prev_half is not None  # the optda slot exists...
    np.testing.assert_array_equal(np.asarray(st.prev_half["w"]),
                                  np.zeros((d,), np.float32))

    keys = jax.random.split(KEY, T)
    for t in range(T):
        toy = eg.qgenx_step(toy, oracle, keys[t], toy_cfg)

        # same key discipline as the toy (5-way split, per-worker oracle
        # keys); optda makes NO fresh call at X_t — it reuses prev_half
        _, _, _, k_o2, _ = jax.random.split(keys[t], 5)
        v1 = st.prev_half
        half = qgenx_opt.extrapolate(opt_cfg, params, st, v1, 1)
        v2 = oracle(half["w"], jax.random.split(k_o2, 1)[0])
        sq = qgenx_opt.local_sq_diff(v1, {"w": v2})
        params, st = qgenx_opt.commit(opt_cfg, params, st, {"w": v2}, sq, 1,
                                      prev_half={"w": v2})

        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      np.asarray(toy.x)), t
        np.testing.assert_array_equal(np.asarray(st.sum_sq),
                                      np.asarray(toy.sum_sq))
        np.testing.assert_array_equal(np.asarray(st.prev_half["w"]),
                                      np.asarray(toy.prev_half[0]))


def test_oracle_calls_per_step_match_method(monkeypatch):
    """Acceptance: --method optda traces exactly ONE oracle evaluation per
    train step, de exactly two (each counted call is one forward+backward
    pair embedded in the jaxpr — counted while make_jaxpr traces)."""
    from repro.core.methods import get_method
    from repro.launch import steps as steps_mod

    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1))
    from repro.core.exchange import null_exchange_state

    counts = {}
    real_make_loss_fn = steps_mod.make_loss_fn
    jaxpr_sizes = {}
    for method in ("de", "optda"):
        calls = []

        def counting_make_loss_fn(m, _calls=calls):
            lf = real_make_loss_fn(m)

            def counted(p, b):
                _calls.append(1)
                return lf(p, b)

            return counted

        monkeypatch.setattr(steps_mod, "make_loss_fn", counting_make_loss_fn)
        opt_cfg = opt.OptimizerConfig(name="qgenx", method=method,
                                      gamma_scale=0.02)
        state = opt.init_state(opt_cfg, params)
        step = steps_mod.make_train_step(model, opt_cfg)
        jaxpr = jax.make_jaxpr(step)(params, state, null_exchange_state(),
                                     batch, KEY)
        counts[method] = len(calls)
        jaxpr_sizes[method] = len(jaxpr.jaxpr.eqns)
    assert counts == {"de": get_method("de").oracle_calls,
                      "optda": get_method("optda").oracle_calls}, counts
    assert counts["optda"] == 1
    # the saved oracle call is visible in the jaxpr itself
    assert jaxpr_sizes["optda"] < jaxpr_sizes["de"], jaxpr_sizes


def test_optda_trains_via_make_train_step():
    """--method optda runs through the production train step, reduces the
    loss, and carries nonzero prev_half feedback across steps."""
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="qgenx", method="optda",
                                  gamma_scale=0.02)
    state = opt.init_state(opt_cfg, params)
    step = jax.jit(make_train_step(model, opt_cfg))
    from repro.core.exchange import null_exchange_state

    ex_state = null_exchange_state()
    batch = _batch(jax.random.PRNGKey(1))
    losses = []
    for t in range(8):
        params, state, ex_state, metrics = step(
            params, state, ex_state, batch, jax.random.fold_in(KEY, t)
        )
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert float(state.sum_sq) > 0.0
    ph_norm = sum(float(jnp.sum(jnp.abs(l)))
                  for l in jax.tree_util.tree_leaves(state.prev_half))
    assert ph_norm > 0.0  # the carried feedback is live


def test_de_state_pytree_unchanged_by_method_engine():
    """method=de leaves prev_half=None — the de state pytree has the same
    structure as before the engine existed (checkpoints stay loadable)."""
    params = {"a": jnp.ones((8,), jnp.float32)}
    st_de = opt.init_state(opt.OptimizerConfig(name="qgenx"), params)
    assert st_de.prev_half is None
    leaves = jax.tree_util.tree_leaves(st_de)
    assert len(leaves) == 3 + 1  # anchor, y, sum_sq, count — no 5th slot
    st_opt = opt.init_state(
        opt.OptimizerConfig(name="qgenx", method="optda"), params
    )
    assert len(jax.tree_util.tree_leaves(st_opt)) == 5


def test_unknown_method_rejected():
    with pytest.raises(ValueError):
        opt.init_state(opt.OptimizerConfig(name="qgenx", method="nope"),
                       {"a": jnp.ones((2,))})
    from repro.core.methods import get_method
    with pytest.raises(ValueError):
        get_method("nope")


def test_qgenx_state_shapes_and_anchor_copy():
    params = {"a": jnp.ones((8,), jnp.float32), "b": jnp.zeros((2, 3))}
    cfg = opt.OptimizerConfig(name="qgenx")
    st = opt.init_state(cfg, params)
    assert jax.tree_util.tree_structure(st.y) == jax.tree_util.tree_structure(params)
    assert float(st.sum_sq) == 0.0 and int(st.count) == 0
    # the anchor is a fresh buffer (donation-safe), not an alias of params
    assert st.anchor["a"] is not params["a"]
    np.testing.assert_array_equal(np.asarray(st.anchor["a"]),
                                  np.asarray(params["a"]))


# ---------------------------------------------------------------------------
# qgenx through make_train_step
# ---------------------------------------------------------------------------


def test_qgenx_trains_via_make_train_step():
    """Acceptance: --optimizer qgenx runs through the production train
    step and reduces the loss (1 device, no exchange)."""
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="qgenx", gamma_scale=0.02)
    state = opt.init_state(opt_cfg, params)
    step = jax.jit(make_train_step(model, opt_cfg))
    from repro.core.exchange import null_exchange_state

    ex_state = null_exchange_state()
    batch = _batch(jax.random.PRNGKey(1))
    losses = []
    for t in range(6):
        params, state, ex_state, metrics = step(
            params, state, ex_state, batch, jax.random.fold_in(KEY, t)
        )
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert float(state.sum_sq) > 0.0  # the adaptive statistic accumulated
    assert int(state.count) == 6
    assert float(metrics["param_drift"]) == 0.0  # no exchange, no regime


def test_qgenx_trains_with_compressed_exchange_1dev():
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="qgenx", gamma_scale=0.02)
    state = opt.init_state(opt_cfg, params)
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=15, bucket_size=256),
        mode="gather", axis_name="data",
    ))
    mesh = _one_dev_mesh()
    step = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh))
    ex_state = ex.init_state()
    batch = _batch(jax.random.PRNGKey(1))
    losses = []
    with mesh:
        for t in range(5):
            params, state, ex_state, metrics = step(
                params, state, ex_state, batch, jax.random.fold_in(KEY, t)
            )
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(ex_state.step) == 10  # 2 exchanges per extragradient step
    n = sum(l.size for l in jax.tree_util.tree_leaves(params))
    assert float(metrics["wire_bytes"]) == 2 * ex.wire_bytes(n, 1)


# ---------------------------------------------------------------------------
# sync_every: gating, parity at K=1, wire accounting, drift
# ---------------------------------------------------------------------------


def test_sync_every_validation():
    with pytest.raises(ValueError):
        ExchangeConfig(sync_every=0)
    with pytest.raises(ValueError):
        ExchangeConfig(drift_probe=0)


def _quant8():
    return QuantConfig(num_levels=15, bucket_size=256)


def _run_steps(ex_cfg, n_steps, opt_name="extra_adam"):
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name=opt_name, lr=1e-3, gamma_scale=0.02)
    state = opt.init_state(opt_cfg, params)
    ex = make_exchange(ex_cfg)
    mesh = _one_dev_mesh()
    step = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh))
    # placed where the step returns its state: one type, one trace
    params, state, ex_state = jax.device_put(
        (params, state, ex.init_state()), NamedSharding(mesh, P()))
    batch = _batch(jax.random.PRNGKey(1))
    out = []
    with mesh:
        for t in range(n_steps):
            params, state, ex_state, metrics = step(
                params, state, ex_state, batch, jax.random.fold_in(KEY, t)
            )
            out.append((params, {k: float(v) for k, v in metrics.items()}))
    return out, ex, ex_state


def test_sync_every_1_reproduces_pr2_path():
    """The regression the satellite asks for: a config with sync_every=1
    must train byte-identically (params AND wire_bytes) to the PR 2
    construction that predates the field."""
    base = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                          mode="gather", axis_name="data")
    sync1 = dataclasses.replace(base, sync_every=1)
    out_a, _, _ = _run_steps(base, 2)
    out_b, _, _ = _run_steps(sync1, 2)
    for (pa, ma), (pb, mb) in zip(out_a, out_b):
        assert ma == mb
        for la, lb in zip(jax.tree_util.tree_leaves(pa),
                          jax.tree_util.tree_leaves(pb)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_sync_every_1_has_no_cond_in_jaxpr():
    """Trace-level evidence: the gate only exists when K>1 (sync_every=1
    pays zero overhead), and DOES exist when K>1."""
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="extra_adam", lr=1e-3)
    state = opt.init_state(opt_cfg, params)
    mesh = _one_dev_mesh()
    batch = _batch(jax.random.PRNGKey(1))
    jaxprs = {}
    for k in (1, 3):
        cfg = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                             mode="gather", axis_name="data", sync_every=k)
        ex = make_exchange(cfg)
        step = make_train_step(model, opt_cfg, exchange=ex, mesh=mesh)
        jaxprs[k] = str(jax.make_jaxpr(step)(
            params, state, ex.init_state(), batch, KEY
        ))
    assert " cond" not in jaxprs[1]
    assert " cond" in jaxprs[3]


def test_sync_every_wire_only_on_sync_steps_and_recorder_agrees():
    """1-device version of the 8-dev payload: wire_bytes = 0 off sync
    steps; on the sync step it equals 2 grad exchanges + the drift probe,
    and the trace-time recorder sees exactly those operands."""
    cfg = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                         mode="gather", axis_name="data", sync_every=3)
    exchange_mod.wire_trace_start()
    out, ex, ex_state = _run_steps(cfg, 4)
    rec = exchange_mod.wire_trace_stop()

    n = sum(l.size for l in jax.tree_util.tree_leaves(out[0][0]))
    per_call = ex.wire_bytes(n, 1)
    probe = 4.0 * min(cfg.drift_probe, n)
    want_sync = 2 * per_call + probe

    wires = [m["wire_bytes"] for _, m in out]
    drifts = [m["param_drift"] for _, m in out]
    assert wires[0] == wires[1] == wires[3] == 0.0, wires
    assert wires[2] == want_sync, (wires, want_sync)
    # one trace; the sync branch's operands recorded exactly once
    assert sum(b for _, b in rec) == want_sync, rec
    assert any(name == "drift_probe" for name, _ in rec)
    # 1 device: the local params ARE the mean — drift identically zero
    assert drifts == [0.0] * 4, drifts
    # exchange state advanced only on the sync step (2 pmean calls)
    assert int(ex_state.step) == 2


def test_recenter_validation():
    with pytest.raises(ValueError):
        ExchangeConfig(recenter_every=-1)


def test_recenter_moves_bytes_only_on_recenter_steps():
    """Compressed parameter re-centering: wire_bytes gains exactly one
    params-shaped exchange on re-center steps (the trace recorder agrees),
    and nothing anywhere else."""
    base = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                          mode="gather", axis_name="data", sync_every=3)
    rc = dataclasses.replace(base, recenter_every=3)
    exchange_mod.wire_trace_start()
    out_rc, ex, ex_state = _run_steps(rc, 4, opt_name="qgenx")
    rec = exchange_mod.wire_trace_stop()

    n = sum(l.size for l in jax.tree_util.tree_leaves(out_rc[0][0]))
    per_call = ex.wire_bytes(n, 1)
    probe = 4.0 * min(rc.drift_probe, n)
    # sync step t=2: 2 grad exchanges + probe + 1 re-centering exchange
    want_sync = 3 * per_call + probe
    wires = [m["wire_bytes"] for _, m in out_rc]
    assert wires[0] == wires[1] == wires[3] == 0.0, wires
    assert wires[2] == want_sync, (wires, want_sync)
    assert sum(b for _, b in rec) == want_sync, rec
    # 3 exchange-state bumps on the sync step (2 grads + 1 re-center)
    assert int(ex_state.step) == 3


def test_recenter_changes_params_on_schedule_only():
    """The re-centered params differ from the no-recenter run exactly
    from the first re-center step on (1 device: the compressed pmean is a
    quantize-dequantize pass, so the effect is visible immediately)."""
    base = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                          mode="gather", axis_name="data")
    rc = dataclasses.replace(base, recenter_every=2)
    out_a, _, _ = _run_steps(base, 3, opt_name="extra_adam")
    out_b, _, _ = _run_steps(rc, 3, opt_name="extra_adam")

    def same(pa, pb):
        return all(
            np.array_equal(np.asarray(la), np.asarray(lb))
            for la, lb in zip(jax.tree_util.tree_leaves(pa),
                              jax.tree_util.tree_leaves(pb))
        )

    assert same(out_a[0][0], out_b[0][0])  # step 0: no recenter yet
    assert not same(out_a[1][0], out_b[1][0])  # step 1 recentered
    # loss stays finite through the compressed re-centering
    assert all(np.isfinite(m["loss"]) for _, m in out_b)


def test_recenter_qgenx_keeps_anchor_recursion_consistent():
    """For the qgenx optimizer the DUAL accumulator is re-centered and the
    params recomputed as anchor + gamma * Y — the recursion invariant
    X = anchor + gamma(sum_sq) * Y must hold after a re-center step."""
    from repro.core.extragradient import adaptive_gamma

    cfg = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                         mode="gather", axis_name="data", recenter_every=2)
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="qgenx", gamma_scale=0.02)
    state = opt.init_state(opt_cfg, params)
    ex = make_exchange(cfg)
    mesh = _one_dev_mesh()
    step = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh))
    ex_state = ex.init_state()
    batch = _batch(jax.random.PRNGKey(1))
    with mesh:
        for t in range(2):  # t=1 is the re-center step
            params, state, ex_state, _ = step(
                params, state, ex_state, batch, jax.random.fold_in(KEY, t)
            )
    gamma = float(adaptive_gamma(state.sum_sq, 1, opt_cfg.gamma_scale))
    for p, a, y in zip(jax.tree_util.tree_leaves(params),
                       jax.tree_util.tree_leaves(state.anchor),
                       jax.tree_util.tree_leaves(state.y)):
        np.testing.assert_allclose(np.asarray(p),
                                   np.asarray(a + gamma * y),
                                   rtol=1e-5, atol=1e-8)


def test_sync_every_reduces_total_wire_by_k():
    """~K× reduction over a window of K steps (one sync step per window)."""
    base = ExchangeConfig(compressor="qgenx", quant=_quant8(),
                          mode="gather", axis_name="data")
    k4 = dataclasses.replace(base, sync_every=4)
    out_1, _, _ = _run_steps(base, 4)
    out_4, _, _ = _run_steps(k4, 4)
    tot_1 = sum(m["wire_bytes"] for _, m in out_1)
    tot_4 = sum(m["wire_bytes"] for _, m in out_4)
    assert tot_4 > 0
    ratio = tot_1 / tot_4
    assert 3.0 < ratio <= 4.0, ratio  # probe bytes keep it just under 4x
