"""Theorems 3/4 + Fig. 4 benchmark: convergence-rate table.

Emits the measured restricted-gap decay across T for:
  * absolute noise (Thm 3: O(1/sqrt(TK)))  — rate exponent fit
  * relative noise + cocoercivity (Thm 4: O(1/(TK))) — rate exponent fit
  * worker scaling K in {1, 4, 16} at fixed T
  * Q-GenX vs QSGDA on the bilinear problem (Fig. 4)
  * quantized (UQ8/UQ4) vs full-precision Q-GenX (rate preservation +
    bits-per-iteration savings)
  * de vs optda at EQUAL ORACLE BUDGET (method engine, core/methods.py):
    the one-call optimistic schedule takes 2x the iterations for the
    same oracle/wire spend — toy VI loop and model-scale trainer rows
  * MODEL SCALE: the qgenx optimizer (adaptive gamma rule through
    make_train_step) vs extra_adam/adam on a reduced LM, and the
    sync_every local-update wire/quality trade-off (K in {1, 4, 16},
    8 forced host devices, subprocess)
  * drift vs wire across compressed parameter re-centering cadences
    (recenter_every in {0, 8, 4} on top of sync_every=4, 8 host devices)
  * ERROR FEEDBACK at EQUAL WIRE BUDGET: contractive ef21-topk/ef-randk
    vs unbiased randk at the same keep fraction (identical 8k-byte
    pricing per exchange) — toy VI row plus a model-scale trainer row
    (8 forced host devices, subprocess)
"""

import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.extragradient import QGenXConfig, qgenx_run, qsgda_run
from repro.core.quantization import QuantConfig
from repro.core.vi import (
    absolute_noise_oracle,
    bilinear_saddle,
    cocoercive_quadratic,
    relative_noise_oracle,
    restricted_gap,
)

KEY = jax.random.PRNGKey(0)


def _fit_rate(Ts, gaps):
    """Slope of log(gap) vs log(T) — the empirical rate exponent."""
    lt = np.log(np.asarray(Ts, float))
    lg = np.log(np.maximum(np.asarray(gaps, float), 1e-12))
    return float(np.polyfit(lt, lg, 1)[0])


def run():
    # --- Thm 3: absolute noise rate ------------------------------------
    vi = bilinear_saddle(d=16, seed=0)
    oracle = absolute_noise_oracle(vi, sigma=0.5)
    cfg = QGenXConfig(variant="de", num_workers=4)
    Ts = [256, 1024, 4096]
    gaps = []
    t0 = time.perf_counter()
    for T in Ts:
        x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
        st = qgenx_run(x0, oracle, cfg, KEY, T)
        gaps.append(restricted_gap(vi, st.x_avg))
    us = (time.perf_counter() - t0) * 1e6 / sum(Ts)
    slope = _fit_rate(Ts, gaps)
    emit("thm3_absolute_noise_rate", us,
         f"gaps={['%.4f' % g for g in gaps]};slope={slope:.2f};target=-0.5")

    # --- Thm 4: relative noise fast rate --------------------------------
    vi = cocoercive_quadratic(d=32, seed=1)
    oracle = relative_noise_oracle(vi, c=0.5)
    gaps = []
    t0 = time.perf_counter()
    for T in Ts:
        x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
        st = qgenx_run(x0, oracle, cfg, KEY, T)
        gaps.append(restricted_gap(vi, st.x_avg))
    us = (time.perf_counter() - t0) * 1e6 / sum(Ts)
    slope = _fit_rate(Ts, gaps)
    emit("thm4_relative_noise_rate", us,
         f"gaps={['%.4f' % g for g in gaps]};slope={slope:.2f};target=-1.0")

    # --- K scaling -------------------------------------------------------
    vi = bilinear_saddle(d=16, seed=2)
    oracle = absolute_noise_oracle(vi, sigma=1.0)
    T = 4096
    row = []
    for K in (1, 4, 16):
        x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
        st = qgenx_run(x0, oracle, QGenXConfig(variant="de", num_workers=K), KEY, T)
        row.append((K, restricted_gap(vi, st.x_avg)))
    emit("thm3_worker_scaling", 0.0,
         ";".join(f"K{k}={g:.4f}" for k, g in row))

    # --- Fig. 4: Q-GenX vs QSGDA ----------------------------------------
    vi = bilinear_saddle(d=16, seed=6)
    oracle = absolute_noise_oracle(vi, sigma=0.1)
    x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
    st = qgenx_run(x0, oracle, QGenXConfig(variant="de", num_workers=4), KEY, 2048)
    g_qgenx = restricted_gap(vi, st.x_avg)
    _, x_avg = qsgda_run(x0, oracle, KEY, 2048, num_workers=4, lr=0.05)
    g_qsgda = restricted_gap(vi, x_avg)
    emit("fig4_qgenx_vs_qsgda", 0.0,
         f"qgenx={g_qgenx:.4f};qsgda={g_qsgda:.4f};qgenx_wins={g_qgenx < g_qsgda}")

    # --- compression preserves the rate ----------------------------------
    vi = bilinear_saddle(d=32, seed=4)
    oracle = absolute_noise_oracle(vi, sigma=0.5)
    x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
    results = {}
    for tag, quant in (
        ("fp32", None),
        ("uq8", QuantConfig(num_levels=15, bits=8, bucket_size=64, q_norm=math.inf)),
        ("uq4", QuantConfig(num_levels=5, bits=4, bucket_size=64, q_norm=math.inf)),
    ):
        cfgq = QGenXConfig(variant="de", num_workers=4, quant=quant)
        st = qgenx_run(x0, oracle, cfgq, KEY, 2048)
        results[tag] = (restricted_gap(vi, st.x_avg), float(st.bits_sent))
    derived = ";".join(
        f"{t}_gap={g:.4f};{t}_bits={b:.2e}" for t, (g, b) in results.items()
    )
    emit("qgenx_compression_rate_preservation", 0.0, derived)

    # --- compressor registry: the same loop under other unbiased policies
    from repro.core.exchange import ExchangeConfig

    results = {}
    for tag, exc in (
        ("randk50", ExchangeConfig(compressor="randk", rand_frac=0.5)),
        ("layerwise", ExchangeConfig(
            compressor="layerwise",
            quant=QuantConfig(num_levels=5, bits=4, bucket_size=64,
                              q_norm=math.inf),
            layerwise_threshold=16,
        )),
    ):
        cfgq = QGenXConfig(variant="de", num_workers=4, exchange=exc)
        st = qgenx_run(x0, oracle, cfgq, KEY, 2048)
        results[tag] = (restricted_gap(vi, st.x_avg), float(st.bits_sent))
    derived = ";".join(
        f"{t}_gap={g:.4f};{t}_bits={b:.2e}" for t, (g, b) in results.items()
    )
    emit("exchange_registry_rate_preservation", 0.0, derived)

    # --- error feedback vs unbiased sparsification at EQUAL wire budget --
    # same keep fraction -> byte-identical wire bills (asserted), so the
    # gap difference is purely the estimator: EF21's compensated biased
    # estimate vs randk's unbiased-but-high-variance rescale
    vi = cocoercive_quadratic(d=64, seed=1)
    oracle = relative_noise_oracle(vi, c=0.5)
    x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
    results = {}
    for tag, exc in (
        ("ef21_topk", ExchangeConfig(compressor="ef21-topk",
                                     ef_topk_frac=0.1)),
        ("ef_randk", ExchangeConfig(compressor="ef-randk", rand_frac=0.1)),
        ("randk", ExchangeConfig(compressor="randk", rand_frac=0.1)),
    ):
        cfgq = QGenXConfig(variant="de", num_workers=4, exchange=exc)
        st = qgenx_run(x0, oracle, cfgq, KEY, 2048)
        results[tag] = (restricted_gap(vi, st.x_avg), float(st.bits_sent))
    bits = {b for _, b in results.values()}
    assert len(bits) == 1, results  # the equal-wire premise, enforced
    derived = ";".join(
        f"{t}_gap={g:.4f};{t}_bits={b:.2e}" for t, (g, b) in results.items()
    )
    emit("ef21_vs_unbiased_equal_wire_toy_vi", 0.0, derived)

    # --- de vs optda at equal oracle budget (toy VI loop) ----------------
    # de spends 2 oracle calls + 2 broadcasts per iteration, optda 1+1:
    # at an equal call budget optda runs 2x the iterations for the same
    # bits_sent — the Example 3.3 oracle-efficiency claim
    vi = bilinear_saddle(d=32, seed=8)
    oracle = absolute_noise_oracle(vi, sigma=0.5)
    x0 = jnp.asarray(vi.z_star, jnp.float32) + 1.0
    quant = QuantConfig(num_levels=15, bits=8, bucket_size=64,
                        q_norm=math.inf)
    budget = 2 * 1024  # oracle calls per worker
    rows = {}
    for method, iters in (("de", budget // 2), ("optda", budget)):
        cfgm = QGenXConfig(variant=method, num_workers=4, quant=quant)
        st = qgenx_run(x0, oracle, cfgm, KEY, iters)
        rows[method] = (iters, restricted_gap(vi, st.x_avg),
                        float(st.bits_sent))
    emit("de_vs_optda_equal_oracle_budget", 0.0,
         ";".join(f"{m}_T={t};{m}_gap={g:.4f};{m}_bits={b:.3e}"
                  for m, (t, g, b) in rows.items()))

    # --- model scale: the paper's optimizer vs the adam family ----------
    _model_scale_qgenx_vs_extra_adam()
    _model_scale_de_vs_optda()
    _sync_every_tradeoff()
    _recenter_tradeoff()
    _error_feedback_model_scale()


def _model_scale_qgenx_vs_extra_adam(steps: int = 12):
    """Same reduced LM, same batches: qgenx (adaptive gamma, no tuning
    beyond gamma_scale) vs extra_adam vs adam, through make_train_step."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.core.exchange import null_exchange_state
    from repro.launch.steps import make_train_step
    from repro.models.model import build
    from repro.optim import optimizers as opt

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    model = build(cfg)
    params0 = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
    batch = {"tokens": toks, "labels": toks}
    results = {}
    t0 = time.perf_counter()
    for name, kw in (("adam", {"lr": 1e-3}),
                     ("extra_adam", {"lr": 1e-3}),
                     ("qgenx", {"gamma_scale": 0.02})):
        ocfg = opt.OptimizerConfig(name=name, **kw)
        step = jax.jit(make_train_step(model, ocfg))
        params, st, ex_st = params0, opt.init_state(ocfg, params0), \
            null_exchange_state()
        for t in range(steps):
            params, st, ex_st, m = step(params, st, ex_st, batch,
                                        jax.random.fold_in(KEY, t))
        results[name] = float(m["loss"])
    us = (time.perf_counter() - t0) * 1e6 / (3 * steps)
    emit("model_scale_qgenx_vs_extra_adam", us,
         ";".join(f"{k}_loss={v:.4f}" for k, v in results.items()))


def _model_scale_de_vs_optda(oracle_budget: int = 16):
    """Equal oracle budget on the reduced LM through make_train_step:
    de takes budget/2 steps (2 grads each), optda budget steps (1 grad
    each) — same number of forward+backward passes and broadcast rounds,
    the optimistic schedule gets 2x the parameter updates."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.core.exchange import null_exchange_state
    from repro.launch.steps import make_train_step
    from repro.models.model import build
    from repro.optim import optimizers as opt

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    model = build(cfg)
    params0 = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
    batch = {"tokens": toks, "labels": toks}
    results = {}
    t0 = time.perf_counter()
    for method, steps in (("de", oracle_budget // 2), ("optda", oracle_budget)):
        ocfg = opt.OptimizerConfig(name="qgenx", method=method,
                                   gamma_scale=0.02)
        step = jax.jit(make_train_step(model, ocfg))
        params, st, ex_st = params0, opt.init_state(ocfg, params0), \
            null_exchange_state()
        for t in range(steps):
            params, st, ex_st, m = step(params, st, ex_st, batch,
                                        jax.random.fold_in(KEY, t))
        results[method] = (steps, float(m["loss"]))
    us = (time.perf_counter() - t0) * 1e6 / (2 * oracle_budget)
    emit("model_scale_de_vs_optda_equal_oracle", us,
         ";".join(f"{m}_steps={s};{m}_loss={l:.4f}"
                  for m, (s, l) in results.items()))


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_child_env() -> dict:
    """Environment of a train-CLI child simulating 8 workers on forced
    host devices.  It runs on the CPU: this process may hold the
    accelerator, and a device belongs to one process at a time."""
    src = os.path.join(_ROOT, "src")
    pp = os.environ.get("PYTHONPATH")
    return {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": src + os.pathsep + pp if pp else src}


def _sync_every_tradeoff(steps: int = 16):
    """Wire/quality trade-off of the local-update regime: total measured
    wire_bytes (the metric == trace recorder, see tests) and final loss
    at sync_every in {1, 4, 16} on 8 forced host devices (subprocess —
    this process stays single-device)."""
    env = _cpu_child_env()
    rows = []
    for sync in (1, 4, 16):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.train",
             "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
             "--steps", str(steps), "--batch", "16", "--seq", "32",
             "--repeat-batch", "--optimizer", "qgenx",
             "--gamma-scale", "0.02", "--compression", "int8",
             "--compress-axis", "data", "--sync-every", str(sync)],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900,
        )
        if r.returncode != 0:
            emit(f"sync_every{sync}_wire_quality", 0.0,
                 "ERROR=" + r.stderr[-160:].replace("\n", " "))
            continue
        lines = [l for l in r.stdout.splitlines()
                 if l.startswith("[train] step=")]
        wire = sum(float(l.split("wire=")[1].split("B")[0]) for l in lines)
        loss = float(r.stdout.split("final_loss=")[1].split()[0])
        rows.append((sync, wire, loss))
        emit(f"sync_every{sync}_wire_quality", 0.0,
             f"total_wire={wire:.3e}B;final_loss={loss:.4f}")
    if len(rows) > 1 and rows[0][0] == 1:  # reductions need the K=1 baseline
        base = rows[0][1]
        emit("sync_every_wire_reduction", 0.0,
             ";".join(f"K{s}={base / w:.2f}x" for s, w, _ in rows if w))


def _recenter_tradeoff(steps: int = 16):
    """Drift vs wire across compressed parameter re-centering cadences:
    sync_every=4 with recenter_every in {0, 8, 4} (8 forced host devices,
    subprocess) — total wire_bytes, final loss, and the drift reported on
    the last sync step."""
    env = _cpu_child_env()
    for rc in (0, 8, 4):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.train",
             "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
             "--steps", str(steps), "--batch", "16", "--seq", "32",
             "--repeat-batch", "--optimizer", "qgenx", "--method", "optda",
             "--gamma-scale", "0.02", "--compression", "int8",
             "--compress-axis", "data", "--sync-every", "4",
             "--recenter-every", str(rc)],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900,
        )
        if r.returncode != 0:
            emit(f"recenter_every{rc}_drift_wire", 0.0,
                 "ERROR=" + r.stderr[-160:].replace("\n", " "))
            continue
        lines = [l for l in r.stdout.splitlines()
                 if l.startswith("[train] step=")]
        wire = sum(float(l.split("wire=")[1].split("B")[0]) for l in lines)
        drifts = [float(l.split("drift=")[1].split()[0])
                  for l in lines if "drift=" in l]
        last_drift = next((d for d in reversed(drifts) if d > 0.0), 0.0)
        loss = float(r.stdout.split("final_loss=")[1].split()[0])
        emit(f"recenter_every{rc}_drift_wire", 0.0,
             f"total_wire={wire:.3e}B;last_sync_drift={last_drift:.3e};"
             f"final_loss={loss:.4f}")


def _error_feedback_model_scale(steps: int = 12):
    """EF21-top-k vs unbiased randk at the SAME keep fraction (identical
    8k-byte wire bill per exchange — the per-step wire is cross-checked
    in the derived row) on the reduced LM through the train CLI, 8 forced
    host devices (subprocess — this process stays single-device)."""
    env = _cpu_child_env()
    for tag, extra in (
        ("ef21_topk", ["--compressor", "ef21-topk", "--ef-topk-frac", "0.1"]),
        ("randk", ["--compressor", "randk", "--rand-frac", "0.1"]),
    ):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.train",
             "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
             "--steps", str(steps), "--batch", "16", "--seq", "32",
             "--repeat-batch", "--optimizer", "qgenx",
             "--gamma-scale", "0.02", "--compress-axis", "data"] + extra,
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900,
        )
        if r.returncode != 0:
            emit(f"model_scale_{tag}_equal_wire", 0.0,
                 "ERROR=" + r.stderr[-160:].replace("\n", " "))
            continue
        lines = [l for l in r.stdout.splitlines()
                 if l.startswith("[train] step=")]
        wire = sum(float(l.split("wire=")[1].split("B")[0]) for l in lines)
        loss = float(r.stdout.split("final_loss=")[1].split()[0])
        emit(f"model_scale_{tag}_equal_wire", 0.0,
             f"total_wire={wire:.3e}B;final_loss={loss:.4f}")


if __name__ == "__main__":
    run()
