"""Subprocess payload: compressed parameter re-centering on 8 devices.

Trains the paper's optimizer under the ONE-CALL optimistic schedule
(``--method optda`` — prev_half feedback) with the local-update regime
(``sync_every=4``) and compressed re-centering (``recenter_every=4``),
and asserts the acceptance criteria:

1. bytes move ONLY on re-center/sync steps: wire_bytes is 0 on local
   steps; on the combined sync+re-center step it equals exactly
   1 gradient exchange (optda = one broadcast round) + 1 params-shaped
   re-centering exchange + the f32 drift probe — and the trace-time
   recorder agrees to the byte (cond branches trace once);
2. re-centering actually trades drift for wire: at the same cadence the
   re-centered run shows strictly smaller param_drift on later sync
   steps than the plain sync_every run, and pays exactly one extra
   exchange per re-center;
3. the optda state carries live prev_half feedback, the adaptive
   statistic accumulates, and the loss stays finite.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import repro.core.exchange as exchange_mod  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.exchange import ExchangeConfig, make_exchange  # noqa: E402
from repro.core.quantization import QuantConfig  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.models.model import build  # noqa: E402
from repro.optim import optimizers as opt  # noqa: E402

K = 8
SYNC = 4
assert jax.device_count() == K, jax.device_count()
mesh = Mesh(np.array(jax.devices()).reshape(K), ("data",))

cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                          dtype="float32")
model = build(cfg)
params0 = model.init(jax.random.PRNGKey(0))
opt_cfg = opt.OptimizerConfig(name="qgenx", method="optda", gamma_scale=0.02)
quant = QuantConfig(num_levels=15, bits=8, bucket_size=256)
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(5), (16, 32), 0, 256),
    "labels": jax.random.randint(jax.random.PRNGKey(6), (16, 32), 0, 256),
}
n = sum(l.size for l in jax.tree_util.tree_leaves(params0))


def run(recenter_every, steps):
    ex_cfg = ExchangeConfig(compressor="qgenx", quant=quant, mode="two_phase",
                            axis_name="data", sync_every=SYNC,
                            recenter_every=recenter_every)
    ex = make_exchange(ex_cfg)
    step = make_train_step(model, opt_cfg, exchange=ex, mesh=mesh)
    params = params0
    opt_state = opt.init_state(opt_cfg, params)
    ex_state = ex.init_state()
    # placed where the step returns its state: one type, one trace
    params, opt_state, ex_state = jax.device_put(
        (params, opt_state, ex_state), NamedSharding(mesh, P()))
    exchange_mod.wire_trace_start()
    mets = []
    with mesh:
        jit_step = jax.jit(step)
        for t in range(steps):
            params, opt_state, ex_state, m = jit_step(
                params, opt_state, ex_state, batch, jax.random.PRNGKey(100 + t)
            )
            mets.append({k: float(v) for k, v in m.items()})
    rec = exchange_mod.wire_trace_stop()
    return mets, rec, ex, opt_state, ex_state


per_call = make_exchange(ExchangeConfig(
    compressor="qgenx", quant=quant, mode="two_phase", axis_name="data",
)).wire_bytes(n, K)
probe = 4.0 * min(4096, n)

# --- re-centered run -------------------------------------------------------
mets, rec, ex, opt_state, ex_state = run(SYNC, 2 * SYNC)
recorded = sum(b for _, b in rec)
# optda: ONE gradient broadcast round per sync step, plus the re-centering
# exchange (the dual accumulator — params-shaped, same per-call bytes)
want_sync = 2 * per_call + probe
assert recorded == want_sync, (recorded, want_sync, rec)
assert any(name == "drift_probe" for name, _ in rec), rec

for t, m in enumerate(mets):
    assert np.isfinite(m["loss"]), (t, m)
    if t % SYNC == SYNC - 1:
        assert m["wire_bytes"] == want_sync, (t, m, want_sync)
        assert m["param_drift"] > 0.0, (t, m)
        assert m["coded_bits_est"] > 0.0, (t, m)
    else:
        assert m["wire_bytes"] == 0.0, (t, m)
        assert m["param_drift"] == 0.0, (t, m)
        assert m["coded_bits_est"] == 0.0, (t, m)
# 2 sync steps x (1 optda grad exchange + 1 re-center exchange)
assert int(ex_state.step) == 2 * 2
assert float(opt_state.sum_sq) > 0.0
ph = sum(float(np.abs(np.asarray(l)).sum())
         for l in jax.tree_util.tree_leaves(opt_state.prev_half))
assert ph > 0.0  # optda feedback is live at 8 devices
print(f"PASS recenter accounting: wire/sync={want_sync:.0f}B "
      f"(1 optda exchange + 1 re-center + probe)", flush=True)

# --- drift-for-wire: compare against the same regime WITHOUT re-centering --
mets0, _, _, _, ex_state0 = run(0, 2 * SYNC)
assert int(ex_state0.step) == 2  # 2 sync steps x 1 optda exchange only
drift_rc = mets[2 * SYNC - 1]["param_drift"]
drift_no = mets0[2 * SYNC - 1]["param_drift"]
assert drift_rc < drift_no, (drift_rc, drift_no)
wire_rc = sum(m["wire_bytes"] for m in mets)
wire_no = sum(m["wire_bytes"] for m in mets0)
assert wire_rc == wire_no + 2 * per_call, (wire_rc, wire_no)
print(f"PASS drift-for-wire: drift@{2*SYNC-1} {drift_no:.3e} -> "
      f"{drift_rc:.3e} for +{2*per_call:.0f}B", flush=True)

print("ALL OK", flush=True)
