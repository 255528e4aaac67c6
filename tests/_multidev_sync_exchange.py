"""Subprocess payload: the sync_every local-update regime on 8 devices.

Trains the paper's OWN optimizer (``qgenx`` — adaptive gamma rule) through
``make_train_step`` with a compressed exchange gated at ``sync_every=4``
and asserts the acceptance criteria of the local-update regime:

1. wire_bytes is 0 on local steps and, on sync steps, equals exactly
   2 grad exchanges + the f32 drift probe — the trace-time recorder
   (one trace, cond branches traced once) agrees to the byte;
2. total wire over a window is ~K× below the sync_every=1 baseline;
3. params actually drift between syncs (param_drift > 0 on sync steps
   with per-device batch shards) and stay 0 when every step syncs;
4. the adaptive statistic accumulates (sum_sq > 0) and the loss is
   finite on every step.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
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
opt_cfg = opt.OptimizerConfig(name="qgenx", gamma_scale=0.02)
quant = QuantConfig(num_levels=15, bits=8, bucket_size=256)
# per-device batch shards must differ, or params cannot drift
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(5), (16, 32), 0, 256),
    "labels": jax.random.randint(jax.random.PRNGKey(6), (16, 32), 0, 256),
}
n = sum(l.size for l in jax.tree_util.tree_leaves(params0))


def run(sync_every, steps):
    ex_cfg = ExchangeConfig(compressor="qgenx", quant=quant, mode="two_phase",
                            axis_name="data", sync_every=sync_every)
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

# --- gated run -------------------------------------------------------------
mets, rec, ex, opt_state, ex_state = run(SYNC, 2 * SYNC)
recorded = sum(b for _, b in rec)
want_sync = 2 * per_call + probe
assert recorded == want_sync, (recorded, want_sync, rec)
assert any(name == "drift_probe" for name, _ in rec), rec

for t, m in enumerate(mets):
    assert np.isfinite(m["loss"]), (t, m)
    if t % SYNC == SYNC - 1:
        assert m["wire_bytes"] == want_sync, (t, m, want_sync)
        assert m["param_drift"] > 0.0, (t, m)  # locals drifted since init
    else:
        assert m["wire_bytes"] == 0.0, (t, m)
        assert m["param_drift"] == 0.0, (t, m)
total_gated = sum(m["wire_bytes"] for m in mets)
assert int(ex_state.step) == 2 * 2  # 2 sync steps x 2 exchanges
assert float(opt_state.sum_sq) > 0.0
print(f"PASS gated sync_every={SYNC}: wire/sync={want_sync:.0f}B "
      f"drift@sync={[m['param_drift'] for m in mets[SYNC-1::SYNC]]}",
      flush=True)

# --- sync_every=1 baseline: every step pays, no drift ----------------------
mets1, rec1, _, _, _ = run(1, 2 * SYNC)
assert sum(b for _, b in rec1) == 2 * per_call, rec1  # no probe when K=1
for t, m in enumerate(mets1):
    assert m["wire_bytes"] == 2 * per_call, (t, m)
    assert m["param_drift"] == 0.0, (t, m)
total_base = sum(m["wire_bytes"] for m in mets1)
ratio = total_base / total_gated
assert SYNC - 1 < ratio <= SYNC, ratio  # ~K× (probe keeps it just below K)
print(f"PASS wire reduction: {total_base:.3e}B -> {total_gated:.3e}B "
      f"({ratio:.2f}x, target ~{SYNC}x)", flush=True)

print("ALL OK", flush=True)
