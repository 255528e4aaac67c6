"""Subprocess payload: error-feedback (EF21) acceptance on 8 devices.

Run with 8 forced host devices.  Exercises the contractive-compressor
stack end-to-end:

1. EF21 TRAIN — qgenx(optda) + ef21-topk exchange, guard armed, fault
   ``nan_grad@2:worker=4``: six steps complete with finite loss; the
   trace recorder's EF entries sum EXACTLY to the step's analytic
   ``wire_bytes`` metric (the packed flat buffer prices as 8k bytes per
   exchange: k f32 values + k int32 indices).
2. ERROR-MEMORY STATE MACHINE — per-worker rows of the [K, n] error
   matrix diverge pairwise (workers see different batch rows, so their
   innovations differ); a successful exchange ADVANCES the memory; the
   guard-rejected step carries it through bit-UNCHANGED (rejection
   restores the pre-exchange state).
3. CHECKPOINT ROUND-TRIP — ``save``/``restore`` of the 5-child
   ExchangeState reproduces the error matrix bit-exactly.
4. PLACEHOLDER LOUDNESS — feeding an EF exchange a state built without
   ``init_state(template=..., num_workers=...)`` fails at trace time
   with a pointed message, not with a silent shape blow-up.
5. LEGACY PARITY GRID (no-EF) — the unbiased qgenx path is bitwise
   identical to the pre-EF ``compressed_pmean_tree`` across
   bits{4,8} x mode{gather,two_phase} on 8 devices: adding the error
   slot changed NOTHING for unbiased-tier entries.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.checkpoint.checkpointing import restore, save  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.exchange import (  # noqa: E402
    ExchangeConfig,
    _qgenx_pmean,
    make_exchange,
    wire_trace_start,
    wire_trace_stop,
)
from repro.core.faults import FaultSpec  # noqa: E402
from repro.core.quantization import QuantConfig, uniform_levels  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.models.model import build  # noqa: E402
from repro.optim import optimizers as opt  # noqa: E402

K = 8
assert jax.device_count() == K, jax.device_count()
mesh = Mesh(np.array(jax.devices()).reshape(K), ("data",))

cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                          dtype="float32")
model = build(cfg)
params0 = model.init(jax.random.PRNGKey(0))
n_params = int(sum(l.size for l in jax.tree_util.tree_leaves(params0)))
opt_cfg = opt.OptimizerConfig(name="qgenx", method="optda", gamma_scale=0.02)
# distinct rows per worker: the batch axis shards over "data", so each
# worker grads differently and the error rows must diverge
tok = jax.random.randint(jax.random.PRNGKey(9), (16, 32), 0, 256, jnp.int32)
batch = {"tokens": tok, "labels": tok}

ex = make_exchange(ExchangeConfig(compressor="ef21-topk", ef_topk_frac=0.1,
                                  axis_name="data"))
STEPS, NAN_AT = 6, 2
spec = FaultSpec.parse(f"nan_grad@{NAN_AT}:worker=4")
step_f = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh,
                                 guard=True, fault_spec=spec))

pf = params0
of_ = opt.init_state(opt_cfg, params0)
ef_ = ex.init_state(template=params0, num_workers=K)
assert ef_.error.shape == (K, n_params), ef_.error.shape

# -- 1 + 2. EF21 train: recorder == analytic, error state machine -----------
prev_err = np.asarray(ef_.error)
with mesh:
    for t in range(STEPS):
        k = jax.random.fold_in(jax.random.PRNGKey(1), t)
        if t == 0:
            wire_trace_start()
        pf, of_, ef_, m = step_f(pf, of_, ef_, batch, k, t)
        if t == 0:
            rec = wire_trace_stop()
            ef_entries = [(nm, b) for nm, b in rec if nm.startswith("ef21")]
            assert ef_entries, rec
            got = float(sum(b for _, b in ef_entries))
            want = float(m["wire_bytes"])
            assert got == want, (got, want, rec)
            print(f"PASS recorder == analytic wire "
                  f"({got:.0f} B over {len(ef_entries)} EF operands)",
                  flush=True)
        assert np.isfinite(float(m["loss"])), (t, float(m["loss"]))
        rej = float(m["rejected"])
        assert rej == (1.0 if t == NAN_AT else 0.0), (t, rej)
        err = np.asarray(ef_.error)
        if t == NAN_AT:
            # a rejected step must NOT advance the error memory
            assert np.array_equal(err, prev_err), "error advanced on reject"
        else:
            # a successful exchange must advance it
            assert not np.array_equal(err, prev_err), t
        prev_err = err
rows = np.asarray(ef_.error)
for i in range(K):
    for j in range(i + 1, K):
        assert not np.array_equal(rows[i], rows[j]), (i, j)
print(f"PASS error memory: [K={K}, n={n_params}] rows pairwise distinct, "
      f"bit-frozen through the rejected step @{NAN_AT}", flush=True)

# -- 3. checkpoint round-trip ------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    save(td, STEPS, {"params": pf, "ex_state": ef_})
    got_step, trees = restore(td, {"params": pf, "ex_state": ef_})
    assert got_step == STEPS
    assert np.array_equal(np.asarray(trees["ex_state"].error),
                          np.asarray(ef_.error))
    for a, b in zip(jax.tree_util.tree_leaves(trees["params"]),
                    jax.tree_util.tree_leaves(pf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
print("PASS checkpoint round-trip: error matrix bit-exact", flush=True)

# -- 4. placeholder loudness -------------------------------------------------
try:
    with mesh:
        step_f(pf, of_, ex.init_state(), batch,
               jax.random.PRNGKey(3), STEPS)
    raise SystemExit("placeholder EF state was accepted silently")
except ValueError as e:
    assert "init_state" in str(e), e
print("PASS placeholder error state rejected with pointed message",
      flush=True)

# -- 5. no-EF legacy parity grid ---------------------------------------------
KEY = jax.random.PRNGKey(7)
grid_tree = {
    "w": jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32),
    "b": jax.random.normal(jax.random.PRNGKey(3), (77,), jnp.float32),
}
for bits in (8, 4):
    for mode in ("gather", "two_phase"):
        q = QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                        bucket_size=256)
        exq = make_exchange(ExchangeConfig(compressor="qgenx", quant=q,
                                           mode=mode, axis_name="data"))
        levels = uniform_levels(q.num_levels)

        def f(tl, kk, exq=exq, q=q, mode=mode, levels=levels):
            new, _ = exq.pmean_tree(tl, exq.init_state(), kk)
            # pre-plan reference: naive concatenate + flat qgenx exchange
            # (the retired compressed_pmean_tree wrapper, inlined)
            leaves, treedef = jax.tree_util.tree_flatten(tl)
            flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32) for l in leaves]
            )
            mean = _qgenx_pmean(flat, "data", levels, kk, q, mode)
            outs, off = [], 0
            for l in leaves:
                outs.append(mean[off: off + l.size].reshape(l.shape))
                off += l.size
            old = jax.tree_util.tree_unflatten(treedef, outs)
            return new, old

        with mesh:
            new, old = jax.jit(
                shard_map(f, mesh=mesh,
                          in_specs=({"w": P(), "b": P()}, P()),
                          out_specs=({"w": P(), "b": P()},) * 2,
                          check_vma=False)
            )(grid_tree, KEY)
        for kk in grid_tree:
            np.testing.assert_array_equal(
                np.asarray(new[kk]), np.asarray(old[kk]),
                err_msg=f"bits={bits} mode={mode}")
        print(f"PASS no-EF legacy parity bits={bits} mode={mode}", flush=True)

print("ALL OK", flush=True)
