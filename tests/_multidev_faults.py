"""Subprocess payload: the fault-tolerance acceptance run on 8 devices.

Run with 8 forced host devices.  Exercises the whole tentpole stack:

1. ACCEPTANCE RUN — qgenx(optda) + int8 two_phase exchange, guard armed,
   fault schedule ``nan_grad@5:worker=2;drop@8-10:worker=3``: all 12
   steps complete, exactly step 5 is rejected (one worker's NaN poisons
   the exchanged mean fleet-wide), steps 8-10 run with 7/8 workers and a
   wire bill scaled byte-exactly to the alive set, and the final loss is
   finite.
2. PREFIX PARITY — the faulted run's params are bitwise equal to a clean
   (guard-only, no faults) run's params on every step before the first
   fault fires: inactive fault predicates add 0.0 and mask 1.0, neither
   of which changes a value.
3. ALL-ONES MASK PARITY GRID — ``pmean_tree(..., mask=1.0)`` is bitwise
   identical to ``mask=None`` across bits{4,8} x mode{gather,two_phase}
   (the PR-5 parity-grid discipline applied to the mask seam:
   where(True, g, 0) is g, psum of exact ones is K, K/K renorm is 1.0).
4. ALIVE-SET RENORMALIZATION — with the exact (compressor="none")
   exchange and worker 3 masked dead, the aggregate equals the explicit
   mean over the 7 survivors.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.exchange import ExchangeConfig, make_exchange  # noqa: E402
from repro.core.faults import FaultSpec  # noqa: E402
from repro.core.quantization import QuantConfig  # noqa: E402
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
opt_cfg = opt.OptimizerConfig(name="qgenx", method="optda", gamma_scale=0.02)
batch = {
    "tokens": jnp.zeros((16, 32), jnp.int32),
    "labels": jnp.zeros((16, 32), jnp.int32),
}

ex_cfg = ExchangeConfig(
    compressor="qgenx",
    quant=QuantConfig(num_levels=15, bits=8, bucket_size=256),
    mode="two_phase", axis_name="data",
)
ex = make_exchange(ex_cfg)

STEPS, NAN_AT, DROP = 12, 5, range(8, 11)
spec = FaultSpec.parse("nan_grad@5:worker=2;drop@8-10:worker=3")
step_f = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh,
                                 guard=True, fault_spec=spec))
step_c = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh,
                                 guard=True))


def tree_eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


# -- 1. acceptance run + 2. prefix parity against the clean run -------------
pf, of_, ef = params0, opt.init_state(opt_cfg, params0), ex.init_state()
pc, oc, ec = params0, opt.init_state(opt_cfg, params0), ex.init_state()
full_wire = None
with mesh:
    for t in range(STEPS):
        k = jax.random.fold_in(jax.random.PRNGKey(1), t)
        pf, of_, ef, m = step_f(pf, of_, ef, batch, k, t)
        assert np.isfinite(float(m["loss"])), (t, float(m["loss"]))
        rej, alive = float(m["rejected"]), float(m["alive"])
        assert rej == (1.0 if t == NAN_AT else 0.0), (t, rej)
        assert float(m["nonfinite"]) == (1.0 if t == NAN_AT else 0.0), t
        want_alive = K - 1 if t in DROP else K
        assert alive == want_alive, (t, alive)
        wire = float(m["wire_bytes"])
        if t not in DROP:
            if full_wire is None:
                full_wire = wire
            assert wire == full_wire, (t, wire, full_wire)
        else:
            # wire accounting prices only alive workers — byte-exact
            # alive/K scaling of the full bill (same f32 op order)
            want = float(np.float32(full_wire)
                         * (np.float32(K - 1) / np.float32(K)))
            assert wire == want, (t, wire, want)
        if t < NAN_AT:
            kc = jax.random.fold_in(jax.random.PRNGKey(1), t)
            pc, oc, ec, mc = step_c(pc, oc, ec, batch, kc)
            assert tree_eq(pf, pc), f"pre-fault params diverged at step {t}"
            assert tree_eq(of_.y, oc.y), t
print(f"PASS acceptance: 12 steps, rejected@{NAN_AT}, alive=7@8-10, "
      f"wire byte-exact over alive set", flush=True)


# -- 3. all-ones mask parity grid -------------------------------------------
def run_pmean(ex1, tree, with_mask):
    def f(tl, kk):
        mask = jnp.float32(1.0) if with_mask else None
        mean, st = ex1.pmean_tree(tl, ex1.init_state(), kk, mask=mask)
        return mean, st.step

    specs = {k: P() for k in tree}
    with mesh:
        return jax.jit(
            shard_map(f, mesh=mesh,
                      in_specs=({k: P("data") for k in tree}, P()),
                      out_specs=(specs, P()), check_vma=False)
        )(tree, jax.random.PRNGKey(7))


grid_tree = {
    "emb": jax.random.normal(jax.random.PRNGKey(2), (K * 25, 40), jnp.float32),
    "w": jax.random.normal(jax.random.PRNGKey(3), (K * 16, 32), jnp.float32),
    "b": jax.random.normal(jax.random.PRNGKey(4), (K * 11,), jnp.float32),
}
for bits in (8, 4):
    for mode in ("gather", "two_phase"):
        q = QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                        bucket_size=256)
        ex1 = make_exchange(ExchangeConfig(compressor="qgenx", quant=q,
                                           mode=mode, axis_name="data"))
        base, st_b = run_pmean(ex1, grid_tree, with_mask=False)
        masked, st_m = run_pmean(ex1, grid_tree, with_mask=True)
        for k in grid_tree:
            np.testing.assert_array_equal(np.asarray(base[k]),
                                          np.asarray(masked[k]),
                                          err_msg=f"bits={bits} mode={mode}")
        assert int(st_b) == int(st_m) == 1
        print(f"PASS mask parity bits={bits} mode={mode}", flush=True)


# -- 4. alive-set renormalization (exact exchange) --------------------------
DEAD = 3
ex_none = make_exchange(ExchangeConfig(compressor="none", axis_name="data"))


def f_masked(x, ixs):
    mask = jnp.where(ixs[0] == DEAD, jnp.float32(0.0), jnp.float32(1.0))
    mean, _ = ex_none.pmean_tree({"v": x}, ex_none.init_state(),
                                 jax.random.PRNGKey(0), mask=mask)
    return mean["v"]


x = jax.random.normal(jax.random.PRNGKey(5), (K, 257), jnp.float32)
with mesh:
    got = jax.jit(
        shard_map(f_masked, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=P("data"), check_vma=False)
    )(x, jnp.arange(K, dtype=jnp.int32))
alive_mean = np.asarray(x)[[i for i in range(K) if i != DEAD]].mean(axis=0)
for i in range(K):  # every worker (incl. the dead one) holds the alive mean
    np.testing.assert_allclose(np.asarray(got)[i], alive_mean, rtol=2e-6,
                               err_msg=f"worker {i}")
print("PASS alive-set renormalization (mean over 7 survivors)", flush=True)

print("ALL OK", flush=True)
