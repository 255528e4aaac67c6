"""Subprocess payload for multi-device collective tests (8 host devices).

Run with: XLA_FLAGS=--xla_force_host_platform_device_count=8.
Prints PASS lines; exits nonzero on failure.

NOTE: the Pallas-kernel path is exercised single-device elsewhere
(tests/test_kernels.py); inside an 8-fake-device shard_map on a 1-core CPU
container the interpret-mode Python callbacks can starve the collective
rendezvous (XLA aborts after 40 s), so here we run the jnp reference path —
the two are bit-identical by test_kernels.py.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402
import math  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.core.exchange import ExchangeConfig, make_exchange  # noqa: E402
from repro.core.quantization import QuantConfig  # noqa: E402

assert jax.device_count() == 8, jax.device_count()

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
N = 4096
CFG = QuantConfig(num_levels=15, q_norm=math.inf, bucket_size=512)
TRIALS = 16


def _ex(mode):
    return make_exchange(ExchangeConfig(
        compressor="qgenx", quant=CFG, axis_name="data", mode=mode,
        use_pallas=False,
    ))


xs = jnp.asarray(np.random.RandomState(0).randn(8, N), jnp.float32)
true_mean = np.asarray(xs).mean(0)


@functools.partial(jax.jit, static_argnames=("mode",))
def run(x, key, mode):
    ex = _ex(mode)

    def f(xl, k):
        out, _ = ex.pmean(xl.reshape(-1), ex.init_state(), k)
        return out.reshape(1, N)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(P("data", None), P()),
        out_specs=P("data", None),
        check_vma=False,
    )(x, key)


for mode in ("gather", "two_phase"):
    acc = 0
    for t in range(TRIALS):
        out = np.asarray(run(xs, jax.random.PRNGKey(t), mode))
        assert np.allclose(out, out[0:1], atol=1e-5), f"{mode} replicas differ"
        acc = acc + out[0]
    est = acc / TRIALS
    scale = np.abs(true_mean).max()
    err = np.abs(est - true_mean).max()
    assert err < 0.2 * scale + 0.05, (mode, err, scale)
    print(f"PASS {mode} maxerr={err:.4f}", flush=True)

# pytree fusion path
tree = {
    "w": jnp.asarray(np.random.RandomState(1).randn(8, 64, 32), jnp.float32),
    "b": jnp.asarray(np.random.RandomState(2).randn(8, 77), jnp.float32),
}
true = {k: np.asarray(v).mean(0) for k, v in tree.items()}


EX_TREE = _ex("two_phase")


def ftree(t, k):
    local = {"w": t["w"][0], "b": t["b"][0]}
    out, _ = EX_TREE.pmean_tree(local, EX_TREE.init_state(), k)
    return {"w": out["w"][None], "b": out["b"][None]}


tree_specs = {"w": P("data", None, None), "b": P("data", None)}
run_tree = jax.jit(
    shard_map(ftree, mesh=mesh, in_specs=(tree_specs, P()), out_specs=tree_specs,
              check_vma=False)
)
acc_w, acc_b = 0, 0
for t in range(TRIALS):
    out = run_tree(tree, jax.random.PRNGKey(100 + t))
    acc_w = acc_w + np.asarray(out["w"])[0]
    acc_b = acc_b + np.asarray(out["b"])[0]
err_w = np.abs(acc_w / TRIALS - true["w"]).max()
err_b = np.abs(acc_b / TRIALS - true["b"]).max()
assert err_w < 0.3 and err_b < 0.3, (err_w, err_b)
print(f"PASS tree two_phase errw={err_w:.4f} errb={err_b:.4f}", flush=True)


EX_EXACT = make_exchange(ExchangeConfig(compressor="none", axis_name="data"))


def fexact(t, k):
    local = {"w": t["w"][0], "b": t["b"][0]}
    out, _ = EX_EXACT.pmean_tree(local, EX_EXACT.init_state(), k)
    return {"w": out["w"][None], "b": out["b"][None]}


out = jax.jit(
    shard_map(fexact, mesh=mesh, in_specs=(tree_specs, P()), out_specs=tree_specs,
              check_vma=False)
)(tree, jax.random.PRNGKey(0))
np.testing.assert_allclose(np.asarray(out["w"])[0], true["w"], rtol=1e-5)
print("PASS fp32 fallback exact", flush=True)
print("ALL OK", flush=True)
