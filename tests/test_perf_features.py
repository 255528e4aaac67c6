"""Tests for the beyond-paper perf features (EXPERIMENTS.md §Perf):
blockwise (flash-style) attention and the sharding-preserving leafwise
compressed exchange (incl. int4 packing)."""

import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import blockwise_attention, full_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("S,qc,kc", [(256, 64, 64), (512, 128, 64), (384, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_full(S, qc, kc, causal):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, hd = 2, 4, 32
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    ref = full_attention(q, k, v, causal)
    got = blockwise_attention(q, k, v, causal, q_chunk=qc, k_chunk=kc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-3, atol=3e-3)


def test_blockwise_grad_finite():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, hd = 1, 256, 2, 16
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))

    def f(q):
        return jnp.sum(blockwise_attention(q, k, v, True, q_chunk=64, k_chunk=64) ** 2)

    g = jax.grad(f)(q)
    assert np.isfinite(np.asarray(g)).all()


_LEAFWISE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=4"
import math
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core.exchange import ExchangeConfig, make_exchange
from repro.core.quantization import QuantConfig
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
tree = {"w": jnp.asarray(np.random.RandomState(0).randn(4, 16, 64), jnp.float32)}
true = np.asarray(tree["w"]).mean(0)
for bits, s in ((8, 15), (4, 5)):
    CFG = QuantConfig(num_levels=s, bits=bits, q_norm=math.inf, bucket_size=64)
    EX = make_exchange(ExchangeConfig(compressor="qgenx", quant=CFG,
                                      axis_name="data", mode="leafwise"))
    @jax.jit
    def run(t, key):
        def f(tl, k):
            out, _ = EX.pmean_tree({"w": tl["w"][0]}, EX.init_state(), k)
            return {"w": out["w"][None]}
        return shard_map(f, mesh=mesh, in_specs=({"w": P("data",None,None)}, P()),
                         out_specs={"w": P("data",None,None)}, check_vma=False)(t, key)
    acc = 0
    T = 40
    for t in range(T):
        acc = acc + np.asarray(run(tree, jax.random.PRNGKey(t))["w"])[0]
    err = np.abs(acc/T - true).max()
    assert err < 0.25, (bits, err)
    print(f"PASS bits={bits} err={err:.4f}")
print("ALL OK")
"""


def test_leafwise_exchange_unbiased_multidev():
    src = os.path.join(ROOT, "src")
    pp = os.environ.get("PYTHONPATH")
    r = subprocess.run(
        [sys.executable, "-c", _LEAFWISE_SCRIPT],
        cwd=ROOT,
        env={**os.environ, "PYTHONPATH": src + os.pathsep + pp if pp else src},
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "ALL OK" in r.stdout
