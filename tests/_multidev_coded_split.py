"""Subprocess payload: the Theorem-2 estimate split over 4 devices.

``Exchange.coded_bits_tree(..., axis_name=...)`` has each device price a
contiguous share of every run's buckets and psums the bracket sums.  On a
replicated tree it must equal the whole-tree pass on every device:

1. bucket-aligned leaves read in place (rows divisible by 4 and not),
   packed runs whose bucket count does not divide by 4, padding tails;
   int8 and int4, L-inf and L2 bucket norms; the position from the
   sharded arange and from ``lax.axis_index``;
2. ``gate``: the pass inside a ``lax.cond``, the psum outside it —
   the estimate where the gate holds, 0.0 where it does not;
3. the train step's ``coded_bits_est`` (extragradient Adam, two
   exchanges a step) is twice the split estimate of the exchanged
   gradient, and that equals the whole-tree estimate of it.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses  # noqa: E402
import math  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.exchange import ExchangeConfig, make_exchange  # noqa: E402
from repro.core.quantization import QuantConfig  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.models.model import build  # noqa: E402
from repro.optim import optimizers as opt  # noqa: E402

K = 4
B = 256
assert jax.device_count() == K, jax.device_count()
mesh = Mesh(np.array(jax.devices()), ("data",))
keys = jax.random.split(jax.random.PRNGKey(3), 8)

TREES = {
    # aligned rows 6 (not divisible by 4), a packed run of 5 buckets,
    # then an all-zero leaf back on the bucket grid, 3 rows
    "uneven": {
        "a": jax.random.normal(keys[0], (6, 2 * B)),
        "n": jax.random.normal(keys[1], (1, 128)),
        "w": jax.random.normal(keys[2], (1, 8, 144)).astype(jnp.bfloat16),
        "z": jnp.zeros((3, B)),
    },
    # everything divides: aligned [8, 3B] and [1, 4, B] (leading unit
    # axis), then a packed run of 4 buckets
    "even": {
        "a": jax.random.normal(keys[3], (8, 3 * B)) ** 3,
        "b": jax.random.normal(keys[4], (1, 4, B)),
        "c": jax.random.normal(keys[5], (4 * B - 7,)),
    },
    # fewer rows than devices: [2, B] read by two of the four
    "short": {
        "a": jax.random.normal(keys[6], (2, B)),
        "c": jax.random.normal(keys[7], (3 * B + 5,)),
    },
}
QUANTS = {
    "int8-linf": QuantConfig(num_levels=15, bits=8, bucket_size=B),
    "int4-l2": QuantConfig(num_levels=5, bits=4, bucket_size=B, q_norm=2.0),
}
ix_sharding = NamedSharding(mesh, P("data"))
axis_ix = jax.device_put(jnp.arange(K, dtype=jnp.int32), ix_sharding)


def per_device(fn):
    """fn(tree, state, ix) on every device, replicated inputs; returns the
    [K] stack of each device's scalar."""
    return jax.jit(jax.shard_map(
        lambda t, s, ix: fn(t, s, ix[0])[None], mesh=mesh,
        in_specs=(P(), P(), P("data")), out_specs=P("data"),
        check_vma=False))


for tname, tree in TREES.items():
    for qname, quant in QUANTS.items():
        ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant,
                                          axis_name="data"))
        state = ex.init_state()
        whole = float(jax.jit(ex.coded_bits_tree)(tree, state))
        assert whole > 0, (tname, qname)
        runs = ex._coded_layout(jax.tree_util.tree_leaves(tree))[2]
        assert any(r.in_place for r in runs), (tname, runs)
        assert any(not r.in_place for r in runs), (tname, runs)
        for how, fn in {
            "arange": lambda t, s, ix: ex.coded_bits_tree(
                t, s, axis_name="data", axis_index=ix),
            "axis_index": lambda t, s, ix: ex.coded_bits_tree(
                t, s, axis_name="data"),
            "gate_on": lambda t, s, ix: ex.coded_bits_tree(
                t, s, axis_name="data", axis_index=ix, gate=ix >= 0),
        }.items():
            got = np.asarray(per_device(fn)(tree, state, axis_ix))
            np.testing.assert_allclose(got, whole, rtol=1e-6,
                                       err_msg=f"{tname} {qname} {how}")
        off = np.asarray(per_device(lambda t, s, ix: ex.coded_bits_tree(
            t, s, axis_name="data", axis_index=ix, gate=ix < 0))(
                tree, state, axis_ix))
        assert (off == 0.0).all(), (tname, qname, off)
        print(f"PASS split {tname} {qname}: {whole:.6e} bits", flush=True)

# --- through the train step --------------------------------------------------
cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                          dtype="float32")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
opt_cfg = opt.OptimizerConfig(name="extra_adam", lr=1e-3)
ex = make_exchange(ExchangeConfig(
    compressor="qgenx", quant=QuantConfig(num_levels=15, bucket_size=B),
    mode="two_phase", axis_name="data"))
seen = []
split_pass = ex.coded_bits_tree


def both(tree, state, axis_name=None, axis_index=None, gate=None):
    """The split estimate the step asks for, beside the whole-tree one,
    both sent to the host."""
    split = split_pass(tree, state, axis_name, axis_index, gate)
    whole = split_pass(tree, state)
    jax.debug.callback(lambda s, w: seen.append((float(s), float(w))),
                       split, whole)
    return split


ex.coded_bits_tree = both
step = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh))
rep = NamedSharding(mesh, P())
state = jax.device_put(
    (params, opt.init_state(opt_cfg, params), ex.init_state()), rep)
batch = {"tokens": jax.random.randint(keys[0], (8, 16), 0, cfg.vocab_size),
         "labels": jax.random.randint(keys[1], (8, 16), 0, cfg.vocab_size)}
with mesh:
    for t in range(2):
        seen.clear()
        *state, m = step(*state, batch, jax.random.PRNGKey(10 + t))
        coded = float(m["coded_bits_est"])
        jax.block_until_ready(state)
        assert len(seen) == K, seen
        for split, whole in seen:
            assert math.isclose(split, whole, rel_tol=1e-6), (split, whole)
            assert math.isclose(coded, 2 * split, rel_tol=1e-6), (coded, split)
        print(f"PASS train step {t}: coded_bits_est={coded:.6e}", flush=True)

print("ALL OK", flush=True)
