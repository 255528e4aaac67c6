"""The fused Pallas exchange path of the flat qgenx exchange == the jnp
reference path, bit-exactly, under identical noise.

Multi-device rendezvous starves with interpret-mode Pallas callbacks (see
tests/_multidev_collectives.py), so the full fused pipeline runs here on a
single-device mesh (the collectives are trivial but every kernel — packed
quantize, fused dequant+reduce, fused dequant+reduce+requantize, packed
dequantize — executes on its real [K, nb, P] shapes); the multi-device
semantics of the identical jnp path are covered by
tests/test_wire_accounting.py and tests/_multidev_collectives.py.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.exchange import _qgenx_pmean
from repro.core.quantization import QuantConfig, uniform_levels

N = 3000  # not a bucket multiple — exercises padding


def _run(mode, bits, use_pallas, use_device_prng=False):
    cfg = QuantConfig(
        num_levels=5 if bits == 4 else 15, q_norm=math.inf,
        bucket_size=256, bits=bits,
    )
    levels = uniform_levels(cfg.num_levels)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(3), (N,), jnp.float32)

    @jax.jit
    def run(xl, key):
        f = functools.partial(
            _qgenx_pmean, axis_name="data", levels=levels, cfg=cfg,
            mode=mode, use_pallas=use_pallas, use_device_prng=use_device_prng,
        )
        return shard_map(
            lambda a, k: f(a, key=k), mesh=mesh,
            in_specs=(P(), P()), out_specs=P(), check_vma=False,
        )(xl, key)

    return run(x, jax.random.PRNGKey(11))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("mode", ["gather", "two_phase"])
def test_fused_pallas_path_matches_jnp_reference(mode, bits):
    got = _run(mode, bits, use_pallas=True)
    want = _run(mode, bits, use_pallas=False)
    assert got.shape == want.shape == (N,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_device_prng_requires_pallas():
    """The jnp reference path has no on-core PRNG — asking for it must be
    a loud error, not a silent fall-back to the host noise buffer."""
    from repro.core.exchange import _quantize_2d

    cfg = QuantConfig(num_levels=5, bucket_size=256, bits=4)
    x2d = jnp.zeros((4, 256), jnp.float32)
    with pytest.raises(ValueError, match="use_pallas"):
        _quantize_2d(
            x2d, uniform_levels(5), jax.random.PRNGKey(0), cfg,
            use_pallas=False, use_device_prng=True,
        )


def test_device_prng_exchange_traces():
    """The TPU-only PRNG path must at least trace end-to-end (no noise
    buffer in the jaxpr inputs); lowering needs real TPU hardware."""
    cfg = QuantConfig(num_levels=5, q_norm=math.inf, bucket_size=256, bits=4)
    levels = uniform_levels(5)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (N,), jnp.float32)

    def run(xl, key):
        return shard_map(
            lambda a, k: _qgenx_pmean(
                a, "data", levels, k, cfg, mode="two_phase",
                use_pallas=True, use_device_prng=True,
            ),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
        )(xl, key)

    out = jax.eval_shape(run, x, jax.random.PRNGKey(1))
    assert out.shape == (N,) and out.dtype == jnp.float32
