"""The exchange kernels compile for a TPU v5e, with no chip attached.

Interpret mode accepts kernels that Mosaic, the TPU's kernel compiler,
refuses (1-D blocks narrower than 128 lanes, strided lane slices, vector
reads from SMEM).  Here every kernel of the exchange is compiled for a
described ``v5e:2x2`` topology at the gradient size of one qwen3-4b layer
(~101 M coordinates, bucket 512): int8 and int4, host noise and the
on-core PRNG, and the quantized exchange itself over the four chips.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the tests of this
file must run in the process that does.  The persistent compilation
cache is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.exchange import ExchangeConfig, make_exchange
from repro.core.quantization import QuantConfig
from repro.kernels.dequant_reduce import (
    dequant_reduce_blocks,
    dequant_reduce_requantize_blocks,
)
from repro.kernels.dequantize import dequantize_blocks
from repro.kernels.quantize import quantize_blocks
from repro.kernels.segment_quantize import quantize_dequantize_segments
from repro.models import transformer as T

BUCKET = 512
K = 4  # workers of the two-phase middle step (the 2x2 host)
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def n():
    """Parameters of one qwen3-4b layer — its gradient's coordinates."""
    cfg = get_config("qwen3-4b")
    shapes = jax.eval_shape(
        lambda k: T.layer_init(k, cfg, False),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))


@pytest.fixture(scope="module")
def nb(n):
    return -(-n // BUCKET)  # bucket rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    # Mosaic-compiled, not interpreted: the kernel is a TPU custom call
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM, used
    return compiled


def _quant(bits):
    return QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                       bucket_size=BUCKET)


def test_layer_size(n):
    # one qwen3-4b layer is ~101 M coordinates (attention + SwiGLU MLP)
    assert 100e6 < n < 102e6


@pytest.mark.parametrize("bits,prng", [(8, False), (4, False), (8, True)])
def test_quantize_compiles(one_chip, nb, bits, prng):
    q = _quant(bits)
    ns = q.num_symbols

    def f(x, noise, lv, seed):
        return quantize_blocks(
            x, None if prng else noise, lv, num_symbols=ns, q_is_inf=False,
            bits=bits, use_device_prng=prng, seed=seed if prng else None)

    _compile(f, _sds((nb, BUCKET), jnp.float32, one_chip),
             _sds((nb, BUCKET), jnp.float32, one_chip),
             _sds((ns,), jnp.float32, one_chip),
             _sds((1,), jnp.int32, one_chip))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequantize_compiles(one_chip, nb, bits):
    q = _quant(bits)
    cols = BUCKET if bits == 8 else BUCKET // 2

    def f(idx, norms, lv):
        return dequantize_blocks(idx, norms, lv, num_symbols=q.num_symbols,
                                 bits=bits)

    _compile(f, _sds((nb, cols), jnp.int8, one_chip),
             _sds((nb,), jnp.float32, one_chip),
             _sds((q.num_symbols,), jnp.float32, one_chip))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_reduce_compiles(one_chip, nb, bits):
    q = _quant(bits)
    cols = BUCKET if bits == 8 else BUCKET // 2
    nbk = -(-nb // K)  # each worker's chunk of the two-phase exchange

    def f(idx, norms, lv):
        return dequant_reduce_blocks(idx, norms, lv,
                                     num_symbols=q.num_symbols,
                                     num_workers=K, bits=bits)

    _compile(f, _sds((K, nbk, cols), jnp.int8, one_chip),
             _sds((K, nbk), jnp.float32, one_chip),
             _sds((q.num_symbols,), jnp.float32, one_chip))


@pytest.mark.parametrize("bits,prng", [(8, False), (4, False), (4, True)])
def test_dequant_reduce_requantize_compiles(one_chip, nb, bits, prng):
    q = _quant(bits)
    cols = BUCKET if bits == 8 else BUCKET // 2
    nbk = -(-nb // K)

    def f(idx, norms, lv, noise, seed):
        return dequant_reduce_requantize_blocks(
            idx, norms, lv, None if prng else noise,
            num_symbols=q.num_symbols, num_workers=K, q_is_inf=False,
            bits=bits, use_device_prng=prng, seed=seed if prng else None)

    _compile(f, _sds((K, nbk, cols), jnp.int8, one_chip),
             _sds((K, nbk), jnp.float32, one_chip),
             _sds((q.num_symbols,), jnp.float32, one_chip),
             _sds((nbk, BUCKET), jnp.float32, one_chip),
             _sds((1,), jnp.int32, one_chip))


@pytest.mark.parametrize("prng", [False, True])
def test_segment_qdq_compiles(one_chip, nb, prng):
    # two level tables (int8 and int4 policies) in one planned buffer
    nsym = (_quant(8).num_symbols, _quant(4).num_symbols)

    def f(x, noise, tables, seg, seed):
        return quantize_dequantize_segments(
            x, None if prng else noise, tables, seg, num_symbols=nsym,
            q_is_inf=False, use_device_prng=prng,
            seed=seed if prng else None)

    _compile(f, _sds((nb, BUCKET), jnp.float32, one_chip),
             _sds((nb, BUCKET), jnp.float32, one_chip),
             _sds((2, max(nsym)), jnp.float32, one_chip),
             _sds((nb,), jnp.int32, one_chip),
             _sds((1,), jnp.int32, one_chip))


def test_kernel_platform_follows_the_devices(one_chip):
    """One kernel call: Mosaic-compiled when lowered for the TPU, run by
    the Pallas interpreter on the CPU (and right there)."""
    q = _quant(8)
    lv = jnp.linspace(0.0, 1.0, q.num_symbols, dtype=jnp.float32)

    def f(idx, norms, lv):
        return dequantize_blocks(idx, norms, lv, num_symbols=q.num_symbols)

    idx = jnp.ones((3, BUCKET), jnp.int8)
    norms = jnp.full((3,), 2.0, jnp.float32)
    on_cpu = jax.jit(f).lower(idx, norms, lv).compile()
    assert "tpu_custom_call" not in on_cpu.as_text()
    np.testing.assert_allclose(np.asarray(on_cpu(idx, norms, lv)),
                               2.0 * float(lv[1]), rtol=1e-6)
    _compile(f, _sds(idx.shape, idx.dtype, one_chip),
             _sds(norms.shape, norms.dtype, one_chip),
             _sds(lv.shape, lv.dtype, one_chip))


def test_exchange_compiles_on_2x2(topo, n):
    """The Pallas two-phase exchange of one layer's gradient across the
    four chips: kernels plus the all-to-all / all-gather between them."""
    mesh = Mesh(np.array(topo.devices).reshape(K), ("data",))
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx", quant=_quant(8), mode="two_phase",
        axis_name="data", use_pallas=True))
    repl = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, repl), jax.eval_shape(ex.init_state))

    def f(x, st, key):
        def body(xl, stl, k):
            mean, stl = ex.pmean(xl[0], stl, k)
            return mean[None], stl

        return jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P(), P()),
            out_specs=(P("data"), P()), check_vma=False)(x, st, key)

    compiled = _compile(
        f, _sds((K, n), jnp.float32, NamedSharding(mesh, P("data"))), state,
        _sds((2,), jnp.uint32, repl))
    text = compiled.as_text()
    assert "all-to-all" in text and "all-gather" in text
