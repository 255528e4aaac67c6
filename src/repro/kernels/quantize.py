"""Pallas TPU kernel for unbiased bucketed quantization (Definition 1).

This is the bandwidth-critical hot spot of Q-GenX: every iteration each
worker compresses its full dual vector (the gradient pytree) before the
collective exchange.  The kernel is a pure VPU/bandwidth kernel — no MXU —
so the design goals are (a) stream HBM->VMEM in (8,128)-aligned tiles,
(b) one pass: norm reduction, normalization, level search, stochastic
rounding, int8 emission AND int4 packing fused, (c) per-bucket norms
computed on-chip so the f32 input is read exactly once.

Layout: the wrapper reshapes the flat vector to [nb, bucket] and every
grid step works on one (ROWS_PER_BLOCK, bucket) tile (the last one may be
partial); the per-row norms leave as one lane-dense [1, rows] row.  The level table (s+2 <= 128 scalars) sits in
SMEM and is read a scalar at a time (see kernels/common.py).

In 4-bit mode the payload is packed two-per-byte *inside* the kernel —
the [nb, bucket/2] int8 buffer this kernel writes is exactly what the
collective moves, halving wire bytes versus shipping unpacked indices.

Randomness: production TPUs use the on-core PRNG (``use_device_prng=True``
— ``pltpu.prng_seed`` / ``prng_random_bits`` seeded from a traced int32
scalar), which skips generating and re-reading a full-size f32 noise
buffer every exchange.  The Pallas interpreter (any platform but a TPU)
cannot lower those primitives, so the path it validates streams uniform
noise generated with ``jax.random`` (bit-compatible with the jnp
reference oracle) — selected by ``use_device_prng=False`` (default).
See DESIGN.md §Hardware adaptation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    ROWS_PER_BLOCK,
    pack4_rows,
    prng_uniform,
    quant_rows,
    row_block,
    row_grid,
    tpu_pallas_call,
)


def _quantize_kernel(
    *refs,  # x [BB, bucket] f32; noise [BB, bucket] f32 | seed [1] i32 SMEM;
            # levels [s+2] f32 SMEM; out: idx [BB, P] int8, norms [1, BB] f32
    num_symbols: int,
    q_is_inf: bool,
    pack4: bool,
    use_device_prng: bool,
):
    if use_device_prng:
        x_ref, levels_ref, seed_ref, idx_ref, norms_ref = refs
    else:
        x_ref, noise_ref, levels_ref, idx_ref, norms_ref = refs
    x = x_ref[...]
    r = prng_uniform(seed_ref, x.shape) if use_device_prng else noise_ref[...]
    signed, norms = quant_rows(x, levels_ref, r, num_symbols, q_is_inf)
    norms_ref[...] = norms[None, :]
    idx_ref[...] = pack4_rows(signed) if pack4 else signed.astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("num_symbols", "q_is_inf", "bits", "use_device_prng"),
)
def quantize_blocks(
    x2d: jax.Array,
    noise,
    levels: jax.Array,
    *,
    num_symbols: int,
    q_is_inf: bool,
    bits: int = 8,
    use_device_prng: bool = False,
    seed=None,
):
    """Quantize [nb, bucket] f32 -> (payload int8, f32 norms).

    The payload is [nb, bucket] signed indices (``bits=8``) or the packed
    [nb, bucket // 2] two-per-byte buffer (``bits=4``) — in 4-bit mode the
    packing happens inside the kernel, so this buffer is the wire payload.

    ``use_device_prng=True`` (TPU only): ``noise`` must be None and
    ``seed`` a traced int32 array of shape [1]; the kernel draws its own
    stochastic-rounding bits on-core instead of reading a noise buffer.
    """
    nb, bucket = x2d.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if bits == 4 and bucket % 2:
        raise ValueError("4-bit packing needs an even bucket size")
    payload_cols = bucket if bits == 8 else bucket // 2
    inputs = [x2d.astype(jnp.float32)]
    in_specs = [pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0))]
    if not use_device_prng:
        if noise is None:
            raise ValueError("host-noise path needs the uniform noise buffer")
        inputs.append(noise.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0)))
    inputs.append(levels.astype(jnp.float32))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if use_device_prng:
        if seed is None:
            raise ValueError("use_device_prng needs a traced int32 seed array [1]")
        inputs.append(jnp.asarray(seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    kernel = functools.partial(
        _quantize_kernel,
        num_symbols=num_symbols,
        q_is_inf=q_is_inf,
        pack4=bits == 4,
        use_device_prng=use_device_prng,
    )
    idx, norms = tpu_pallas_call(
        kernel,
        grid=row_grid(nb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((ROWS_PER_BLOCK, payload_cols), lambda i: (i, 0)),
            row_block(1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, payload_cols), jnp.int8),
            jax.ShapeDtypeStruct((1, nb), jnp.float32),
        ],
    )(*inputs)
    return idx, norms[0]
