"""Pallas TPU kernels: fused consumer side of Algorithm 1's exchange.

``dequant_reduce_blocks`` — after the ``all_gather`` each device holds K
payloads + K norm vectors and must produce ``mean_k DEQ(payload_k)``.
Doing this as dequantize-then-mean (two jnp ops) writes K full f32 buffers
to HBM and reads them back; this kernel streams the K payloads
tile-by-tile through VMEM and emits only the final mean — HBM traffic
drops from ``(2K+1) x 4n`` bytes to ``K x n x per + 4n`` (the payload
reads plus one f32 write; per = 1 for int8, 1/2 packed int4) — ~8x less
at K=8, ~16x in 4-bit mode.

``dequant_reduce_requantize_blocks`` — the two-phase middle step.  The
seed pipeline ran dequantize + mean + quantize as three kernels
(~(3K+2) x 4n bytes of HBM traffic); this kernel fuses all three: the
reduced f32 chunk never leaves VMEM, only the requantized payload
(K x n x per read + n x per write, plus the noise read on the host-noise
path).  With on-device PRNG and 4-bit packing that is the paper-grade
``K x n/2 + n/2`` wire-and-HBM figure.

Grid tiles rows of buckets (``row_grid``); the K-reduction is an unrolled loop in the kernel body (K is a static mesh
constant: 2 pods / 3 GAN nodes / 8 DP hosts), so partial sums live in
VREGs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    ROWS_PER_BLOCK,
    dequant_rows,
    pack4_rows,
    prng_uniform,
    quant_rows,
    row_block,
    row_grid,
    tpu_pallas_call,
    unpack4_rows,
)


def _mean_rows(idx_ref, norms_ref, lv, num_workers: int, pack4: bool):
    """Accumulate mean_k DEQ(payload_k) for one [BB, bucket] tile."""
    acc = None
    for k in range(num_workers):  # static unroll — K is a mesh constant
        signed = idx_ref[k]
        signed = unpack4_rows(signed) if pack4 else signed.astype(jnp.int32)
        term = dequant_rows(signed, lv, norms_ref[k])
        acc = term if acc is None else acc + term
    return acc * (1.0 / num_workers)


def _dequant_reduce_kernel(
    idx_ref,     # [K, BB, P] int8 VMEM (P = bucket, or bucket/2 packed)
    norms_ref,   # [K, BB] f32 VMEM
    levels_ref,  # [s+2] f32 SMEM
    out_ref,     # [BB, bucket] f32 VMEM
    *,
    num_workers: int,
    pack4: bool,
):
    out_ref[...] = _mean_rows(idx_ref, norms_ref, levels_ref, num_workers, pack4)


@functools.partial(
    jax.jit, static_argnames=("num_symbols", "num_workers", "bits")
)
def dequant_reduce_blocks(
    idx: jax.Array,    # [K, nb, P] int8
    norms: jax.Array,  # [K, nb] f32
    levels: jax.Array,
    *,
    num_symbols: int,
    num_workers: int,
    bits: int = 8,
):
    """Fused DEQ + mean over K workers -> [nb, bucket] f32."""
    del num_symbols
    K, nb, payload_cols = idx.shape
    assert K == num_workers
    bucket = payload_cols if bits == 8 else payload_cols * 2
    kernel = functools.partial(
        _dequant_reduce_kernel, num_workers=num_workers, pack4=bits == 4
    )
    out = tpu_pallas_call(
        kernel,
        grid=row_grid(nb),
        in_specs=[
            pl.BlockSpec((K, ROWS_PER_BLOCK, payload_cols), lambda i: (0, i, 0)),
            row_block(K),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), jnp.float32),
    )(idx, norms.astype(jnp.float32), levels.astype(jnp.float32))
    return out


def _dequant_reduce_requant_kernel(
    *refs,  # idx [K, BB, P]; norms [K, BB]; noise [BB, bucket] | seed [1];
            # levels SMEM; out: idx [BB, P] int8, norms [1, BB] f32
    num_symbols: int,
    num_workers: int,
    q_is_inf: bool,
    pack4: bool,
    use_device_prng: bool,
):
    if use_device_prng:
        idx_ref, norms_ref, levels_ref, seed_ref, oidx_ref, onorms_ref = refs
    else:
        idx_ref, norms_ref, noise_ref, levels_ref, oidx_ref, onorms_ref = refs
    reduced = _mean_rows(idx_ref, norms_ref, levels_ref, num_workers, pack4)
    r = prng_uniform(seed_ref, reduced.shape) if use_device_prng else noise_ref[...]
    signed, norms2 = quant_rows(reduced, levels_ref, r, num_symbols, q_is_inf)
    onorms_ref[...] = norms2[None, :]
    oidx_ref[...] = pack4_rows(signed) if pack4 else signed.astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_symbols", "num_workers", "q_is_inf", "bits", "use_device_prng"
    ),
)
def dequant_reduce_requantize_blocks(
    idx: jax.Array,    # [K, nb, P] int8
    norms: jax.Array,  # [K, nb] f32
    levels: jax.Array,
    noise,             # [nb, bucket] f32, or None with use_device_prng
    *,
    num_symbols: int,
    num_workers: int,
    q_is_inf: bool,
    bits: int = 8,
    use_device_prng: bool = False,
    seed=None,
):
    """Fused DEQ + mean + re-quantize -> (payload [nb, P] int8, norms [nb]).

    One kernel for the whole two-phase middle step: the reduced f32 chunk
    lives only in VMEM.  The re-quantization draws fresh unbiased noise
    (``noise`` buffer, or on-device PRNG), so the output is itself an
    unbiased quantization of the chunk mean (Theorem 1 composes).
    """
    K, nb, payload_cols = idx.shape
    assert K == num_workers
    bucket = payload_cols if bits == 8 else payload_cols * 2
    inputs = [idx, norms.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((K, ROWS_PER_BLOCK, payload_cols), lambda i: (0, i, 0)),
        row_block(K),
    ]
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
        _dequant_reduce_requant_kernel,
        num_symbols=num_symbols,
        num_workers=num_workers,
        q_is_inf=q_is_inf,
        pack4=bits == 4,
        use_device_prng=use_device_prng,
    )
    oidx, onorms = tpu_pallas_call(
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
    return oidx, onorms[0]


def dequant_reduce_ref(idx, norms, levels):
    """Pure-jnp oracle: mean_k levels[|idx_k|] * sign(idx_k) * norm_k.

    Takes *unpacked* int8 indices [K, nb, bucket] (use
    :func:`repro.kernels.common.unpack4_rows` first for packed payloads).
    """
    signed = idx.astype(jnp.int32)
    vals = levels.astype(jnp.float32)[jnp.abs(signed)]
    out = vals * jnp.sign(signed).astype(jnp.float32) * norms[..., None]
    return jnp.mean(out, axis=0)
