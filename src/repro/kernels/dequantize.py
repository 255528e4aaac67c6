"""Pallas TPU kernel for dequantization (DEQ of Algorithm 1).

Reads the wire payload (int8 signed indices, or the packed two-per-byte
int4 buffer) and per-bucket norms, reconstructs f32 values:
v = sign(idx) * levels[|idx|] * norm_bucket.  Like the quantizer this is a
pure bandwidth kernel; the payload is 4x (8x packed) smaller than the
output, so the kernel is output-bandwidth-bound — tiles are chosen so each
(ROWS_PER_BLOCK, bucket) f32 output tile is produced from a single
contiguous int8 input tile.  The level lookup reads the SMEM level table
a scalar at a time (kernels/common.py); int4 unpacking happens in-kernel
so the packed buffer is read directly off the wire.
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
    row_block,
    row_grid,
    tpu_pallas_call,
    unpack4_rows,
)


def _dequantize_kernel(
    idx_ref,     # [BB, P] int8 VMEM (P = bucket, or bucket/2 packed)
    norms_ref,   # [1, BB] f32 VMEM
    levels_ref,  # [s+2] f32 SMEM
    out_ref,     # [BB, bucket] f32 VMEM
    *,
    pack4: bool,
):
    signed = idx_ref[...]
    signed = unpack4_rows(signed) if pack4 else signed.astype(jnp.int32)
    out_ref[...] = dequant_rows(signed, levels_ref, norms_ref[0])


@functools.partial(
    jax.jit, static_argnames=("num_symbols", "bits")
)
def dequantize_blocks(
    idx2d: jax.Array,
    norms: jax.Array,
    levels: jax.Array,
    *,
    num_symbols: int,
    bits: int = 8,
):
    """DEQ [nb, P] payload -> [nb, bucket] f32 (P = bucket or bucket/2).

    ``num_symbols`` is kept for API symmetry with the quantizer (the gather
    needs only the level table itself).
    """
    del num_symbols
    nb, payload_cols = idx2d.shape
    bucket = payload_cols if bits == 8 else payload_cols * 2
    kernel = functools.partial(_dequantize_kernel, pack4=bits == 4)
    out = tpu_pallas_call(
        kernel,
        grid=row_grid(nb),
        in_specs=[
            pl.BlockSpec((ROWS_PER_BLOCK, payload_cols), lambda i: (i, 0)),
            row_block(1),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), jnp.float32),
    )(idx2d, norms.astype(jnp.float32)[None], levels.astype(jnp.float32))
    return out
