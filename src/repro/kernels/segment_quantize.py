"""Pallas TPU kernel for segment-fused quantize∘dequantize (ExchangePlan).

The ``compress_tree`` / parameter re-centering paths used to launch one
quantize and one dequantize invocation PER LEAF, each with its own padding
tail.  With an :class:`~repro.core.exchange_plan.ExchangePlan` the whole
pytree lives in one flat buffer whose bucket rows are mapped to level
tables by a static segment table — this kernel consumes that layout in a
single invocation: the stacked ``[T, S_max]`` level-table buffer sits in
SMEM (read a scalar at a time like every exchange kernel's table, and
selected per row by the segment id), the bracket search is one masked
compare pass over the union of interior levels, and the payload
indices never leave registers — only the dequantized f32 estimate is
written, so HBM traffic is read-4n + write-4n regardless of how many
per-layer policies the plan carries.

Like every exchange kernel: host-noise mode (``use_device_prng=False``,
bit-compatible with the jnp reference, and the only mode the Pallas
interpreter runs) or the on-core PRNG (TPU only, seeded per grid step
from a traced int32 scalar).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    ROWS_PER_BLOCK,
    prng_uniform,
    row_block,
    row_grid,
    segment_quant_dequant_rows,
    tpu_pallas_call,
)


def _seg_qdq_kernel(
    *refs,  # x [BB, bucket] f32; noise [BB, bucket] f32 | seed [1] i32 SMEM;
            # seg [1, BB] i32; tables [T, S_max] f32 SMEM; out [BB, bucket] f32
    num_symbols: tuple,
    q_is_inf: bool,
    stochastic: bool,
    use_device_prng: bool,
):
    if use_device_prng:
        x_ref, seg_ref, tables_ref, seed_ref, out_ref = refs
        r = prng_uniform(seed_ref, x_ref.shape)
    else:
        x_ref, noise_ref, seg_ref, tables_ref, out_ref = refs
        r = noise_ref[...]
    out_ref[...] = segment_quant_dequant_rows(
        x_ref[...], tables_ref, seg_ref[0], r,
        num_symbols=num_symbols, q_is_inf=q_is_inf, stochastic=stochastic,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_symbols", "q_is_inf", "stochastic", "use_device_prng",
    ),
)
def quantize_dequantize_segments(
    x2d: jax.Array,
    noise,
    tables: jax.Array,
    seg_ids: jax.Array,
    *,
    num_symbols: tuple,
    q_is_inf: bool,
    stochastic: bool = True,
    use_device_prng: bool = False,
    seed=None,
):
    """Fused Q∘DEQ of [nb, bucket] f32 under per-row level tables.

    ``tables``: stacked ``[T, S_max]`` level tables (SMEM); ``seg_ids``:
    [nb] int32 table id per bucket row; ``num_symbols``: static tuple of
    live symbol counts per table.  Returns the [nb, bucket] f32 unbiased
    estimate ``hat x`` — no payload buffer is materialized.

    ``use_device_prng=True`` (TPU only): ``noise`` must be None and
    ``seed`` a traced int32 [1]; rounding bits are drawn on-core.
    """
    nb, bucket = x2d.shape
    if seg_ids.shape != (nb,):
        raise ValueError(f"seg_ids must be [nb]={nb}, got {seg_ids.shape}")
    inputs = [x2d.astype(jnp.float32)]
    in_specs = [pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0))]
    if not use_device_prng:
        if noise is None:
            raise ValueError("host-noise path needs the uniform noise buffer")
        inputs.append(noise.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0)))
    inputs.append(seg_ids.astype(jnp.int32)[None])
    in_specs.append(row_block(1))
    inputs.append(tables.astype(jnp.float32))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if use_device_prng:
        if seed is None:
            raise ValueError("use_device_prng needs a traced int32 seed array [1]")
        inputs.append(jnp.asarray(seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    kernel = functools.partial(
        _seg_qdq_kernel,
        num_symbols=num_symbols,
        q_is_inf=q_is_inf,
        stochastic=stochastic,
        use_device_prng=use_device_prng,
    )
    out = tpu_pallas_call(
        kernel,
        grid=row_grid(nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), jnp.float32),
    )(*inputs)
    return out
