"""Jitted public wrappers around the Pallas quantize/dequantize kernels.

Drop-in replacements for :func:`repro.core.quantization.quantize` /
``dequantize`` that route the hot inner loop through the Pallas kernels.
In 4-bit mode the pack/unpack happens *inside* the kernels, so the
``Quantized.payload`` these wrappers produce/consume is the in-kernel
packed buffer — byte-identical to the host-side
:func:`repro.core.quantization.pack_int4` layout.

Mosaic compiles the kernels on a TPU and the Pallas interpreter runs them
elsewhere (``kernels.common.tpu_pallas_call``); on a TPU,
``use_device_prng=True`` draws the rounding bits on-core and skips the
host noise buffer entirely.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.quantization import (
    QuantConfig,
    Quantized,
    _pad_to_buckets,
)
from repro.kernels.common import derive_prng_seed
from repro.kernels.dequantize import dequantize_blocks
from repro.kernels.quantize import quantize_blocks


def quantize_pallas(
    v: jax.Array,
    levels: jax.Array,
    key: jax.Array,
    cfg: QuantConfig,
    *,
    use_device_prng: bool = False,
) -> Quantized:
    flat = v.reshape(-1)
    x2d, n = _pad_to_buckets(flat, cfg.bucket_size)
    if use_device_prng:
        noise = None
        seed = derive_prng_seed(key)
    else:
        noise = jax.random.uniform(key, x2d.shape, dtype=jnp.float32)
        seed = None
    idx, norms = quantize_blocks(
        x2d,
        noise,
        levels,
        num_symbols=cfg.num_symbols,
        q_is_inf=math.isinf(cfg.q_norm),
        bits=cfg.bits,
        use_device_prng=use_device_prng,
        seed=seed,
    )
    return Quantized(payload=idx.reshape(-1), norms=norms, n=n)


def dequantize_pallas(
    qt: Quantized,
    levels: jax.Array,
    cfg: QuantConfig,
) -> jax.Array:
    payload_cols = cfg.bucket_size if cfg.bits == 8 else cfg.bucket_size // 2
    idx2d = qt.payload.reshape(-1, payload_cols)
    out = dequantize_blocks(
        idx2d,
        qt.norms,
        levels,
        num_symbols=cfg.num_symbols,
        bits=cfg.bits,
    )
    return out.reshape(-1)[: qt.n]
