"""Shared row-level primitives for the fused exchange kernels.

Every exchange kernel (quantize, dequantize, dequant+reduce,
dequant+reduce+requantize) operates on [rows, bucket] tiles where a row is
one norm bucket.  This module holds the pieces they compose:

* ``quant_rows`` / ``dequant_rows`` — the Definition-1 value maps.  The
  level-bracket search is one vectorized compare pass over the s interior
  levels that also carries the bracket endpoints (a select per level),
  and the dequant value lookup is a select chain over the level table:
  the table sits in SMEM, which a TPU kernel reads one scalar at a time
  (Mosaic has no vector gather from SMEM).
* ``pack4_rows`` / ``unpack4_rows`` — in-kernel int4 two-per-byte packing,
  so the payload a kernel emits is the payload that goes on the wire
  (DESIGN.md §Wire format).  Column pairs are split and joined on the
  sublane axis of the transposed tile: TPU lanes have no strided slice.
* ``row_grid`` / ``row_block`` — the row tiling every kernel shares.
* ``tpu_pallas_call`` — one kernel, compiled by Mosaic where the call is
  lowered for a TPU and run by the Pallas interpreter elsewhere.

All helpers are pure jnp on values, so they are usable both inside Pallas
kernel bodies and in the jnp reference oracles (bit-exact by construction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bucket rows per grid step.  Per-row norms and segment ids travel as
#: lane-dense [K, rows] blocks, and Mosaic tiles the last dimension of a
#: block in units of 128 lanes, so a tile spans 128 bucket rows
#: (bucket=512 -> 256 KiB f32).
ROWS_PER_BLOCK = 128


def row_grid(nb: int) -> tuple:
    """Grid over ``nb`` bucket rows in ROWS_PER_BLOCK tiles.  The last
    tile may hang past the last row: its reads there are padding and its
    writes there are dropped, and every kernel is row-local — so no
    padded copy of a (gradient-sized) input is ever made."""
    return (pl.cdiv(nb, ROWS_PER_BLOCK),)


def row_block(k: int) -> pl.BlockSpec:
    """BlockSpec of a [k, rows] per-row vector (norms, segment ids):
    grid step i gets the [k, ROWS_PER_BLOCK] slice of rows i*128.."""
    return pl.BlockSpec((k, ROWS_PER_BLOCK), lambda i: (0, i))


def tpu_pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` that Mosaic compiles where the
    call is lowered for a TPU, and that the Pallas interpreter runs on any
    other platform.  The choice is made at lowering time from the devices
    the computation is placed on, so a program compiled for a TPU never
    carries an interpreted kernel."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call


def derive_prng_seed(key):
    """Traced int32[1] seed for the in-kernel PRNG, derived from a jax key.

    The single place the key -> on-core-PRNG-seed contract lives; the
    kernel adds ``pl.program_id`` per grid step on top.
    """
    return jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)


def prng_uniform(seed_ref, shape):
    """In-kernel uniform [0, 1) draw from the on-core PRNG (TPU only).

    Seeds per grid step from the traced ``seed_ref`` scalar.  The bits come
    back int32, so the sign extension of the arithmetic shift is masked off
    AFTER shifting to keep the 24-bit mantissa draw uniform.
    """
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    bits32 = pltpu.prng_random_bits(shape)
    return ((bits32 >> 8) & 0xFFFFFF).astype(jnp.float32) * (2.0**-24)


def norm_rows(x, q_is_inf: bool):
    """Per-row L^inf or L^2 norm of a [rows, bucket] f32 tile."""
    if q_is_inf:
        return jnp.max(jnp.abs(x), axis=1)
    return jnp.sqrt(jnp.sum(x * x, axis=1))


def pack4_rows(signed_idx):
    """Pack signed 4-bit indices two-per-byte along the bucket axis.

    [rows, bucket] int32 in [-7, 7] -> [rows, bucket // 2] int8 with
    byte = (a & 0xF) | ((b & 0xF) << 4) for column pairs (2j, 2j + 1) —
    the same flat order as :func:`repro.core.quantization.pack_int4`.
    """
    rows, bucket = signed_idx.shape
    pairs = (signed_idx & 0xF).T.reshape(bucket // 2, 2, rows)
    return (pairs[:, 0, :] | (pairs[:, 1, :] << 4)).T.astype(jnp.int8)


def unpack4_rows(packed):
    """Inverse of :func:`pack4_rows`: [rows, P] int8 -> [rows, 2P] int32."""
    u = packed.astype(jnp.int32) & 0xFF
    a = u & 0xF
    b = (u >> 4) & 0xF
    a = jnp.where(a >= 8, a - 16, a)
    b = jnp.where(b >= 8, b - 16, b)
    rows, half = packed.shape
    return jnp.stack([a.T, b.T], axis=1).reshape(2 * half, rows).T


def level_at(lv, idx):
    """``lv[idx]`` for an int array ``idx`` with entries in
    [0, len(lv)): a select chain over the table, whose entries are read
    as scalars (``lv`` may be an SMEM ref or an array)."""
    out = jnp.full(idx.shape, lv[0], jnp.float32)
    for j in range(1, lv.shape[0]):
        out = jnp.where(idx == j, lv[j], out)
    return out


def dequant_rows(signed_idx, lv, norms):
    """DEQ: signed int32 indices [rows, bucket] -> f32 values."""
    vals = level_at(lv, jnp.abs(signed_idx))
    sign = jnp.where(signed_idx < 0, -1.0, 1.0)
    return vals * sign * norms[:, None]


def segment_quant_dequant_rows(x, tables, seg, r, *, num_symbols,
                               q_is_inf: bool, stochastic: bool = True):
    """Fused Q∘DEQ over [rows, bucket] tiles with a PER-ROW level table.

    The segment-fused twin of :func:`quant_rows` + :func:`dequant_rows`
    (ExchangePlan): ``tables`` is the stacked ``[T, S_max]`` level-table
    buffer (short tables right-padded with 1.0 — see
    ``exchange_plan.stack_level_tables``), ``seg`` maps each bucket row
    to its table, ``num_symbols`` is the static tuple of live symbol
    counts per table.  One pass: row norms, normalization, a masked
    level search over the UNION of interior levels (rows of shorter
    tables mask the surplus comparisons) that carries the bracket
    endpoints, stochastic rounding against ``r``, and the dequantized
    value — the payload indices never materialize, so a planned
    ``compress_tree`` is one invocation instead of a quantize +
    dequantize launch per leaf.  ``tables`` may be the SMEM table ref
    or an array.

    For T = 1 this is bit-identical to ``dequant_rows(quant_rows(...))``
    with the same noise (same bracket math, same table entries).
    """
    norms = norm_rows(x, q_is_inf)
    safe = jnp.where(norms > 0, norms, 1.0)
    u = jnp.clip(jnp.abs(x) / safe[:, None], 0.0, 1.0)
    s_max = tables.shape[1]
    n_tables = len(num_symbols)
    segc = seg[:, None]

    def row_level(j):
        # level j of each row's own table, [rows, 1]: scalar table reads
        # selected by the row's segment id
        out = jnp.full(segc.shape, tables[0, j], jnp.float32)
        for t in range(1, n_tables):
            out = jnp.where(segc == t, tables[t, j], out)
        return out

    # bracket [lo, hi] = [level tau, level tau + 1], tau = #{interior
    # levels <= u}; levels ascend, so the last hit is level tau
    lo, hi = row_level(0), row_level(1)
    for j in range(1, s_max - 1):
        # tables whose interior includes level j (static set — rows of
        # shorter tables mask the surplus comparisons)
        live = [t for t in range(n_tables) if j <= num_symbols[t] - 2]
        if not live:
            continue
        hit = u >= row_level(j)
        if len(live) < n_tables:
            act = jnp.zeros(segc.shape, jnp.bool_)
            for t in live:
                act = act | (segc == t)
            hit = hit & act
        lo = jnp.where(hit, row_level(j), lo)
        hi = jnp.where(hit, row_level(j + 1), hi)
    xi = (u - lo) / (hi - lo)
    up = (r < xi) if stochastic else (xi >= 0.5)
    vals = jnp.where(up, hi, lo)
    signed = jnp.where(x < 0, -vals, vals)
    return signed * norms[:, None]


def quant_rows(x, lv, r, num_symbols: int, q_is_inf: bool):
    """Q: f32 [rows, bucket] -> (signed int32 indices, f32 row norms).

    One pass: row norms, normalization, level search (single vectorized
    compare pass over the s interior levels, carrying the bracket
    endpoints), stochastic rounding against uniform noise ``r``.
    ``lv`` may be the SMEM level-table ref or an array.  Bit-compatible
    with the ``searchsorted``-based jnp oracle.
    """
    norms = norm_rows(x, q_is_inf)
    safe = jnp.where(norms > 0, norms, 1.0)
    u = jnp.clip(jnp.abs(x) / safe[:, None], 0.0, 1.0)
    # tau = #{j >= 1 : levels[j] <= u}, in [0, s]; u = 1.0 deterministically
    # reaches the top bracket (levels[s+1] = 1 is excluded from the count).
    # Levels ascend, so the last hit j is tau: the bracket endpoints
    # lo = levels[tau], hi = levels[tau + 1] ride along the same pass.
    tau = jnp.zeros(u.shape, jnp.int32)
    lo = jnp.full(u.shape, lv[0], jnp.float32)
    hi = jnp.full(u.shape, lv[1], jnp.float32)
    for j in range(1, num_symbols - 1):
        hit = u >= lv[j]
        tau += hit.astype(jnp.int32)
        lo = jnp.where(hit, lv[j], lo)
        hi = jnp.where(hit, lv[j + 1], hi)
    xi = (u - lo) / (hi - lo)
    up = (r < xi).astype(jnp.int32)
    idx = tau + up
    return jnp.where(x < 0, -idx, idx), norms
