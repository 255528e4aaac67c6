"""ExchangePlan — static flat-buffer layout for tree exchanges.

Every tree exchange before this module rebuilt its memory layout at every
call: ``Compressor.pmean_tree`` ran a fresh ``jnp.concatenate`` over all
reshaped+cast leaves and then a second full copy when ``_qgenx_pmean``
padded the result to bucket/chunk alignment (two extra HBM round-trips of
the gradient per sync), and the ``compress_tree`` / re-centering paths
launched one quantize+dequantize invocation per leaf, each with its own
padding tail.

An :class:`ExchangePlan` precomputes the layout ONCE per (leaf shapes,
exchange config, axis size) — it is pure static metadata, cached on those
keys — and every planned call routes through it:

* **leaf table** — the order leaves are packed, their coordinate
  ``offsets`` into the flat buffer, shapes and dtypes (what
  :meth:`ExchangePlan.unpack` slices back out);
* **segment table** — contiguous ``[start, stop)`` ranges of the buffer,
  each carrying its own :class:`~repro.core.quantization.QuantConfig`
  (per-layer bit-widths), which ``ExchangeState`` level table quantizes
  it, and the exchange-key tag — the per-layer-policy generalization of
  "one flat vector";
* **tile-aligned padding** — each segment ends on its own bucket (or
  ``axis_size * bucket`` two-phase quota) boundary, so the packed buffer
  needs NO further padding downstream: :meth:`ExchangePlan.pack` emits
  one ``jnp.concatenate`` of the leaf views plus the static zero tails —
  one write of the buffer in its final wire layout, in place of the old
  concatenate-then-pad double copy.

The padding semantics are the exact ones the per-call path used (leaves
concatenated contiguously in group order, one shared tail per segment),
which is what makes the planned qgenx gather/two_phase exchange
*bit-exact* with the unplanned one — same buffer, same noise draws, same
collectives (the parity grid in ``tests/test_exchange_plan.py`` pins
this).  For per-leaf-policy compressors the plan's segment table feeds the
segment-fused quantization (:mod:`repro.kernels.segment_quantize`): one
(Pallas-capable) invocation per row-geometry class with segment-indexed
level tables, instead of one launch per leaf.

Wire accounting stays honest about the layout change: a planned
``compress_tree`` pays ONE padding tail per segment
(:meth:`ExchangePlan.compress_payload_bytes`) where the per-leaf path
paid one per leaf — the delta is documented and tested, never silently
absorbed.

The error-feedback compressors (ef21-topk / ef-randk) also route their
tree exchange through a plan — ``pack`` assembles the one flat buffer
their [num_workers, n] error memory indexes into, and ``unpack`` slices
the compensated mean back out.  Their segments are UNQUANTIZED (no level
table, no bucket quota), so the plan adds zero padding and the packed
length equals the plain sum of leaf sizes: the error matrix's column
count, the top-k support space, and the analytic 8k-byte wire bill all
agree on the same ``n`` by construction.

This module is layout + dispatch only; it imports nothing from
:mod:`repro.core.exchange` (the Exchange/compressor registry builds plans
through :func:`build_plan` and owns all collective logic).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import QuantConfig
from repro.kernels.common import derive_prng_seed

Array = jax.Array


# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanSegment:
    """One contiguous range of the flat buffer under one quantizer policy.

    Attributes:
      start: coordinate offset of the segment in the flat buffer.
      n: live coordinates (sum of the member leaves' sizes).
      padded: segment length INCLUDING its alignment tail; the next
        segment starts at ``start + padded``.
      table: which ExchangeState level table quantizes this segment
        (0 = ``levels``, 1 = ``levels_lo`` — the layerwise low-bit table).
      quant: the segment's QuantConfig (None = uncompressed policy;
        no alignment padding).
      key_tag: ``fold_in`` tag for this segment's exchange key (None =
        the call key is used as-is) — mirrors the per-group keys the
        unplanned layerwise path derives, keeping it bit-exact.
      leaf_ids: indices (into the flat leaf list) packed into this
        segment, in pack order.
    """

    start: int
    n: int
    padded: int
    table: int = 0
    quant: Optional[QuantConfig] = None
    key_tag: Optional[int] = None
    leaf_ids: tuple = ()

    @property
    def stop(self) -> int:
        return self.start + self.padded

    @property
    def pad(self) -> int:
        """Coordinates in this segment's shared padding tail."""
        return self.padded - self.n


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Static layout of one pytree in the flat exchange buffer.

    Built by :func:`build_plan` (cached); carries no traced values — only
    shapes, offsets and configs — so it is safe to close over in jitted
    functions and share across steps (XLA sees the same static layout
    every trace, which with donated carry state lets it reuse the buffer
    allocation across steps).
    """

    shapes: tuple  # per-leaf shape tuples, original tree order
    offsets: tuple  # per-leaf coord offset in the flat buffer
    pack_order: tuple  # leaf ids sorted by offset (group packing order)
    segments: tuple  # PlanSegment, ascending by start
    total: int  # flat buffer length incl. all padding tails
    n_live: int  # sum of leaf sizes

    # -- buffer movement ------------------------------------------------

    def pack(self, leaves) -> Array:
        """Leaves -> the flat f32 buffer, ONE concatenate in final layout.

        The zero tails are part of the concatenation, so no downstream
        pad (and no second copy of the gradient) is ever needed: the
        result is already bucket/quota aligned per segment.
        """
        parts, pos = [], 0
        for i in self.pack_order:
            off = self.offsets[i]
            if off > pos:  # previous segment's padding tail
                parts.append(jnp.zeros((off - pos,), jnp.float32))
            parts.append(leaves[i].reshape(-1).astype(jnp.float32))
            pos = off + _size(self.shapes[i])
        if pos < self.total:
            parts.append(jnp.zeros((self.total - pos,), jnp.float32))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def unpack(self, flat: Array, leaves) -> list:
        """Flat buffer -> per-leaf arrays (static slices at the plan's
        offsets, padding tails skipped), cast back to each leaf's dtype."""
        return [
            flat[off: off + l.size].reshape(l.shape).astype(l.dtype)
            for l, off in zip(leaves, self.offsets)
        ]

    # -- accounting -----------------------------------------------------

    def compress_payload_bytes(self) -> float:
        """Fixed-width broadcast bytes of ONE planned compression of this
        buffer: each segment pays its payload plus ONE shared padding
        tail (``quant.payload_bytes(segment.n)`` — the tail is exactly
        the bucket ceil), where the per-leaf path paid one tail per leaf.
        Uncompressed segments price f32.
        """
        total = 0.0
        for s in self.segments:
            if s.quant is None:
                total += 4.0 * s.n
            else:
                total += float(s.quant.payload_bytes(s.n))
        return total

    def describe(self) -> str:
        """One-line layout summary (docs/bench rows): per-segment
        ``[start:stop) table=T bits=B pad=P``."""
        return " | ".join(
            f"[{s.start}:{s.stop}) table={s.table} "
            f"bits={s.quant.bits if s.quant else 32} pad={s.pad}"
            for s in self.segments
        )


def size_of(s) -> int:
    """Coordinate count of an array / ShapeDtypeStruct / bare shape tuple
    — THE shape-product helper the plan and the exchange accounting
    share (one definition, offsets and wire bytes cannot disagree)."""
    shape = s.shape if hasattr(s, "shape") else s
    n = 1
    for d in shape:
        n *= d
    return int(n)


_size = size_of  # internal alias (plan code passes bare shape tuples)


def leaf_key(leaves) -> tuple:
    """Hashable static descriptor of a leaf list — the plan cache key.

    Accepts arrays, ShapeDtypeStructs, or bare shape tuples (the wire
    accounting hooks pass whichever they were handed).
    """
    out = []
    for l in leaves:
        shape = tuple(l.shape) if hasattr(l, "shape") else tuple(l)
        dt = jnp.dtype(l.dtype).name if hasattr(l, "dtype") else "float32"
        out.append((shape, dt))
    return tuple(out)


def _align(n: int, quant: Optional[QuantConfig], mode: str,
           axis_size: int, purpose: str) -> int:
    """Padded length of an n-coordinate segment.

    Mirrors (exactly) the padding the per-call path applied downstream:
    two-phase pmean pads to the ``axis_size * bucket`` chunk quota,
    everything else quantized pads to whole buckets, uncompressed
    segments don't pad.  (The sharding-preserving leafwise exchange has
    no flat buffer at all and stays outside the plan entirely.)
    """
    if quant is None or n == 0:
        return n
    quota = quant.bucket_size
    if purpose == "pmean" and mode == "two_phase":
        quota = axis_size * quant.bucket_size
    return -(-n // quota) * quota


@functools.lru_cache(maxsize=None)
def partition_leaf_ids(sizes: tuple, num_buckets: int) -> tuple:
    """Split leaf ids ``0..len(sizes)-1`` into ``num_buckets`` contiguous
    layer-ordered runs, greedily balanced by coordinate count.

    Contiguity in tree-flatten order is the load-bearing property: the
    bucketed exchange issues one quantize+collective chain per bucket as
    backprop produces that bucket's leaves, so a bucket must be a run of
    *adjacent* layers — never an interleaving (which would serialize the
    whole backward behind every bucket).  Each bucket is later planned
    independently through the compressor's own ``plan_groups``, so
    per-segment quantizer policies, tile padding, and key tags are
    decided exactly as in the monolithic plan, just over a sub-range.

    Effective bucket count is ``min(num_buckets, len(sizes))`` (every
    bucket non-empty).  Deterministic and cached: the same sizes always
    map to the same partition, which is what keeps bucketed wire
    accounting and the per-bucket recorder in static agreement.

    Returns a tuple of leaf-id tuples, ascending and contiguous.
    """
    n_leaves = len(sizes)
    k = max(1, min(int(num_buckets), n_leaves))
    if k == 1:
        return (tuple(range(n_leaves)),)
    total = sum(sizes)
    target = total / k
    out, cur, acc, remaining = [], [], 0, k
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        # close the bucket once it reaches the running average target,
        # but never leave fewer leaves than buckets still to fill
        left = n_leaves - i - 1
        if len(out) < k - 1 and acc >= target and left >= remaining - 1:
            out.append(tuple(cur))
            cur, acc = [], 0
            remaining -= 1
            total_left = total - sum(
                sizes[j] for b in out for j in b)
            target = total_left / max(remaining, 1)
    if cur:
        out.append(tuple(cur))
    # guarantee exactly k buckets: split trailing leaves off if the greedy
    # pass under-produced (can happen when one huge leaf dominates)
    while len(out) < k:
        for bi in range(len(out) - 1, -1, -1):
            if len(out[bi]) > 1:
                head, tail = out[bi][:-1], (out[bi][-1],)
                out = out[:bi] + [head, tail] + out[bi + 1:]
                break
        else:  # pragma: no cover — k <= n_leaves makes this unreachable
            break
    return tuple(tuple(b) for b in out)


@functools.lru_cache(maxsize=None)
def build_plan(leaves_key: tuple, groups: tuple, mode: str,
               axis_size: int, purpose: str) -> ExchangePlan:
    """Build (and cache) the plan for one static layout.

    Args:
      leaves_key: :func:`leaf_key` of the tree's leaves.
      groups: ``((leaf_ids, quant, table, key_tag), ...)`` — the
        compressor's grouping policy (one group per segment; a group
        with no leaves is dropped).  Group order IS buffer order.
      mode: exchange mode ("gather" | "two_phase" | "leafwise") — drives
        the alignment quota.
      axis_size: exchange-axis size (two-phase quota); 1 outside
        shard_map (compress paths).
      purpose: "pmean" (collective layout) or "compress" (per-worker
        broadcast layout — always plain bucket alignment).
    """
    sizes = [_size(shape) for shape, _ in leaves_key]
    offsets = [0] * len(sizes)
    pack_order, segments, pos = [], [], 0
    for ids, quant, table, key_tag in groups:
        ids = tuple(ids)
        if not ids:
            continue
        start = pos
        for i in ids:
            offsets[i] = pos
            pos += sizes[i]
            pack_order.append(i)
        n = pos - start
        padded = _align(n, quant, mode, axis_size, purpose)
        pos = start + padded
        segments.append(PlanSegment(
            start=start, n=n, padded=padded, table=table, quant=quant,
            key_tag=key_tag, leaf_ids=ids,
        ))
    return ExchangePlan(
        shapes=tuple(shape for shape, _ in leaves_key),
        # (leaf dtypes live only in the cache key; unpack() casts via the
        # caller's actual leaves, the single source of dtype truth)
        offsets=tuple(offsets),
        pack_order=tuple(pack_order),
        segments=tuple(segments),
        total=pos,
        n_live=sum(sizes),
    )


# ---------------------------------------------------------------------------
# Segment-fused compression dispatch (Q∘DEQ over the whole buffer)
# ---------------------------------------------------------------------------


def fused_compress(plan: ExchangePlan, flat: Array, tables: tuple,
                   key: Array, *, use_pallas: bool = False,
                   use_device_prng: bool = False) -> Array:
    """One fused quantize∘dequantize pass over the planned buffer.

    ``tables`` holds one (traced) level table per plan segment, in
    segment order.  Segments that share row geometry — (bucket size,
    norm order, rounding mode) — are processed by ONE kernel invocation
    with stacked segment-indexed level tables (held in SMEM by
    :mod:`repro.kernels.segment_quantize`); the per-leaf path paid
    one quantize + one dequantize launch per leaf.  Returns the f32
    ``hat`` buffer of length ``plan.total`` (padding tails stay zero in
    expectation; live coords are the Definition-1 unbiased estimate).
    """
    assert len(tables) == len(plan.segments)
    classes: dict = {}
    for si, seg in enumerate(plan.segments):
        q = seg.quant
        assert q is not None, "fused_compress needs quantized segments"
        geo = (q.bucket_size, float(q.q_norm), q.stochastic)
        classes.setdefault(geo, []).append(si)

    out_parts: list = [None] * len(plan.segments)
    for gi, (geo, seg_ids) in enumerate(sorted(classes.items())):
        bucket, q_norm, stochastic = geo
        q_is_inf = math.isinf(q_norm)
        chunks, row_tab, grp_tables = [], [], []
        for local_t, si in enumerate(seg_ids):
            seg = plan.segments[si]
            chunks.append(flat[seg.start: seg.stop])
            row_tab.extend([local_t] * (seg.padded // bucket))
            grp_tables.append(tables[si])
        x2d = (chunks[0] if len(chunks) == 1
               else jnp.concatenate(chunks)).reshape(-1, bucket)
        seg_rows = jnp.asarray(row_tab, jnp.int32)
        stacked, num_symbols = stack_level_tables(grp_tables)
        k = jax.random.fold_in(key, gi) if len(classes) > 1 else key
        if use_pallas:
            from repro.kernels.segment_quantize import (
                quantize_dequantize_segments,
            )

            if use_device_prng:
                noise, seed = None, derive_prng_seed(k)
            else:
                noise = jax.random.uniform(k, x2d.shape, jnp.float32)
                seed = None
            hat2d = quantize_dequantize_segments(
                x2d, noise, stacked, seg_rows,
                num_symbols=num_symbols, q_is_inf=q_is_inf,
                stochastic=stochastic, use_device_prng=use_device_prng,
                seed=seed,
            )
        else:
            from repro.kernels.common import segment_quant_dequant_rows

            noise = jax.random.uniform(k, x2d.shape, jnp.float32)
            hat2d = segment_quant_dequant_rows(
                x2d, stacked, seg_rows, noise,
                num_symbols=num_symbols, q_is_inf=q_is_inf,
                stochastic=stochastic,
            )
        hat = hat2d.reshape(-1)
        row0 = 0
        for si in seg_ids:
            seg = plan.segments[si]
            out_parts[si] = hat[row0: row0 + seg.padded]
            row0 += seg.padded
    return (out_parts[0] if len(out_parts) == 1
            else jnp.concatenate(out_parts))


def stack_level_tables(tables) -> tuple:
    """Stack level tables of (possibly) different sizes into one
    ``[T, S_max]`` f32 array (rows right-padded with 1.0 — beyond each
    table's live range, never gathered) plus the static per-table symbol
    counts.  This is the buffer the segment-fused kernels keep in SMEM.
    """
    num_symbols = tuple(int(t.shape[0]) for t in tables)
    s_max = max(num_symbols)
    rows = [
        jnp.pad(t.astype(jnp.float32), (0, s_max - ns),
                constant_values=1.0) if ns < s_max
        else t.astype(jnp.float32)
        for t, ns in zip(tables, num_symbols)
    ]
    return jnp.stack(rows), num_symbols
