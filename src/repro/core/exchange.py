"""Unified Exchange API — the single seam for Algorithm 1's communication.

Everything the repo previously threaded by hand through ``compressed_pmean*``
call sites — ``(levels, key, cfg, mode, use_pallas, use_device_prng)`` —
is captured once in an :class:`ExchangeConfig` (frozen,
hashable, safe as a jit static argument), and :func:`make_exchange` returns
an :class:`Exchange` whose methods are usable inside ``shard_map``:

    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=qcfg,
                                      axis_name="data", mode="two_phase"))
    state = ex.init_state()
    mean, state = ex.pmean(x, state, key)          # flat vector
    tree, state = ex.pmean_tree(grads, state, key) # pytree (bucket-fused)

All stateful pieces — the quantization level table and the QAda sufficient
statistics (Section 3.3) — live in an explicit :class:`ExchangeState`
pytree that the caller threads through its step function, which is what
makes adaptive levels available in model-scale training (the train step
carries the state; level refreshes are visible in it).

Compressors are a registry (:func:`register_compressor`) behind a
TWO-TIER contract, declared per entry as ``Compressor.contract``:

* ``"unbiased"``   — ``E[compress(v)] = v`` (Definition 1 / Theorem 1 of
  the paper; the property the wider unbiased-compressor family of
  Beznosikov et al. relies on).
* ``"contractive"`` — ``E‖compress(v) − v‖² ≤ (1 − α)‖v‖²`` for some
  α ∈ (0, 1] exposed as ``Compressor.contraction_alpha(n, cfg)``
  (the EF21 / error-feedback family of Richtárik et al.; biased, so it
  MUST run with per-worker error memory — see ``ExchangeState.error``).

Registered entries:

* ``none``      — exact ``lax.pmean`` (FP32 control, still shard_map-routed).
* ``qgenx``     — the paper's bucketed stochastic quantization, bit-exact
  with the legacy ``compressed_pmean`` path (gather / two_phase / leafwise
  modes, fused Pallas kernels, packed int4 wire format).  Unbiased.
* ``randk``     — unbiased rand-K sparsification: each worker keeps a
  uniform random subset of ``rand_frac * n`` coordinates scaled by
  ``n / k`` (classic Rand-K; value+index wire format).
* ``layerwise`` — per-leaf bit-width policy (Nguyen et al., layer-wise
  quantization): large leaves take the aggressive low-bit config, small
  leaves a conservative 8-bit one, each group bucket-fused separately.
  Unbiased.
* ``ef21-topk`` — CONTRACTIVE magnitude top-k with EF21 error feedback:
  each worker ships the top ``ef_topk_frac * n`` coordinates of the
  innovation ``g − h`` against its persistent estimate ``h`` (no
  rescaling — biased but contractive), every device replays the gathered
  sparse innovations into the replicated ``[K, n]`` memory, and the
  aggregate is ``mean_k(h_k)``.
* ``ef-randk``  — the contractive variant of randk: the same EF21
  memory recursion with a uniform-random support of ``rand_frac * n``
  coordinates instead of magnitude top-k (and no ``n/k`` scaling).

Wire accounting is honest and lives here too: :func:`exchange_buffer_bytes`
returns the exact byte-sizes of the buffers handed to collectives, the
trace-time recorder (:func:`wire_trace_start` / :func:`wire_trace_stop`)
captures what was actually passed, and ``Exchange.wire_bytes`` /
``Exchange.wire_bytes_tree`` return the same numbers analytically so the
train step can emit a ``wire_bytes`` metric that tests assert equal to the
recorder.

This module IS the seam: the pre-refactor ``compressed_collectives``
wrappers were retired once every call site migrated here (the underlying
``_qgenx_pmean`` / ``_qgenx_pmean_leafwise`` implementations are
unchanged and stay bit-exact with the pre-Exchange behavior).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import adaptive_levels as qada
from repro.core import exchange_plan as xplan
from repro.core.quantization import (
    QuantConfig,
    _pad_to_buckets,
    bucket_norms,
    quantize_dequantize,
    quantize_dequantize_pytree,
    uniform_levels,
)
from repro.kernels.common import derive_prng_seed, pack4_rows, unpack4_rows
from repro.kernels.dequant_reduce import (
    dequant_reduce_blocks,
    dequant_reduce_requantize_blocks,
)
from repro.kernels.dequantize import dequantize_blocks
from repro.kernels.quantize import quantize_blocks

Array = jax.Array


# ---------------------------------------------------------------------------
# Wire accounting (trace-time recorder + analytic buffer sizes)
# ---------------------------------------------------------------------------

_WIRE_TRACE: Optional[list] = None


def wire_trace_start() -> None:
    """Begin recording (name, nbytes) for every collective operand.

    Recording happens at *trace* time (shapes are static), so it works
    under jit/shard_map — but only when the enclosing function is actually
    traced; re-running a cached jit records nothing.  Both branches of a
    ``lax.cond`` are traced, so a gated exchange records its operands once
    per call site regardless of which branch runs.

    Example — assert a train step's wire metric is honest (with
    ``sync_every > 1`` compare against a *sync* step's metric: the
    recorder sees the traced exchange operands even when the first
    executed step skips them)::

        wire_trace_start()
        _, _, ex_state, metrics = jax.jit(step)(params, opt_st, ex_st,
                                               batch, key)
        recorded = sum(nbytes for _, nbytes in wire_trace_stop())
        assert recorded == float(metrics["wire_bytes"])  # sync_every == 1
    """
    global _WIRE_TRACE
    _WIRE_TRACE = []


def wire_trace_stop() -> list:
    """End recording; return the ``[(name, nbytes), ...]`` collected since
    :func:`wire_trace_start` (empty list if nothing was traced)."""
    global _WIRE_TRACE
    rec, _WIRE_TRACE = _WIRE_TRACE, None
    return rec or []


_WIRE_PREFIX: str = ""


@contextlib.contextmanager
def wire_scope(prefix: str):
    """Trace-time attribution scope: every operand recorded inside gets
    ``prefix`` prepended to its name (the bucketed exchange wraps each
    bucket's chain in ``wire_scope(f"b{i}/")``, so the recorder output
    can be grouped per bucket — ``b0/gather_payload``, ... — and the
    per-bucket sums asserted against the analytic accounting).  Purely a
    recorder concern: no traced value changes, and outside an active
    trace this is free.  Nests by concatenation."""
    global _WIRE_PREFIX
    old = _WIRE_PREFIX
    _WIRE_PREFIX = old + prefix
    try:
        yield
    finally:
        _WIRE_PREFIX = old


def _record_wire(name: str, arr) -> None:
    if _WIRE_TRACE is not None:
        _WIRE_TRACE.append(
            (_WIRE_PREFIX + name, int(arr.size) * arr.dtype.itemsize)
        )


def record_wire(name: str, arr) -> None:
    """Public hook: count ``arr`` as a collective operand in the active
    wire trace.  For callers outside this module that hand their own
    buffers to collectives and want the accounting to stay honest (e.g.
    the train step's ``sync_every`` drift probe)::

        record_wire("drift_probe", probe)
        probe_mean = jax.lax.pmean(probe, axis_name)
    """
    _record_wire(name, arr)


def exchange_buffer_bytes(
    n: int, axis_size: int, cfg: QuantConfig, mode: str = "two_phase"
) -> dict:
    """Exact sizes (bytes) of each buffer one device hands to a collective.

    Matches ``size * itemsize`` of the arrays the qgenx exchange passes to
    ``all_gather`` / ``all_to_all`` — the honest wire numbers, including
    bucket/chunk padding and int4 packing.

    Example::

        >>> exchange_buffer_bytes(4096, axis_size=8,
        ...                       cfg=QuantConfig(num_levels=15, bits=8,
        ...                                       bucket_size=512),
        ...                       mode="gather")
        {'gather_payload': 4096, 'gather_norms': 32}
    """
    per = 1.0 if cfg.bits == 8 else 0.5
    b = cfg.bucket_size
    if mode == "gather":
        nb = -(-n // b)
        return {"gather_payload": int(nb * b * per), "gather_norms": 4 * nb}
    if mode == "two_phase":
        quota = axis_size * b
        n_pad = -(-n // quota) * quota
        nb = n_pad // b
        nb_per_chunk = nb // axis_size
        return {
            "a2a_payload": int(n_pad * per),
            "a2a_norms": 4 * nb,
            "gather_payload": int(nb_per_chunk * b * per),
            "gather_norms": 4 * nb_per_chunk,
        }
    raise ValueError(f"unknown mode {mode!r}")


def leafwise_buffer_bytes(shape: tuple, cfg: QuantConfig) -> dict:
    """Collective-operand bytes for one leaf of the leafwise exchange.

    Mirrors the payload/norms arrays ``_qgenx_pmean_leafwise`` records:
    the payload keeps the leaf's shape (trailing dim halved when packed
    int4 applies) and there is one f32 norm per trailing row.
    """
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    pack4 = cfg.bits == 4 and d % 2 == 0
    payload = rows * (d // 2 if pack4 else d)
    return {"leaf_payload": payload, "leaf_norms": 4 * rows}


def wire_bytes_per_device(
    n: int, axis_size: int, cfg: Optional[QuantConfig], mode: str = "two_phase"
) -> float:
    """Analytic bytes each device *transmits* per reduction (EXPERIMENTS).

    Derived from :func:`exchange_buffer_bytes` (the actual collective
    operands): an ``all_gather`` operand is injected into the network once
    (broadcast semantics); a tiled ``all_to_all`` keeps 1/K of the buffer
    local and transmits the remaining (K-1)/K.
    """
    if cfg is None:
        # ring all-reduce of f32: 2 * (K-1)/K * 4n
        return 2 * (axis_size - 1) / axis_size * 4.0 * n
    sizes = exchange_buffer_bytes(n, axis_size, cfg, mode)
    if mode == "gather":
        return float(sizes["gather_payload"] + sizes["gather_norms"])
    a2a = sizes["a2a_payload"] + sizes["a2a_norms"]
    gather = sizes["gather_payload"] + sizes["gather_norms"]
    return float(a2a * (axis_size - 1) / axis_size + gather)


# ---------------------------------------------------------------------------
# Gather-free level-table primitives (partial-manual-mesh safe)
# ---------------------------------------------------------------------------


def _select_gather(table: Array, idx: Array) -> Array:
    """``table[idx]`` without a gather op: unrolled selects over the
    (small, static) level table.  Bit-identical values; used on the
    partially-manual production mesh, where XLA's SPMD partitioner cannot
    lower dynamic gathers (same lowering limit that forces
    ``ModelConfig.unroll_scan`` and ``onehot_embed`` there)."""
    out = jnp.full(idx.shape, table[0], table.dtype)
    for j in range(1, table.shape[0]):
        out = jnp.where(idx == j, table[j], out)
    return out


def _bracket_select(u: Array, levels: Array):
    """(tau, lo, hi, xi) for normalized magnitudes ``u`` in [0, 1]: the
    bracket index (compare-accumulate over the static interior levels —
    equal to ``clip(searchsorted(levels, u, 'right') - 1, 0, s)``), its
    endpoints, and the fractional position.  THE single definition of
    the Definition-1 bracket used by both the leafwise rounding
    (:func:`_round_indices_select`) and its expectation
    (:func:`expected_index_pmf`) — the two cannot drift apart."""
    s2 = levels.shape[0]
    tau = jnp.zeros(u.shape, jnp.int32)
    for j in range(1, s2 - 1):
        tau += (u >= levels[j]).astype(jnp.int32)
    lo = _select_gather(levels, tau)
    hi = _select_gather(levels, tau + 1)
    return tau, lo, hi, (u - lo) / (hi - lo)


# ---------------------------------------------------------------------------
# Entropy-coded wire estimate (Theorem 2) — traced twin of core/coding.py
# ---------------------------------------------------------------------------


def expected_index_pmf(u: Array, levels: Array) -> Array:
    """Expected |level-index| distribution under unbiased stochastic
    rounding (Definition 1) of normalized magnitudes ``u`` in [0, 1].

    A coordinate whose magnitude falls in the bracket [l_tau, l_tau+1)
    rounds up with probability xi = (u - l_tau)/(l_tau+1 - l_tau), so it
    contributes mass (1-xi) to symbol tau and xi to tau+1 — no PRNG draw
    needed for the expectation.  Returns a [num_symbols] f32 pmf.

    Built from per-symbol masked reductions (the symbol count is static
    and small) rather than a scatter-add: this runs inside the train
    step's shard_map, and XLA's SPMD partitioner cannot lower scatter
    under a partially-manual mesh (the same class of lowering limit that
    forces ``ModelConfig.unroll_scan`` there).  Each reduction reads only
    ``u`` and scalar levels — the bracket of :func:`_bracket_select`
    spelled as two compares per symbol — so no per-coordinate bracket
    index is materialised: over a model's gradient those intermediates
    would outgrow the device.
    """
    lv = levels.astype(jnp.float32)
    num_symbols = lv.shape[0]
    top = num_symbols - 2  # last bracket [l_top, l_top+1] holds u == 1
    u = u.reshape(-1)

    def in_bracket(t):
        lower = u >= lv[t] if t > 0 else True
        upper = u < lv[t + 1] if t < top else True
        return jnp.logical_and(lower, upper)

    def xi(t):
        return jnp.clip((u - lv[t]) / (lv[t + 1] - lv[t]), 0.0, 1.0)

    pmf = []
    for j in range(num_symbols):
        mass = jnp.float32(0.0)
        if j <= top:  # rounded down from bracket j
            mass += jnp.sum(jnp.where(in_bracket(j), 1.0 - xi(j), 0.0))
        if j > 0:  # rounded up from bracket j - 1
            mass += jnp.sum(jnp.where(in_bracket(j - 1), xi(j - 1), 0.0))
        pmf.append(mass)
    return jnp.stack(pmf) / u.shape[0]


def theorem2_bits_traced(pmf: Array, d, num_buckets) -> Array:
    """Theorem 2 expected CODE o Q bits, as a traced scalar.

    The same formula as :func:`repro.core.coding.theorem2_expected_bits`
    (the host-side numpy oracle — parity-tested):

        C_b * num_buckets + (1 - p0) * d + (H(L) + 1) * d

    i.e. one f32 norm per bucket, a sign bit per expected nonzero, and an
    entropy-optimal prefix code (within 1 bit of H) per index.
    """
    from repro.core.coding import C_B  # numpy-free constant (32)

    nz = pmf > 0
    h = -jnp.sum(jnp.where(nz, pmf * jnp.log2(jnp.where(nz, pmf, 1.0)), 0.0))
    d = jnp.float32(d)
    return C_B * jnp.float32(num_buckets) + (1.0 - pmf[0]) * d + (h + 1.0) * d


# ---------------------------------------------------------------------------
# Quantize / dequantize dispatch (Pallas kernels vs jnp reference)
# ---------------------------------------------------------------------------
#
# Every qgenx exchange names its kernels with ``jax.named_scope`` so a device
# trace can time each one: ``noise`` (the uniform draw of stochastic
# rounding, or the device PRNG's seed), ``quantize`` (phase-1 quantize),
# ``reduce`` (dequant-reduce(-requantize), Pallas or its jnp twin) and
# ``dequantize`` (the final dequantize).  The scopes never nest, so each op
# of the exchange belongs to at most one of them; everything else under
# ``exchange`` is layout (pad, pack, reshape, unpack).


def _rounding_noise(key, shape):
    """The uniform draw of stochastic rounding, under the ``noise`` scope."""
    with jax.named_scope("noise"):
        return jax.random.uniform(key, shape, dtype=jnp.float32)


def _quantize_with(x2d, noise, levels, cfg: QuantConfig, use_pallas: bool):
    """[nb, bucket] f32 and its rounding noise -> (payload, norms)."""
    q_is_inf = math.isinf(cfg.q_norm)
    if use_pallas:
        return quantize_blocks(
            x2d, noise, levels,
            num_symbols=cfg.num_symbols, q_is_inf=q_is_inf, bits=cfg.bits,
        )
    from repro.kernels.ref import quantize_blocks_ref

    return quantize_blocks_ref(x2d, noise, levels, q_is_inf=q_is_inf, bits=cfg.bits)


def _dequantize_with(payload2d, norms, levels, cfg: QuantConfig,
                     use_pallas: bool):
    """Wire payload [nb, P] -> [nb, bucket] f32 (unpacks in 4-bit mode)."""
    if use_pallas:
        return dequantize_blocks(
            payload2d, norms, levels, num_symbols=cfg.num_symbols, bits=cfg.bits,
        )
    from repro.kernels.ref import dequantize_blocks_ref

    return dequantize_blocks_ref(payload2d, norms, levels, bits=cfg.bits)


def _quantize_2d(
    x2d,
    levels,
    key,
    cfg: QuantConfig,
    use_pallas: bool,
    *,
    use_device_prng: bool = False,
):
    """[nb, bucket] f32 -> (wire payload [nb, P], norms [nb]).

    P = bucket (8-bit) or bucket/2 (packed 4-bit) — both the Pallas and
    the jnp reference path emit the *packed* wire payload.  With
    ``use_device_prng`` (Pallas on TPU) no host noise buffer is created:
    only a [1] int32 seed derived from ``key`` reaches the kernel.
    """
    if use_device_prng and not use_pallas:
        raise ValueError(
            "use_device_prng requires use_pallas=True (the jnp reference "
            "path has no on-core PRNG and would silently fall back to the "
            "full-size host noise buffer)"
        )
    if use_pallas and use_device_prng:
        with jax.named_scope("noise"):
            seed = derive_prng_seed(key)
        with jax.named_scope("quantize"):
            return quantize_blocks(
                x2d, None, levels,
                num_symbols=cfg.num_symbols, q_is_inf=math.isinf(cfg.q_norm),
                bits=cfg.bits, use_device_prng=True, seed=seed,
            )
    noise = _rounding_noise(key, x2d.shape)
    with jax.named_scope("quantize"):
        return _quantize_with(x2d, noise, levels, cfg, use_pallas)


def _dequantize_2d(payload2d, norms, levels, cfg: QuantConfig,
                   use_pallas: bool):
    """Wire payload [nb, P] -> [nb, bucket] f32, under the ``dequantize``
    scope."""
    with jax.named_scope("dequantize"):
        return _dequantize_with(payload2d, norms, levels, cfg, use_pallas)


def _axis_key(key: Array, axis_name, axis_index=None) -> Array:
    """Per-device independent key (independent quantization noise).

    ``axis_index=None`` derives the device's position from
    ``lax.axis_index`` — correct under a fully-manual shard_map, but the
    lowering emits a ``partition-id`` instruction that XLA's SPMD
    partitioner rejects when OTHER mesh axes stay automatic (the
    partially-manual ``auto=`` production mesh: "PartitionId instruction
    is not supported for SPMD partitioning").  Callers on that path pass
    the index explicitly instead — a [1] slice of an ``arange`` sharded
    over the exchange axis (see ``make_train_step``) — which folds in the
    SAME integer value, so the derived keys (and every downstream byte)
    are identical to the axis_index path.
    """
    if axis_index is None:
        axis_index = jax.lax.axis_index(axis_name)
    return jax.random.fold_in(key, axis_index)


# ---------------------------------------------------------------------------
# The qgenx exchange primitives (Algorithm 1 on the wire)
# ---------------------------------------------------------------------------


def _qgenx_pmean(
    x: Array,
    axis_name,
    levels: Array,
    key: Array,
    cfg: QuantConfig,
    mode: str = "two_phase",
    use_pallas: bool = False,
    use_device_prng: bool = False,
    axis_index=None,
) -> Array:
    """Unbiased quantized mean-reduction of a flat vector over ``axis_name``.

    Must be called inside shard_map with ``axis_name`` in scope. ``x`` is
    each device's local full vector (e.g. its data-parallel gradient).
    ``axis_index`` (optional)
    supplies the device's position on partially-manual meshes where
    ``lax.axis_index`` cannot lower (see :func:`_axis_key`).
    """
    key = _axis_key(key, axis_name, axis_index)
    k1, k2 = jax.random.split(key)
    n = x.shape[0]
    # psum of a Python literal is evaluated at trace time -> static size
    axis_size = jax.lax.psum(1, axis_name)
    bucket = cfg.bucket_size

    if mode == "gather":
        x2d, _ = _pad_to_buckets(x, bucket)
        payload, norms = _quantize_2d(
            x2d, levels, k1, cfg, use_pallas,
            use_device_prng=use_device_prng,
        )
        _record_wire("gather_payload", payload)
        _record_wire("gather_norms", norms)
        all_p = jax.lax.all_gather(payload, axis_name)  # [K, nb, P] int8
        all_norms = jax.lax.all_gather(norms, axis_name)  # [K, nb] f32
        nb = x2d.shape[0]
        if use_pallas:
            # fused consumer: K payloads stream through VMEM, only the
            # final mean is written — no K intermediate f32 buffers.
            with jax.named_scope("reduce"):
                mean2d = dequant_reduce_blocks(
                    all_p, all_norms, levels,
                    num_symbols=cfg.num_symbols, num_workers=axis_size,
                    bits=cfg.bits,
                )
            return mean2d.reshape(-1)[:n]
        with jax.named_scope("reduce"):
            deq = _dequantize_with(
                all_p.reshape(axis_size * nb, -1),
                all_norms.reshape(axis_size * nb),
                levels, cfg, use_pallas,
            ).reshape(axis_size, nb * bucket)
            mean = jnp.mean(deq, axis=0)
        return mean[:n]

    if mode == "two_phase":
        # pad so n splits into K chunks of whole buckets
        chunk_quota = axis_size * bucket
        n_pad = -(-n // chunk_quota) * chunk_quota
        xp = jnp.pad(x, (0, n_pad - n))
        chunk = n_pad // axis_size
        nb_per_chunk = chunk // bucket
        x2d = xp.reshape(axis_size * nb_per_chunk, bucket)
        payload, norms = _quantize_2d(
            x2d, levels, k1, cfg, use_pallas,
            use_device_prng=use_device_prng,
        )
        # [K, nb_per_chunk, P] — row k is the chunk destined to device k
        payload = payload.reshape(axis_size, nb_per_chunk, -1)
        norms = norms.reshape(axis_size, nb_per_chunk)
        _record_wire("a2a_payload", payload)
        _record_wire("a2a_norms", norms)
        # all_to_all: device k receives everyone's copy of chunk k
        p_t = jax.lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0, tiled=True)
        n_t = jax.lax.all_to_all(norms, axis_name, split_axis=0, concat_axis=0, tiled=True)
        if use_pallas:
            # fused middle step: DEQ + mean + requantize in one kernel —
            # the reduced f32 chunk never leaves VMEM.
            if use_device_prng:
                noise2 = None
                with jax.named_scope("noise"):
                    seed2 = derive_prng_seed(k2)
            else:
                noise2 = _rounding_noise(k2, (nb_per_chunk, bucket))
                seed2 = None
            with jax.named_scope("reduce"):
                ridx, rnorms = dequant_reduce_requantize_blocks(
                    p_t, n_t, levels, noise2,
                    num_symbols=cfg.num_symbols, num_workers=axis_size,
                    q_is_inf=math.isinf(cfg.q_norm), bits=cfg.bits,
                    use_device_prng=use_device_prng, seed=seed2,
                )
        else:
            # the jnp twin of the fused kernel, with the same noise draw
            noise2 = _rounding_noise(k2, (nb_per_chunk, bucket))
            with jax.named_scope("reduce"):
                deq = _dequantize_with(
                    p_t.reshape(axis_size * nb_per_chunk, -1),
                    n_t.reshape(axis_size * nb_per_chunk),
                    levels, cfg, use_pallas,
                ).reshape(axis_size, chunk)
                reduced = jnp.mean(deq, axis=0)  # this device's chunk of the mean
                # re-quantize (unbiased) and share the reduced chunk
                r2d = reduced.reshape(nb_per_chunk, bucket)
                ridx, rnorms = _quantize_with(r2d, noise2, levels, cfg,
                                              use_pallas)
        _record_wire("gather_payload", ridx)
        _record_wire("gather_norms", rnorms)
        g_idx = jax.lax.all_gather(ridx, axis_name, tiled=True)
        g_norms = jax.lax.all_gather(rnorms, axis_name, tiled=True)
        out = _dequantize_2d(g_idx, g_norms, levels, cfg, use_pallas)
        return out.reshape(-1)[:n]

    raise ValueError(f"unknown mode {mode!r}")


def _round_indices_select(u: Array, levels: Array,
                          noise: Optional[Array]) -> Array:
    """Gather-free twin of ``quantization._stochastic_round_indices``:
    bracket via :func:`_bracket_select` — given the same noise draw
    (``jax.random.uniform(key, u.shape)``; None rounds to nearest),
    bit-identical indices."""
    tau, _, _, xi = _bracket_select(u, levels)
    if noise is not None:
        up = (noise < xi).astype(jnp.int32)
    else:
        up = (xi >= 0.5).astype(jnp.int32)
    return tau + up


def _qgenx_pmean_leafwise(
    tree,
    axis_name,
    levels: Array,
    key: Array,
    cfg: Optional[QuantConfig],
    axis_index=None,
    allreduce_fallback: bool = False,
):
    """Quantized pmean that PRESERVES inner (auto-axis) shardings.

    For use inside ``shard_map(..., axis_names={axis_name})`` where the
    other mesh axes stay under GSPMD: the flat-concat path reshapes every
    leaf, which forces XLA to re-gather the inner-sharded gradients.  Here
    each leaf is quantized *in place* — per-row L^q norms over the last dim
    (the "bucket" is the trailing dimension), elementwise stochastic
    rounding, int8 payload of identical shape — so only the ``all_gather``
    over the manual axis moves data, and it moves int8 (packed int4 when
    the trailing dim is even).

    Semantically still Definition 1 (unbiased, normalized quantization);
    the bucket size is the leaf's trailing dim instead of a fixed 1024 —
    Theorem 1 holds with d = trailing dim.
    """
    if cfg is None:
        return jax.lax.pmean(tree, axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(_axis_key(key, axis_name, axis_index), len(leaves))
    out = []
    lv = levels.astype(jnp.float32)
    for g, k in zip(leaves, keys):
        noise = _rounding_noise(k, g.shape) if cfg.stochastic else None
        with jax.named_scope("quantize"):
            gf = g.astype(jnp.float32)
            if math.isinf(cfg.q_norm):
                norms = jnp.max(jnp.abs(gf), axis=-1, keepdims=True)
            else:
                norms = jnp.sqrt(jnp.sum(gf * gf, axis=-1, keepdims=True))
            safe = jnp.where(norms > 0, norms, 1.0)
            u = jnp.clip(jnp.abs(gf) / safe, 0.0, 1.0)
            # gather-free rounding/dequant lookups: this is the exchange
            # the partially-manual production mesh runs (bit-identical to
            # the quantization-module oracle; see _round_indices_select)
            idx = _round_indices_select(u, lv, noise)
            signed = jnp.where(gf < 0, -idx, idx)
        if allreduce_fallback:
            # partially-manual meshes lower ONLY all-reduce (see
            # ExchangeConfig.allreduce_fallback): dequantize the OWN
            # payload locally — identical rounding noise, identical
            # unbiased mean — and psum the f32 estimate.  The f32 operand
            # IS the wire payload here; record it as such.
            with jax.named_scope("dequantize"):
                hat = (_select_gather(lv, jnp.abs(signed))
                       * jnp.sign(gf) * norms)
            _record_wire("leaf_fallback", hat)
            axis_size = jax.lax.psum(1, axis_name)
            out.append((jax.lax.psum(hat, axis_name) / axis_size)
                       .astype(g.dtype))
            continue
        # the only cross-device traffic: int8/int4 payload + f32 row norms
        # (packing reuses the kernels' wire-format helpers — one layout)
        d = g.shape[-1]
        pack4 = cfg.bits == 4 and d % 2 == 0
        with jax.named_scope("quantize"):
            if pack4:
                payload = pack4_rows(signed.reshape(-1, d)).reshape(
                    g.shape[:-1] + (d // 2,)
                )
            else:
                payload = signed.astype(jnp.int8)
        _record_wire("leaf_payload", payload)
        _record_wire("leaf_norms", norms)
        all_p = jax.lax.all_gather(payload, axis_name)  # [K, ...]
        all_norms = jax.lax.all_gather(norms, axis_name)
        # every worker's payload dequantized and averaged: the reduce
        with jax.named_scope("reduce"):
            if pack4:
                all_idx = unpack4_rows(all_p.reshape(-1, d // 2)).reshape(
                    all_p.shape[:-1] + (d,)
                )
            else:
                all_idx = all_p.astype(jnp.int32)
            mag = jnp.abs(all_idx)
            vals = (_select_gather(lv, mag)
                    * jnp.sign(all_idx.astype(jnp.float32)) * all_norms)
            mean = jnp.mean(vals, axis=0)
        out.append(mean.astype(g.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Config + state
# ---------------------------------------------------------------------------

_DEFAULT_QUANT_LO = QuantConfig(num_levels=5, bits=4, bucket_size=512)
_DEFAULT_QUANT_HI = QuantConfig(num_levels=15, bits=8, bucket_size=512)


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """Everything the exchange needs, in one frozen (hashable) bundle.

    Frozen + hashable means it is safe as a jit static argument and as a
    field of other frozen configs; ``make_exchange`` caches on it.

    Example — the paper's DDP-over-Ethernet setting, int8 two-phase::

        cfg = ExchangeConfig(
            compressor="qgenx",
            quant=QuantConfig(num_levels=15, bits=8, bucket_size=512),
            mode="two_phase", axis_name="data",
        )
        ex = make_exchange(cfg)

    Attributes:
      compressor: registry name — "none" | "qgenx" | "randk" | "layerwise".
      quant: the quantizer config (qgenx: the config; layerwise: the
        aggressive config for LARGE leaves; ignored by none/randk).
      quant_small: layerwise only — conservative config for small leaves.
      mode: "gather" | "two_phase" | "leafwise" (tree exchanges; flat
        ``pmean`` accepts gather/two_phase).
      axis_name: the shard_map axis the exchange reduces over.
      use_pallas / use_device_prng: kernel routing flags
        (previously dropped on the floor between the train step and the
        exchange — now carried here so every consumer forwards them).
      level_schedule: "fixed" | "qada" — QAda (Section 3.3) accumulates
        the weighted coordinate histogram in ExchangeState.hist (psum-merged
        across workers) and refreshes ExchangeState.levels every
        ``level_update_every`` pmean calls.
      rand_frac: randk / ef-randk — fraction of coordinates each worker
        keeps.
      ef_topk_frac: ef21-topk — fraction of coordinates each worker keeps
        (of the innovation against its error memory).
      layerwise_threshold: leaves with more elements than this take the
        low-bit ``quant`` config; the rest take ``quant_small``.
      sync_every: local-update regime (Beznosikov et al. 2023; Zhang &
        Stich 2023): workers take ``sync_every`` local (extra)gradient
        steps between compressed exchanges.  1 (default) = exchange every
        step (the classic Algorithm 1 path, byte-identical to a config
        without the field); K>1 = the train step gates its exchanges
        behind ``lax.cond`` so collective traffic only happens on every
        K-th step (wire_bytes metric and trace recorder agree), and emits
        a ``param_drift`` metric from a small f32 probe of the params.
      drift_probe: number of leading parameter coordinates in the drift
        probe (the only extra wire traffic a sync step pays; counted).
      allreduce_fallback: leafwise mode only — exchange the locally
        DEQUANTIZED per-worker estimate via one f32 ``psum`` instead of
        all-gathering the int payloads.  Same quantization noise, same
        unbiased mean (Definition 1 variance unchanged); needed on the
        PARTIALLY-manual production mesh, where XLA's SPMD partitioner on
        jaxlib 0.4.36 lowers ONLY all-reduce collectives (all-gather /
        ppermute / all-to-all all hit fatal IsManualSubgroup checks — the
        multi-pod dryrun sets this).  Wire accounting is honest about the
        cost: the psum operand is f32, so ``wire_bytes`` reports 4 B per
        coordinate, not the packed payload — on real-TPU jax versions
        whose partitioner lowers all-gather, leave this off and keep the
        compressed wire format.
      use_plan: route tree exchanges through a static ExchangePlan
        (:mod:`repro.core.exchange_plan`): the flat buffer is written
        ONCE in its final tile-aligned layout (no concatenate-then-pad
        double copy), per-layer policies become segments of one buffer,
        and the ``compress_tree``/re-centering paths take ONE
        segment-fused quantize∘dequantize invocation instead of a launch
        pair per leaf.  Bit-exact with the per-call path for the qgenx
        and layerwise pmean exchanges (same concatenation order, same
        padding semantics, same keys — parity-tested); the planned
        compression paths stay unbiased but draw different noise and pay
        one shared padding tail per SEGMENT instead of per leaf (the
        accounting follows, see ``compress_wire_bytes_tree``).  True by
        default; ``--no-exchange-plan`` on the train CLI is the escape
        hatch back to the per-call layout.
      recenter_every: compressed parameter re-centering cadence (local
        updates trade drift for wire).  0 (default) = never; R>0 = every
        R-th optimizer step the train step re-centers the drifted
        iterates through THIS exchange's compressor (one extra
        ``pmean_tree`` of a params-shaped pytree — for the ``qgenx``
        optimizer the dual accumulator Y is exchanged and the params
        recomputed, for the adam family the params themselves), gated
        behind ``lax.cond`` exactly like the sync gate.  Wire bytes are
        counted by the same recorder/metric as every other exchange.
      num_buckets: bucketed-pipeline fan-out of tree exchanges.  1
        (default) = the monolithic PR 5 path, byte-identical jaxpr.
        B>1 = the leaf list is split into B contiguous layer-ordered
        runs (:func:`repro.core.exchange_plan.partition_leaf_ids`), each
        planned and exchanged as an INDEPENDENT quantize+collective op
        chain that depends only on its own gradient leaves — which is
        what lets XLA's latency-hiding scheduler overlap each bucket's
        collective with the cotangent compute of earlier layers instead
        of serializing one monolithic gather after the full gradient.
        Per-segment quantizer policies, tile padding and key tags are
        decided per bucket by the same ``plan_groups`` policy (segments
        stay whole); noise keys are folded per bucket, so B>1 draws
        different (still unbiased) noise than B=1.  Requires
        ``use_plan`` and a flat-buffer mode (not leafwise); the
        contractive (error-feedback) compressors reject B>1 loudly —
        their [K, n] memory indexes the WHOLE-plan buffer atomically.
      overlap: "off" | "bucketed" | "defer_tail".  "off" (default) keeps
        the monolithic exchange even when ``num_buckets`` > 1 would be
        legal elsewhere (the two knobs are gated together: bucketing is
        only entered when overlap != "off").  "bucketed" = issue the
        per-bucket chains within the step (in backprop order, last
        leaves first).  "defer_tail" = additionally double-buffer the
        TAIL bucket (bucket 0 — the first layers, whose cotangents
        backprop produces LAST): its collective result is NOT consumed
        this step but carried in ``ExchangeState.pending`` and applied
        at the top of the NEXT sync, so step N's tail collective
        overlaps step N+1's forward.  The applied tail mean is one sync
        STALE (zeros on the very first sync) — a documented semantics
        change, not a silent one; partial-participation masks are
        rejected with defer_tail (a stale mean under a changed alive-set
        renorm is undefined).
    """

    compressor: str = "qgenx"
    quant: Optional[QuantConfig] = None
    quant_small: QuantConfig = _DEFAULT_QUANT_HI
    mode: str = "two_phase"
    axis_name: str = "data"
    use_pallas: bool = False
    use_device_prng: bool = False
    level_schedule: str = "fixed"
    level_update_every: int = 0
    qada_bins: int = 512
    qada_sweeps: int = 2
    qada_bisect_iters: int = 20
    rand_frac: float = 0.25
    ef_topk_frac: float = 0.25
    layerwise_threshold: int = 65536
    sync_every: int = 1
    drift_probe: int = 4096
    recenter_every: int = 0
    allreduce_fallback: bool = False
    use_plan: bool = True
    num_buckets: int = 1
    overlap: str = "off"

    def __post_init__(self):
        if self.mode not in ("gather", "two_phase", "leafwise"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.overlap not in ("off", "bucketed", "defer_tail"):
            raise ValueError(f"unknown overlap {self.overlap!r}")
        if self.num_buckets < 1:
            raise ValueError(
                f"num_buckets must be >= 1, got {self.num_buckets}"
            )
        if self.overlap != "off":
            if self.num_buckets < 2:
                raise ValueError(
                    f"overlap={self.overlap!r} needs num_buckets >= 2 "
                    "(one bucket has nothing to overlap); use "
                    "overlap='off' for the monolithic exchange"
                )
            if not self.use_plan:
                raise ValueError(
                    "bucketed overlap requires use_plan=True: the bucket "
                    "sub-plans ARE ExchangePlans (contiguous runs of "
                    "whole segments) — there is no per-call-layout "
                    "bucketing"
                )
            if self.mode == "leafwise":
                raise ValueError(
                    "mode='leafwise' has no flat buffer to bucket (each "
                    "leaf is already an independent collective chain; "
                    "XLA overlaps them natively) — bucketing applies to "
                    "the gather/two_phase flat-buffer modes"
                )
        elif self.num_buckets > 1:
            raise ValueError(
                f"num_buckets={self.num_buckets} with overlap='off' is "
                "ambiguous — the monolithic path ignores buckets; set "
                "overlap='bucketed' (or 'defer_tail') to enter the "
                "bucketed pipeline, or num_buckets=1 to be explicit"
            )
        if self.level_schedule not in ("fixed", "qada"):
            raise ValueError(f"unknown level_schedule {self.level_schedule!r}")
        if self.level_schedule == "qada" and self.level_update_every <= 0:
            raise ValueError("level_schedule='qada' needs level_update_every > 0")
        if not (0.0 < self.rand_frac <= 1.0):
            raise ValueError(f"rand_frac must be in (0, 1], got {self.rand_frac}")
        if not (0.0 < self.ef_topk_frac <= 1.0):
            raise ValueError(
                f"ef_topk_frac must be in (0, 1], got {self.ef_topk_frac}"
            )
        if self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {self.sync_every}")
        if self.drift_probe < 1:
            raise ValueError(f"drift_probe must be >= 1, got {self.drift_probe}")
        if self.recenter_every < 0:
            raise ValueError(
                f"recenter_every must be >= 0, got {self.recenter_every}"
            )
        if self.allreduce_fallback and self.mode != "leafwise":
            raise ValueError(
                "allreduce_fallback is a leafwise-exchange escape hatch; "
                f"mode={self.mode!r} would still all-gather/all-to-all and "
                "hit the partial-manual partitioner abort — use "
                "mode='leafwise'"
            )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ExchangeState:
    """Explicit exchange state, threaded through the train step as a pytree.

    Produced by ``Exchange.init_state()`` and returned (possibly updated)
    by every ``Exchange.pmean*`` call; the caller owns the threading::

        state = ex.init_state()
        mean, state = ex.pmean(x, state, key)     # inside shard_map
        assert int(state.step) == 1

    It rides in train checkpoints next to params/opt_state (QAda level
    refreshes survive restarts; incompatible states reset gracefully).

    Attributes:
      levels: current level table of the primary quantizer (qgenx, and the
      layerwise small-leaf group); a [2] placeholder for none/randk.
    levels_lo: layerwise large-leaf (low-bit) table; [2] placeholder
      elsewhere.
    hist: QAda sufficient statistics accumulated since the last refresh
      ([qada_bins] under the qada schedule, [1] placeholder otherwise).
    step: number of pmean calls performed with this state.
    error: per-worker error-feedback memory — a ``[num_workers, n]`` f32
      matrix for the contractive compressors (row k is worker k's
      persistent gradient estimate ``h_k``; every device replays ALL
      workers' gathered sparse innovations, so the matrix stays
      replicated across the exchange axis — bit-identical buffers, which
      is what makes checkpoint round-trips and guard rollbacks exact);
      a [1] placeholder for every unbiased compressor.  Sized by
      ``Exchange.init_state(template, num_workers)``.
    pending: the double-buffered TAIL-bucket slot of
      ``overlap='defer_tail'`` — the padded flat mean buffer of bucket
      0's most recent collective, carried one sync and applied at the
      top of the next (replicated across the exchange axis: every
      device runs the same collective, so checkpoint round-trips, guard
      rollbacks and the donated carry stay exact — the same argument as
      ``error``); a [1] placeholder everywhere else.  Sized by
      ``Exchange.init_state(template, num_workers)``.
    """

    levels: Array
    levels_lo: Array
    hist: Array
    step: Array
    error: Array
    pending: Array

    def tree_flatten(self):
        return (
            self.levels, self.levels_lo, self.hist, self.step, self.error,
            self.pending,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _null_error() -> Array:
    """The [1] error-memory placeholder of every unbiased compressor."""
    return jnp.zeros((1,), jnp.float32)


def _null_pending() -> Array:
    """The [1] pending-tail placeholder of every non-defer_tail config."""
    return jnp.zeros((1,), jnp.float32)


def null_exchange_state() -> ExchangeState:
    """Placeholder state for steps built without an exchange (uniform
    signature: callers always thread an ExchangeState)."""
    lv = jnp.asarray([0.0, 1.0], jnp.float32)
    return ExchangeState(
        levels=lv, levels_lo=jnp.copy(lv),  # donation-safe: no aliasing
        hist=jnp.zeros((1,), jnp.float32), step=jnp.zeros((), jnp.int32),
        error=_null_error(), pending=_null_pending(),
    )


# ---------------------------------------------------------------------------
# Compressor registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_compressor(cls):
    """Class decorator: add a Compressor implementation to the registry.

    The decorated class is instantiated once and keyed on its ``name``;
    it is immediately reachable from every consumer (ExchangeConfig, the
    train CLI's ``--compressor``, the contract tests)::

        @register_compressor
        class TopKCompressor(Compressor):
            name = "topk"
            def pmean(self, x, cfg, state, key): ...
            def compress(self, v, cfg, levels, key): ...   # E[.] = v !
            def wire_bytes(self, n, axis_size, cfg): ...
    """
    inst = cls()
    _REGISTRY[inst.name] = inst
    return cls


def get_compressor(name: str):
    """Registry lookup: ``get_compressor("qgenx").name == "qgenx"``;
    unknown names raise ValueError listing what IS registered, with each
    entry's contract tier (unbiased vs contractive matters to the caller:
    a contractive compressor needs error memory and a different proof)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        entries = ", ".join(
            f"'{n}' ({_REGISTRY[n].contract})" for n in sorted(_REGISTRY)
        )
        raise ValueError(
            f"unknown compressor {name!r}; registered: {entries}"
        ) from None


def registered_compressors() -> tuple:
    """Sorted names, e.g. ``('layerwise', 'none', 'qgenx', 'randk')`` —
    the train CLI's ``--compressor`` choices come from here."""
    return tuple(sorted(_REGISTRY))


class Compressor:
    """One compression policy under a declared contract tier.

    ``contract`` is ``"unbiased"`` (E[compress(v)] = v — Definition 1 /
    Theorem 1) or ``"contractive"`` (E‖compress(v) − v‖² ≤ (1 − α)‖v‖²
    with ``α = contraction_alpha(n, cfg)`` — the error-feedback family;
    set ``has_error = True`` so the Exchange threads the per-worker
    memory).  ``tests/test_compressor_contracts.py`` property-tests every
    registry entry against its declared tier — a new compressor is
    contract-tested for free.

    ``pmean`` runs inside shard_map and may use collectives; ``compress``
    is the collective-free per-worker point estimate used by the
    simulated-worker paths (Q-GenX loop, WGAN testbed) and the contract
    harness.  Minimal unbiased-tier check every implementation must
    satisfy::

        ex = make_exchange(cfg)
        draws = jax.vmap(lambda k: ex.compress(v, state, k))(keys)
        assert jnp.allclose(draws.mean(0), v, atol=the_variance_bound)
    """

    name = "?"
    has_levels = False
    has_error = False
    contract = "unbiased"

    def validate(self, cfg: ExchangeConfig) -> None:
        """Reject config combinations this compressor cannot honor (called
        by make_exchange and before any leafwise dispatch)."""
        if cfg.mode == "leafwise" and self.name not in ("qgenx", "none"):
            raise ValueError(
                f"compressor {self.name!r} ({self.contract} contract) has "
                "no sharding-preserving leafwise path; use mode='gather' "
                "or 'two_phase'"
            )
        if cfg.overlap != "off" and self.has_error:
            raise ValueError(
                f"compressor {self.name!r} (contractive contract) cannot "
                "run the bucketed overlapped exchange: its [num_workers, "
                "n] error memory scatter-adds row offsets into the "
                "WHOLE-plan flat buffer atomically, and bucketing would "
                "split that update across independently-keyed chains — "
                "use overlap='off' (the EF path stays monolithic)"
            )

    def contraction_alpha(self, n: int, cfg: ExchangeConfig) -> float:
        """The α of the contractive tier; only meaningful there."""
        raise NotImplementedError(
            f"compressor {self.name!r} declares the {self.contract!r} "
            "contract, which has no contraction factor"
        )

    def init_levels(self, cfg: ExchangeConfig):
        # distinct buffers, never aliases: ExchangeState is donated by the
        # train loop, and XLA rejects the same buffer donated twice
        lv = jnp.asarray([0.0, 1.0], jnp.float32)
        return lv, jnp.copy(lv)

    def init_error(self, cfg: ExchangeConfig, template, num_workers):
        """The error-memory slot this compressor carries in ExchangeState
        (default: the [1] placeholder of the unbiased tier).  ``template``
        is the pytree the memory must cover (params/grads) and
        ``num_workers`` the exchange-axis size; both may be None for
        compressors that do not use them."""
        return _null_error()

    # -- ExchangePlan hooks (static flat-buffer layout) -----------------

    def plan_groups(self, leaves_key: tuple, cfg: ExchangeConfig) -> tuple:
        """Segment grouping policy for the plan: one
        ``(leaf_ids, quant, table, key_tag)`` tuple per segment, in
        buffer order.  Default: every leaf in one unquantized segment —
        no alignment padding, so :meth:`ExchangePlan.pack` is then
        exactly the legacy flat concatenation (randk keeps its
        bit-identical layout for free)."""
        return ((tuple(range(len(leaves_key))), None, 0, None),)

    def plan_for(self, leaves, cfg: ExchangeConfig, axis_size,
                 purpose: str) -> xplan.ExchangePlan:
        """The (cached) static plan for this leaf list under this config."""
        lk = xplan.leaf_key(leaves)
        return xplan.build_plan(
            lk, self.plan_groups(lk, cfg), cfg.mode, int(axis_size), purpose
        )

    def _pmean_planned(self, flat, plan: xplan.ExchangePlan,
                       cfg: ExchangeConfig, state: ExchangeState, key,
                       axis_index):
        """Exchange the packed buffer (default: one flat stream; per-
        segment-policy compressors override with a per-segment loop)."""
        return self.pmean(flat, cfg, state, key, axis_index)

    # -- bucketed overlapped exchange -----------------------------------

    def bucket_partition(self, leaves, cfg: ExchangeConfig) -> tuple:
        """The contiguous layer-ordered bucket split of this leaf list
        (tuple of leaf-id tuples) — shared by the exchange, the analytic
        accounting and ``init_state``'s pending-slot sizing, so all
        three see the same static partition."""
        sizes = tuple(_size_of(l) for l in leaves)
        return xplan.partition_leaf_ids(sizes, cfg.num_buckets)

    def pmean_tree_bucketed(self, tree, cfg: ExchangeConfig,
                            state: ExchangeState, key, axis_index=None):
        """Bucketed-pipeline tree exchange: one independent
        quantize+collective chain per contiguous leaf bucket, each
        planned through the compressor's own ``plan_groups`` (segments
        whole, per-segment policies/padding/key tags untouched within
        the bucket).  Chains are issued in BACKPROP order (highest leaf
        ids first — the cotangents backprop produces first), and each
        depends only on its own bucket's leaves, which is the data-flow
        property that lets XLA's latency-hiding scheduler hoist bucket
        k's collective over bucket j<k's remaining cotangent compute.

        With ``overlap='defer_tail'`` the tail bucket (bucket 0) is
        double-buffered: its collective result goes into the returned
        ``new_pending`` and the value APPLIED for its leaves is
        ``state.pending`` — the previous sync's tail mean (zeros on the
        very first sync).  Returns ``(mean_tree, new_pending)``.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        buckets = self.bucket_partition(leaves, cfg)
        axis_size = jax.lax.psum(1, cfg.axis_name)
        out = [None] * len(leaves)
        new_pending = state.pending
        defer = cfg.overlap == "defer_tail"
        for bi in range(len(buckets) - 1, -1, -1):
            ids = buckets[bi]
            sub = [leaves[i] for i in ids]
            plan = self.plan_for(sub, cfg, axis_size, "pmean")
            # per-bucket key fold: chains draw independent noise (still
            # Definition-1 unbiased; num_buckets=1 never reaches here,
            # so the monolithic jaxpr keeps its exact keys)
            bkey = jax.random.fold_in(key, bi)
            with jax.named_scope(f"bucket{bi}"):
                with jax.named_scope("pack"):
                    flat = plan.pack(sub)
                with wire_scope(f"b{bi}/"):
                    mean_flat = self._pmean_planned(
                        flat, plan, cfg, state, bkey, axis_index
                    )
                if defer and bi == 0:
                    _check_pending(self.name, state.pending, plan.total)
                    new_pending = mean_flat
                    mean_flat = state.pending
                with jax.named_scope("unpack"):
                    parts = plan.unpack(mean_flat, sub)
            for i, p in zip(ids, parts):
                out[i] = p
        return jax.tree_util.tree_unflatten(treedef, out), new_pending

    def pmean(self, x, cfg: ExchangeConfig, state: ExchangeState, key,
              axis_index=None):
        raise NotImplementedError

    def pmean_tree(self, tree, cfg: ExchangeConfig, state: ExchangeState, key,
                   axis_index=None):
        """Default: bucket-fuse all leaves into one flat vector.

        With ``cfg.use_plan`` (default) the buffer is packed ONCE in its
        final tile-aligned layout through the static ExchangePlan — same
        concatenation order and padding semantics as the per-call path
        (bit-exact; the downstream exchange's own pad becomes a no-op),
        without the concatenate-then-pad double copy.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if cfg.use_plan:
            axis_size = jax.lax.psum(1, cfg.axis_name)
            plan = self.plan_for(leaves, cfg, axis_size, "pmean")
            flat = plan.pack(leaves)
            out = self._pmean_planned(flat, plan, cfg, state, key, axis_index)
            return jax.tree_util.tree_unflatten(
                treedef, plan.unpack(out, leaves)
            )
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
        out = self.pmean(flat, cfg, state, key, axis_index)
        return jax.tree_util.tree_unflatten(treedef, _split_like(out, leaves))

    def compress(self, v, cfg: ExchangeConfig, levels, key):
        raise NotImplementedError

    def compress_tree(self, tree, cfg: ExchangeConfig, levels, key):
        """Per-worker unbiased compression of a pytree, leaf-wise."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = [
            self.compress(l.reshape(-1), cfg, levels, k)
            .reshape(l.shape).astype(l.dtype)
            for l, k in zip(leaves, keys)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def refresh_tables(self, levels, levels_lo, hist, cfg: ExchangeConfig):
        """QAda refresh of this compressor's level tables from merged
        sufficient statistics (default: primary table only)."""
        new = qada.optimize_levels(
            levels, hist,
            sweeps=cfg.qada_sweeps, bisect_iters=cfg.qada_bisect_iters,
        )
        return new, levels_lo

    def wire_bytes(self, n: int, axis_size: int, cfg: ExchangeConfig) -> float:
        """Collective-operand bytes per device per pmean call (the exact
        sizes the trace recorder sees)."""
        raise NotImplementedError

    def wire_bytes_tree(self, shapes, axis_size: int, cfg: ExchangeConfig) -> float:
        return self.wire_bytes(sum(_size_of(s) for s in shapes), axis_size, cfg)

    def compress_wire_bytes(self, n: int, cfg: ExchangeConfig) -> float:
        """Bytes one worker broadcasts for one compressed n-vector (the
        Algorithm 1 / Q-GenX per-iteration accounting)."""
        raise NotImplementedError

    def compress_wire_bytes_tree(self, shapes, cfg: ExchangeConfig) -> float:
        """Broadcast bytes for one compressed pytree, matching what
        :meth:`compress_tree` actually emits.  Per-leaf paths pay one
        padding tail (and any per-leaf minimum support) per leaf; under
        the plan, level-table compressors emit ONE fused buffer per
        segment, so the accounting charges one shared padding tail per
        SEGMENT instead — always ≤ the per-leaf bytes, and the delta is
        exactly the saved per-leaf bucket ceils (documented + tested in
        ``tests/test_exchange_plan.py``)."""
        if cfg.use_plan and self.has_levels:
            plan = self.plan_for(shapes, cfg, 1, "compress")
            return plan.compress_payload_bytes()
        return float(sum(
            self.compress_wire_bytes(_size_of(s), cfg) for s in shapes
        ))


# single shape-product definition shared with the plan's offset math
_size_of = xplan.size_of


def _check_pending(name: str, pending, total: int) -> None:
    """Trace-time shape check of the defer_tail slot (mirrors the EF
    ``_check_error`` contract: a placeholder reaching a real exchange is
    a pointed error, not garbage math)."""
    if pending.ndim != 1 or pending.shape[0] != total:
        raise ValueError(
            f"compressor {name!r} with overlap='defer_tail' needs a "
            f"pending-tail buffer of shape [{total}] (the tail bucket's "
            f"padded plan length), found {tuple(pending.shape)} — "
            "initialize the state with ex.init_state(template=params, "
            "num_workers=axis_size)"
        )


def _split_like(flat: Array, leaves):
    outs, off = [], 0
    for l in leaves:
        outs.append(flat[off: off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return outs


@register_compressor
class NoneCompressor(Compressor):
    """Exact FP32 pmean — the shard_map-routed control arm."""

    name = "none"

    def pmean(self, x, cfg, state, key, axis_index=None):
        return self.pmean_tree(x, cfg, state, key, axis_index)

    def pmean_tree(self, tree, cfg, state, key, axis_index=None):
        # the mean is taken in f32 (what wire_bytes prices), whatever the
        # leaves' dtype; a bf16 all-reduce also crashes XLA:CPU's
        # all-reduce promotion inside a partially-manual shard_map
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g.astype(jnp.float32), cfg.axis_name)
            .astype(g.dtype), tree)

    def compress(self, v, cfg, levels, key):
        return v

    def compress_tree(self, tree, cfg, levels, key):
        return tree

    def wire_bytes(self, n, axis_size, cfg):
        # XLA's ring all-reduce; NOT visible to the trace recorder (no
        # explicit buffer is handed to a collective by this module).
        return 2 * (axis_size - 1) / axis_size * 4.0 * n

    def compress_wire_bytes(self, n, cfg):
        return 4.0 * n


@register_compressor
class QgenxCompressor(Compressor):
    """The paper's bucketed stochastic quantization (Definition 1),
    bit-exact with the legacy ``compressed_pmean`` path."""

    name = "qgenx"
    has_levels = True

    def _quant(self, cfg: ExchangeConfig) -> QuantConfig:
        if cfg.quant is None:
            raise ValueError("compressor='qgenx' requires ExchangeConfig.quant")
        return cfg.quant

    def validate(self, cfg):
        super().validate(cfg)
        self._quant(cfg)

    def init_levels(self, cfg):
        lv = uniform_levels(self._quant(cfg).num_levels)
        return lv, jnp.copy(lv)  # distinct buffers — see Compressor.init_levels

    def plan_groups(self, leaves_key, cfg):
        # one segment, every leaf, the primary table — the plan's padded
        # tail IS the bucket/quota pad _qgenx_pmean would have applied
        return ((tuple(range(len(leaves_key))), self._quant(cfg), 0, None),)

    def pmean(self, x, cfg, state, key, axis_index=None):
        if cfg.mode == "leafwise":
            raise ValueError("mode='leafwise' is a tree exchange; use pmean_tree")
        return _qgenx_pmean(
            x, cfg.axis_name, state.levels, key, self._quant(cfg), cfg.mode,
            cfg.use_pallas, cfg.use_device_prng,
            axis_index=axis_index,
        )

    def pmean_tree(self, tree, cfg, state, key, axis_index=None):
        if cfg.mode == "leafwise":
            return _qgenx_pmean_leafwise(
                tree, cfg.axis_name, state.levels, key, self._quant(cfg),
                axis_index=axis_index,
                allreduce_fallback=cfg.allreduce_fallback,
            )
        return super().pmean_tree(tree, cfg, state, key, axis_index)

    def compress(self, v, cfg, levels, key):
        return quantize_dequantize(v, levels, key, self._quant(cfg)).reshape(v.shape)

    def compress_tree(self, tree, cfg, levels, key):
        """Per-worker unbiased compression of a pytree.

        Planned (default): ONE segment-fused quantize∘dequantize
        invocation over the packed flat buffer (one shared padding
        tail), instead of a quantize + dequantize launch pair per leaf.
        Still Definition 1 per bucket — different noise partitioning
        than the per-leaf path, same unbiased contract.
        """
        q = self._quant(cfg)
        lv = levels if levels is not None else uniform_levels(q.num_levels)
        if not cfg.use_plan:
            return quantize_dequantize_pytree(tree, lv, key, q)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        plan = self.plan_for(leaves, cfg, 1, "compress")
        hat = xplan.fused_compress(
            plan, plan.pack(leaves), (lv,) * len(plan.segments), key,
            use_pallas=cfg.use_pallas, use_device_prng=cfg.use_device_prng,
        )
        return jax.tree_util.tree_unflatten(treedef, plan.unpack(hat, leaves))

    def wire_bytes(self, n, axis_size, cfg):
        if cfg.mode == "leafwise":
            if cfg.allreduce_fallback:
                return 4.0 * n  # the f32 psum operand IS the payload
            sizes = leafwise_buffer_bytes((n,), self._quant(cfg))
        else:
            sizes = exchange_buffer_bytes(n, axis_size, self._quant(cfg), cfg.mode)
        return float(sum(sizes.values()))

    def wire_bytes_tree(self, shapes, axis_size, cfg):
        if cfg.mode == "leafwise":
            # the sharding-preserving leafwise exchange is per-leaf BY
            # CONSTRUCTION (payloads keep each leaf's shape, no flat
            # buffer exists to plan) — it deliberately stays outside the
            # ExchangePlan, and so does its accounting
            if cfg.allreduce_fallback:
                return float(sum(4.0 * _size_of(s) for s in shapes))
            return float(sum(
                sum(leafwise_buffer_bytes(
                    s.shape if hasattr(s, "shape") else s, self._quant(cfg)
                ).values())
                for s in shapes
            ))
        return super().wire_bytes_tree(shapes, axis_size, cfg)

    def compress_wire_bytes(self, n, cfg):
        return float(self._quant(cfg).payload_bytes(n))


def _randk_k(n: int, cfg: ExchangeConfig) -> int:
    return max(1, int(round(cfg.rand_frac * n)))


@register_compressor
class RandKCompressor(Compressor):
    """Unbiased rand-K sparsification: keep k = rand_frac * n coordinates
    chosen uniformly without replacement, scaled by n/k so
    E[compress(v)] = v.  Wire format: k f32 values + k int32 indices per
    worker, all-gathered (broadcast semantics, like the paper's CODE o Q)."""

    name = "randk"

    def _support(self, n, k, key):
        return jax.random.permutation(key, n)[:k]

    def pmean(self, x, cfg, state, key, axis_index=None):
        n = x.shape[0]
        k = _randk_k(n, cfg)
        key = _axis_key(key, cfg.axis_name, axis_index)
        axis_size = jax.lax.psum(1, cfg.axis_name)
        with jax.named_scope("quantize"):
            idx = self._support(n, k, key).astype(jnp.int32)
            vals = x[idx] * (n / k)
        _record_wire("randk_vals", vals)
        _record_wire("randk_idx", idx)
        all_vals = jax.lax.all_gather(vals, cfg.axis_name)  # [K, k] f32
        all_idx = jax.lax.all_gather(idx, cfg.axis_name)  # [K, k] i32
        with jax.named_scope("dequantize"):
            out = jnp.zeros((n,), jnp.float32).at[all_idx.reshape(-1)].add(
                all_vals.reshape(-1)
            )
            return out / axis_size

    def compress(self, v, cfg, levels, key):
        n = v.shape[0]
        k = _randk_k(n, cfg)
        idx = self._support(n, k, key)
        return jnp.zeros((n,), v.dtype).at[idx].set(v[idx] * (n / k))

    def wire_bytes(self, n, axis_size, cfg):
        return 8.0 * _randk_k(n, cfg)  # 4 B value + 4 B index

    def compress_wire_bytes(self, n, cfg):
        return 8.0 * _randk_k(n, cfg)


class _ErrorFeedbackCompressor(Compressor):
    """Shared EF21-style machinery of the contractive tier.

    Per-worker recursion (Richtárik et al., EF21), with C the bare
    contraction operator (:meth:`compress` — top-k or rand-k support,
    NO unbiasing rescale)::

        c_k  = C(g_k − h_k)          # sparse innovation, shipped
        h_k' = h_k + c_k             # persistent per-worker estimate
        mean = (1/K) Σ_k h_k'        # the aggregate the step consumes

    Wire format matches randk — k f32 values + k int32 indices per
    worker, all-gathered — so ``wire_bytes == 8k`` and the trace
    recorder sees exactly that.  The [K, n] memory update applies ALL
    workers' gathered innovations on every device, which keeps
    ``ExchangeState.error`` replicated (bit-identical across devices):
    checkpoint round-trips, guard rollbacks, and the donated-buffer
    carry all stay exact.  The memory covers the ExchangePlan-packed
    flat buffer — EF segments are unquantized, so the plan's layout is
    the legacy flat concatenation with zero padding and the memory
    length is exactly the live coordinate count.

    Interactions (defined + tested):

    * ``sync_every`` — local (non-sync) steps carry the state through
      ``lax.cond`` untouched: error memory only advances on steps that
      actually exchange.
    * step guard — a rejected step restores the PRE-exchange state, so
      rejected steps never advance error memory.
    * ``recenter_every`` / participation ``mask`` — rejected loudly
      (:meth:`validate` / ``Exchange.pmean*``): the memory tracks
      gradient innovations, and both features would silently corrupt it.
    """

    contract = "contractive"
    has_error = True
    wire_tag = "ef"

    def _k(self, n: int, cfg: ExchangeConfig) -> int:
        raise NotImplementedError

    def _support(self, innov, k, cfg, key):
        """Indices of the k coordinates C keeps (subclass policy)."""
        raise NotImplementedError

    def validate(self, cfg):
        super().validate(cfg)
        if cfg.recenter_every > 0:
            raise ValueError(
                f"compressor {self.name!r} (contractive contract) cannot "
                "re-center parameters: the per-worker error memory tracks "
                "GRADIENT innovations, and a recenter exchange would fold "
                "iterate residuals into it — set recenter_every=0"
            )

    def contraction_alpha(self, n, cfg):
        return self._k(n, cfg) / float(n)

    def init_error(self, cfg, template, num_workers):
        if template is None or num_workers is None:
            # keep init_state() callable without a template (toy-VI loop,
            # generic helpers); the pmean path raises a pointed error if
            # this placeholder ever reaches an actual EF exchange
            return _null_error()
        n = sum(_size_of(l) for l in jax.tree_util.tree_leaves(template))
        return jnp.zeros((int(num_workers), n), jnp.float32)

    def _check_error(self, h, n: int):
        if h.ndim != 2 or h.shape[1] != n:
            raise ValueError(
                f"compressor {self.name!r} (contractive contract) needs "
                f"error memory of shape [num_workers, {n}], found "
                f"{tuple(h.shape)} — initialize the state with "
                "ex.init_state(template=params, num_workers=axis_size)"
            )

    def _ef_exchange(self, flat, cfg, h, key, axis_index):
        """One EF21 round on the packed flat buffer.  Returns
        ``(mean, new_error)``; the Exchange threads new_error back into
        the state."""
        n = flat.shape[0]
        self._check_error(h, n)
        num_workers = h.shape[0]
        axis_size = jax.lax.psum(1, cfg.axis_name)  # static at trace time
        if int(axis_size) != num_workers:
            raise ValueError(
                f"compressor {self.name!r}: error memory was initialized "
                f"for {num_workers} workers but the exchange axis "
                f"{cfg.axis_name!r} has {int(axis_size)} devices"
            )
        k = self._k(n, cfg)
        key = _axis_key(key, cfg.axis_name, axis_index)
        row = (axis_index if axis_index is not None
               else jax.lax.axis_index(cfg.axis_name))
        with jax.named_scope("quantize"):
            innov = flat.astype(jnp.float32) - h[row]
            idx = self._support(innov, k, cfg, key).astype(jnp.int32)
            vals = innov[idx]
        _record_wire(f"{self.wire_tag}_vals", vals)
        _record_wire(f"{self.wire_tag}_idx", idx)
        all_vals = jax.lax.all_gather(vals, cfg.axis_name)  # [K, k] f32
        all_idx = jax.lax.all_gather(idx, cfg.axis_name)  # [K, k] i32
        # every device replays ALL workers' innovations so the [K, n]
        # memory stays replicated across the exchange axis
        with jax.named_scope("dequantize"):
            row_off = jnp.arange(num_workers, dtype=jnp.int32)[:, None] * n
            h_new = h.reshape(-1).at[(all_idx + row_off).reshape(-1)].add(
                all_vals.reshape(-1)
            ).reshape(num_workers, n)
            return jnp.mean(h_new, axis=0), h_new

    def pmean_ef(self, x, cfg, state, key, axis_index=None):
        return self._ef_exchange(x, cfg, state.error, key, axis_index)

    def pmean_tree_ef(self, tree, cfg, state, key, axis_index=None):
        """Packed EF exchange of a pytree.  Always routed through the
        static ExchangePlan: the EF segment is unquantized, so the plan
        is the legacy flat concatenation with zero padding (use_plan=False
        would produce the identical buffer) and the [K, n] memory maps
        1:1 onto plan offsets."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        axis_size = jax.lax.psum(1, cfg.axis_name)
        plan = self.plan_for(leaves, cfg, axis_size, "pmean")
        mean_flat, new_error = self._ef_exchange(
            plan.pack(leaves), cfg, state.error, key, axis_index
        )
        mean = jax.tree_util.tree_unflatten(
            treedef, plan.unpack(mean_flat, leaves)
        )
        return mean, new_error

    def pmean(self, x, cfg, state, key, axis_index=None):
        raise ValueError(
            f"compressor {self.name!r} (contractive contract) must be "
            "called through Exchange.pmean/pmean_tree, which thread the "
            "error memory back into ExchangeState"
        )

    def ef_compress(self, v, err, cfg, key):
        """One worker's collective-free EF21 update (the simulated-worker
        toy-VI path): ``c = C(v − h); h' = h + c``.  Returns
        ``(h', h')`` — the contribution to the aggregate IS the new
        memory row, so ``mean_k`` of the first element reproduces the
        collective path's aggregate."""
        n = v.shape[0]
        k = self._k(n, cfg)
        innov = v.astype(jnp.float32) - err
        idx = self._support(innov, k, cfg, key).astype(jnp.int32)
        h_new = err.at[idx].add(innov[idx])
        return h_new, h_new

    def compress(self, v, cfg, levels, key):
        """The bare contraction operator C: keep k coordinates, NO
        rescale — biased, but E‖C(v) − v‖² ≤ (1 − k/n)‖v‖² (the
        contract the harness property-tests)."""
        n = v.shape[0]
        k = self._k(n, cfg)
        idx = self._support(v.astype(jnp.float32), k, cfg, key)
        return jnp.zeros((n,), v.dtype).at[idx].set(v[idx])

    def wire_bytes(self, n, axis_size, cfg):
        return 8.0 * self._k(n, cfg)  # 4 B value + 4 B index

    def compress_wire_bytes(self, n, cfg):
        return 8.0 * self._k(n, cfg)


@register_compressor
class EF21TopKCompressor(_ErrorFeedbackCompressor):
    """EF21 with magnitude top-k: C keeps the ``ef_topk_frac * n``
    largest-|.| coordinates of the innovation (deterministic, so the
    contraction E‖C(x) − x‖² ≤ (1 − k/n)‖x‖² holds per draw)."""

    name = "ef21-topk"
    wire_tag = "ef21"

    def _k(self, n, cfg):
        return max(1, int(round(cfg.ef_topk_frac * n)))

    def _support(self, innov, k, cfg, key):
        return jax.lax.top_k(jnp.abs(innov), k)[1]


@register_compressor
class EFRandKCompressor(_ErrorFeedbackCompressor):
    """Contractive rand-k: the EF21 recursion with a uniform-random
    support of ``rand_frac * n`` coordinates and NO ``n/k`` rescale
    (E‖C(x) − x‖² = (1 − k/n)‖x‖² exactly, in expectation over the
    support draw)."""

    name = "ef-randk"
    wire_tag = "ef_randk"

    def _k(self, n, cfg):
        return _randk_k(n, cfg)

    def _support(self, innov, k, cfg, key):
        return jax.random.permutation(key, innov.shape[0])[:k]


@register_compressor
class LayerwiseCompressor(Compressor):
    """Per-leaf bit-width policy (layer-wise quantization): leaves larger
    than ``layerwise_threshold`` take the aggressive low-bit ``quant``
    config (default packed int4), the rest the conservative 8-bit
    ``quant_small`` — each group bucket-fused through the qgenx exchange
    with its own level table.  Still unbiased: every group is Definition 1
    quantization."""

    name = "layerwise"
    has_levels = True

    def _cfgs(self, cfg: ExchangeConfig):
        lo = cfg.quant if cfg.quant is not None else _DEFAULT_QUANT_LO
        return lo, cfg.quant_small

    def init_levels(self, cfg):
        lo, hi = self._cfgs(cfg)
        return uniform_levels(hi.num_levels), uniform_levels(lo.num_levels)

    def _group(self, leaves, cfg):
        big = [i for i, l in enumerate(leaves) if l.size > cfg.layerwise_threshold]
        small = [i for i, l in enumerate(leaves) if l.size <= cfg.layerwise_threshold]
        return big, small

    def plan_groups(self, leaves_key, cfg):
        """Segment table of the per-layer policy: the big-leaf group is
        one low-bit segment against ``levels_lo``, the small-leaf group
        one conservative segment against ``levels`` — group order and
        per-group key tags exactly mirror the per-call path (bit-exact
        pmean)."""
        lo, hi = self._cfgs(cfg)
        sizes = [_size_of(shape) for shape, _ in leaves_key]
        big = tuple(i for i, s in enumerate(sizes) if s > cfg.layerwise_threshold)
        small = tuple(i for i, s in enumerate(sizes) if s <= cfg.layerwise_threshold)
        return tuple(
            (ids, qc, table, gid)
            for gid, (ids, qc, table) in enumerate(
                ((big, lo, 1), (small, hi, 0))
            )
            if ids
        )

    def _pmean_planned(self, flat, plan, cfg, state, key, axis_index):
        """One exchange per plan segment, each a pre-padded slice of the
        SHARED buffer with its own level table and quantizer — the
        downstream pad in ``_qgenx_pmean`` is a no-op."""
        outs = []
        for seg in plan.segments:
            levels = state.levels_lo if seg.table == 1 else state.levels
            outs.append(_qgenx_pmean(
                flat[seg.start: seg.stop], cfg.axis_name, levels,
                jax.random.fold_in(key, seg.key_tag), seg.quant, cfg.mode,
                cfg.use_pallas, cfg.use_device_prng,
                axis_index=axis_index,
            ))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def pmean(self, x, cfg, state, key, axis_index=None):
        self.validate(cfg)
        lo, hi = self._cfgs(cfg)
        big = x.shape[0] > cfg.layerwise_threshold
        qcfg = lo if big else hi
        levels = state.levels_lo if big else state.levels
        return _qgenx_pmean(
            x, cfg.axis_name, levels, key, qcfg, cfg.mode,
            cfg.use_pallas, cfg.use_device_prng,
            axis_index=axis_index,
        )

    def pmean_tree(self, tree, cfg, state, key, axis_index=None):
        self.validate(cfg)
        if cfg.use_plan:
            # base plan path packs the segmented buffer once;
            # _pmean_planned above runs one exchange per segment
            return super().pmean_tree(tree, cfg, state, key, axis_index)
        lo, hi = self._cfgs(cfg)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        big, small = self._group(leaves, cfg)
        mode = cfg.mode
        out = [None] * len(leaves)
        for gid, (idxs, qcfg, levels) in enumerate(
            ((big, lo, state.levels_lo), (small, hi, state.levels))
        ):
            if not idxs:
                continue
            group = [leaves[i] for i in idxs]
            flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32) for l in group]
            )
            mean = _qgenx_pmean(
                flat, cfg.axis_name, levels, jax.random.fold_in(key, gid),
                qcfg, mode, cfg.use_pallas, cfg.use_device_prng,
                axis_index=axis_index,
            )
            for i, o in zip(idxs, _split_like(mean, group)):
                out[i] = o
        return jax.tree_util.tree_unflatten(treedef, out)

    def compress(self, v, cfg, levels, key):
        lo, hi = self._cfgs(cfg)
        qcfg = lo if v.size > cfg.layerwise_threshold else hi
        # use the caller's (possibly QAda-refreshed) table when it belongs
        # to this size class; fall back to uniform otherwise
        if levels is None or levels.shape[0] != qcfg.num_symbols:
            levels = uniform_levels(qcfg.num_levels)
        return quantize_dequantize(v, levels, key, qcfg).reshape(v.shape)

    def _segment_table(self, seg, levels):
        """The caller's table when it fits this segment's quantizer (same
        size-class rule as :meth:`compress`); uniform otherwise."""
        if levels is not None and levels.shape[0] == seg.quant.num_symbols:
            return levels
        return uniform_levels(seg.quant.num_levels)

    def compress_tree(self, tree, cfg, levels, key):
        """Planned (default): the whole pytree through the segment-fused
        quantize∘dequantize — segments sharing row geometry take ONE
        invocation with segment-indexed level tables (the per-leaf path
        paid a quantize + dequantize launch pair per leaf)."""
        if not cfg.use_plan:
            return super().compress_tree(tree, cfg, levels, key)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        plan = self.plan_for(leaves, cfg, 1, "compress")
        tables = tuple(
            self._segment_table(seg, levels) for seg in plan.segments
        )
        hat = xplan.fused_compress(
            plan, plan.pack(leaves), tables, key,
            use_pallas=cfg.use_pallas, use_device_prng=cfg.use_device_prng,
        )
        return jax.tree_util.tree_unflatten(treedef, plan.unpack(hat, leaves))

    def wire_bytes(self, n, axis_size, cfg):
        self.validate(cfg)
        lo, hi = self._cfgs(cfg)
        qcfg = lo if n > cfg.layerwise_threshold else hi
        return float(sum(
            exchange_buffer_bytes(n, axis_size, qcfg, cfg.mode).values()
        ))

    def wire_bytes_tree(self, shapes, axis_size, cfg):
        self.validate(cfg)
        lo, hi = self._cfgs(cfg)
        sizes = [_size_of(s) for s in shapes]
        mode = cfg.mode
        total = 0.0
        for qcfg, group in (
            (lo, [s for s in sizes if s > cfg.layerwise_threshold]),
            (hi, [s for s in sizes if s <= cfg.layerwise_threshold]),
        ):
            if group:
                total += sum(
                    exchange_buffer_bytes(sum(group), axis_size, qcfg, mode).values()
                )
        return float(total)

    def compress_wire_bytes(self, n, cfg):
        lo, hi = self._cfgs(cfg)
        qcfg = lo if n > cfg.layerwise_threshold else hi
        return float(qcfg.payload_bytes(n))

    def refresh_tables(self, levels, levels_lo, hist, cfg):
        # both tables adapt from the same (table-independent) histogram
        new = qada.optimize_levels(
            levels, hist,
            sweeps=cfg.qada_sweeps, bisect_iters=cfg.qada_bisect_iters,
        )
        new_lo = qada.optimize_levels(
            levels_lo, hist,
            sweeps=cfg.qada_sweeps, bisect_iters=cfg.qada_bisect_iters,
        )
        return new, new_lo


# ---------------------------------------------------------------------------
# Partial participation (liveness masking)
# ---------------------------------------------------------------------------


def _mask_tree(tree, mask: Array):
    """Zero every leaf of a DEAD worker (mask == 0) via ``jnp.where`` —
    NOT a multiply, so a dropped worker's non-finite payload (NaN * 0 is
    NaN) still vanishes from the aggregate.  ``where(1 > 0, g, 0)`` is
    ``g`` bitwise, which is what keeps the all-ones mask exact."""
    return jax.tree_util.tree_map(
        lambda g: jnp.where(mask > 0, g, jnp.zeros((), g.dtype)), tree
    )


def _alive_renorm(mask: Array, axis_name) -> tuple:
    """(renorm, alive): the mean-over-K -> mean-over-alive correction.

    psum(masked payload) / psum(mask) is an unbiased mean over the
    SURVIVORS; every pmean below computes psum/K, so the correction is
    K / alive.  ``alive`` is clamped at 1 so an (unsupported) all-dead
    step yields zeros instead of NaN — the step guard, not the exchange,
    owns rejecting that step.  With an all-ones mask alive == K exactly
    (a psum of exact 1.0s), renorm == 1.0, and x * 1.0 is bitwise x —
    the parity the fault tests pin across the bits x mode grid.
    """
    axis_size = jax.lax.psum(1, axis_name)
    alive = jnp.maximum(jax.lax.psum(mask.astype(jnp.float32), axis_name), 1.0)
    return jnp.float32(axis_size) / alive, alive


def _renorm_tree(tree, renorm: Array):
    return jax.tree_util.tree_map(
        lambda m: (m.astype(jnp.float32) * renorm).astype(m.dtype), tree
    )


# ---------------------------------------------------------------------------
# The Exchange object
# ---------------------------------------------------------------------------


def _exchange_scope(method):
    """Run an Exchange entry point under the ``exchange`` named scope.

    Every path (monolithic, bucketed, leafwise, error feedback) goes
    through one of the scoped entry points exactly once, so a device
    trace finds each exchange op under one ``exchange/`` and nothing else
    carries that name; entry points call each other's unscoped bodies."""
    @functools.wraps(method)
    def scoped(self, *args, **kwargs):
        with jax.named_scope("exchange"):
            return method(self, *args, **kwargs)

    return scoped


class Exchange:
    """A configured exchange: compressor + state management + accounting.

    All ``pmean*`` methods must run inside shard_map with
    ``cfg.axis_name`` in scope; they return ``(mean, new_state)`` so the
    caller threads :class:`ExchangeState` explicitly (that is what makes
    QAda level schedules reachable from jitted training steps).

    Example — the whole lifecycle::

        ex = make_exchange(ExchangeConfig(
            compressor="qgenx", quant=qcfg, axis_name="data"))
        state = ex.init_state()
        # inside shard_map over "data":
        mean_tree, state = ex.pmean_tree(grads, state, key)
        # analytic accounting (== what the trace recorder would see):
        bytes_per_call = ex.wire_bytes_tree(grads, axis_size=8)
    """

    def __init__(self, cfg: ExchangeConfig):
        self.cfg = cfg
        self.compressor = get_compressor(cfg.compressor)

    # -- state ---------------------------------------------------------

    def init_state(self, template=None,
                   num_workers: Optional[int] = None) -> ExchangeState:
        """Fresh state.  ``template`` (a params/grads-shaped pytree) and
        ``num_workers`` (the exchange-axis size) size the error-memory
        slot of contractive compressors; unbiased compressors ignore both
        (every pre-existing ``init_state()`` call stays valid)."""
        levels, levels_lo = self.compressor.init_levels(self.cfg)
        bins = self.cfg.qada_bins if self.cfg.level_schedule == "qada" else 1
        return ExchangeState(
            levels=levels, levels_lo=levels_lo,
            hist=jnp.zeros((bins,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
            error=self.compressor.init_error(self.cfg, template, num_workers),
            pending=self._init_pending(template, num_workers),
        )

    def _init_pending(self, template, num_workers) -> Array:
        """Zeroed defer_tail slot, sized to the TAIL bucket's padded plan
        length (the buffer ``pmean_tree_bucketed`` carries across syncs);
        the [1] placeholder for every other overlap mode — and, like the
        EF memory, when no template is given (the pmean path then raises
        a pointed error instead of computing garbage)."""
        if self.cfg.overlap != "defer_tail":
            return _null_pending()
        if template is None or num_workers is None:
            return _null_pending()
        leaves = jax.tree_util.tree_leaves(template)
        buckets = self.compressor.bucket_partition(leaves, self.cfg)
        tail = [leaves[i] for i in buckets[0]]
        plan = self.compressor.plan_for(
            tail, self.cfg, int(num_workers), "pmean"
        )
        return jnp.zeros((plan.total,), jnp.float32)

    def _qada_active(self) -> bool:
        return (
            self.cfg.level_schedule == "qada" and self.compressor.has_levels
        )

    def _hist_quant(self) -> QuantConfig:
        return self.cfg.quant if self.cfg.quant is not None else _DEFAULT_QUANT_LO

    def _flat_hist(self, x_flat) -> Array:
        q = self._hist_quant()
        v2d, _ = _pad_to_buckets(
            x_flat.reshape(-1).astype(jnp.float32), q.bucket_size
        )
        return qada.normalized_coord_histogram(
            v2d, bucket_norms(v2d, q.q_norm), bins=self.cfg.qada_bins
        )

    def _tree_hist(self, tree) -> Array:
        """Sufficient statistics of a pytree, leaf-by-leaf — no full-size
        flat concatenation (the only O(n) pass is the histogram reads)."""
        hist = jnp.zeros((self.cfg.qada_bins,), jnp.float32)
        for g in jax.tree_util.tree_leaves(tree):
            hist = hist + self._flat_hist(g.reshape(-1))
        return hist

    def _leafwise_hist(self, tree) -> Array:
        # per-leaf rows over the trailing dim (the leafwise "bucket"), no
        # flat concat — keeps the sharding-preserving property
        q = self._hist_quant()
        hist = jnp.zeros((self.cfg.qada_bins,), jnp.float32)
        for g in jax.tree_util.tree_leaves(tree):
            v2d = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
            hist = hist + qada.normalized_coord_histogram(
                v2d, bucket_norms(v2d, q.q_norm), bins=self.cfg.qada_bins
            )
        return hist

    def _advance(self, state: ExchangeState, local_hist=None) -> ExchangeState:
        """Bump the call counter; with QAda stats, merge + maybe refresh.

        The histogram (weighted distribution of normalized coordinates) is
        table-independent, so one merged histogram refreshes every level
        table the compressor carries (both layerwise tables).  The
        coordinate-descent solve runs under ``lax.cond`` — it is only paid
        on refresh steps, not on every exchange call.
        """
        cfg = self.cfg
        if local_hist is None:
            return dataclasses.replace(state, step=state.step + 1)
        # merge sufficient statistics across workers so the state stays
        # replicated over the exchange axis (QAda line 4 of Algorithm 1);
        # the histogram is a real collective operand — record it so the
        # wire metric stays honest under the qada schedule
        _record_wire("qada_hist", local_hist)
        hist = state.hist + jax.lax.psum(local_hist, cfg.axis_name)
        every = cfg.level_update_every
        refresh = (state.step % every) == (every - 1)

        def do_refresh(args):
            levels, levels_lo, h = args
            new, new_lo = self.compressor.refresh_tables(
                levels, levels_lo, h, cfg
            )
            return new, new_lo, jnp.zeros_like(h)

        levels, levels_lo, hist = jax.lax.cond(
            refresh, do_refresh, lambda args: args,
            (state.levels, state.levels_lo, hist),
        )
        return ExchangeState(
            levels=levels, levels_lo=levels_lo,
            hist=hist, step=state.step + 1, error=state.error,
            pending=state.pending,
        )

    # -- exchanges -----------------------------------------------------

    @_exchange_scope
    def pmean(self, x: Array, state: ExchangeState, key: Array,
              axis_index=None, mask: Optional[Array] = None):
        """Unbiased mean of a flat vector over the exchange axis.

        ``axis_index`` (optional traced scalar) supplies this device's
        position along the exchange axis for per-device key derivation on
        partially-manual meshes where ``lax.axis_index`` cannot lower
        (see :func:`_axis_key`); byte-identical when the value matches.

        ``mask`` (optional traced 0/1 scalar, one per device) is the
        PARTIAL-PARTICIPATION hook: a device with ``mask == 0`` is
        excluded from the aggregate — its payload is where-zeroed before
        quantization and the result is renormalized by ``K / psum(mask)``,
        i.e. psum(masked payloads) / psum(mask): an unbiased mean over
        the alive set, for every compressor in the registry.  ``None``
        (default) keeps the exact pre-mask jaxpr; an all-ones mask is
        bit-exact with it (see :func:`_alive_renorm`).  Dropped devices
        still participate in the collectives (this is algorithm-level
        dropout simulation inside one SPMD program — a real communicator
        shrink is a launcher concern), but the WIRE accounting the train
        step emits prices only alive workers.
        """
        if self.compressor.has_error:
            self._reject_mask(mask)
            mean, err = self.compressor.pmean_ef(
                x, self.cfg, state, key, axis_index
            )
            return mean, dataclasses.replace(
                self._advance(state, None), error=err
            )
        if mask is not None:
            x = jnp.where(mask > 0, x, jnp.zeros((), x.dtype))
        mean = self.compressor.pmean(x, self.cfg, state, key, axis_index)
        hist = self._flat_hist(x) if self._qada_active() else None
        return self._finish(mean, state, hist, mask)

    @_exchange_scope
    def pmean_tree(self, tree, state: ExchangeState, key: Array,
                   axis_index=None, mask: Optional[Array] = None):
        """Unbiased mean of a gradient pytree (bucket-fused / per policy).

        ``mask`` excludes this device from the aggregate (renormalized
        over the alive set — see :meth:`pmean`)."""
        if self.cfg.mode == "leafwise":
            return self._pmean_leafwise(tree, state, key, axis_index, mask)
        if self.compressor.has_error:
            self._reject_mask(mask)
            mean, err = self.compressor.pmean_tree_ef(
                tree, self.cfg, state, key, axis_index
            )
            return mean, dataclasses.replace(
                self._advance(state, None), error=err
            )
        if self.cfg.overlap != "off":
            if mask is not None and self.cfg.overlap == "defer_tail":
                raise ValueError(
                    "overlap='defer_tail' does not support partial-"
                    "participation masks: the applied tail mean is one "
                    "sync stale, and renormalizing it over THIS step's "
                    "alive set would rescale a buffer aggregated under a "
                    "different one — use overlap='bucketed' with masks"
                )
            if mask is not None:
                tree = _mask_tree(tree, mask)
            mean, new_pending = self.compressor.pmean_tree_bucketed(
                tree, self.cfg, state, key, axis_index
            )
            hist = self._tree_hist(tree) if self._qada_active() else None
            mean, new_state = self._finish(mean, state, hist, mask)
            return mean, dataclasses.replace(new_state, pending=new_pending)
        if mask is not None:
            tree = _mask_tree(tree, mask)
        mean = self.compressor.pmean_tree(tree, self.cfg, state, key, axis_index)
        hist = self._tree_hist(tree) if self._qada_active() else None
        return self._finish(mean, state, hist, mask)

    def _pmean_leafwise(self, tree, state: ExchangeState, key: Array,
                        axis_index=None, mask: Optional[Array] = None):
        """Sharding-preserving per-leaf exchange (production mesh)."""
        cfg = dataclasses.replace(self.cfg, mode="leafwise")
        self.compressor.validate(cfg)  # loud, not a silent flat fallback
        if mask is not None:
            tree = _mask_tree(tree, mask)
        mean = self.compressor.pmean_tree(tree, cfg, state, key, axis_index)
        hist = self._leafwise_hist(tree) if self._qada_active() else None
        return self._finish(mean, state, hist, mask)

    pmean_leafwise = _exchange_scope(_pmean_leafwise)

    def _reject_mask(self, mask):
        """Error feedback + partial participation is undefined here: a
        dead worker's memory would go stale while the alive-set renorm
        rescales its stored innovations — reject at trace time rather
        than aggregate garbage."""
        if mask is not None:
            raise ValueError(
                f"compressor {self.cfg.compressor!r} (contractive "
                "contract) does not support partial-participation masks; "
                "run error-feedback exchanges with full participation"
            )

    def _finish(self, mean, state: ExchangeState, hist, mask):
        """Common masked-exchange epilogue: renormalize the mean over the
        alive set and keep dead workers out of the QAda statistics (their
        where-zeroed payload would otherwise pile histogram mass at 0 and
        skew every future level table)."""
        if mask is not None:
            renorm, _ = _alive_renorm(mask, self.cfg.axis_name)
            mean = _renorm_tree(mean, renorm)
            if hist is not None:
                # where, not multiply: a dead worker's stats may be NaN
                # (that can be WHY it was dropped) and NaN * 0 is NaN —
                # it must not poison the psum-merged QAda state
                hist = jnp.where(mask > 0, hist, jnp.zeros_like(hist))
        return mean, self._advance(state, hist)

    # -- collective-free per-worker compression ------------------------

    def compress(self, v: Array, state: ExchangeState, key: Array) -> Array:
        """Per-worker unbiased point estimate hat{v} (no collectives)."""
        return self.compressor.compress(v, self.cfg, state.levels, key)

    def compress_with_levels(self, v: Array, levels: Array, key: Array) -> Array:
        """Like :meth:`compress` with an externally-carried level table
        (the Q-GenX loop keeps levels in QGenXState)."""
        return self.compressor.compress(v, self.cfg, levels, key)

    def compress_tree(self, tree, key: Array, levels: Optional[Array] = None):
        """Per-worker unbiased compression of a pytree, leaf-wise."""
        return self.compressor.compress_tree(tree, self.cfg, levels, key)

    # -- QAda (externally-carried levels, Q-GenX loop) ------------------

    def qada_propose(self, levels: Array, v: Array) -> Array:
        """One QAda refresh proposal from fresh dual vectors ``v`` (any
        shape whose trailing dim is the coordinate dim)."""
        q = self.cfg.quant if self.cfg.quant is not None else _DEFAULT_QUANT_LO
        b = min(q.bucket_size, v.shape[-1])
        v2d = v.reshape(-1, b)
        hist = qada.normalized_coord_histogram(
            v2d, bucket_norms(v2d, q.q_norm), bins=self.cfg.qada_bins
        )
        return qada.optimize_levels(
            levels, hist,
            sweeps=self.cfg.qada_sweeps, bisect_iters=self.cfg.qada_bisect_iters,
        )

    # -- layout --------------------------------------------------------

    def plan_for_tree(self, tree, axis_size: int = 1,
                      purpose: str = "pmean") -> xplan.ExchangePlan:
        """The static ExchangePlan this exchange uses for ``tree`` —
        offsets, segment table, padding tails (benchmarks and tests
        introspect it; ``plan.describe()`` is the layout one-liner)."""
        leaves = jax.tree_util.tree_leaves(tree)
        return self.compressor.plan_for(leaves, self.cfg, axis_size, purpose)

    # -- accounting ----------------------------------------------------

    def coded_bits_tree(self, tree, state: ExchangeState) -> Array:
        """Traced Theorem-2 estimate of the entropy-coded bits ONE worker
        would broadcast for this pytree (CODE o Q with an optimal prefix
        code), under the current level table.

        The fixed-width payloads actually shipped (int8/int4 — XLA cannot
        move ragged bitstreams) are accounted by :meth:`wire_bytes_tree`;
        this is the Section 3.2 code-length the paper proves on top, so
        EXPERIMENTS tables can show both.  The pmf is the *expected*
        index distribution of the unbiased rounding (no PRNG), over the
        bucket-padded flat vector — the same coordinates the fixed-width
        payload pays for, so the two are directly comparable
        (``coded_bits <= 8 * compress_wire_bytes`` for 8-bit configs;
        tested against the :mod:`repro.core.coding` numpy oracle).
        Returns f32 0.0 for every compressor except ``qgenx`` — randk
        ships values+indices (no index entropy to code) and layerwise
        would need per-group pmfs against BOTH level tables (its
        dominant big-leaf group is quantized with ``levels_lo``, which a
        single-table estimate would silently misprice).
        """
        if self.cfg.compressor != "qgenx":
            return jnp.float32(0.0)
        q = self._hist_quant()
        leaves = jax.tree_util.tree_leaves(tree)
        if self.cfg.use_plan:
            # the same (cached) plan the compress path uses: the packed
            # buffer is already bucket-aligned, so the pad is free — and
            # bit-identical to the concat+pad it replaces
            plan = self.compressor.plan_for(leaves, self.cfg, 1, "compress")
            v2d = plan.pack(leaves).reshape(-1, q.bucket_size)
        else:
            flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32) for l in leaves]
            )
            v2d, _ = _pad_to_buckets(flat, q.bucket_size)
        norms = bucket_norms(v2d, q.q_norm)
        safe = jnp.where(norms > 0, norms, 1.0)
        u = jnp.clip(jnp.abs(v2d) / safe[:, None], 0.0, 1.0)
        pmf = expected_index_pmf(u, state.levels)
        nb = v2d.shape[0]
        return theorem2_bits_traced(pmf, nb * q.bucket_size, nb)

    def _qada_wire_bytes(self) -> float:
        """The qada schedule psums the [qada_bins] f32 histogram once per
        pmean call — real collective traffic, counted like any operand."""
        return 4.0 * self.cfg.qada_bins if self._qada_active() else 0.0

    def wire_bytes(self, n: int, axis_size: int) -> float:
        """Analytic collective-operand bytes per device for ONE flat pmean
        of n coordinates — equals the sum of the trace recorder's entries
        (for compressors that hand explicit buffers to collectives)."""
        return (self.compressor.wire_bytes(n, axis_size, self.cfg)
                + self._qada_wire_bytes())

    def wire_bytes_tree(self, tree, axis_size: int) -> float:
        """Same, for one pmean_tree of this pytree (leaf shapes may matter:
        leafwise mode and the layerwise policy account per leaf/group).
        Under the bucketed pipeline the bill is the sum of the per-bucket
        exchanges (each bucket pays its own padding tails — honest about
        the fragmentation cost; see :meth:`bucket_wire_bytes_tree`)."""
        if self.cfg.overlap != "off":
            return (float(sum(self.bucket_wire_bytes_tree(tree, axis_size)))
                    + self._qada_wire_bytes())
        shapes = [l for l in jax.tree_util.tree_leaves(tree)]
        return (self.compressor.wire_bytes_tree(shapes, axis_size, self.cfg)
                + self._qada_wire_bytes())

    def bucket_wire_bytes_tree(self, tree, axis_size: int) -> list:
        """Per-bucket analytic collective-operand bytes for one bucketed
        ``pmean_tree`` — entry i is exactly what the trace recorder's
        ``b{i}/``-prefixed operands sum to (each bucket is accounted as
        its own monolithic exchange over its sub-leaves: same
        ``plan_groups`` policy, same per-bucket quota padding the
        sub-plan applies)."""
        leaves = jax.tree_util.tree_leaves(tree)
        buckets = self.compressor.bucket_partition(leaves, self.cfg)
        mono = dataclasses.replace(self.cfg, num_buckets=1, overlap="off")
        return [
            float(self.compressor.wire_bytes_tree(
                [leaves[i] for i in ids], axis_size, mono
            ))
            for ids in buckets
        ]

    def compress_wire_bytes(self, n: int) -> float:
        """Bytes one worker broadcasts for one compressed n-vector."""
        return self.compressor.compress_wire_bytes(n, self.cfg)

    def compress_wire_bytes_tree(self, tree) -> float:
        """Broadcast bytes for one compressed pytree (per-leaf policies
        accounted leaf-by-leaf, matching :meth:`compress_tree`)."""
        shapes = list(jax.tree_util.tree_leaves(tree))
        return self.compressor.compress_wire_bytes_tree(shapes, self.cfg)


@functools.lru_cache(maxsize=None)
def make_exchange(cfg: ExchangeConfig) -> Exchange:
    """Build (and cache — ExchangeConfig is frozen/hashable) an Exchange.

    Invalid combinations fail loudly here (``Compressor.validate``), not
    deep inside a traced step::

        >>> make_exchange(ExchangeConfig(compressor="randk",
        ...                              mode="leafwise"))
        Traceback (most recent call last):
        ValueError: compressor 'randk' has no sharding-preserving ...
    """
    ex = Exchange(cfg)
    ex.compressor.validate(cfg)
    return ex
