"""Kernel micro-benchmarks: Pallas (interpret mode) vs jnp reference for
the fused exchange pipeline, plus derived wire/HBM-traffic models.

NOTE: on this CPU container the Pallas numbers measure the *interpret mode*
(Python-level) path and are NOT representative of TPU throughput — the jnp
reference timing is the CPU-meaningful number; the Pallas rows prove the
kernel contract at the same shapes.  The ``hbm_model`` columns are the
analytic HBM-traffic ratios (bytes moved fused / bytes moved unfused) that
the fusion buys on real hardware — the quantity the paper's exchange-cost
argument depends on.

HBM traffic model per n coordinates (per = 1 byte int8, 0.5 packed int4;
norms are n/bucket f32 and negligible):

* unfused exchange consumer (dequantize + mean):
  read K.n.per + write 4Kn + read 4Kn + write 4n  = n(K.per + 8K + 4)
* fused dequant_reduce: read K.n.per + write 4n
* unfused two-phase middle (dequantize + mean + quantize), host noise:
  n(K.per + 8K + 12 + per)
* fused dequant_reduce_requantize, host noise: n(K.per + 4 + per)
* fused + on-device PRNG: n(K.per + per)   — the paper-grade K.n/2 + n/2
  wire-and-HBM figure in 4-bit mode.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.quantization import QuantConfig, uniform_levels
from repro.kernels.dequantize import dequantize_blocks
from repro.kernels.quantize import quantize_blocks
from repro.kernels.ref import dequantize_blocks_ref, quantize_blocks_ref

KEY = jax.random.PRNGKey(0)


def _hbm_unfused_consumer(K, per):
    return K * per + 8 * K + 4


def _hbm_fused_consumer(K, per):
    return K * per + 4


def _hbm_unfused_two_phase_mid(K, per):
    return K * per + 8 * K + 12 + per


def _hbm_fused_two_phase_mid(K, per, device_prng=False):
    return K * per + per + (0 if device_prng else 4)


def run():
    s = 15
    levels = uniform_levels(s)
    for nb, bucket in ((16, 1024), (64, 1024)):
        x = jax.random.normal(KEY, (nb, bucket), jnp.float32)
        noise = jax.random.uniform(jax.random.PRNGKey(1), (nb, bucket))
        n = nb * bucket

        ref_q = jax.jit(lambda a, r: quantize_blocks_ref(a, r, levels, q_is_inf=True))
        us = time_fn(ref_q, x, noise, iters=5)
        emit(f"quantize_ref_jnp_{n}", us, f"GBps={(n*4/us*1e6)/1e9:.2f}")

        pl_q = lambda a, r: quantize_blocks(
            a, r, levels, num_symbols=s + 2, q_is_inf=True
        )
        us = time_fn(pl_q, x, noise, iters=3)
        emit(f"quantize_pallas_interp_{n}", us, "interpret-mode;contract-only")

        idx, norms = ref_q(x, noise)
        ref_d = jax.jit(lambda i, m: dequantize_blocks_ref(i, m, levels))
        us = time_fn(ref_d, idx, norms, iters=5)
        emit(f"dequantize_ref_jnp_{n}", us, f"GBps={(n*4/us*1e6)/1e9:.2f}")

        pl_d = lambda i, m: dequantize_blocks(i, m, levels, num_symbols=s + 2)
        us = time_fn(pl_d, idx, norms, iters=3)
        emit(f"dequantize_pallas_interp_{n}", us, "interpret-mode;contract-only")

    # in-kernel int4 packing: payload leaving the kernel IS the wire buffer
    lv4 = uniform_levels(5)
    nb, bucket = 16, 1024
    n = nb * bucket
    x = jax.random.normal(KEY, (nb, bucket), jnp.float32)
    noise = jax.random.uniform(jax.random.PRNGKey(1), (nb, bucket))
    pl_q4 = lambda a, r: quantize_blocks(a, r, lv4, num_symbols=7, q_is_inf=True, bits=4)
    us = time_fn(pl_q4, x, noise, iters=3)
    emit(f"quantize_pallas_int4_packed_{n}", us,
         f"payload_bytes={n // 2};wire_halved")

    # fused dequant+mean (exchange consumer) vs unfused pipeline
    from repro.kernels.dequant_reduce import (
        dequant_reduce_blocks,
        dequant_reduce_ref,
        dequant_reduce_requantize_blocks,
    )

    K, nb, bucket = 8, 16, 1024
    n = nb * bucket
    rng = np.random.RandomState(0)
    idxs = jnp.asarray(rng.randint(-16, 17, size=(K, nb, bucket)), jnp.int8)
    nrm = jnp.asarray(np.abs(rng.randn(K, nb)) + 0.1, jnp.float32)
    from repro.kernels.common import pack4_rows

    for bits, per in ((8, 1.0), (4, 0.5)):
        if bits == 8:
            payload = idxs
        else:
            # legal 4-bit payload: |idx| <= 6 for the 7-entry level table
            raw = rng.randint(-6, 7, size=(K * nb, bucket))
            payload = jnp.stack([
                pack4_rows(jnp.asarray(raw[r * nb:(r + 1) * nb], jnp.int32))
                for r in range(K)
            ])
        lv = levels if bits == 8 else lv4
        ns = s + 2 if bits == 8 else 7
        fused = lambda a, b: dequant_reduce_blocks(
            a, b, lv, num_symbols=ns, num_workers=K, bits=bits
        )
        us = time_fn(fused, payload, nrm, iters=3)
        ratio = _hbm_fused_consumer(K, per) / _hbm_unfused_consumer(K, per)
        emit(f"dequant_reduce_pallas_interp_b{bits}_K{K}_{n}",
             us, f"hbm_model={ratio:.3f}x_of_unfused")

        # fused two-phase middle step (deq+mean+requantize, one kernel)
        noise2 = jax.random.uniform(jax.random.PRNGKey(2), (nb, bucket))
        fused_rq = lambda a, b, r: dequant_reduce_requantize_blocks(
            a, b, lv, r, num_symbols=ns, num_workers=K, q_is_inf=True, bits=bits
        )
        us = time_fn(fused_rq, payload, nrm, noise2, iters=3)
        ratio = _hbm_fused_two_phase_mid(K, per) / _hbm_unfused_two_phase_mid(K, per)
        ratio_prng = _hbm_fused_two_phase_mid(K, per, device_prng=True) / \
            _hbm_unfused_two_phase_mid(K, per)
        emit(f"dequant_reduce_requant_pallas_interp_b{bits}_K{K}_{n}", us,
             f"hbm_model={ratio:.3f}x_of_unfused;device_prng={ratio_prng:.3f}x")

    us = time_fn(jax.jit(lambda a, b: dequant_reduce_ref(a, b, levels)), idxs, nrm, iters=5)
    emit(f"dequant_reduce_ref_jnp_K{K}_{n}", us, "")

    # derived wire bytes per setting (App. I trade-off inputs) — from the
    # exact collective-buffer accounting (exchange_buffer_bytes)
    from repro.core.exchange import (
        ExchangeConfig,
        make_exchange,
        wire_bytes_per_device,
    )

    n = 1 << 20
    for tag, cfg in (
        ("fp32", None),
        ("uq8", QuantConfig(num_levels=15, bits=8, bucket_size=1024)),
        ("uq4", QuantConfig(num_levels=5, bits=4, bucket_size=1024)),
    ):
        for mode in ("gather", "two_phase"):
            for K in (3, 16, 512):
                b = wire_bytes_per_device(n, K, cfg, mode=mode)
                emit(f"wire_bytes_{tag}_{mode}_K{K}", 0.0, f"bytes={b:.3e}")

    # the registry's non-quantization compressors, same accounting surface
    for tag, exc in (
        ("randk1pct", ExchangeConfig(compressor="randk", rand_frac=0.01)),
        ("layerwise", ExchangeConfig(
            compressor="layerwise",
            quant=QuantConfig(num_levels=5, bits=4, bucket_size=1024),
        )),
    ):
        ex = make_exchange(exc)
        for K in (3, 16, 512):
            emit(f"wire_bytes_{tag}_K{K}", 0.0,
                 f"bytes={ex.wire_bytes(n, K):.3e}")

    # local-update regime (ExchangeConfig.sync_every): amortized bytes per
    # optimizer step — 2 grad exchanges + the f32 drift probe paid once
    # every sync_every steps (extragradient step, 16-way axis, uq8
    # two_phase; same analytic accounting the train step's wire_bytes
    # metric emits and the trace recorder confirms)
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=15, bits=8, bucket_size=1024),
    ))
    base = 2 * ex.wire_bytes(n, 16)
    probe_bytes = 4.0 * ex.cfg.drift_probe  # single-sourced with the metric
    for sync in (1, 4, 16):
        per_step = (base + (probe_bytes if sync > 1 else 0.0)) / sync
        emit(f"wire_bytes_sync_every{sync}_uq8_two_phase_K16", 0.0,
             f"bytes_per_step={per_step:.3e};reduction={base / per_step:.2f}x")

    # method engine (core/methods.py): broadcast rounds per optimizer step
    # scale the amortized wire — optda's one-call schedule halves the de
    # gradient traffic at equal steps (the oracle-efficiency headline)
    from repro.core.methods import METHODS

    per_ex = ex.wire_bytes(n, 16)
    for mname in ("de", "optda"):
        m = METHODS[mname]
        emit(f"wire_bytes_method_{mname}_uq8_two_phase_K16", 0.0,
             f"bytes_per_step={m.exchanges * per_ex:.3e};"
             f"oracle_calls={m.oracle_calls};exchanges={m.exchanges}")

    # compressed parameter re-centering (ExchangeConfig.recenter_every):
    # one extra params-shaped exchange every R steps on top of the
    # sync_every=4 regime — amortized drift-for-wire price
    sync_step = base + probe_bytes  # 2 grad exchanges + probe, every 4th
    for rc in (0, 16, 4):
        per_step = (sync_step / 4) + (per_ex / rc if rc else 0.0)
        emit(f"wire_bytes_recenter_every{rc}_sync4_uq8_two_phase_K16", 0.0,
             f"bytes_per_step={per_step:.3e};"
             f"recenter_overhead={(per_ex / rc if rc else 0.0):.3e}")

    # ExchangePlan (DESIGN §1.5): plan-vs-legacy launch counts and the
    # fused-segment layout — the planned compress_tree/re-centering path
    # collapses the per-leaf quantize+dequantize launch pair per leaf
    # into one segment-fused invocation per row-geometry class
    import dataclasses

    # a params-like pytree: 24 mixed-size leaves, none bucket-aligned
    tree = {
        f"layer{i}": jax.random.normal(
            jax.random.fold_in(KEY, i),
            ((130 + 17 * i, 96) if i % 3 else (510 + i,)), jnp.float32)
        for i in range(24)
    }
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    n_tree = sum(l.size for l in jax.tree_util.tree_leaves(tree))
    key = jax.random.PRNGKey(7)
    plan_cfg = ExchangeConfig(
        compressor="qgenx",
        quant=QuantConfig(num_levels=15, bits=8, bucket_size=512),
    )
    def _geometry_classes(ex):
        plan = ex.plan_for_tree(tree, purpose="compress")
        return len({(s.quant.bucket_size, s.quant.q_norm, s.quant.stochastic)
                    for s in plan.segments})

    for use_plan, tag in ((False, "legacy_perleaf"), (True, "plan_fused")):
        ex = make_exchange(dataclasses.replace(plan_cfg, use_plan=use_plan))
        fn = jax.jit(lambda t, k, ex=ex: ex.compress_tree(t, k))
        us = time_fn(fn, tree, key, iters=5)
        # invocation counts derived from the actual dispatch structure:
        # the per-leaf path loops once per leaf by construction; the plan
        # path launches once per row-geometry class of ITS OWN plan
        launches = _geometry_classes(ex) if use_plan else n_leaves
        # the pallas variant's jaxpr proves the launch count at trace time
        ex_pl = make_exchange(dataclasses.replace(
            plan_cfg, use_plan=use_plan, use_pallas=True))
        # (a kernel is staged once per platform branch; count the TPU's)
        pallas_calls = str(jax.make_jaxpr(
            lambda t, k: ex_pl.compress_tree(t, k))(tree, key)
        ).count("interpret=False")
        emit(f"compress_tree_{tag}_{n_tree}", us,
             f"quantize_invocations={launches};leaves={n_leaves};"
             f"pallas_calls={pallas_calls}")

    # fused-segment row: the layerwise per-layer policy as segments of
    # ONE planned buffer — segment-indexed level tables, one invocation
    # per row-geometry class instead of per leaf
    lw = make_exchange(ExchangeConfig(
        compressor="layerwise",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=512),
        quant_small=QuantConfig(num_levels=15, bits=8, bucket_size=512),
        layerwise_threshold=16384,
    ))
    plan = lw.plan_for_tree(tree, purpose="compress")
    geometries = {(s.quant.bucket_size, s.quant.q_norm, s.quant.stochastic)
                  for s in plan.segments}
    fn = jax.jit(lambda t, k: lw.compress_tree(t, k))
    us = time_fn(fn, tree, key, iters=5)
    emit(f"compress_tree_layerwise_plan_segments_{n_tree}", us,
         f"segments={len(plan.segments)};tables={len(plan.segments)};"
         f"fused_invocations={len(geometries)};"
         f"legacy_invocations={n_leaves};"
         f"pad_coords={plan.total - plan.n_live}")


if __name__ == "__main__":
    run()
