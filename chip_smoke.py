#!/usr/bin/env python3
"""Smoke test of Q-GenX's main paths on a TPU v5e.

    python3 chip_smoke.py                # one chip: kernels, train, serve
    python3 chip_smoke.py --four-chips   # four chips: int8 vs fp32 DP training

Every phase runs in this one process (a chip belongs to one process) and
drives the system through the entry points a user calls, with qwen3-4b at
its published widths and random weights from a fixed seed:

* kernels — the Mosaic-compiled exchange kernels (quantize, dequantize,
  dequant-reduce, dequant-reduce-requantize, segment Q∘DEQ) at the
  gradient size of one qwen3-4b layer, each checked against its jnp
  reference, and the on-core PRNG's round-up share checked for bias;
* train — ``repro.launch.train.main`` with ``--compression int8
  --use-pallas``: the quantized exchange over the one-chip mesh, bf16, at
  the largest depth the compiler's memory analysis says fits the chip;
  the loss must be finite and fall, and ``wire`` must be > 0;
* serve — ``ServeEngine`` over all 36 layers in bf16 with the int8 paged
  KV cache and the decode guard: 8 requests x 32 tokens must all finish
  ``ok`` with every page freed; compile, prefill and decode time are
  printed apart.

``--four-chips`` runs only data-parallel training across the four chips:
int8 ``qgenx`` ``two_phase`` with the Pallas kernels against the exact
fp32 mean (``--compressor none``), same widths, same depth.  The final
losses must agree within ``LOSS_BAND`` of the fp32 run's fall, and the
logged wire bytes must equal the ExchangePlan's analytic count.  Where a
layer does not fit, the exchange's scratch is first bounded with its
buckets (``--num-buckets``); only then is depth lowered.

Depth is the only cut.  The last line of standard output is one JSON
object ``{"ok": true, "device": {...}}``, printed only when every check
passed; with no TPU the script exits 1 and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-4b"
BUCKET = 512
TRAIN_STEPS = 8
TRAIN_BATCH = 4  # per chip
TRAIN_SEQ = 128
SERVE_REQUESTS = 8
SERVE_PROMPT = 32
SERVE_NEW = 32
#: 4 chips: |final int8 loss - final fp32 loss| allowed, as a share of
#: the fp32 run's own loss decrease over the same steps
LOSS_BAND = 0.2


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


class _Tee(io.TextIOBase):
    """stdout that is also kept: the launchers report through it."""

    def __init__(self, out):
        self.out, self.kept = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.kept.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


@contextlib.contextmanager
def kept_stdout():
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        yield tee.kept


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from jax's
    monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _index_agreement(jnp, got, want, bits, what):
    """Kernel and reference indices agree: XLA and Mosaic may round the
    normalisation differently in the last bit, which can move a value
    sitting on a rounding threshold by one level — nothing else."""
    from repro.kernels.common import unpack4_rows

    if bits == 4:
        got, want = unpack4_rows(got), unpack4_rows(want)
    diff = jnp.abs(got.astype(jnp.int32) - want.astype(jnp.int32))
    frac = float(jnp.mean((diff > 0).astype(jnp.float32)))
    worst = int(jnp.max(diff))
    log(f"{what}: index mismatch share {frac:.3e}, largest gap {worst}")
    check(worst <= 1 and frac <= 1e-5, f"{what} matches its reference")


def _close(jnp, got, want, what, rtol=1e-5):
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    log(f"{what}: max |err| {err:.3e} (scale {scale:.3e})")
    check(err <= rtol * scale, f"{what} matches its reference")


def kernel_phase(jax, jnp, n):
    """Every exchange kernel on ``n`` coordinates against its reference."""
    from repro.core.quantization import QuantConfig, uniform_levels
    from repro.kernels.common import derive_prng_seed, unpack4_rows
    from repro.kernels.dequant_reduce import (
        dequant_reduce_blocks,
        dequant_reduce_ref,
        dequant_reduce_requantize_blocks,
    )
    from repro.kernels.dequantize import dequantize_blocks
    from repro.kernels.quantize import quantize_blocks
    from repro.kernels.ref import (
        dequantize_blocks_ref,
        quantize_blocks_ref,
        quantize_dequantize_segments_ref,
    )
    from repro.kernels.segment_quantize import quantize_dequantize_segments

    nb = -(-n // BUCKET)
    k = 4  # workers folded into the dequant-reduce kernels
    nbk = nb // k
    kx, kn, kn2, kp = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kx, (nb, BUCKET), jnp.float32)
    noise = jax.random.uniform(kn, (nb, BUCKET), jnp.float32)
    noise2 = jax.random.uniform(kn2, (nbk, BUCKET), jnp.float32)
    log(f"kernels: {n} coordinates, bucket {BUCKET}, {nb} rows")
    tables = []
    for bits in (8, 4):
        q = QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                        bucket_size=BUCKET)
        inf = math.isinf(q.q_norm)
        lv = uniform_levels(q.num_levels)
        tables.append(lv)
        ns = q.num_symbols
        tag = f"int{bits}"

        idx, norms = quantize_blocks(x, noise, lv, num_symbols=ns,
                                     q_is_inf=inf, bits=bits)
        idx_r, norms_r = quantize_blocks_ref(x, noise, lv, q_is_inf=inf,
                                             bits=bits)
        _close(jnp, norms, norms_r, f"{tag} quantize norms")
        _index_agreement(jnp, idx, idx_r, bits, f"{tag} quantize")

        out = dequantize_blocks(idx, norms, lv, num_symbols=ns, bits=bits)
        _close(jnp, out, dequantize_blocks_ref(idx, norms, lv, bits=bits),
               f"{tag} dequantize", rtol=1e-6)

        # K workers' payloads of one chunk, as the two-phase exchange
        # hands them to the middle step
        pk = idx[: k * nbk].reshape(k, nbk, -1)
        nk = norms[: k * nbk].reshape(k, nbk)
        unpacked = (unpack4_rows(pk.reshape(k * nbk, -1)).reshape(k, nbk, -1)
                    if bits == 4 else pk)
        mean_r = dequant_reduce_ref(unpacked, nk, lv)
        mean = dequant_reduce_blocks(pk, nk, lv, num_symbols=ns,
                                     num_workers=k, bits=bits)
        _close(jnp, mean, mean_r, f"{tag} dequant-reduce")

        ridx, rnorms = dequant_reduce_requantize_blocks(
            pk, nk, lv, noise2, num_symbols=ns, num_workers=k, q_is_inf=inf,
            bits=bits)
        ridx_r, rnorms_r = quantize_blocks_ref(mean_r, noise2, lv,
                                               q_is_inf=inf, bits=bits)
        _close(jnp, rnorms, rnorms_r, f"{tag} dequant-reduce-requantize norms")
        _index_agreement(jnp, ridx, ridx_r, bits,
                         f"{tag} dequant-reduce-requantize")

        prng_check(jax, jnp, x, lv, q, derive_prng_seed(kp), norms_r, tag)

    # segment Q∘DEQ: the int8 and int4 tables over halves of the buffer
    from repro.core.exchange_plan import stack_level_tables

    stacked, nsym = stack_level_tables(tables)
    seg = (jnp.arange(nb) >= nb // 2).astype(jnp.int32)
    hat = quantize_dequantize_segments(x, noise, stacked, seg,
                                       num_symbols=nsym, q_is_inf=True)
    hat_r = quantize_dequantize_segments_ref(x, noise, stacked, seg,
                                             num_symbols=nsym, q_is_inf=True)
    # a threshold case moves one level: at most 1/4 (int4) of the norm
    norm = jnp.max(jnp.abs(x), axis=1)[:, None]
    off = jnp.abs(hat - hat_r) > 1e-6 * norm
    frac = float(jnp.mean(off.astype(jnp.float32)))
    worst = float(jnp.max(jnp.abs(hat - hat_r) / norm))
    log(f"segment Q∘DEQ: mismatch share {frac:.3e}, largest gap "
        f"{worst:.3f} of the bucket norm")
    check(frac <= 1e-5 and worst <= 0.25 + 1e-6,
          "segment Q∘DEQ matches its reference")


def prng_check(jax, jnp, x, lv, q, seed, norms_r, tag):
    """On-core PRNG: the share of values rounded up must be the rounding
    probability xi (Definition 1 unbiasedness), within 6 sigma."""
    from repro.kernels.common import unpack4_rows
    from repro.kernels.quantize import quantize_blocks

    ns = q.num_symbols
    pidx, pnorms = quantize_blocks(
        x, None, lv, num_symbols=ns, q_is_inf=math.isinf(q.q_norm),
        bits=q.bits, use_device_prng=True, seed=seed)
    _close(jnp, pnorms, norms_r, f"{tag} device-PRNG quantize norms")
    signed = unpack4_rows(pidx) if q.bits == 4 else pidx.astype(jnp.int32)
    safe = jnp.where(norms_r > 0, norms_r, 1.0)[:, None]
    u = jnp.clip(jnp.abs(x) / safe, 0.0, 1.0)
    tau = jnp.clip(jnp.searchsorted(lv, u, side="right") - 1, 0, ns - 2)
    xi = (u - lv[tau]) / (lv[tau + 1] - lv[tau])
    up = jnp.abs(signed) - tau
    # a value on a threshold may bracket one level off (see
    # _index_agreement); it is left out of the count
    inside = (up == 0) | (up == 1)
    frac = float(jnp.mean((~inside).astype(jnp.float32)))
    check(frac <= 1e-5, f"{tag} device-PRNG indices bracket the value "
                        f"(outside share {frac:.3e})")
    xi = jnp.where(inside, xi, 0.0)
    ups = jnp.sum(jnp.where(inside, up, 0).astype(jnp.float32))
    z = float((ups - jnp.sum(xi)) / jnp.sqrt(jnp.sum(xi * (1.0 - xi))))
    log(f"{tag} device-PRNG round-up share {float(ups) / x.size:.6f} vs "
        f"expected {float(jnp.sum(xi)) / x.size:.6f} (z = {z:+.2f})")
    check(abs(z) < 6.0, f"{tag} device-PRNG rounding is unbiased")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _step_bytes(jax, jnp, train, argv, cfg, mesh):
    """Device bytes the compiled train step plans for (fullest device)."""
    from repro.core import faults

    args = train.build_parser().parse_args(argv)
    run = train.build_run(args, cfg, mesh,
                          faults.parse_fault_spec_arg("", scope="train"))
    state = jax.eval_shape(run.init_state, jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=run.state_sharding), state)
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
             for k in ("tokens", "labels")}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    try:
        compiled = run.step.lower(*state, batch, key).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        # the compiler refuses a program that cannot fit the device
        log(f"depth {cfg.num_layers}: {str(e).splitlines()[0][:160]}")
        return math.inf
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def deepest_fit(jax, jnp, train, argv, full, mesh, budget):
    """Largest depth at which the run of ``argv`` fits ``budget`` bytes
    per device, by the compiler's memory analysis: two depths fix the
    per-layer slope, then the prediction is confirmed."""
    cache: dict = {}

    def need(depth):
        if depth not in cache:
            cfg = dataclasses.replace(full, num_layers=depth)
            cache[depth] = _step_bytes(jax, jnp, train, argv, cfg, mesh)
            log(f"depth {depth}: fullest device plans "
                f"{cache[depth] / 2**30:.2f} GiB of {budget / 2**30:.2f}")
        return cache[depth]

    if need(1) > budget:
        raise SmokeFailure("one layer does not fit the chip")
    slope = need(2) - need(1)
    depth = (1 if math.isinf(slope) else
             min(full.num_layers, 1 + int((budget - need(1)) // max(slope, 1))))
    while depth > 1 and need(depth) > budget:
        depth -= 1
    while depth < full.num_layers and need(depth + 1) <= budget:
        depth += 1
    return depth, need(depth)


def _train(train, argv, cfg):
    """One ``train.main`` run on ``cfg``; returns its per-step (loss,
    wire) pairs and its log."""
    with mock.patch.object(train, "get_config", lambda arch: cfg), \
            kept_stdout() as out:
        train.main(argv)
    text = out.getvalue()
    steps = [(float(m.group(1)), float(m.group(2))) for m in re.finditer(
        r"\[train\] step=\d+ loss=(\S+) dt=\S+ wire=(\S+)B", text)]
    if len(steps) != TRAIN_STEPS:
        raise SmokeFailure(f"expected {TRAIN_STEPS} step lines, got "
                           f"{len(steps)}")
    return steps, text


def _train_argv(batch, *extra):
    return ["--arch", ARCH, "--steps", str(TRAIN_STEPS), "--batch",
            str(batch), "--seq", str(TRAIN_SEQ), "--repeat-batch",
            "--log-every", "1", *extra]


INT8 = ("--compression", "int8", "--compressor", "qgenx",
        "--compress-mode", "two_phase", "--use-pallas")


def train_phase(jax, jnp, np, n_chips, limit):
    """Train through ``train.main`` at the deepest depth whose step fits
    ``limit`` bytes per device; on 4 chips, int8 against fp32."""
    from jax.sharding import Mesh

    from repro.configs.registry import get_config
    from repro.launch import train

    full = get_config(ARCH)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    batch = TRAIN_BATCH * n_chips
    # the exchange's scratch is bounded by its buckets before any further
    # cut: monolithic first, then 2 and 4 buckets
    for buckets in (1, 2, 4):
        split = (("--num-buckets", str(buckets), "--overlap", "bucketed")
                 if buckets > 1 else ())
        int8 = _train_argv(batch, *INT8, *split)
        try:
            depth, need = deepest_fit(jax, jnp, train, int8, full, mesh,
                                      limit)
            break
        except SmokeFailure as e:
            log(f"{buckets} exchange bucket(s): {e}")
    else:
        raise SmokeFailure("no bucket count fits one layer on the chip")
    runs = {"int8": int8}
    cfg = dataclasses.replace(full, num_layers=depth)
    log(f"train: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads}/"
        f"{cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}; exchange buckets "
        f"{buckets}")
    log(f"train: cut depth {full.num_layers} -> {depth} layers "
        f"({cfg.param_count() / 1e9:.3f} B params); fullest device plans "
        f"{need} B of {limit} B")
    if n_chips > 1:
        runs["fp32"] = _train_argv(batch, "--compressor", "none")
        fp32 = _step_bytes(jax, jnp, train, runs["fp32"], cfg, mesh)
        log(f"fp32 control at depth {depth} plans {fp32} B")
        check(fp32 <= limit, "the fp32 control fits too")
    results = {}
    for name, argv in runs.items():
        steps, text = _train(train, argv, cfg)
        losses = [s[0] for s in steps]
        wires = [s[1] for s in steps]
        check("[train] exchange: compressor=" in text,
              f"{name}: the exchange is built over {n_chips} chip(s)")
        check(all(math.isfinite(v) for v in losses), f"{name}: loss finite")
        check(losses[-1] < losses[0],
              f"{name}: loss falls {losses[0]:.4f} -> {losses[-1]:.4f}")
        check(all(w > 0 for w in wires), f"{name}: wire > 0 every step")
        if name == "int8":
            check("use_pallas=True" in text, "int8: exchange uses the kernels")
        results[name] = (losses, wires)
    if n_chips == 1:
        return
    # 4 chips: int8 against the exact mean, and the wire against the plan
    (l8, w8), (l32, _) = results["int8"], results["fp32"]
    gap = abs(l8[-1] - l32[-1])
    progress = l32[0] - l32[-1]
    log(f"final loss int8 {l8[-1]:.4f} vs fp32 {l32[-1]:.4f}: gap "
        f"{gap:.4f} = {gap / progress:.4f} of fp32's fall {progress:.4f} "
        f"(band {LOSS_BAND})")
    check(gap <= LOSS_BAND * progress,
          "int8 and fp32 final losses agree within the band")
    want = plan_wire_bytes(jax, train, int8, cfg, mesh, n_chips)
    log(f"wire per step: logged {w8[0]:.4e} B, ExchangePlan {want:.6e} B")
    check(all(abs(w - want) <= 5e-4 * want for w in w8),
          "logged wire bytes equal the ExchangePlan count")


def plan_wire_bytes(jax, train, argv, cfg, mesh, n_chips):
    """Collective-operand bytes one device moves per step, counted from
    the ExchangePlan of each exchange bucket and the two-phase buffer
    sizes."""
    from repro.core import faults
    from repro.core.exchange import exchange_buffer_bytes

    args = train.build_parser().parse_args(argv)
    run = train.build_run(args, cfg, mesh,
                          faults.parse_fault_spec_arg("", scope="train"))
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(run.model.init, jax.random.PRNGKey(0)))
    comp, ex_cfg = run.ex.compressor, run.ex_cfg
    buckets = (comp.bucket_partition(leaves, ex_cfg)
               if ex_cfg.num_buckets > 1 else (range(len(leaves)),))
    mono = dataclasses.replace(ex_cfg, num_buckets=1, overlap="off")
    per_exchange = 0
    for ids in buckets:
        plan = comp.plan_for([leaves[i] for i in ids], mono, n_chips,
                             "pmean")
        per_exchange += sum(
            sum(exchange_buffer_bytes(s.padded, n_chips, s.quant,
                                      mono.mode).values())
            for s in plan.segments)
    return 2.0 * per_exchange  # extra_adam exchanges two gradients a step


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_phase(jax, np):
    from repro.configs.registry import get_config
    from repro.models.model import build
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request

    cfg = get_config(ARCH)
    log(f"serve: {ARCH} {cfg.num_layers} layers, dtype={cfg.dtype}, int8 "
        f"paged KV, {SERVE_REQUESTS} requests x {SERVE_NEW} tokens "
        f"(prompts {SERVE_PROMPT}), guard on")
    params = jax.jit(build(cfg).init)(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, policy="int8", page_size=8,
                      n_slots=SERVE_REQUESTS,
                      max_len=SERVE_PROMPT + SERVE_NEW, seed=0, guard=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, SERVE_PROMPT).tolist()
               for _ in range(SERVE_REQUESTS)]

    def run(max_new):
        eng.reset()
        reqs = [Request(rid=r, prompt=p, max_new=max_new)
                for r, p in enumerate(prompts)]
        t0 = time.perf_counter()
        out = eng.run(reqs)  # ends on host tokens: the device is done
        return time.perf_counter() - t0, out

    cold, out = run(SERVE_NEW)
    results = eng.results()
    kinds = sorted({r.kind for r in results.values()})
    check(len(results) == SERVE_REQUESTS and kinds == ["ok"],
          f"serve: {SERVE_REQUESTS}/{SERVE_REQUESTS} results ok "
          f"(kinds {kinds})")
    check(all(len(out[r]) == SERVE_NEW for r in range(SERVE_REQUESTS)),
          f"serve: every request got {SERVE_NEW} tokens")
    check(eng.allocator.n_free == eng.allocator.num_pages,
          f"serve: all {eng.allocator.num_pages} pages freed")
    waves = eng.sched.decode_steps
    prefill, _ = run(1)  # one token each: prefill only, no decode wave
    warm, out2 = run(SERVE_NEW)
    check(out2 == out, "serve: a warm rerun gives the same tokens")
    decode = warm - prefill
    n_tok = SERVE_REQUESTS * SERVE_NEW
    distinct = len({t for toks in out.values() for t in toks})
    log(f"serve: compile {cold - warm:.3f} s (cold run {cold:.3f} s - warm "
        f"run {warm:.3f} s); prefill {prefill:.3f} s for "
        f"{SERVE_REQUESTS} prompts; decode {decode:.3f} s for {waves} "
        f"waves ({1e3 * decode / max(waves, 1):.1f} ms/wave); warm "
        f"{n_tok / warm:.1f} tok/s")
    log(f"serve: {distinct} distinct token ids among {n_tok} sampled "
        f"(random weights)")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only data-parallel int8 vs fp32 training "
                         "across four chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.cache import enable_compilation_cache

    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chips else 1
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: jax found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) != want:
        print(f"[smoke] need {want} chip(s), found {len(devices)}",
              file=sys.stderr)
        return 1
    stats = CompileStats(jax)
    log(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
        f"jax {jax.__version__}; compilation cache "
        f"{enable_compilation_cache() or 'off'}")
    limit = dev.memory_stats()["bytes_limit"]
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            train_phase(jax, jnp, np, 4, limit)
        else:
            from repro.models import transformer as T
            from repro.configs.registry import get_config

            layer = jax.eval_shape(
                lambda k: T.layer_init(k, get_config(ARCH), False),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            n = sum(math.prod(a.shape)
                    for a in jax.tree_util.tree_leaves(layer))
            kernel_phase(jax, jnp, n)
            train_phase(jax, jnp, np, 1, limit)
            serve_phase(jax, np)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"total {time.perf_counter() - t0:.1f} s; backend compile "
        f"{stats.seconds:.1f} s; persistent cache hits {stats.hits} of "
        f"{stats.requests} lookups")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
