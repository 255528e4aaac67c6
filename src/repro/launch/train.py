"""End-to-end training driver.

Runs on whatever devices are visible (1 CPU, 8 forced host devices via
--host-devices, or a real TPU slice).  The paper's technique is enabled
with --compression int8|int4 (+ --compress-axis data for the DDP setting);
the full exchange subsystem is reachable from here: --compressor selects
the registered compressor (qgenx | randk | layerwise | none, plus the
contractive error-feedback entries ef21-topk | ef-randk, whose per-worker
memory rides in ExchangeState.error), --level-schedule qada turns on
adaptive levels (QAda, Section 3.3) carried in the explicit ExchangeState,
and --use-pallas routes the exchange through the fused Pallas kernels.

Example (CPU, reduced model, compressed 8-way DP exchange):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --host-devices 8 --steps 20 --batch 8 --seq 128 \
      --compression int8 --compress-axis data
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpointing
from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCHS, get_config
from repro.core import faults
from repro.core.exchange import (
    ExchangeConfig,
    make_exchange,
    null_exchange_state,
    registered_compressors,
)
from repro.core.quantization import QuantConfig
from repro.data.pipeline import add_modality_stubs, make_pipeline
from repro.launch.cache import enable_compilation_cache, profile_trace
from repro.launch.steps import make_train_step
from repro.models.model import build
from repro.optim import optimizers as opt
from repro.optim import qgenx as qgenx_opt


def build_exchange_config(args):
    """Translate CLI flags into one ExchangeConfig (or None = no exchange).

    This is the only place the launcher decides between the compressed
    shard_map path and plain GSPMD training; every knob the exchange has
    (kernel flags, level schedule, compressor choice) rides in the config.
    """
    quant = None
    if args.compression != "none":
        bits = 8 if args.compression == "int8" else 4
        quant = QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                            bucket_size=512)
    # exchange is active when there is something to compress (or an
    # explicitly requested non-default compressor) — on one device too:
    # the quantized exchange then runs over the 1-device mesh
    if quant is None and args.compressor == "qgenx":
        return None
    return ExchangeConfig(
        compressor=args.compressor,
        quant=quant,
        mode=args.compress_mode,
        axis_name=args.compress_axis,
        use_pallas=args.use_pallas,
        level_schedule=args.level_schedule,
        level_update_every=args.level_update_every,
        rand_frac=args.rand_frac,
        ef_topk_frac=args.ef_topk_frac,
        sync_every=args.sync_every,
        recenter_every=args.recenter_every,
        use_plan=not args.no_exchange_plan,
        num_buckets=args.num_buckets,
        overlap=args.overlap,
    )


class CompileCounter:
    """Backend compiles while entered, from jax's monitoring events (the
    event ``bench/harness.CompileStats`` times)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _on_duration(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


class TrainRun(NamedTuple):
    """What one training run is built from (see :func:`build_run`)."""

    model: Any
    opt_cfg: opt.OptimizerConfig
    ex_cfg: Optional[ExchangeConfig]
    ex: Any  # Exchange | None
    step: Callable  # the jitted step; donates params, opt_state, ex_state
    state_sharding: NamedSharding  # where the step keeps its state
    n_dev: int

    def init_state(self, key):
        """Fresh (params, opt_state, ex_state); ``jax.eval_shape`` of it
        gives the shapes without allocating them."""
        params = self.model.init(key)
        opt_state = opt.init_state(self.opt_cfg, params)
        # template + axis size let contractive compressors size their
        # per-worker error memory; unbiased compressors ignore both
        ex_state = (self.ex.init_state(template=params,
                                       num_workers=self.n_dev)
                    if self.ex is not None else null_exchange_state())
        return params, opt_state, ex_state


def build_run(args, cfg, mesh, fault_spec) -> TrainRun:
    """Model, optimizer, exchange and jitted step for the parsed ``args``
    on ``mesh`` (its ``data`` axis is the exchange's)."""
    n_dev = mesh.size
    opt_cfg = opt.OptimizerConfig(name=args.optimizer, lr=args.lr,
                                  gamma_scale=args.gamma_scale,
                                  method=args.method)
    ex_cfg = build_exchange_config(args)
    ex = make_exchange(ex_cfg) if ex_cfg is not None else None
    model = build(cfg)
    step_fn = make_train_step(
        model, opt_cfg, exchange=ex, mesh=mesh, guard=args.guard,
        fault_spec=fault_spec if fault_spec.events else None,
    )
    # donate ALL carried state — params, opt_state AND ex_state — so XLA
    # reuses the buffers (incl. the plan's flat exchange scratch) across
    # steps instead of allocating fresh ones; the step returns each tree
    # with identical structure, and checkpointing copies host-side before
    # the next call invalidates the donated inputs
    step = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    return TrainRun(model, opt_cfg, ex_cfg, ex, step,
                    NamedSharding(mesh, P()), n_dev)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", help="smoke-size model")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="extra_adam",
                    choices=("adam", "extra_adam", "optimistic_adam", "qgenx"))
    ap.add_argument("--method", default="de", choices=("de", "optda"),
                    help="qgenx oracle schedule (core/methods.py): de = "
                         "2 oracle calls/step (Example 3.2), optda = 1 "
                         "call/step reusing prev_half feedback (Example 3.3)")
    ap.add_argument("--gamma-scale", type=float, default=0.02,
                    help="qgenx: scale on the adaptive step-size rule "
                         "(gamma_t = scale*K/sqrt(1+sum_sq))")
    ap.add_argument("--compression", default="none",
                    choices=("none", "int8", "int4"))
    ap.add_argument("--compressor", default="qgenx",
                    choices=sorted(registered_compressors()))
    ap.add_argument("--compress-axis", default="data")
    ap.add_argument("--compress-mode", default="two_phase",
                    choices=("two_phase", "gather", "leafwise"))
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the exchange through the fused Pallas kernels")
    ap.add_argument("--no-exchange-plan", action="store_true",
                    help="escape hatch: per-call exchange layout instead of "
                         "the static ExchangePlan flat buffer (bit-exact for "
                         "qgenx/layerwise pmean either way; DESIGN.md §1.5)")
    ap.add_argument("--num-buckets", type=int, default=1,
                    help="bucketed overlapped exchange: split the gradient "
                         "into this many contiguous layer-ordered buckets, "
                         "each an independent quantize+collective chain XLA "
                         "can overlap with backprop compute (1 = monolithic "
                         "PR 5 path, byte-identical; requires --overlap)")
    ap.add_argument("--overlap", default="off",
                    choices=("off", "bucketed", "defer_tail"),
                    help="off = monolithic exchange; bucketed = per-bucket "
                         "chains issued in backprop order within the step; "
                         "defer_tail = additionally double-buffer the tail "
                         "bucket (first layers) — its collective result is "
                         "carried in ExchangeState.pending and applied one "
                         "sync late, overlapping step N's tail exchange "
                         "with step N+1's forward (DESIGN.md §10)")
    ap.add_argument("--profile-dir", default="",
                    help="emit a jax.profiler trace of the train loop here: "
                         "one 'train' step span per step with its host phases, "
                         "the step's stage and exchange-kernel named_scopes "
                         "inside its HLO (view in TensorBoard/Perfetto — "
                         "DESIGN.md §10)")
    ap.add_argument("--level-schedule", default="fixed",
                    choices=("fixed", "qada"))
    ap.add_argument("--level-update-every", type=int, default=0,
                    help="QAda refresh period in exchange calls (qada schedule)")
    ap.add_argument("--rand-frac", type=float, default=0.25,
                    help="randk/ef-randk: fraction of coordinates kept "
                         "per worker")
    ap.add_argument("--ef-topk-frac", type=float, default=0.25,
                    help="ef21-topk: fraction of innovation coordinates "
                         "each worker ships (error-feedback top-k)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="local-update regime: K local steps between "
                         "compressed exchanges (1 = exchange every step)")
    ap.add_argument("--recenter-every", type=int, default=0,
                    help="compressed parameter re-centering cadence under "
                         "local updates (0 = never; R = every R-th step "
                         "the drifted iterates are exchanged through the "
                         "same compressor)")
    ap.add_argument("--guard", action="store_true",
                    help="arm the non-finite step guard: psum'd finiteness "
                         "check over the candidate update, lax.cond-reject "
                         "bad steps (state carries through unchanged), plus "
                         "a host-side watchdog that rolls back to the last-"
                         "known-good snapshot (DESIGN.md §8)")
    ap.add_argument("--rollback-after", type=int, default=3,
                    help="watchdog: roll back after this many CONSECUTIVE "
                         "rejected steps (a >=50%% rejection rate over a "
                         "4x window also triggers)")
    faults.add_fault_spec_flag(ap, scope="train")
    ap.add_argument("--allow-ckpt-reset", action="store_true",
                    help="on restore, reset INCOMPATIBLE auxiliary state "
                         "(ex_state) to fresh init instead of exiting; "
                         "params/opt_state mismatches always exit")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host (CPU) devices")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeat-batch", action="store_true",
                    help="train on one repeated batch (fast-convergence tests)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.host_devices:
        # read when jax first initialises its backends, which no code
        # before this line does
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    cache = enable_compilation_cache()
    if cache:
        print(f"[train] compilation cache: {cache}", flush=True)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    n_dev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("data",))
    fault_spec = faults.parse_fault_spec_arg(args.fault_spec, scope="train")
    if fault_spec.events:
        print(f"[train] fault schedule: {args.fault_spec}", flush=True)
        if fault_spec.has_device_events and not args.guard:
            print("[train] WARNING: device faults scheduled without --guard "
                  "— non-finite steps will NOT be rejected", flush=True)
    run = build_run(args, cfg, mesh, fault_spec)
    model, ex_cfg, ex = run.model, run.ex_cfg, run.ex
    key = jax.random.PRNGKey(args.seed)
    # made where the step keeps its state (replicated on the mesh): state
    # placed otherwise has another type, and the step would be traced and
    # compiled a second time
    params, opt_state, ex_state = jax.jit(
        run.init_state, out_shardings=run.state_sharding)(key)
    if ex is not None:
        print(f"[train] exchange: compressor={ex_cfg.compressor} "
              f"mode={ex_cfg.mode} axis={ex_cfg.axis_name} "
              f"use_pallas={ex_cfg.use_pallas} schedule={ex_cfg.level_schedule} "
              f"sync_every={ex_cfg.sync_every} "
              f"recenter_every={ex_cfg.recenter_every} "
              f"plan={ex_cfg.use_plan} "
              f"num_buckets={ex_cfg.num_buckets} overlap={ex_cfg.overlap} "
              f"coded_in_place={ex.coded_in_place_share(params):.1%}",
              flush=True)
    if args.optimizer == "qgenx":
        print(f"[train] qgenx method={args.method}", flush=True)
    needs_fault_step = fault_spec.has_device_events
    watchdog = faults.Watchdog(args.rollback_after) if args.guard else None
    jitted = run.step

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    pipe = make_pipeline(cfg, shape, seed=args.seed)

    start_step = 0
    have_ckpts = args.checkpoint_dir and (
        checkpointing.latest_step(args.checkpoint_dir) is not None
        or checkpointing.available_steps(args.checkpoint_dir)
    )
    if have_ckpts:
        # Explicit-detection restore (no broad except): structure
        # mismatches are diagnosed per-tree from the checkpoint meta.
        # ExchangeState is auxiliary training state (QAda levels/stats/
        # counter) — a checkpoint saved under a different exchange config
        # may only reset it under --allow-ckpt-reset; params/opt_state
        # mismatches always exit (resetting those silently would discard
        # the run).  Corrupt files walk back to the newest intact step.
        allow = ("ex_state",) if args.allow_ckpt_reset else ()
        try:
            start_step, trees, reset = checkpointing.restore_with_fallback(
                args.checkpoint_dir,
                {"params": params, "opt_state": opt_state,
                 "ex_state": ex_state},
                allow_reset=allow,
            )
        except checkpointing.CheckpointStructureError as e:
            print(f"[train] checkpoint tree {e.tree!r} does not match this "
                  f"run's state: {e.detail}", file=sys.stderr)
            print("[train] pass --allow-ckpt-reset to reset incompatible "
                  "auxiliary state (ex_state), or fix the run config to "
                  "match the checkpoint", file=sys.stderr)
            raise SystemExit(2)
        except checkpointing.CheckpointCorruptError as e:
            print(f"[train] no intact checkpoint at "
                  f"{args.checkpoint_dir}: {e}", file=sys.stderr)
            raise SystemExit(2)
        params, opt_state, ex_state = jax.device_put(
            (trees.get("params", params), trees.get("opt_state", opt_state),
             trees.get("ex_state", ex_state)), run.state_sharding)
        for name in reset:
            print(f"[train] checkpoint {name} incompatible with this run's "
                  f"config; reset to fresh init (--allow-ckpt-reset)")
        pipe.restore({"step": start_step, "seed": args.seed})
        print(f"[train] restored step {start_step}")

    # ambient mesh for sharding propagation
    if n_dev > 1:
        mesh.__enter__()
    times = []
    fixed_batch = add_modality_stubs(next(pipe), cfg, seed=args.seed)
    # --profile-dir: one jax.profiler trace spanning the whole loop (the
    # step's named scopes land inside its HLO; each loop iteration is a
    # "train" step span with its phases as host spans inside it; closed
    # right after the last step so the final flush happens before any
    # checkpoint I/O)
    profiler = contextlib.ExitStack()
    profiler.enter_context(profile_trace(args.profile_dir))
    compiles = profiler.enter_context(CompileCounter())
    span = jax.profiler.TraceAnnotation
    recompiles = 0
    for step in range(start_step, args.steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            with span("data"):
                batch = fixed_batch if args.repeat_batch else (
                    add_modality_stubs(next(pipe), cfg, seed=args.seed))
            t0 = time.time()
            with span("dispatch"):
                step_args = [params, opt_state, ex_state, batch,
                             jax.random.fold_in(key, step)]
                if needs_fault_step:
                    # the fault schedule is keyed on the TRAIN-LOOP step
                    # (not the optimizer count — a rejected step does not
                    # advance count and a count-keyed fault would re-fire
                    # forever)
                    step_args.append(step)
                before = compiles.count
                params, opt_state, ex_state, metrics = jitted(*step_args)
                # the first call compiles the step; any later compile is
                # a recompile (state of another type or placement)
                step_compiles = (compiles.count - before
                                 if step > start_step else 0)
                recompiles += step_compiles
            # fence the async dispatch for honest step timing WITHOUT
            # moving the metrics: device->host transfers (the float()
            # fetches) are blocking round-trips and are only paid on log
            # steps
            with span("wait"):
                jax.block_until_ready(metrics["loss"])
            times.append(time.time() - t0)
            rejected = False
            if watchdog is not None:
                # guard mode pays two scalar fetches per step; the
                # snapshot is a host copy, taken BEFORE the next jitted
                # call invalidates the donated output buffers
                with span("metric fetch"):
                    rejected = bool(float(metrics["rejected"]))
                    nonfin = bool(float(metrics["nonfinite"]))
                with span("watchdog snapshot"):
                    if watchdog.observe(step, rejected, nonfin):
                        if isinstance(opt_state, qgenx_opt.QGenXOptState):
                            print(f"[train] watchdog: optimizer stats at "
                                  f"rollback "
                                  f"{qgenx_opt.state_norms(opt_state)}",
                                  flush=True)
                        snap_step, trees = watchdog.rollback()
                        params, opt_state, ex_state = jax.device_put(
                            (trees["params"], trees["opt_state"],
                             trees["ex_state"]), run.state_sharding)
                        print(f"[train] watchdog: rolled back to the step-"
                              f"{snap_step} snapshot ({watchdog.summary()})",
                              flush=True)
                    elif not rejected:
                        watchdog.record_good(step + 1, {
                            "params": params, "opt_state": opt_state,
                            "ex_state": ex_state,
                        })
            is_last = step == args.steps - 1
            log_now = step % args.log_every == 0 or step_compiles > 0
            if log_now or is_last:
                with span("metric fetch"):
                    loss = float(metrics["loss"])
                    wire = float(metrics["wire_bytes"])
                    drift = float(metrics["param_drift"])
                    coded = float(metrics["coded_bits_est"])
            if log_now:
                tail = f" drift={drift:.3e}" if args.sync_every > 1 else ""
                if coded:
                    tail += f" coded_bits={coded:.3e}"
                if rejected:
                    tail += " REJECTED"
                if needs_fault_step and ex is not None:
                    alive = float(metrics["alive"])
                    if alive != n_dev:
                        tail += f" alive={alive:.0f}/{n_dev}"
                if step_compiles:
                    tail += f" RECOMPILED={step_compiles}"
                print(f"[train] step={step} loss={loss:.4f} "
                      f"dt={times[-1]*1e3:.0f}ms wire={wire:.3e}B{tail}",
                      flush=True)
            if args.checkpoint_dir and args.checkpoint_every and (
                (step + 1) % args.checkpoint_every == 0
            ):
                with span("checkpoint save"):
                    checkpointing.save(
                        args.checkpoint_dir, step + 1,
                        {"params": params, "opt_state": opt_state,
                         "ex_state": ex_state},
                    )
                    for kind in fault_spec.ckpt_faults_at(step + 1):
                        faults.inject_ckpt_fault(args.checkpoint_dir,
                                                 step + 1, kind)
                        print(f"[train] fault: injected {kind} into "
                              f"checkpoint {step + 1}", flush=True)
    profiler.close()
    if not times:  # restored checkpoint already at/past --steps: nothing
        # ran, so save NOTHING — a save here would rewind the checkpoint
        # 'latest' pointer below the restored step
        print(f"[train] done. no steps run (restored step {start_step} "
              f">= --steps {args.steps})")
        return None
    if args.checkpoint_dir:
        checkpointing.save(
            args.checkpoint_dir, args.steps,
            {"params": params, "opt_state": opt_state, "ex_state": ex_state},
        )
        for kind in fault_spec.ckpt_faults_at(args.steps):
            faults.inject_ckpt_fault(args.checkpoint_dir, args.steps, kind)
            print(f"[train] fault: injected {kind} into checkpoint "
                  f"{args.steps}", flush=True)
    if watchdog is not None:
        print(f"[train] guard: {watchdog.summary()}", flush=True)
    if (ex is not None and ex_cfg.level_schedule == "qada"
            and ex.compressor.has_levels):
        print(f"[train] qada levels={np.round(np.asarray(ex_state.levels), 4)}",
              flush=True)
    med = sorted(times[1:])[len(times[1:]) // 2] if len(times) > 1 else times[0]
    print(f"[train] done. final_loss={loss:.4f} median_step={med*1e3:.0f}ms "
          f"recompiles={recompiles}")
    return loss


if __name__ == "__main__":
    main()
