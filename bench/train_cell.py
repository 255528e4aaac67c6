"""A training cell: the program's jitted train step, driven from the seed.

Set-up builds one object, the step that ``repro.launch.train.build_run``
returns (the one ``train.main`` runs) with its state, made on the chips
in one jitted call from the benchmark's own weights.  It drives that
object through its first steps on a ring of distinct token batches,
reading what the comparison needs, warms it, and hands the same object to
the window.  The window dispatches steps until ``--seconds`` have passed
and ends on ``block_until_ready``; it is not fenced per step.

Once the window has closed and the program's state is freed, the plain
reference follows the first steps in float32 on the exact mean and the
comparison decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import statistics
import time

import numpy as np

from bench import flops, harness, layout, reference, seeds
from bench.readings import busiest
from bench import trace as tr
from bench.traffic import generate

TRACE_DIR = os.path.join(harness.ROOT, ".bench_trace")


def build(run: harness.Run):
    """(program TrainRun, model config, mesh)."""
    from jax.sharding import Mesh

    from repro.configs.base import ModelConfig
    from repro.core import faults
    from repro.launch import train

    cfg = ModelConfig(**run.config["program"])
    t = run.traffic
    batch = t["batch_per_chip"] * run.chips
    argv = list(t["argv"]) + ["--batch", str(batch), "--seq",
                              str(t["seq_len"])]
    args = train.build_parser().parse_args(argv)
    mesh = Mesh(np.array(run.devices), ("data",))
    built = train.build_run(args, cfg, mesh,
                            faults.parse_fault_spec_arg("", scope="train"))
    return built, cfg, mesh


def _numbers(prog: dict, ref: dict) -> dict:
    """The three numbers the comparison judges (PERF.md, section 2)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))

    def worst(p, r, keep):
        med = statistics.median(r[k] for k in keep)
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keep)

    raw = ref["raw_grad_norms"]
    med_raw = statistics.median(raw.values())
    moved = [k for k in raw if raw[k] >= 1e-3 * med_raw]
    return {"loss_gap": loss,
            "grad_norm_gap": worst(prog["grad_norms"], ref["grad_norms"],
                                   list(raw)),
            "update_norm_gap": worst(prog["update_norms"],
                                     ref["update_norms"], moved)}


class Prepared:
    """The program's step and what drives it, built once per process."""

    def __init__(self, run: harness.Run):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.run = run
        self.ref = ref = reference.of(run.config)
        self.kinds = kinds = ref.PROGRAM_KINDS
        dm = ref.Dims.from_config(run.config)
        self.built, self.cfg, self.mesh = build(run)
        t = run.traffic
        self.batch, self.seq = t["batch_per_chip"] * run.chips, t["seq_len"]
        dtype = jnp.dtype(self.cfg.dtype)

        def make_params(key):
            return ref.program_params(ref.init_stacked(key, dm), dm, dtype)

        key = seeds.root_key(0)
        layout.check_same_tree(jax.eval_shape(make_params, key),
                               jax.eval_shape(self.built.model.init, key))
        ours = self.built._replace(
            model=dataclasses.replace(self.built.model, init=make_params))
        self.init = jax.jit(ours.init_state,
                            out_shardings=self.built.state_sharding)
        self.data_sharding = (NamedSharding(self.mesh, P("data", None))
                              if self.built.ex is not None
                              else run.devices[0])
        self.step = self.built.step
        oc = self.built.opt_cfg
        self.opt = {k: getattr(oc, k)
                    for k in ("lr", "b1", "b2", "eps", "grad_clip")}
        b1 = oc.b1
        self.first_grad_norms = jax.jit(lambda mu: {
            k: v / (1.0 - b1) for k, v in layout.kind_norms(mu, kinds).items()})

    def start(self, seed: int):
        """State, device ring of batches and keys, and the host batches."""
        import jax

        root = seeds.root_key(seed)
        state = self.init(root)
        host = generate.train_batches(seed, self.run.traffic, self.batch,
                                      self.cfg.vocab_size)
        ring = [{"tokens": jax.device_put(a, self.data_sharding),
                 "labels": jax.device_put(b, self.data_sharding)}
                for a, b in host]
        kroot = jax.random.fold_in(root, seeds.STEP_KEYS)
        keys = [jax.device_put(jax.random.fold_in(kroot, i),
                               self.built.state_sharding)
                for i in range(len(ring))]
        return state, ring, keys, host

    def first_steps(self, state, ring, keys):
        """Drive the step through the cell's first ``check_steps`` steps,
        through the window's own call and feed, reading each step's loss,
        the first gradient as the optimizer got it (its first moment after
        one step over 1 - b1) and the parameters' change."""
        import jax

        p0 = jax.device_get(state[0])
        losses, grad_norms = [], None
        for i in range(self.run.traffic["check_steps"]):
            *state, metrics = self.step(*state, ring[i], keys[i])
            losses.append(metrics["loss"])
            if i == 0:
                grad_norms = jax.device_get(self.first_grad_norms(state[1].mu))
        p3 = jax.device_get(state[0])
        prog = {"loss": [float(v) for v in losses],
                "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                "update_norms": _host_change_norms(p0, p3, self.kinds)}
        return state, prog

    def reference(self, seed: int, host, fault: str = "") -> dict:
        n = self.run.traffic["check_steps"]
        return self.ref.train_reference(self.run.config, seeds.root_key(seed),
                                        host[:n], self.run.chips, self.opt,
                                        steps=n, fault=fault)


def run_cell(run: harness.Run) -> dict:
    import jax

    t = run.traffic
    pp = Prepared(run)
    state, ring, keys, host = pp.start(run.seed)
    harness.log(f"state made: {harness.since(run.t_start):.1f} s")
    state, prog = pp.first_steps(state, ring, keys)
    harness.log(f"checked steps done: {harness.since(run.t_start):.1f} s")
    step, batch, seq = pp.step, pp.batch, pp.seq
    i = t["check_steps"]
    for _ in range(t["warm_steps"]):
        *state, metrics = step(*state, ring[i % len(ring)], keys[i % len(ring)])
        i += 1
    jax.block_until_ready(state)
    mem = run.devices[0].memory_stats() or {}
    harness.log(f"warm: {mem.get('bytes_in_use')} bytes in use on chip 0, "
                f"peak {mem.get('peak_bytes_in_use')}")

    values, readings, breakdown, dev_extra = {}, None, None, {}
    t0 = time.perf_counter()
    values["setup_s"] = t0 - run.t_start
    if not run.trace:
        n, inflight = 0, []
        while time.perf_counter() - t0 < run.seconds:
            *state, metrics = step(*state, ring[i % len(ring)],
                                   keys[i % len(ring)])
            i += 1
            n += 1
            # at most two steps queued ahead of the chip, so the window
            # closes within a step of --seconds
            inflight.append(metrics["loss"])
            if len(inflight) > 2:
                inflight.pop(0).block_until_ready()
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        values["train_tokens_per_s"] = n * batch * seq / elapsed
        attempted = n
        harness.log(f"window: {n} steps in {elapsed:.3f} s")
    else:
        hlo = step.lower(*state, ring[0], keys[0]).compile().as_text()
        n = t["trace_steps"]
        with tr.capture(TRACE_DIR) as cap:
            for _ in range(n):
                *state, metrics = step(*state, ring[i % len(ring)],
                                       keys[i % len(ring)])
                i += 1
            jax.block_until_ready(state)
        attempted = n
        red = cap["reduced"]
        quant = pp.built.ex_cfg.quant if pp.built.ex is not None else None
        readings = {
            "kind": "train", "reduced": red, "op_names": tr.hlo_op_names(hlo),
            "steps": n, "chips": run.chips, "peaks": run.peaks,
            "flops_per_step": flops.extragradient_step_flops(
                run.config, batch, seq),
            "exchange_bytes_per_step": (
                None if quant is None else
                2 * flops.exchange_bytes(pp.ref.param_count(run.config),
                                         run.chips, quant.bits,
                                         quant.bucket_size)),
        }
        dev = busiest({"reduced": red})
        breakdown = {"device_ops": tr.top_ops(dev, readings["op_names"]),
                     "idle_gaps": tr.idle_gaps(dev, red.host)}
        dev_extra = {"busy_s": statistics.mean(tr.busy_ns(d) for d in
                                               red.devices) / 1e9,
                     "window_s": red.window_ns / 1e9}
    final_loss = float(metrics["loss"])
    failed = 0 if math.isfinite(final_loss) else attempted
    device = harness.device_info(run)
    device.update(dev_extra)
    del state, metrics, ring, keys, step
    pp.built = pp.step = pp.init = None
    gc.collect()

    harness.log(f"reference from {harness.since(run.t_start):.1f} s")
    reference = pp.reference(run.seed, host)
    numbers = _numbers(prog, reference)
    correct, checks = harness.judge(numbers, run.limits["numbers"])
    harness.log(f"program {prog}")
    harness.log(f"reference {reference}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": (harness.read_per_layer(run, readings) if run.trace
                          else harness.end_to_end(run, values)),
              "device": device, "checks": checks}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def _host_change_norms(p0, p3, kinds: dict) -> dict:
    import jax

    out = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p0),
                            jax.tree_util.tree_leaves(p3)):
        d = np.asarray(b, np.float32) - np.asarray(a, np.float32)
        out[kinds[layout.path_of(path)]] = float(np.linalg.norm(d.ravel()))
    return out


def calibrate(run: harness.Run, seeds_: list, control_seeds: list,
              fault_seeds: list):
    """Readings for the limits (PERF.md, section 2), in one process: the
    sound program on ``seeds_``; the control on ``control_seeds``; and each
    fault of the timed path the cell can have, planted in the reference
    put in the program's place, on ``fault_seeds``.  The control is the
    program with its own next lower exchange precision (int8 -> int4);
    a cell that quantizes no exchange has none here yet.  Yields one dict
    per reading."""
    import jax

    pp = Prepared(run)
    quant = pp.built.ex_cfg.quant if pp.built.ex is not None else None
    if control_seeds and quant is None:
        raise ValueError("the cell quantizes no exchange: it has no control "
                         "here")

    def program_rows(pp, seeds_, what):
        for seed in seeds_:
            state, ring, keys, host = pp.start(seed)
            state, prog = pp.first_steps(state, ring, keys)
            jax.block_until_ready(state)
            del state, ring, keys
            gc.collect()
            ref = pp.reference(seed, host)
            yield {"what": what, "seed": seed, **_numbers(prog, ref),
                   "program": prog, "reference": ref}

    yield from program_rows(pp, seeds_, "program")
    faults = ["half_batch", "unchanged"]
    if run.chips > 1:
        faults.insert(1, "no_exchange")
    for seed in fault_seeds:
        host = generate.train_batches(seed, run.traffic, pp.batch,
                                      pp.cfg.vocab_size)
        sound = pp.reference(seed, host)
        for fault in faults:
            got = pp.reference(seed, host, fault)
            yield {"what": f"fault:{fault}", "seed": seed,
                   **_numbers(got, sound)}
    if control_seeds:
        argv = [("int4" if a == "int8" else a) for a in run.traffic["argv"]]
        low = dataclasses.replace(run, traffic=dict(run.traffic, argv=argv))
        pp = Prepared(low)
        yield from program_rows(pp, control_seeds, "control:int4")
