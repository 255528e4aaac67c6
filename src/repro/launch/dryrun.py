"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without TPU hardware:
``jax.jit(step).lower(...).compile()`` against 512 forced host devices.
Emits per-combo JSON artifacts (memory analysis, HLO FLOPs/bytes,
per-collective byte counts parsed from the compiled HLO) that
benchmarks/roofline.py and EXPERIMENTS.md consume.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single            # one combo
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --all-shapes \
      --mesh multi --mode qgenx                  # compressed pod exchange
"""

# The VERY FIRST lines, before ANY other import (jax locks device count on
# first init):
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig  # noqa: E402
from repro.configs.registry import ARCHS, get_config  # noqa: E402
from repro.core.exchange import (  # noqa: E402
    ExchangeConfig,
    make_exchange,
    null_exchange_state,
)
from repro.core.quantization import QuantConfig  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import data_axes, make_production_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.model import (  # noqa: E402
    batch_pspecs,
    build,
    cache_pspecs,
    fit_pspecs,
    input_specs,
    param_pspecs,
)
from repro.optim import optimizers as opt  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

# HLO collective ops whose operand bytes we account for the roofline
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:\S+\s*=\s*)?"
    r"(?:\([^)]*\)|\S+)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|u64|f64)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the HLO, by op kind."""
    per_kind: dict[str, float] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        kind = m.group(1)
        if "-done(" in line:
            continue  # count the -start (or the sync op), not the -done
        # output shape(s) = the shape tokens before the op name
        head = line.split(kind)[0]
        shapes = _SHAPE_RE.findall(head)
        nbytes = 0.0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_kind[kind] = per_kind.get(kind, 0.0) + nbytes
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "count_by_kind": count,
            "total_bytes": sum(per_kind.values())}


def _shardings(mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _apply_overrides(cfg, overrides):
    if not overrides:
        return cfg
    kw = {}
    for ov in overrides:
        k, v = ov.split("=", 1)
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            kw[k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            kw[k] = int(v)
        elif isinstance(cur, float):
            kw[k] = float(v)
        else:
            kw[k] = v
    return dataclasses.replace(cfg, **kw)


def lower_combo(
    arch: str,
    shape_name: str,
    mesh,
    mode: str = "baseline",
    quant_bits: int = 8,
    overrides=None,
    tag: str = "",
    optimizer: str = "extra_adam",
    method: str = "de",
    num_buckets: int = 1,
    overlap: str = "off",
):
    _hlo_tag = tag
    """Lower+compile one (arch, shape) on the given mesh. Returns report."""
    cfg = _apply_overrides(get_config(arch), overrides)
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()

    if shape.kind == "decode" and shape.name == "long_500k":
        if not cfg.supports_long_context:
            return {"arch": arch, "shape": shape_name, "status": "skipped",
                    "reason": "pure full attention — no sub-quadratic variant "
                              "(see DESIGN.md long_500k table)"}
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat=True)
    multi_pod = "pod" in mesh.axis_names
    if mode == "qgenx":
        cfg = dataclasses.replace(cfg, onehot_embed=True)
        if multi_pod:
            # the pod exchange wraps the step in a PARTIALLY-manual
            # shard_map (auto= inner axes) whose while-loop lowering
            # XLA's SPMD partitioner rejects (IsManualSubgroup check):
            # unroll the layer scan and take the scan-free attention path
            cfg = dataclasses.replace(cfg, unroll_scan=True,
                                      blockwise_attn=False)

    model = build(cfg)
    dp = data_axes(mesh)

    # abstract params
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if mode == "qgenx" and multi_pod:
        # Q-GenX: replicated over pod (compressed exchange), FSDP over data
        fsdp = ("data",)
    else:
        fsdp = dp
    pspecs = fit_pspecs(
        param_pspecs(params_shape, fsdp=fsdp, tp="model",
                     shard_vocab=(mode != "qgenx")),
        params_shape, mesh,
    )
    param_sharding = _shardings(mesh, pspecs)

    batch_struct = input_specs(cfg, shape)
    bspecs = batch_pspecs(cfg, shape, dp=dp)
    batch_sharding = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_cfg = opt.OptimizerConfig(name=optimizer, method=method)
        # params as an argument (not a closure) so abstract leaves trace
        opt_shape = jax.eval_shape(
            lambda p: opt.init_state(opt_cfg, p), params_shape
        )
        if optimizer == "qgenx":
            # anchor/dual accumulator shard like their params; scalars
            # (sum_sq, count) replicated; the optda method additionally
            # carries the params-shaped prev_half feedback (same pspecs)
            from repro.core.methods import get_method
            from repro.optim.qgenx import QGenXOptState

            opt_pspecs = QGenXOptState(
                anchor=pspecs, y=pspecs, sum_sq=P(), count=P(),
                prev_half=(pspecs if get_method(method).uses_prev_half
                           else None),
            )
        else:
            # moments shard like their params; count replicated; the
            # optimistic variant carries a params-shaped half-step grad
            opt_pspecs = opt.AdamState(
                mu=pspecs, nu=pspecs, count=P(),
                prev_half_grad=pspecs if optimizer == "optimistic_adam" else None,
            )
        opt_sharding = _shardings(mesh, opt_pspecs)
        if mode == "qgenx" and quant_bits < 32:
            quant = QuantConfig(
                num_levels=15 if quant_bits == 8 else 5, bits=quant_bits
            )
        else:
            quant = None  # qgenx with quant_bits=32: fp32 pod exchange control
        ex_cfg = None
        if mode == "qgenx" and multi_pod:
            # the pure-pmean control (quant=None) still routes through the
            # shard_map via the "none" compressor; allreduce_fallback:
            # this jaxlib's SPMD partitioner lowers only all-reduce under
            # the partially-manual mesh (see ExchangeConfig docstring)
            # num_buckets/overlap thread through so the CLI surface is
            # uniform with train — but the pod exchange is LEAFWISE
            # (this jaxlib's partial-manual partitioner lowers only
            # all-reduce), and leafwise has no flat buffer to bucket:
            # ExchangeConfig validation rejects the combination loudly
            # rather than lowering a program the partitioner would abort
            ex_cfg = ExchangeConfig(
                compressor="qgenx" if quant is not None else "none",
                quant=quant, mode="leafwise", axis_name="pod",
                allreduce_fallback=True,
                num_buckets=num_buckets, overlap=overlap,
            )
        step = make_train_step(model, opt_cfg, exchange=ex_cfg, mesh=mesh)
        ex = make_exchange(ex_cfg) if ex_cfg is not None else None
        ex_struct = jax.eval_shape(
            ex.init_state if ex is not None else null_exchange_state
        )
        ex_sharding = jax.tree_util.tree_map(lambda _: repl, ex_struct)
        metric_sharding = {"loss": repl, "wire_bytes": repl,
                           "param_drift": repl, "coded_bits_est": repl,
                           "rejected": repl, "nonfinite": repl,
                           "alive": repl}
        jitted = jax.jit(
            step,
            in_shardings=(param_sharding, opt_sharding, ex_sharding,
                          batch_sharding, repl),
            out_shardings=(param_sharding, opt_sharding, ex_sharding,
                           metric_sharding),
            donate_argnums=(0, 1),
        )
        args = (params_shape, opt_shape, ex_struct, batch_struct, key_struct)
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        jitted = jax.jit(
            step,
            in_shardings=(param_sharding, batch_sharding),
        )
        args = (params_shape, batch_struct)
    else:  # decode
        serve = make_serve_step(model)
        B = shape.global_batch
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), params_shape
                ),
                {
                    "tokens": jnp.zeros((B, 8), jnp.int32),
                    "frames": jnp.zeros((B, cfg.encoder_seq, cfg.d_model), jnp.float32)
                    if cfg.arch_type in ("encdec", "audio")
                    else None,
                },
                shape.seq_len,
            )
        )
        shard_seq = shape.name == "long_500k"
        cspecs = fit_pspecs(
            cache_pspecs(cache_shape, cfg, dp=dp, shard_seq_global=shard_seq,
                         mesh=mesh),
            cache_shape, mesh,
        )
        cache_sharding = _shardings(mesh, cspecs)
        tok_sharding = NamedSharding(mesh, bspecs["token"])
        jitted = jax.jit(
            serve,
            in_shardings=(param_sharding, cache_sharding, tok_sharding, repl),
            out_shardings=(tok_sharding, None, cache_sharding),
            donate_argnums=(1,),
        )
        args = (
            params_shape,
            cache_shape,
            batch_struct["token"],
            batch_struct["pos"],
        )

    # jax 0.4.x: the Mesh object is the ambient-mesh context manager
    # (jax.sharding.set_mesh arrived in later releases)
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # jax 0.4.x: list of one dict
            cost = cost[0] if cost else {}

    hlo = compiled.as_text()
    analysis = analyze_hlo(hlo)
    # stash the HLO (zstd) so analyzer improvements re-run offline
    try:
        import zstandard

        hdir = os.path.join(os.path.abspath(ARTIFACT_DIR), "hlo")
        os.makedirs(hdir, exist_ok=True)
        suffix = f"__{_hlo_tag}" if _hlo_tag else ""
        fname = (f"{arch}__{shape_name}__"
                 f"{'x'.join(str(s) for s in mesh.devices.shape)}__{mode}{suffix}.hlo.zst")
        with open(os.path.join(hdir, fname), "wb") as fh:
            fh.write(zstandard.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass
    coll = {k: analysis[k] for k in (
        "payload_bytes_by_kind", "wire_bytes_by_kind", "count_by_kind",
        "total_payload_bytes", "total_wire_bytes")}
    n_dev = mesh.devices.size

    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "mode": mode,
        "status": "ok",
        "compile_seconds": round(time.time() - t0, 1),
        "num_devices": n_dev,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {
            # XLA cost_analysis (loop bodies counted ONCE — undercounts)
            "xla_flops": cost.get("flops"),
            "xla_bytes_accessed": cost.get("bytes accessed"),
            # loop-aware reconstruction from the HLO (see hlo_analysis.py)
            "flops": analysis["flops"],
            "bytes": analysis["bytes"],
        },
        "collectives": coll,
    }
    return report


def run_and_save(arch, shape_name, mesh_kind, mode, out_dir, overrides=None,
                 tag="", quant_bits=8, optimizer="extra_adam", method="de",
                 num_buckets=1, overlap="off"):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    name = f"{arch}__{shape_name}__{mesh_kind}__{mode}"
    if optimizer != "extra_adam":
        name += f"__{optimizer}"
    if method != "de":
        name += f"__{method}"
    if tag:
        name += f"__{tag}"
    try:
        rep = lower_combo(arch, shape_name, mesh, mode=mode, overrides=overrides,
                          quant_bits=quant_bits, tag=tag, optimizer=optimizer,
                          method=method, num_buckets=num_buckets,
                          overlap=overlap)
        rep["tag"] = tag
        rep["overrides"] = list(overrides or [])
    except Exception as e:  # record failures as bugs to fix
        rep = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode,
            "status": "error", "error": repr(e),
            "traceback": traceback.format_exc()[-4000:],
        }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rep, f, indent=1)
    status = rep["status"]
    extra = ""
    if status == "ok":
        mem_gb = (rep["memory"]["peak_bytes"] or 0) / 2**30
        extra = (f" compile={rep['compile_seconds']}s peak/dev={mem_gb:.2f}GiB "
                 f"flops={rep['cost']['flops']:.3e} "
                 f"coll={rep['collectives']['total_wire_bytes']:.3e}B")
    elif status == "error":
        extra = " " + rep["error"][:200]
    print(f"[dryrun] {name}: {status}{extra}", flush=True)
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--mode", choices=("baseline", "qgenx"), default="baseline")
    ap.add_argument("--all", action="store_true", help="all archs x all shapes")
    ap.add_argument("--all-shapes", action="store_true")
    ap.add_argument("--out", default=os.environ.get(
        "DRYRUN_OUT", os.path.abspath(ARTIFACT_DIR)))
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (repeatable)")
    ap.add_argument("--tag", default="", help="artifact suffix for perf iters")
    ap.add_argument("--qgenx-bits", type=int, default=8, choices=(4, 8, 32),
                    help="qgenx payload width; 32 = fp32 pod-exchange control")
    ap.add_argument("--optimizer", default="extra_adam",
                    choices=("adam", "extra_adam", "optimistic_adam", "qgenx"),
                    help="train-shape optimizer to lower (qgenx = the "
                         "paper's adaptive-step-size extragradient)")
    ap.add_argument("--method", default="de", choices=("de", "optda"),
                    help="qgenx oracle schedule (optda carries the "
                         "params-shaped prev_half slot in the opt state)")
    ap.add_argument("--num-buckets", type=int, default=1,
                    help="bucketed overlapped exchange fan-out (uniform "
                         "with the train CLI; the multi-pod qgenx exchange "
                         "is leafwise, where bucketing is rejected loudly)")
    ap.add_argument("--overlap", default="off",
                    choices=("off", "bucketed", "defer_tail"))
    args = ap.parse_args()

    from repro.launch.cache import enable_compilation_cache

    cache = enable_compilation_cache()
    if cache:
        print(f"[dryrun] compilation cache: {cache}", flush=True)

    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = (
        sorted(INPUT_SHAPES)
        if (args.all or args.all_shapes or not args.shape)
        else [args.shape]
    )
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            rep = run_and_save(arch, shape, args.mesh, args.mode, args.out,
                               overrides=args.override, tag=args.tag,
                               quant_bits=args.qgenx_bits,
                               optimizer=args.optimizer, method=args.method,
                               num_buckets=args.num_buckets,
                               overlap=args.overlap)
            n_fail += rep["status"] == "error"
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
