"""Serving driver: paged quantized KV-cache + continuous batching.

Attention decoders (gemma/llama/qwen families) run through the real
inference path — :class:`repro.serve.engine.ServeEngine`: a paged arena
storing K/V through the paper's unbiased quantizers (``--kv-bits
8|4|mixed``), a continuous-batching scheduler (requests admitted into
freed slots mid-decode, retired when their budget is spent), one jitted
decode step over the packed batch, and one jitted full-sequence prefill
per prompt shape.  SSM / MLA / enc-dec caches are not token-feature
pages; those archs keep the dense ``decode_step`` fallback (the original
token-loop prefill, retained below).

Examples (CPU, reduced model):
  PYTHONPATH=src python -m repro.launch.serve --reduced --kv-bits 8
  PYTHONPATH=src python -m repro.launch.serve --reduced --kv-bits 4 \
      --batch 4 --requests 12 --prompt-len 16 --gen 16
  # 8 forced host devices: per-device quantization noise, logits
  # ensemble-averaged through the Exchange seam (wire accounting on)
  PYTHONPATH=src python -m repro.launch.serve --reduced --host-devices 8 \
      --logit-exchange int8
  # serve a trained checkpoint
  PYTHONPATH=src python -m repro.launch.serve --reduced \
      --restore /tmp/ckpt
  # hardened: decode guard + quarantine, deadlines, crash-safe snapshots
  PYTHONPATH=src python -m repro.launch.serve --reduced --guard \
      --deadline-ms 5000 --snapshot-dir /tmp/serve_snap --snapshot-every 4
  # deterministic fault drill (same grammar the train CLI uses)
  PYTHONPATH=src python -m repro.launch.serve --reduced --guard \
      --fault-spec 'nan_logits@5:slot=2;slot_drop@8'
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpointing
from repro.configs.registry import ARCHS, get_config
from repro.core import faults
from repro.core.exchange import ExchangeConfig
from repro.core.quantization import QuantConfig
from repro.core.retry import BackoffPolicy
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_serve_step
from repro.models import transformer
from repro.models.model import build
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request


def prefill_into_cache(model, params, tokens, cache):
    """Populate the cache by teacher-forcing the prompt token-by-token.

    Fallback for archs without a paged cache (SSM / MLA / enc-dec fill
    their state through the same ``decode_step`` contract); attention
    archs take the single jitted full-sequence prefill in
    :mod:`repro.serve.engine` instead.
    """
    step = jax.jit(model.decode_step)
    B, S = tokens.shape
    logits = None
    for pos in range(S):
        logits, cache = step(params, cache, tokens[:, pos], jnp.asarray(pos, jnp.int32))
    return logits, cache


def _restore_params(model, cfg, args, key):
    params = model.init(key)
    if not args.restore:
        return params
    try:
        step, trees, _ = checkpointing.restore_with_fallback(
            args.restore, {"params": params}
        )
    except checkpointing.CheckpointStructureError as e:
        print(f"[serve] checkpoint params do not match arch "
              f"{cfg.name!r}: {e.detail}", file=sys.stderr)
        raise SystemExit(2)
    except checkpointing.CheckpointCorruptError as e:
        print(f"[serve] no intact checkpoint at {args.restore}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    print(f"[serve] restored params from {args.restore} @ step {step}")
    return trees["params"]


def _parse_workload_file(path, cfg):
    """Parse a workload file: one request per line,
    ``TOKEN[,TOKEN...]|MAX_NEW[|DEADLINE]`` (blank lines / ``#`` comments
    skipped).  A malformed line is a user error: pointed message naming
    the line, exit code 2 — never an unhandled traceback."""
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError as e:
        print(f"[serve] cannot read workload file {path}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    reqs = []
    for ln, raw in enumerate(lines, 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue

        def die(msg):
            print(f"[serve] bad request line {ln} in {path}: {msg} "
                  f"(got {raw!r}; expected 'TOKEN[,TOKEN...]|MAX_NEW"
                  f"[|DEADLINE]')", file=sys.stderr)
            raise SystemExit(2)

        parts = line.split("|")
        if len(parts) not in (2, 3):
            die(f"expected 2 or 3 '|'-separated fields, got {len(parts)}")
        try:
            prompt = [int(t) for t in parts[0].replace(",", " ").split()]
        except ValueError:
            die("prompt tokens must be integers")
        if not prompt:
            die("empty prompt")
        bad = [t for t in prompt if not 0 <= t < cfg.vocab_size]
        if bad:
            die(f"token {bad[0]} outside vocab [0, {cfg.vocab_size})")
        try:
            max_new = int(parts[1])
        except ValueError:
            die(f"max_new {parts[1]!r} must be an integer")
        if max_new < 1:
            die(f"max_new must be >= 1, got {max_new}")
        deadline = None
        if len(parts) == 3 and parts[2].strip():
            try:
                deadline = float(parts[2])
            except ValueError:
                die(f"deadline {parts[2]!r} must be a number")
        reqs.append(Request(rid=len(reqs), prompt=prompt, max_new=max_new,
                            deadline=deadline))
    if not reqs:
        print(f"[serve] workload file {path} contains no requests",
              file=sys.stderr)
        raise SystemExit(2)
    return reqs


def _workload(args, cfg, key):
    """Staggered request mix: generation budgets differ so sequences
    retire at different steps, opening slots for mid-decode admission.
    ``--requests`` also accepts a workload FILE (see
    :func:`_parse_workload_file`)."""
    spec = args.requests.strip()
    if spec and not spec.lstrip("-").isdigit():
        return _parse_workload_file(spec, cfg)
    n = int(spec) if spec else 0
    if n < 0:
        print(f"[serve] --requests must be >= 0 or a workload file, "
              f"got {n}", file=sys.stderr)
        raise SystemExit(2)
    n = n or 2 * args.batch
    reqs = []
    for r in range(n):
        k = jax.random.fold_in(key, r)
        plen = max(1, args.prompt_len - (r % 3))
        prompt = np.asarray(
            jax.random.randint(k, (plen,), 0, cfg.vocab_size)
        ).tolist()
        max_new = max(1, args.gen - 2 * (r % 3))
        reqs.append(Request(rid=r, prompt=prompt, max_new=max_new))
    return reqs


def _print_resume(info):
    print(f"[serve] resumed from snapshot step {info['step']}: "
          f"in_flight={info['in_flight']} waiting={info['waiting']} "
          f"done={info['done']}", flush=True)
    for rid, n in sorted(info["committed"].items()):
        print(f"[serve]   resume rid={rid} committed={n}", flush=True)


def _run_with_recovery(eng, reqs, args, events):
    """Host watchdog around the decode loop: on an engine failure, roll
    the engine back to the last intact snapshot (resubmitting every
    in-flight request from its last committed token) and continue, with
    bounded jittered backoff between restarts.  Without ``--snapshot-dir``
    there is nothing to restart from — the failure propagates."""
    pending = reqs
    if args.snapshot_dir and checkpointing.available_steps(args.snapshot_dir):
        try:
            info = eng.restore_serve(args.snapshot_dir)
        except checkpointing.CheckpointStructureError as e:
            print(f"[serve] snapshot at {args.snapshot_dir} does not match "
                  f"this engine: {e}", file=sys.stderr)
            raise SystemExit(2)
        except checkpointing.CheckpointCorruptError as e:
            print(f"[serve] no intact snapshot at {args.snapshot_dir} "
                  f"({e}); starting fresh", flush=True)
        else:
            _print_resume(info)
            pending = []  # the snapshot is authoritative over the workload
    policy = BackoffPolicy(base=0.2, factor=2.0, cap=2.0,
                           max_attempts=args.restart_retries, jitter=0.5)
    attempt = 0
    while True:
        try:
            return eng.run(pending, events=events)
        except (SystemExit, KeyboardInterrupt):
            raise
        except Exception as e:
            can_restart = bool(
                args.snapshot_dir
                and checkpointing.available_steps(args.snapshot_dir)
            )
            if not can_restart or attempt >= policy.max_attempts:
                raise
            delay = policy.delay(attempt, token=args.seed)
            attempt += 1
            print(f"[serve] watchdog: engine failed "
                  f"({type(e).__name__}: {e}); restart "
                  f"{attempt}/{policy.max_attempts} from last snapshot "
                  f"in {delay:.2f}s", flush=True)
            time.sleep(delay)
            info = eng.restore_serve(args.snapshot_dir)
            _print_resume(info)
            pending = []


def _serve_paged(args, cfg, model, params, key):
    max_len = args.prompt_len + args.gen
    policy = {"32": "fp32", "8": "int8", "4": "int4"}.get(
        args.kv_bits, args.kv_bits
    )
    mesh = exchange = None
    n_dev = len(jax.devices())
    if args.logit_exchange != "off" and n_dev > 1:
        mesh = make_host_mesh(n_dev)
        if args.logit_exchange == "fp32":
            exchange = ExchangeConfig(compressor="none", axis_name="data")
        else:
            bits = int(args.logit_exchange.replace("int", ""))
            exchange = ExchangeConfig(
                compressor="qgenx",
                quant=QuantConfig(
                    num_levels=15 if bits == 8 else 5, bits=bits,
                    bucket_size=512,
                ),
                mode="two_phase",
                axis_name="data",
            )
    spec = faults.parse_fault_spec_arg(args.fault_spec, scope="serve")
    if spec.events:
        print(f"[serve] fault schedule: {args.fault_spec}", flush=True)
        if spec.has_serve_device_events and not args.guard:
            print("[serve] WARNING: nan_logits scheduled without --guard "
                  "— poisoned slots will NOT be rejected", flush=True)
    robust = bool(args.guard or spec.events or args.snapshot_dir
                  or args.deadline_ms or args.max_queue)
    # with wall-clock deadlines the scheduler clock (and the deadline /
    # backoff units) switch from decode-wave index to monotonic ms
    clock = (lambda: time.monotonic() * 1e3) if args.deadline_ms else None
    eng = ServeEngine(
        cfg, params, policy=policy, page_size=args.page_size,
        n_slots=args.batch, max_len=max_len, num_pages=args.num_pages,
        seed=args.seed, exchange=exchange, mesh=mesh,
        guard=args.guard, guard_retries=args.guard_retries,
        fault_spec=spec if spec.events else None,
        snapshot_dir=args.snapshot_dir, snapshot_every=args.snapshot_every,
        max_queue=args.max_queue, low_watermark=args.shed_watermark,
        deadline_default=args.deadline_ms or None, clock=clock,
    )
    reqs = _workload(args, cfg, key)
    print(f"[serve] arch={cfg.name} slots={args.batch} requests={len(reqs)} "
          f"kv={policy} {eng.pc.describe()}"
          + (f" guard=on retries={args.guard_retries}" if args.guard else ""))

    events: list = []
    t0 = time.time()
    out = _run_with_recovery(eng, reqs, args, events)
    wall = time.time() - t0

    for kind, rid, slot, step in events:
        where = f"slot {slot}" if kind != "retire" else "freed pages"
        print(f"[serve]   step {step:3d} {kind:18s} request {rid} ({where})")
    st = eng.sched.stats
    n_tok = sum(len(v) for v in out.values())
    print(f"[serve] admitted={st['admitted']} retired={st['retired']} "
          f"mid_decode_admits={st['mid_decode_admits']} "
          f"max_concurrent={st['max_concurrent']}")
    print(f"[serve] {n_tok} tokens in {wall*1e3:.0f}ms "
          f"({n_tok/max(wall,1e-9):.1f} tok/s, "
          f"{eng.sched.decode_steps} packed decode steps)")
    ratio = eng.fp32_cache_bytes / eng.cache_bytes
    print(f"[serve] cache {eng.cache_bytes} B vs fp32 {eng.fp32_cache_bytes} B "
          f"({ratio:.2f}x smaller)")
    if exchange is not None:
        print(f"[serve] logit exchange over {eng.K} devices: "
              f"wire={eng.wire_bytes:.0f} B "
              f"({eng.wire_per_step:.0f} B/step), "
              f"coded_bits_est={eng.coded_bits:.0f}")
    if robust:
        for rr in sorted(eng.results().values(), key=lambda r: r.rid):
            print(f"[serve] result rid={rr.rid} kind={rr.kind} "
                  f"tokens={len(rr.tokens)}")
        print(f"[serve] guard_retries={st.get('guard_retries', 0)} "
              f"evicted={st.get('evicted', 0)} "
              f"shed_transient={st.get('shed_transient', 0)} "
              f"page_pressure={eng.sched.page_pressure:.2f}")
        print(f"[serve] pages free={eng.allocator.n_free}"
              f"/{eng.allocator.num_pages}")
    if out:
        sample = out[min(out)]
        print(f"[serve] sample tokens: {sample[:12]}")
    return out


def _serve_dense(args, cfg, model, params, key):
    """Original batch-synchronous greedy loop (SSM / MLA / enc-dec)."""
    if (args.guard or args.fault_spec or args.snapshot_dir
            or args.deadline_ms or args.max_queue):
        print("[serve] note: --guard/--fault-spec/--snapshot-dir/"
              "--deadline-ms/--max-queue harden the PAGED engine; the "
              "dense fallback ignores them")
    if args.kv_bits != "32":
        print(f"[serve] note: arch {cfg.name!r} ({cfg.arch_type}) has no "
              f"paged token cache; --kv-bits {args.kv_bits} ignored "
              f"(dense decode fallback)")
    B = args.batch
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.arch_type in ("encdec", "audio"):
        batch["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
    max_len = args.prompt_len + args.gen
    cache = model.init_cache(params, batch, max_len)

    t0 = time.time()
    logits, cache = prefill_into_cache(model, params, prompts, cache)
    t_prefill = time.time() - t0

    serve = jax.jit(make_serve_step(model))
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [token]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        token, logits, cache = serve(params, cache, token, pos)
        generated.append(token)
    t_decode = time.time() - t0
    gen = np.stack([np.asarray(t) for t in generated], axis=1)
    print(f"[serve] arch={cfg.name} batch={B} prompt={args.prompt_len} gen={args.gen}")
    print(f"[serve] prefill={t_prefill*1e3:.0f}ms decode={t_decode*1e3:.0f}ms "
          f"({t_decode/max(args.gen-1,1)*1e3:.1f}ms/tok)")
    print(f"[serve] sample tokens: {gen[0][:12].tolist()}")
    return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host (CPU) devices")
    ap.add_argument("--batch", type=int, default=4,
                    help="packed decode slots (dense fallback: batch size)")
    ap.add_argument("--requests", default="0",
                    help="requests to serve: a count (default 2x --batch) "
                         "or a workload file, one request per line "
                         "'TOKEN[,TOKEN...]|MAX_NEW[|DEADLINE]'")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv-bits", choices=("32", "8", "4", "mixed"),
                    default="8",
                    help="KV-cache storage policy (mixed: int8 global "
                         "layers, int4 local-window layers)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per cache page")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="arena pages (0 = provision every slot fully; "
                         "smaller forces admission waits)")
    ap.add_argument("--logit-exchange",
                    choices=("off", "fp32", "int8", "int4"), default="int8",
                    help="cross-device logit aggregation policy (active "
                         "when >1 device is visible)")
    ap.add_argument("--restore", default="",
                    help="checkpoint dir: serve trained params "
                         "(restore_with_fallback)")
    ap.add_argument("--guard", action="store_true",
                    help="decode guard: per-slot finiteness flag (psum'd "
                         "across the device ensemble), bounded re-keyed "
                         "retries, quarantine + typed eviction")
    ap.add_argument("--guard-retries", type=int, default=2,
                    help="re-keyed retries before a failing slot is "
                         "quarantined")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request TTL in wall-clock ms (queued past it: "
                         "queue_timeout; active past it: deadline eviction); "
                         "switches the scheduler clock to monotonic ms")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="shed queue overflow from the tail into jittered "
                         "exponential-backoff re-admission (0 = unbounded)")
    ap.add_argument("--shed-watermark", type=float, default=0.0,
                    help="free-page fraction below which shed requests are "
                         "NOT re-admitted (overload protection)")
    ap.add_argument("--snapshot-dir", default="",
                    help="engine snapshot dir: crash-safe periodic state "
                         "(resume happens automatically when intact "
                         "snapshots exist here)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot the engine every N decode waves "
                         "(0 = off)")
    ap.add_argument("--restart-retries", type=int, default=3,
                    help="watchdog: in-process engine restarts from the "
                         "last intact snapshot before giving up")
    faults.add_fault_spec_flag(ap, scope="serve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.host_devices:
        # read when jax first initialises its backends, which no code
        # before this line does
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    cache = enable_compilation_cache()
    if cache:
        print(f"[serve] compilation cache: {cache}", flush=True)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = _restore_params(model, cfg, args, key)

    if transformer.paged_eligible(cfg):
        return _serve_paged(args, cfg, model, params, key)
    return _serve_dense(args, cfg, model, params, key)


if __name__ == "__main__":
    main()
