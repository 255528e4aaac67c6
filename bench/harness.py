"""What every cell shares: the spec, the chip, the peaks, the readers of
per-layer metrics, the comparison's report and the result line."""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclasses.dataclass
class Run:
    """One run of one cell."""

    spec: dict  # BENCHMARK.json
    cell: dict  # its entry in ``workloads``
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<traffic>.json
    limits: dict  # bench/limits/<cell>.json
    seed: int
    seconds: float
    trace: bool
    t_start: float  # perf_counter at process start
    devices: list = dataclasses.field(default_factory=list)
    peaks: dict = dataclasses.field(default_factory=dict)

    @property
    def chips(self) -> int:
        return self.cell["chips"]


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_run(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> Run:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config = load_json("configs", f"{cell['config']}.json")
    from bench.traffic import generate

    traffic = generate.load(cell["traffic"])
    limits = load_json("limits", f"{workload}.json")
    return Run(spec, cell, config, traffic, limits, seed, seconds, trace,
               t_start)


def cell_module(run: Run):
    """The module that drives the cell's kind of traffic:
    ``bench/<kind>_cell.py`` with ``run_cell(run)`` and
    ``calibrate(run, seeds, control_seeds, fault_seeds)``."""
    return importlib.import_module(f"bench.{run.traffic['kind']}_cell")


def claim_chips(run: Run, allow_cpu: bool = False) -> None:
    """Take the cell's chips; raise :class:`NoChip` when this machine has no
    TPU, too few chips, or a kind the peaks table does not know."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: jax found {devs[0].platform} devices")
    if len(devs) < run.chips:
        raise NoChip(f"the cell needs {run.chips} chips, found {len(devs)}")
    run.devices = devs[: run.chips]
    peaks = load_json("peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        if not allow_cpu:
            raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
        kind = next(iter(peaks))
    run.peaks = peaks[kind]


def device_info(run: Run) -> dict:
    d = run.devices[0]
    peak = 0
    for dev in run.devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(run.devices), "memory_peak_bytes": peak}


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from jax's
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.hits, self.requests = 0.0, 0, 0
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


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``launch/cache.py``:
    ``$JAX_COMPILATION_CACHE_DIR`` or the checkout's fixed ``.jax_cache``),
    with every program kept, however quick its compile, so a later run of
    the cell compiles nothing."""
    import jax

    from repro.launch.cache import enable_compilation_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return enable_compilation_cache()


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def _applies(m: dict, run: Run) -> bool:
    if "workloads" in m:
        return run.cell["name"] in m["workloads"]
    return True


def read_per_layer(run: Run, readings: dict) -> dict:
    """Each per-layer metric of the cell, from its reader
    ``bench/metrics/<name>.py`` (``read(readings) -> float | None``).  A
    reader that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in run.spec["per_layer"]:
        if not _applies(m, run):
            continue
        path = os.path.join(BENCH, "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(readings)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(run: Run, values: dict) -> dict:
    out = {}
    for m in run.spec["end_to_end"]:
        if m["name"] in values and _applies(m, run):
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the comparison and the result line
# ---------------------------------------------------------------------------


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit; a number
    that is missing or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok = ok and good
        checks[name] = {"value": (None if v is None else float(v)),
                        "limit": float(limit)}
    return ok, checks


def emit(result: dict) -> None:
    """The checks as the last lines on standard error, then the result as
    the last line of standard output (``checks`` last in it)."""
    for name, c in result["checks"].items():
        print(f"[bench] check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    checks = result.pop("checks")
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def since(t0: float) -> float:
    return time.perf_counter() - t0
