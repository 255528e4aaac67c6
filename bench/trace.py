"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics read.

A traced window is recorded with :func:`capture`; the ``.xplane.pb`` it
writes is read with ``jax.profiler.ProfileData`` in the same process,
reduced to a :class:`Reduced` summary, and deleted, so nothing large is
left behind.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per executed HLO instruction (its name is the
instruction's text, ``%fusion.3 = bf16[..] fusion(..), ...``) and whose
line ``XLA Modules`` holds one event per program execution
(``jit_step(<fingerprint>)``).  On the CPU (the tests' recorded trace)
the same instructions are events on host threads carrying ``hlo_op`` and
``hlo_module`` stats; both are read into one :class:`Device` form.

Instructions are attributed to the program's scopes through the compiled
HLO text, whose ``metadata={op_name="jit(step)/.../exchange/bucket0/..."}``
names the ``jax.named_scope`` each instruction came from.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import time
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-_]*)\(")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OPNAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class Op:
    start: int  # ns
    end: int
    name: str  # HLO instruction name
    opcode: str
    module: str


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # [Op], sorted by start
    modules: list  # [(start, end, module name)]


@dataclasses.dataclass
class Reduced:
    devices: list  # [Device]
    host: list  # [(start, end, label)] host events of the Python threads
    window_ns: int  # length of the traced window (host clock)


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _module_name(s: str) -> str:
    return s.split("(", 1)[0]


def _parse_op(text: str):
    if text.startswith("%") and " = " in text:
        name = text[1: text.index(" = ")]
        m = _OPCODE.search(text)
        return name, (m.group(1) if m else "")
    return text, ""


def read(path: str, window_ns: int) -> Reduced:
    """Read one ``.xplane.pb`` into a :class:`Reduced`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    cpu_ops = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name, opcode = _parse_op(ev.name)
                        ops.append(Op(int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      name, opcode, ""))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     _module_name(ev.name)))
            ops.sort(key=lambda o: o.start)
            mods.sort()
            _assign_modules(ops, mods)
            devices.append(Device(plane.name.split(":", 1)[1], ops, mods))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                    if "hlo_op" in st:
                        cpu_ops[int(st.get("device_ordinal", 0))].append(
                            (Op(s, e, str(st["hlo_op"]), "",
                                str(st.get("hlo_module", ""))),
                             st.get("run_id")))
                    elif e > s:
                        host.append((s, e, ev.name))
    if not devices:  # CPU backend: the ops ran on host threads
        for ordinal in sorted(cpu_ops):
            ops = sorted((o for o, _ in cpu_ops[ordinal]),
                         key=lambda o: o.start)
            spans = defaultdict(lambda: [None, None])
            for o, run_id in cpu_ops[ordinal]:  # one span per execution
                sp = spans[(o.module, run_id)]
                sp[0] = o.start if sp[0] is None else min(sp[0], o.start)
                sp[1] = o.end if sp[1] is None else max(sp[1], o.end)
            mods = sorted((s, e, m) for (m, _), (s, e) in spans.items())
            devices.append(Device(f"CPU:{ordinal}", ops, mods))
    host.sort()
    return Reduced(devices, host, window_ns)


def _assign_modules(ops, mods) -> None:
    j = 0
    for o in ops:
        while j < len(mods) and mods[j][1] < o.start:
            j += 1
        if j < len(mods) and mods[j][0] <= o.start:
            o.module = mods[j][2]


def start(directory: str) -> float:
    """Start the profiler into a fresh ``directory``; returns the host
    clock at the start."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    jax.profiler.start_trace(directory)
    return time.perf_counter()


def stop(t_start: float) -> int:
    """Stop the profiler; returns the traced window's length in ns."""
    import jax

    window = time.perf_counter() - t_start
    jax.profiler.stop_trace()
    return int(window * 1e9)


def collect(directory: str, window_ns: int) -> Reduced:
    """Reduce the trace written under ``directory`` and delete it."""
    try:
        files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{directory}")
        return read(files[0], window_ns)
    finally:
        shutil.rmtree(directory, ignore_errors=True)


@contextlib.contextmanager
def capture(directory: str):
    """Trace the enclosed block; yields a dict that, on exit, holds
    ``reduced`` (a :class:`Reduced`).  The raw trace is deleted."""
    out = {}
    t0 = start(directory)
    try:
        yield out
    finally:
        window = stop(t0)
    out["reduced"] = collect(directory, window)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def union_ns(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(dev: Device) -> int:
    return union_ns((o.start, o.end) for o in dev.ops)


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} of a compiled HLO module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OPNAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def is_collective(op: Op) -> bool:
    code = op.opcode or op.name
    return any(code.startswith(c) or c in op.name for c in COLLECTIVES)


def split_ns(dev: Device, op_names: dict, scope: str, module: str = ""):
    """Device ns of (collective ops, non-collective ops whose op_name has
    ``scope``, every other op), over the ops of ``module`` (all when
    empty).  Ops run one at a time on a chip's core, so the collective
    ns are the collective time with no compute running beside it."""
    coll = scoped = other = 0
    for o in dev.ops:
        if module and o.module != module:
            continue
        d = o.end - o.start
        if is_collective(o):
            coll += d
        elif scope in op_names.get(o.name, ""):
            scoped += d
        else:
            other += d
    return coll, scoped, other


def _label(op: Op, op_names: dict) -> str:
    name = op_names.get(op.name, "")
    if name:
        name = re.sub(r"^jit\([^)]*\)/", "", name)
        return f"{op.opcode or 'op'}:{name}"[:120]
    base = re.sub(r"\.\d+$", "", op.name)
    return f"{op.module}:{base}"[:120] if op.module else base[:120]


def top_ops(dev: Device, op_names: dict, n: int = 10):
    """The ``n`` labels of device operations that took most seconds."""
    acc = defaultdict(int)
    for o in dev.ops:
        acc[_label(o, op_names)] += o.end - o.start
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(dev: Device, host, n: int = 10, min_ns: int = 20_000):
    """The ``n`` host activities under which the device sat idle longest:
    every gap between device ops of at least ``min_ns`` is labelled by the
    shortest host event covering its middle, and gaps are summed by
    label."""
    spans = sorted((o.start, o.end) for o in dev.ops)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    acc = defaultdict(int)
    active, i = [], 0  # host events begun by the current gap's middle
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 < min_ns:
            continue
        mid = (e0 + s1) // 2
        while i < len(host) and host[i][0] <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[1] >= mid]
        best = min(active, key=lambda h: h[1] - h[0], default=None)
        acc[best[2][:120] if best else "no host event"] += s1 - e0
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
