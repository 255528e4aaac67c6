"""The program's names for the stages of a train step, and the device time
under each.

``repro.launch.steps`` runs each oracle round of the step under
``lookahead`` or ``commit``, with ``forward``, ``backward`` and ``update``
(the optimizer) inside it, then ``step_metrics`` and ``guard``; the exchange
runs under ``exchange``, with its kernels under ``noise``, ``quantize``,
``reduce`` and ``dequantize`` (``repro.core.exchange``).  An operation is
placed by the ``op_name`` of its HLO instruction, a fusion by its root's.

The stages split the step's non-collective operations on the slowest chip
in two exact partitions: the five train stages sum to ``train.compute_ms``
(every op outside ``exchange/``), the five exchange stages to
``exchange.device_ms``.  A program that names no stage (no ``commit`` scope)
gives no reading.
"""

from __future__ import annotations

from bench import readings
from bench import trace as tr

ROUNDS = ("lookahead", "commit")
KERNELS = ("noise", "quantize", "reduce", "dequantize")
TRAIN = ("forward", "backward", "optimizer", "step_metrics", "unscoped")
EXCHANGE = KERNELS + ("layout",)


def _scopes(op_name: str) -> list:
    """The scopes of an op_name: its first location, the primitive left
    out."""
    return op_name.split(";", 1)[0].split("/")[:-1]


def stage(op_name: str) -> str:
    """The stage of a non-collective op: one of :data:`TRAIN` or
    :data:`EXCHANGE`.  ``exchange/`` anywhere in the name puts it in the
    exchange, as ``readings.step_split_ms`` does."""
    parts = _scopes(op_name)
    if "exchange/" in op_name:
        kernels = [p for p in parts if p in KERNELS]
        return kernels[-1] if kernels else "layout"
    if "update" in parts:
        return "optimizer"
    if "step_metrics" in parts or "guard" in parts:
        return "step_metrics"
    in_round = any(p in ROUNDS for p in parts)
    if "backward" in parts or (in_round and "transpose(" in op_name):
        return "backward"
    if "forward" in parts or in_round:
        return "forward"
    return "unscoped"


def stage_ms(r: dict):
    """{stage: device ms per step} over the step's program on the slowest
    chip, collectives left out; None where the program names no stage."""
    names = r["op_names"]
    if not any("commit" in _scopes(n) for n in names.values()):
        return None
    dev = readings.busiest(r)
    module = readings.main_module(dev)
    acc = dict.fromkeys(TRAIN + EXCHANGE, 0)
    for o in dev.ops:
        if (module and o.module != module) or tr.is_collective(o):
            continue
        acc[stage(names.get(o.name, ""))] += o.end - o.start
    return {k: v / 1e6 / r["steps"] for k, v in acc.items()}


def train_ms(r: dict, name: str):
    """One train stage's ms per step, or None."""
    if r.get("kind") != "train":
        return None
    ms = stage_ms(r)
    return None if ms is None else ms[name]


def exchange_ms(r: dict, name: str):
    """One exchange stage's ms per step, or None where the cell has no
    exchange or its ops took no time."""
    if r.get("kind") != "train" or r.get("exchange_bytes_per_step") is None:
        return None
    ms = stage_ms(r)
    if ms is None or sum(ms[k] for k in EXCHANGE) <= 0:
        return None
    return ms[name]
