"""Quantities several per-layer readers share, from a cell's readings."""

from __future__ import annotations

from bench import trace as tr


def busiest(r: dict) -> tr.Device:
    """The slowest chip of the traced window: the most device time."""
    return max(r["reduced"].devices, key=tr.busy_ns)


def idle_share(r: dict) -> float:
    red = r["reduced"]
    return 100.0 * (1.0 - tr.busy_ns(busiest(r)) / red.window_ns)


def main_module(dev: tr.Device) -> str:
    """The program that took most device time (a train cell's step)."""
    acc = {}
    for s, e, m in dev.modules:
        acc[m] = acc.get(m, 0) + e - s
    return max(acc, key=acc.get) if acc else ""


def step_split_ms(r: dict) -> tuple:
    """Device ms per step of (collectives, exchange scopes, the rest) in
    the train step's program on the slowest chip."""
    dev = busiest(r)
    ns = tr.split_ns(dev, r["op_names"], "exchange/", main_module(dev))
    return tuple(v / 1e6 / r["steps"] for v in ns)
