#!/usr/bin/env python3
"""Readings from which a cell's limits are set (PERF.md, section 2).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,.. \
        [--control-seeds ..] [--fault-seeds ..]

Runs on the cell's chips, in one process: the sound program on each of
``--seeds``, the cell's control on ``--control-seeds`` and the faults of
its timed path on ``--fault-seeds``; prints one JSON line per reading.
The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    a = ap.parse_args(argv)
    run = harness.load_run(a.workload, 0, 0.0, False, T_START)
    try:
        harness.claim_chips(run)
    except harness.NoChip as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.cell_module(run)
    for row in cell.calibrate(run, a.seeds, a.control_seeds, a.fault_seeds):
        row["t"] = round(harness.since(T_START), 1)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
