#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on this machine's chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``: each number the comparison
judged with its limit.  With no TPU, too few chips or an unknown kind of
chip it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        run = harness.load_run(a.workload, a.seed, a.seconds, bool(a.trace),
                               T_START)
    except (OSError, KeyError) as e:
        print(f"[bench] cannot load the cell: {e!r}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  the system under test, under src/
    except ImportError as e:
        print(f"[bench] the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    try:
        harness.claim_chips(run)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    cache = harness.enable_compile_cache()
    stats = harness.CompileStats()
    harness.log(f"cell {a.workload} seed {a.seed}: {run.chips} x "
                f"{run.devices[0].device_kind}; compile cache {cache!r}")
    result = harness.cell_module(run).run_cell(run)
    harness.log(f"backend compile {stats.seconds:.3f} s, persistent cache "
                f"hits {stats.hits} of {stats.requests}; run "
                f"{harness.since(T_START):.1f} s")
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
