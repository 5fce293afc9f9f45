"""Run one cell of the chip benchmark once and print its result line.

  python3 benchmarks/chip/run.py --workload smollm-135m.train \
      --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name from ``BENCHMARK.json`` at the root of the checkout.  The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, when traced,
``breakdown``; its last key, ``checks``, gives each number compared with
its limit, and the last lines of standard error say the same.  Without a
TPU, or with fewer chips than the cell needs, it prints no result and
exits with 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    import harness

    cell = harness.load_cell(args.workload)
    try:
        out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"run.py: {e}; this benchmark runs only on the chips its cell "
              f"asks for ({cell.chips})", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
