#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's data on the device from ``--seed``, warms up, measures
for ``--seconds``, checks what the timed jobs produced against the plain
reference, and prints the result as the last line of standard output.
With no accelerator, or fewer chips than the cell asks for, it exits 3 and
prints no result. ``--rehearse-rows N`` is for the CPU: the same path on N
rows, platform named in the output, no metric printed.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark import harness

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), T0,
            require_chip=not args.rehearse_rows,
            rehearse_rows=args.rehearse_rows)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
