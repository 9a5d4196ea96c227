#!/usr/bin/env python3
"""Chip benchmark of the real serving path: one cell of BENCHMARK.json.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Builds the cell's configuration through ``build_real_engine`` on one TPU,
replaces its weights with the traffic mix's generator's draw from the
seed, warms the stream up, measures a window of ``--seconds`` on the wall
clock, and checks the served tokens against the plain reference.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``; ``breakdown`` with ``--trace 1``).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the same path on the
CPU at reduced widths with interpret-mode kernels, for the tests only: it
reports no number under a metric's name."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
from pathlib import Path   # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
CHECKOUT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))

from harness import bench   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at reduced widths (tests only)")
    args = ap.parse_args(argv)
    try:
        result = bench.run_cell(
            CHECKOUT, args.workload, args.seed, args.seconds,
            trace=bool(args.trace), rehearse=args.rehearse, t_start=T_START)
    except bench.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
