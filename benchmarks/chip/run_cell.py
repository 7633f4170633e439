"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run_cell.py --workload sec4.round --seed 7 \\
        --seconds 30 --trace 0

Prints progress, the compile count of set-up, ``peak_bytes_in_use`` after
the window and every number that decides ``correct`` beside its limit on
standard error, each line naming the device kind and count; the last line
of standard output is the result as one JSON object.  Exits 3 and prints
no result when JAX finds no TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from benchlib import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(harness.main(parse()))
