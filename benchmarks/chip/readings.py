"""Print the readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/readings.py --workload sec4.round \\
        --seeds 1-12 --variants program,bf16,half_batch

One JSON line per (variant, seed) with every compared number; a summary
line per variant gives each number's largest and smallest reading.  The
variants are "program" (sound runs), the control "bf16" and the planted
faults "half_batch", "unchanged" and "altered".  Runs in one process, on
the chips the cell asks for."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from benchlib import readings  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--detail", default="",
                    help="also write per-leaf and per-round detail here")
    a = ap.parse_args()
    sink = open(a.detail, "a") if a.detail else None
    for v in a.variants.split(","):
        rows = []
        for s in seeds(a.seeds):
            det = {} if sink else None
            nums = readings.reading(a.workload, s, v, detail=det)
            rows.append(nums)
            if sink:
                sink.write(json.dumps({"workload": a.workload, "variant": v,
                                       "seed": s, "detail": det}) + "\n")
                sink.flush()
            print(json.dumps({"workload": a.workload, "variant": v,
                              "seed": s, "numbers": nums}), flush=True)
        print(json.dumps({"workload": a.workload, "variant": v,
                          "max": {k: max(r[k] for r in rows) for k in rows[0]},
                          "min": {k: min(r[k] for r in rows) for k in rows[0]}}),
              flush=True)


if __name__ == "__main__":
    main()
