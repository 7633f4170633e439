"""Print the readings that a language-model cell's limits are set from.

    python3 benchmarks/chip/lm_readings.py --workload moonlight-ep8.round \\
        --seeds 1-2 --variants program,capacity,softmax_router

As ``readings.py``, with the language model's faults and control
(``benchlib/lm_plants.py``): "program" (sound runs), the control
"bf16_master" and the faults "half_batch", "altered", "unchanged",
"capacity" and "softmax_router".  Seeds run one after another, each with
every variant; the reference of a seed, which no variant touches, is
computed once and kept for that seed's variants."""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from benchlib import lm_plants, readings  # noqa: E402
from benchlib.reference import hsfl_lm  # noqa: E402
from readings import seeds  # noqa: E402


def _once_per_seed(simulate):
    """``simulate`` keeping its last result, for the same seeds and rounds."""
    memo = {}

    @functools.wraps(simulate)
    def cached(cfg, seed, n_rounds, train_rounds, weight_seed):
        key = (seed, n_rounds, train_rounds, weight_seed)
        if key not in memo:
            memo.clear()
            memo[key] = simulate(cfg, seed, n_rounds, train_rounds,
                                 weight_seed=weight_seed)
        return memo[key]

    return cached


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    a = ap.parse_args()
    readings.PLANTS.update(lm_plants.PLANTS)
    hsfl_lm.simulate = _once_per_seed(hsfl_lm.simulate)
    rows = {}
    for s in seeds(a.seeds):
        for v in a.variants.split(","):
            nums = readings.reading(a.workload, s, v)
            rows.setdefault(v, []).append(nums)
            print(json.dumps({"workload": a.workload, "variant": v,
                              "seed": s, "numbers": nums}), flush=True)
    for v, rs in rows.items():
        print(json.dumps({"workload": a.workload, "variant": v,
                          "max": {k: max(r[k] for r in rs) for k in rs[0]},
                          "min": {k: min(r[k] for r in rs) for k in rs[0]}}),
              flush=True)


if __name__ == "__main__":
    main()
