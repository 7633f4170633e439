"""Numbers that decide ``correct``, each read against a limit of its own.

Parameter changes are compared by the norm of each leaf's change, not by
the norm of the difference: rounding reorders a chaotic training run, but
leaves the size of its steps alone.  A leaf's gap is
| ||program change|| - ||reference change|| |, over the larger of the
reference's change of that leaf and of the median leaf.  Leaves the
reference leaves unmoved to rounding (under a thousandth of the median
leaf's change) move in the program by rounding alone and are left out."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"


def leaves(tree, prefix=""):
    """{dotted path: array} of a nested dict of arrays."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(leaves(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = np.asarray(v, np.float64)
    return out


def worst_leaf_gap(got, want, start) -> float:
    g, w, s = leaves(got), leaves(want), leaves(start)
    if set(g) != set(w):
        return float("inf")
    ref = {k: float(np.linalg.norm(w[k] - s[k])) for k in w}
    med = float(np.median(list(ref.values())))
    if med == 0.0:
        return float("inf")
    gaps = [abs(float(np.linalg.norm(g[k] - s[k])) - ref[k])
            / max(ref[k], med)
            for k in w if ref[k] >= 1e-3 * med]
    return max(gaps)


def leaf_stats(got, want, start) -> dict:
    """Per leaf: norms of both changes, of their difference, and their
    inner product (for looking at readings, not for a verdict)."""
    g, w, s = leaves(got), leaves(want), leaves(start)
    return {k: [float(np.linalg.norm(g[k] - s[k])),
                float(np.linalg.norm(w[k] - s[k])),
                float(np.linalg.norm(g[k] - w[k])),
                float(np.vdot(g[k] - s[k], w[k] - s[k]))] for k in w}


def limits_for(workload: str) -> dict:
    """``limits`` (number -> limit) and ``not_compared`` (numbers a run
    computes and prints, which no limit holds: see the file's readings)."""
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def verdict(numbers: dict, limits: dict):
    """(correct, [[name, value, limit], ...], {name: value} not compared);
    a number above its limit, or one that is not finite, fails."""
    held, free = limits["limits"], limits.get("not_compared", [])
    unknown = set(numbers) - set(held) - set(free)
    if unknown or set(held) - set(numbers):
        raise KeyError(f"numbers {sorted(numbers)} do not match the limits "
                       f"{sorted(held)} and not_compared {sorted(free)}")
    rows, ok = [], True
    for name, lim in held.items():
        value = numbers[name]
        ok &= bool(np.isfinite(value)) and value <= lim
        rows.append([name, float(value), float(lim)])
    return ok, rows, {k: float(numbers[k]) for k in free}
