"""The arithmetic that decides ``correct``: each number compared is printed
beside its limit, and a run is correct when every number is within its own."""

from __future__ import annotations

import math
import sys
from typing import Dict, Optional

import numpy as np


def flatten(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    """{name: [L] or scalar} -> {"name" or "name.3": value}."""
    out = {}
    for k, v in sorted(norms.items()):
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{k}.{i}"] = float(x)
    return out


def worst_norm_gap(prog: Dict[str, float], ref: Dict[str, float],
                   skip=()) -> tuple:
    """The widest gap between the program's norm of a leaf and the
    reference's (the gap of the norms, not the norm of a difference),
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    keys = [k for k in ref if k not in skip]
    if not keys:
        return math.inf, "no leaf left to compare"
    floor = float(np.median([ref[k] for k in keys]))
    worst, where = 0.0, keys[0]
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-300)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def worst_diff_norm(prog: Dict[str, np.ndarray],
                    ref: Dict[str, np.ndarray]) -> tuple:
    """The widest norm of (the program's leaf - the reference's), measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: where the norms agree it still reads a leaf that
    points another way. Returns (share, leaf)."""
    if not ref:
        return math.inf, "no leaf kept to compare"
    norm = {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in ref.items()}
    floor = float(np.median(list(norm.values())))
    worst, where = 0.0, next(iter(ref))
    for k, v in ref.items():
        d = np.asarray(prog[k], np.float64) - np.asarray(v, np.float64)
        share = float(np.linalg.norm(d.ravel())) / max(norm[k], floor, 1e-300)
        if not math.isfinite(share):
            return math.inf, k
        if share > worst:
            worst, where = share, k
    return worst, where


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            notes: Optional[Dict[str, str]] = None) -> dict:
    """{"correct", "numbers": {name: {"value", "limit"}}}; a number without a
    limit of its own, or one that is not finite, is not correct."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
        if notes and name in notes:
            out[name]["at"] = notes[name]
    return {"correct": bool(ok and numbers), "numbers": out}


def print_numbers(v: dict, file=sys.stderr) -> None:
    for name, rec in v["numbers"].items():
        print(f"compared {name} = {rec['value']!r} limit {rec['limit']!r}"
              + (f" at {rec['at']}" if "at" in rec else ""), file=file)
    print(f"correct = {v['correct']}", file=file, flush=True)
