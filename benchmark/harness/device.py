"""The device a run is on, and the one table of peaks. A run that finds no
TPU, or fewer chips than its cell asks for, fails: nothing here falls back to
the CPU, and a device that is not in the table is an error, not a default."""

from __future__ import annotations

import json
import os


class NoChip(RuntimeError):
    pass


def require(chips: int):
    """The cell's devices, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU: "
                     "the benchmark measures on the chip or not at all")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks(kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"device kind {kind!r} is not in benchmark/harness/"
                       "peaks.json: add its published peaks with their source")
    return table[kind]


def describe(devs) -> dict:
    """The ``device`` object of the result line; ``memory_peak_bytes`` is the
    peak on the fullest chip."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(
                int(d.memory_stats()["peak_bytes_in_use"]) for d in devs)}
