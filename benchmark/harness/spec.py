"""Finding a cell's files by name. Everything that belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own:

    <root>/configs/<config>.json     sizes as run, family, dtype, source
    <root>/traffic/<traffic>.json    parameters of the mix
    <root>/workloads/<cell>.json     configuration, traffic, driver, limits
    <root>/metrics/<metric>.json     layer, unit, moves, reader + parameters

and drivers, readers, shape functions, families and references are modules
found by the name those files give. ``roots`` is ``benchmark/`` and, for the
tests, a directory of tiny files beside them; a later PR adds files and edits
none.
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Iterable, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmark/
CHECKOUT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _roots(extra: Optional[Iterable[str]]) -> List[str]:
    return [HERE] + [os.path.abspath(r) for r in (extra or ())]


def _find(kind: str, name: str, roots) -> str:
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not made of the allowed "
                         "characters")
    for root in _roots(roots):
        path = os.path.join(root, kind, name + ".json")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}.json under {_roots(roots)}")


def load_json(kind: str, name: str, roots=None) -> dict:
    with open(_find(kind, name, roots)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    """``BENCHMARK.json``; where a checkout has none yet, one that lists
    nothing."""
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    if not os.path.isfile(path):
        return {"workloads": [], "end_to_end": [], "per_layer": []}
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, roots=None) -> dict:
    """A cell with its configuration and traffic read in. ``BENCHMARK.json``
    lists the cells the driver checks; where it lists this one, the two have
    to agree on configuration, traffic and chips."""
    cell = load_json("workloads", name, roots)
    cell["name"] = name
    for w in (w for w in benchmark_json()["workloads"] if w["name"] == name):
        for k in ("config", "traffic", "chips"):
            if w[k] != cell[k]:
                raise ValueError(
                    f"{name}: BENCHMARK.json says {k}={w[k]!r}, "
                    f"workloads/{name}.json says {cell[k]!r}")
    cell["config_name"], cell["traffic_name"] = cell["config"], cell["traffic"]
    cell["config"] = load_json("configs", cell["config_name"], roots)
    cell["traffic"] = load_json("traffic", cell["traffic_name"], roots)
    return cell


def module(kind: str, name: str):
    """``benchmark.<kind>.<name>``: drivers, readers, shapes, families,
    reference."""
    if not re.match(r"^[A-Za-z_][A-Za-z0-9_]*$", name):
        raise ValueError(f"{kind} module name {name!r}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def metric_files(roots=None) -> List[dict]:
    """Every per-layer metric file, by name."""
    out = {}
    for root in _roots(roots):
        d = os.path.join(root, "metrics")
        if not os.path.isdir(d):
            continue
        for fn in sorted(os.listdir(d)):
            if fn.endswith(".json"):
                with open(os.path.join(d, fn)) as f:
                    m = json.load(f)
                m["name"] = fn[:-5]
                out.setdefault(m["name"], m)
    return [out[k] for k in sorted(out)]


def metrics_for(end_to_end: Iterable[str], roots=None) -> List[dict]:
    """The per-layer metrics a cell may report: those that move one of the
    end-to-end metrics its driver reports. No metric's file names cells (a
    later PR's cell could not be added to it): a reader that finds nothing to
    read in a cell returns nothing, and the metric is left out of the line."""
    e2e = set(end_to_end)
    return [m for m in metric_files(roots) if m["moves"] in e2e]
