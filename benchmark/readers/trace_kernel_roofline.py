"""A kernel's share of its roofline: the least time the chip could take for
the calls the trace shows (operations and bytes from the call's shapes, by a
function of the family's shapes module) over the device time of the trace
events whose names match the metric's patterns. Returns nothing where no
event matches: never 0."""

import re

from benchmark.harness import spec


def read(metric: dict, facts: dict):
    t = facts.get("trace")
    if not t:
        return None
    pats = [re.compile(p) for p in metric["patterns"]]
    hit = {k: v for k, v in t["ops"].items() if any(p.search(k) for p in pats)}
    seconds = sum(v[1] for v in hit.values())
    calls = sum(v[2] for v in hit.values()) / float(metric.get("events_per_call", 1))
    if not seconds or not calls:
        return None
    cell = facts["cell"]
    shapes = spec.module("shapes", cell["config"]["shapes"])
    fn = getattr(shapes, metric["shape_fn"], None)
    if fn is None:
        return None
    least, _ = shapes.least_seconds(fn(cell["config"], facts), facts["peaks"])
    return 100.0 * least * calls / seconds
