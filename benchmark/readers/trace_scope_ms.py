"""Device time of the operations under the program's own scopes: the self
seconds that benchmark/harness/trace_scopes.py sums by ``jax.named_scope``
path (``scopes`` of its ``reduce``), added up over the paths the metric's
``scopes`` patterns match. With ``per: "step"`` in milliseconds for one turn
of the metric's ``site_span`` (the slice's length over the median distance
between two starts of that span); with ``per: "busy"`` as a percentage of the
device's busy time. Returns nothing where the summary has no scopes (the
harness's own reduction keeps none) or no path matches: never 0."""

import re

from benchmark.harness import trace_scopes


def read(metric: dict, facts: dict):
    t = facts.get("trace")
    if not t or not t.get("scopes"):
        return None
    pats = [re.compile(p) for p in metric["scopes"]]
    hit = [v for k, v in t["scopes"].items() if any(p.search(k) for p in pats)]
    if not hit:
        return None
    if metric["per"] == "busy":
        return 100.0 * sum(hit) / t["busy_s"] if t["busy_s"] else None
    if metric["per"] == "step":
        steps = trace_scopes.steps_in_slice(t, metric["site_span"])
        return 1e3 * sum(hit) / steps if steps else None
    raise ValueError(f"{metric['name']}: unknown per {metric['per']!r}")
