"""The whole step's share of the chip's peak: the operations the mathematics
needs for the work the window did (a shape function of the configuration's
family, kept in benchmark/shapes/), over the window on the host's clock times
the table's bf16 peak times the chips."""

from benchmark.harness import spec


def read(metric: dict, facts: dict):
    cell = facts["cell"]
    fn = getattr(spec.module("shapes", cell["config"]["shapes"]),
                 metric["shape_fn"], None)
    if fn is None or not facts.get("window_s"):
        return None
    flops = fn(cell["config"], facts)
    if not flops:
        return None
    peak = facts["peaks"]["bf16_flops_per_s"] * int(cell["chips"])
    return 100.0 * flops / (facts["window_s"] * peak)
