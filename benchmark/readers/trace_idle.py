"""The device's idle share of the traced window: 1 - the union of the
device-operation intervals over the window (benchmark/harness/trace.py)."""


def read(metric: dict, facts: dict):
    t = facts.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
