"""A count the driver took from the program's counters over the window; with
``per`` it is divided by another such count."""


def read(metric: dict, facts: dict):
    value = facts.get(metric["fact"])
    if value is None:
        return None
    if "per" in metric:
        den = facts.get(metric["per"])
        if not den:
            return None
        return float(value) / float(den)
    return float(value)
