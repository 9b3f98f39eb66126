"""A value from the program's ``obs`` metrics registry, as ``span_stat`` reads
the program's ring: the sum over all series (labels) of the counter the
metric's ``counter`` names, as the registry holds it when the window has
closed; with ``per`` divided by the sum of another counter. Returns nothing
where the program registered no such counter, as a program from before the
counter was added does, or where the divisor is nought: never 0 for
"nothing to read"."""


def _total(name: str):
    from deeplearning4j_tpu import obs

    for family in obs.registry().families():
        if family.name == name:
            values = list(family.as_dict().values())
            return float(sum(values)) if values else None
    return None


def read(metric: dict, facts: dict):
    value = _total(metric["counter"])
    if value is None:
        return None
    if "per" in metric:
        den = _total(metric["per"])
        if not den:
            return None
        return value / den
    return value
