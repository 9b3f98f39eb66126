"""Some series of one family of the program's ``obs`` metrics registry, as
``obs_counter`` reads a whole family: the sum of the series of the counter or
gauge the metric's ``family`` names whose labels match the metric's
``labels`` (label -> a value, or a list of values of which any matches; a
label left out matches every value), as the registry holds them when the
window has closed. Returns nothing where the program registered no such
family, as a program from before the family was added does, where the family
has no such label, or where no series matches: never 0 for "nothing to
read"."""


def read(metric: dict, facts: dict):
    from deeplearning4j_tpu import obs

    want = {label: ({ok} if isinstance(ok, str) else set(ok))
            for label, ok in (metric.get("labels") or {}).items()}
    for family in obs.registry().families():
        if family.name != metric["family"]:
            continue
        if family.kind not in ("counter", "gauge") or \
                not set(want) <= set(family.label_names):
            return None
        values = [
            value for key, value in family.as_dict().items()
            if all(dict(zip(family.label_names, key))[label] in ok
                   for label, ok in want.items())]
        return float(sum(values)) if values else None
    return None
