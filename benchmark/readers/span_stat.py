"""A statistic, in milliseconds, of one of the program's spans: the
durations ``obs.span`` recorded under the metric's ``span`` name, as the
program's own ring of finished spans holds them when the window has closed
(its last 512 spans unless ``DL4J_TPU_SPAN_RING`` says otherwise: in a train
cell the window's last 85 steps or so). Returns nothing where the program
recorded no such span, as a program from before the span was added does:
never 0."""

import statistics


def read(metric: dict, facts: dict):
    from deeplearning4j_tpu import obs

    values = [r["wall_s"] for r in obs.recent_spans()
              if r["span"] == metric["span"] and not r.get("error")]
    if not values:
        return None
    stat = metric.get("stat", "p50")
    if stat == "p50":
        return 1e3 * float(statistics.median(values))
    if stat == "mean":
        return 1e3 * float(statistics.fmean(values))
    raise ValueError(f"{metric['name']}: unknown stat {stat!r}")
