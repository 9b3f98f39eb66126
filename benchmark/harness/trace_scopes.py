"""What the program says about itself inside a profiler trace, read beside
``harness/trace.py``'s reduction and on the same clock:

- **scopes**: the ``jax.named_scope`` path of every device operation
  (``mln.step/jvp(TransformerBlock.3)/attn``). The profiler keeps the HLO's
  ``op_name`` in the ``tf_op`` stat of the operation's *event metadata*,
  which ``jax.profiler.ProfileData`` does not hand out, so the file's
  protobuf is walked here for just that map;
- **spans**: the program's ``obs.span``s, which are ``TraceAnnotation``s in
  the host plane that carry the stat ``span_depth`` (obs/spans.py);
- **idle by span**: every idle instant of the device under the innermost
  program span open at that instant.

``reduce`` gives every key ``trace.reduce`` gives, unchanged, and these
three beside them. As a command it reduces any trace directory, such as one
a ``ProfilerListener`` wrote:

    python benchmark/harness/trace_scopes.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Dict, Iterator, List, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness import trace

SPAN_STAT = "span_depth"    # the stat obs/spans.py puts on every span
SCOPE_STAT = "tf_op"        # the event-metadata stat that holds the op_name
UNSCOPED = "unscoped"
OUTSIDE = "outside_any_span"


# -- the protobuf, as far as the scope map needs it --------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def scope_maps(path: str) -> Dict[str, Dict[str, str]]:
    """plane name -> {operation name as ``trace.op_name`` gives it -> its
    ``op_name`` metadata}, for every plane of the file whose event metadata
    carries the stat. XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id
    = 1, .str_value = 5, .ref_value = 7; XStatMetadata.name = 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum == 4:
                events.append(_map_entry(v)[1])
            elif pnum == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for n, x in _fields(meta) if n == 2), "")
        scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        if not scope_ids:
            continue
        ops: Dict[str, str] = {}
        for meta in events:
            op, scope = "", None
            for mnum, v in _fields(meta):
                if mnum == 2:
                    op = bytes(v).decode()
                elif mnum == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in scope_ids:
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op and scope:
                ops.setdefault(trace.op_name(op), scope)
        out[name] = ops
    return out


def scope_path(op_name: str) -> str:
    """``jit(step)/mln.step/jvp(TransformerBlock.3)/attn/dot_general:`` ->
    ``mln.step/jvp(TransformerBlock.3)/attn``: the scopes between the jitted
    function and the primitive. Forward (``jvp``) and backward
    (``transpose``) keep their marks."""
    parts = op_name.rstrip(":").split("/")[:-1]
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "/".join(parts) or UNSCOPED


def load_xplane(path: str) -> List[dict]:
    """``trace.load_xplane``'s planes, a device plane also with ``scopes``
    (operation -> scope path) and a host plane with ``spans`` (the program's
    annotations as (name, start_ns, duration_ns))."""
    from jax.profiler import ProfileData

    planes = trace.load_xplane(path)
    by_plane = scope_maps(path)
    spans: Dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        found = spans.setdefault(plane.name, [])
        for line in plane.lines:
            for e in line.events:
                if any(k == SPAN_STAT for k, _ in e.stats):
                    found.append((e.name, int(e.start_ns), int(e.duration_ns)))
    for p in planes:
        if p["name"] in by_plane:
            p["scopes"] = {k: scope_path(v)
                           for k, v in by_plane[p["name"]].items()}
        if p["name"] in spans:
            p["spans"] = spans[p["name"]]
    return planes


# -- the reduction -----------------------------------------------------------

def idle_by_span(gaps: List[Tuple[int, int]], spans: List[trace.Event],
                 lo: int, hi: int) -> Dict[str, float]:
    """Idle seconds under the innermost (shortest) program span at each
    instant of every gap; ``outside_any_span`` where no span is open. A gap
    that runs across several spans is split between them."""
    import numpy as np

    if not gaps:
        return {}
    gaps = sorted(gaps)
    edges = np.array([t for g in gaps for t in g], np.float64)
    total = np.cumsum([b - a for a, b in gaps], dtype=np.float64)
    before = np.concatenate(([0.0], total[:-1]))
    idle_until = np.stack([before, total], 1).ravel()   # idle ns before t
    cuts = sorted({lo, hi} | {t for _, s, d in spans
                              for t in (s, s + d) if lo < t < hi})
    by_start = sorted(spans, key=lambda e: e[1])
    open_spans: List[Tuple[int, str, int]] = []     # (length, name, end)
    nxt = 0
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(by_start) and by_start[nxt][1] <= a:
            name, s, d = by_start[nxt]
            open_spans.append((d, name, s + d))
            nxt += 1
        # every span's end is a cut, so one that outlives a outlives b
        open_spans = [x for x in open_spans if x[2] >= b]
        idle = float(np.interp(b, edges, idle_until)
                     - np.interp(a, edges, idle_until))
        if idle > 0:
            label = min(open_spans)[1] if open_spans else OUTSIDE
            out[label] = out.get(label, 0.0) + idle / 1e9
    return out


def reduce(planes: List[dict], top: int = 10) -> dict:
    """``trace.reduce(planes)`` and beside it: ``scopes`` (scope path ->
    self seconds of its operations, adding up to ``busy_s``; operations with
    no scope under ``unscoped``), ``spans`` (program span -> [[start, length]]
    in seconds from the slice's start, for the spans that lie whole inside
    it) and ``idle_by_span`` (every idle instant of the first device plane
    under the innermost program span open then; ``outside_any_span`` where
    none is: unlike ``idle_gaps``, which gives a whole gap to what covers
    its middle, a gap that runs across several spans is split)."""
    out = trace.reduce(planes, top)
    lo, hi = trace.window_of(planes)
    devs = trace.device_planes(planes)

    scope_of: Dict[str, str] = {}
    for p in devs:
        for op, scope in p.get("scopes", {}).items():
            scope_of.setdefault(op, scope)
    scopes: Dict[str, float] = {}
    for op, (self_s, _, _) in out["ops"].items():
        scope = scope_of.get(op, UNSCOPED)
        scopes[scope] = scopes.get(scope, 0.0) + self_s
    out["scopes"] = scopes

    program = [e for p in trace.host_planes(planes) for e in p.get("spans", ())]
    spans: Dict[str, list] = {}
    for name, s, d in sorted(program, key=lambda e: e[1]):
        if s >= lo and s + d <= hi:
            spans.setdefault(name, []).append([(s - lo) / 1e9, d / 1e9])
    out["spans"] = spans

    merged = trace.merge((s, s + d) for _, s, d in
                         trace.clip(trace.op_events(devs[0]), lo, hi))
    edge = [lo] + [t for ab in merged for t in ab] + [hi]
    gaps = [(edge[k], edge[k + 1]) for k in range(0, len(edge), 2)
            if edge[k + 1] > edge[k]]
    out["idle_by_span"] = idle_by_span(gaps, program, lo, hi)
    return out


def steps_in_slice(summary: dict, span: str):
    """How many times ``span`` came round in the slice: the slice's length
    over the median distance between two starts of it. None under two."""
    starts = [s for s, _ in summary.get("spans", {}).get(span, ())]
    if len(starts) < 2:
        return None
    between = statistics.median(b - a for a, b in zip(starts, starts[1:]))
    return summary["window_s"] / between if between > 0 else None


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".pb") else trace.find_xplane(args[0])
    t = reduce(load_xplane(path), top=20)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    print(json.dumps({
        "window_s": t["window_s"], "busy_s": t["busy_s"],
        "scopes": by_time(t["scopes"]),
        "spans_p50_ms": {k: 1e3 * statistics.median(d for _, d in v)
                         for k, v in t["spans"].items()},
        "idle_by_span": by_time(t["idle_by_span"]),
        "device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"],
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
