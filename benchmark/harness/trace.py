"""The reduction from a profiler trace to numbers: device busy and idle time,
time per device operation, idle gaps by what the host was doing. Kept with the
benchmark so that every PR computes them the same way; checked on a small
synthetic trace in tests/benchmark.

A trace is read into plain data first: a list of planes, each
``{"name", "lines": [{"name", "events": [(name, start_ns, duration_ns)]}]}``.
``load_xplane`` makes that from the ``.xplane.pb`` the JAX profiler writes;
the tests build it by hand.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_EVENT = "bench.traced_window"
OPS_LINE = "XLA Ops"
Event = Tuple[str, int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_NUMBER = re.compile(r"\.\d+\b")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """A device event's name is the whole HLO instruction,
    ``%fusion.193 = (bf16[...]{...}, ...) fusion(...operands...), ...``: keep
    the instruction's own name. A custom call also keeps its target and its
    result type without layouts, as in ``jvp__.24 tpu_custom_call
    (bf16[256,1024,64], f32[256,1,1024])``: ops/flash_attention.py gives its
    Pallas kernels no name, so the result type is all that tells the
    forward, the dq and the dk/dv kernel apart."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    name = name.lstrip("%")
    cut = rest.find(" custom-call(")
    target = _TARGET.search(rest) if cut >= 0 else None
    if target is None:
        return name
    return f"{name} {target.group(1)} {_LAYOUT.sub('', rest[:cut])}"


def load_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (op_name(e.name) if device else e.name,
                 int(e.start_ns), int(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith("/device:TPU:")]


def host_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith("/host:")]


def op_events(plane: dict) -> List[Event]:
    """A device plane's operations: its ``XLA Ops`` line where it has one
    (the other lines repeat the same time as modules and steps)."""
    lines = [l for l in plane["lines"] if l["name"] == OPS_LINE]
    if not lines:
        lines = [l for l in plane["lines"] if l["name"] != "Steps"]
    return [e for l in lines for e in l["events"]]


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def self_times(events: List[Event]) -> Dict[str, List[float]]:
    """name -> [self seconds, whole seconds, count]. An operation that
    contains others on the same line (a ``while``, a ``call``) keeps only the
    time its children do not cover, so the list adds up to busy time."""
    out: Dict[str, List[float]] = {}
    stack: List[List] = []          # [name, end, child_ns, dur]

    def close(item):
        name, _, child, dur = item
        rec = out.setdefault(name, [0.0, 0.0, 0])
        rec[0] += (dur - child) / 1e9
        rec[1] += dur / 1e9
        rec[2] += 1

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(d, stack[-1][1] - s)
        stack.append([name, s + d, 0, d])
    while stack:
        close(stack.pop())
    return out


def window_of(planes: List[dict]) -> Optional[Tuple[int, int]]:
    """The traced window as the benchmark marked it on the host, on the
    trace's own clock; without the mark, first to last device operation."""
    for p in host_planes(planes):
        for l in p["lines"]:
            for name, s, d in l["events"]:
                if name == WINDOW_EVENT:
                    return s, s + d
    spans = [(s, s + d) for p in device_planes(planes)
             for _, s, d in op_events(p)]
    if not spans:
        return None
    return min(a for a, _ in spans), max(b for _, b in spans)


class _HostIndex:
    """What the host was doing at an instant: the shortest host event that
    covers it (the innermost call), the window's own mark left out."""

    def __init__(self, events: List[Event]):
        import numpy as np

        events = [e for e in events if e[0] != WINDOW_EVENT]
        self.names = [e[0] for e in events]
        self.start = np.array([e[1] for e in events], np.int64)
        self.dur = np.array([e[2] for e in events], np.int64)

    def label(self, t: int) -> str:
        import numpy as np

        if not self.names:
            return "host:nothing_recorded"
        cover = (self.start <= t) & (t < self.start + self.dur)
        if not cover.any():
            return "host:nothing_recorded"
        idx = np.flatnonzero(cover)
        return self.names[int(idx[np.argmin(self.dur[idx])])]


def reduce(planes: List[dict], top: int = 10) -> dict:
    """``window_s``; ``busy_s`` averaged over the device planes; per-plane
    operation times; the ``top`` operations by self time and the idle time by
    host label (gaps of the first device plane, the longest 200 labelled)."""
    win = window_of(planes)
    devs = device_planes(planes)
    if win is None or not devs:
        raise ValueError("the trace holds no device operation")
    lo, hi = win
    busy, ops_all = [], {}
    gaps: List[Tuple[int, int]] = []
    for i, p in enumerate(devs):
        ev = clip(op_events(p), lo, hi)
        merged = merge((s, s + d) for _, s, d in ev)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, rec in self_times(ev).items():
            tot = ops_all.setdefault(name, [0.0, 0.0, 0])
            for j in range(3):
                tot[j] += rec[j]
        if i == 0:
            edge = [lo] + [t for ab in merged for t in ab] + [hi]
            gaps = [(edge[k], edge[k + 1]) for k in range(0, len(edge), 2)
                    if edge[k + 1] > edge[k]]
    n = len(devs)
    ops = {k: [v[0] / n, v[1] / n, v[2] // n if n > 1 else v[2]]
           for k, v in ops_all.items()}
    host_ev = [e for p in host_planes(planes) for l in p["lines"]
               for e in clip(l["events"], lo, hi)]
    by_label: Dict[str, float] = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:200]
    index = _HostIndex(host_ev)
    for a, b in longest:
        label = index.label((a + b) // 2)
        by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    rest = sum(b - a for a, b in gaps) / 1e9 - sum(by_label.values())
    if rest > 0:
        by_label["gaps_not_among_the_200_longest"] = rest
    # the breakdown gathers an instruction's copies (one per layer:
    # fusion.12, fusion.13, ...) under the name without its number
    kinds: Dict[str, float] = {}
    for k, v in ops.items():
        kind = _NUMBER.sub("", k)
        kinds[kind] = kinds.get(kind, 0.0) + v[0]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n,
        "ops": ops,
        "device_ops": [[k, v] for k, v in sorted(
            kinds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
    }
