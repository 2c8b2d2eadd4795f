"""From the profiler's trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: the device operations of each chip (the ``XLA Ops`` line of each
``/device:`` plane) and the benchmark's own host spans (``bench.*``), all on
the trace's one clock in nanoseconds.  The rest works on that extraction, so
a small recorded one (``tests/data``) checks it without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]  # (name, start ns, duration ns)

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")  # one chip: /device:TPU:0


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text; keep the instruction's name."""
    return event_name.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]  # plane name -> device ops
    host: List[Event]  # bench.* spans

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        as_events = lambda evs: [(str(n), float(s), float(t)) for n, s, t in evs]
        return cls({k: as_events(v) for k, v in d["devices"].items()}, as_events(d["host"]))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane under {log_dir}, found {paths}")
    return paths[0]


def extract(xplane_path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                )
    host.sort(key=lambda e: e[1])
    return Trace(devices, host)


# --------------------------------------------------------------------- #
# reductions
# --------------------------------------------------------------------- #
def window(trace: Trace) -> Tuple[float, float]:
    """(start, end) ns: the first host span's start to the last one's end."""
    if not trace.host:
        raise ValueError("no bench.* host spans in the trace")
    return trace.host[0][1], max(s + d for _, s, d in trace.host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(events: List[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Union of the device-op intervals, clipped to [t0, t1]."""
    clipped = [(max(s, t0), min(s + d, t1)) for _, s, d in events if s < t1 and s + d > t0]
    return _union([iv for iv in clipped if iv[1] > iv[0]])


def busy_seconds(trace: Trace) -> Optional[float]:
    """Busy seconds in the window, averaged over the chips; None if no device ops."""
    if not trace.devices or not any(trace.devices.values()):
        return None
    t0, t1 = window(trace)
    per = [sum(e - s for s, e in busy_intervals(evs, t0, t1)) for evs in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def span_seconds(trace: Trace, name: str) -> List[float]:
    return [d / 1e9 for n, _, d in trace.host if n == name]


def op_seconds(trace: Trace, pattern: str) -> Optional[float]:
    """Seconds inside device ops whose name matches ``pattern``, in the
    window, averaged over the chips; None if no such op ran."""
    rx = re.compile(pattern)
    t0, t1 = window(trace)
    per, found = [], False
    for evs in trace.devices.values():
        sel = [(n, s, d) for n, s, d in evs if rx.search(n)]
        found = found or bool(sel)
        per.append(sum(e - s for s, e in busy_intervals(sel, t0, t1)))
    if not found:
        return None
    return sum(per) / len(per) / 1e9


def self_times(events: List[Event], t0: float, t1: float) -> Dict[str, float]:
    """ns of each op in [t0, t1] less the time of the ops nested inside it
    (a ``while`` or ``cond`` op spans the ops of its body)."""
    tot: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, clipped duration]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, d = stack.pop()
            tot[name] = tot.get(name, 0.0) + d

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        cut = max(0.0, min(s + d, t1) - max(s, t0))
        if stack:
            stack[-1][2] -= cut
        stack.append([name, s + d, cut])
    close(float("inf"))
    return tot


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` device ops with the most self time in the window, seconds
    averaged over the chips."""
    t0, t1 = window(trace)
    tot: Dict[str, float] = {}
    for evs in trace.devices.values():
        for name, v in self_times(evs, t0, t1).items():
            tot[name] = tot.get(name, 0.0) + v
    k = max(1, len(trace.devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v / k / 1e9] for name, v in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of any chip in the window, each named by
    the host span that overlaps it most (``host.other`` if none)."""
    t0, t1 = window(trace)
    gaps = []
    for evs in trace.devices.values():
        edge = t0
        for s, e in busy_intervals(evs, t0, t1) + [(t1, t1)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, over = "host.other", 0.0
        for name, hs, hd in trace.host:
            o = min(e, hs + hd) - max(s, hs)
            if o > over:
                best, over = name, o
        named.append([best, (e - s) / 1e9])
    return named


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
