"""From a profiler trace to device busy time, per-kernel time and the
idle gaps with what the host was doing in them.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Its device planes (``/device:TPU:<n>``) carry a line ``XLA Ops``: one
event per executed HLO op, a loop's op enclosing the ops of its body.
A Pallas kernel is a custom call whose op is named after the kernel
(``%matmul_tiled.31 = ... custom-call(...)``). The host plane
(``/host:CPU``) carries the benchmark's own spans, the
``jax.profiler.TraceAnnotation`` names that start with ``bench.``.
Device and host events share one clock (ns from the trace's start).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

SPAN_PREFIX = "bench."
_OP = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]     # device plane -> its XLA ops
    spans: List[Event]                  # the benchmark's host spans


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read an .xplane.pb (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(Event(e.name, float(e.start_ns),
                                   float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, sorted(spans, key=lambda e: e.start_ns))


def to_json(trace: Trace) -> dict:
    return {"devices": {k: [list(e) for e in v]
                        for k, v in trace.devices.items()},
            "spans": [list(e) for e in trace.spans]}


def from_json(obj: dict) -> Trace:
    return Trace({k: [Event(*e) for e in v]
                  for k, v in obj["devices"].items()},
                 [Event(*e) for e in obj["spans"]])


# ----------------------------------------------------------------------
# reduction
# ----------------------------------------------------------------------

def op_name(event_name: str) -> str:
    """The HLO op's name without its numeric suffix and its shapes."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def kernel_name(event_name: str) -> Optional[str]:
    """The Pallas kernel an op runs, or None for any other op."""
    if "custom-call" not in event_name:
        return None
    m = _OP.match(event_name)
    return m.group(1) if m else None


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window [lo, hi)."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by any event."""
    iv = sorted((e.start_ns, e.end_ns) for e in events if e.dur_ns > 0)
    out: List[List[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(t - s for s, t in union(events))


def kernel_ns(events: Iterable[Event], match) -> float:
    """Summed device time of the kernels whose name `match` accepts."""
    total = 0.0
    for e in events:
        k = kernel_name(e.name)
        if k is not None and match(k):
            total += e.dur_ns
    return total


def self_times(events: List[Event]) -> Dict[str, float]:
    """Per op name, device time not covered by ops nested inside it (a
    loop's own time excludes its body's ops)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    own: Dict[str, float] = {}
    stack: List[List] = []          # [event, time covered by children]

    def pop():
        ev, covered = stack.pop()
        own[op_name(ev.name)] = own.get(op_name(ev.name), 0.0) \
            + max(ev.dur_ns - covered, 0.0)

    for e in evs:
        while stack and e.start_ns >= stack[-1][0].end_ns:
            pop()
        if stack:
            stack[-1][1] += min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, 0.0])
    while stack:
        pop()
    return own


def idle_gaps(events: List[Event], spans: List[Event], lo: float,
              hi: float, least_ns: float = 0.0) -> List[Tuple[str, float]]:
    """Every stretch of [lo, hi) with no op on the device (of at least
    `least_ns`), named by the innermost benchmark span that holds its
    midpoint."""
    gaps, cur = [], lo
    for s, t in union(events):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = [(s, t) for s, t in gaps if t - s >= least_ns]
    out = []
    for s, t in gaps:
        mid = (s + t) / 2
        holders = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        name = min(holders, key=lambda sp: sp.dur_ns).name if holders \
            else "no bench span"
        out.append((name, (t - s) / 1e9))
    return out


def window(spans: List[Event], name: str) -> Tuple[float, float]:
    """[start, end) of the first span called `name`."""
    for sp in spans:
        if sp.name == name:
            return sp.start_ns, sp.end_ns
    raise KeyError(f"no span {name!r} in the trace")


def reduce(trace: Trace, window_span: str, top: int = 10) -> dict:
    """What a traced window says: busy and window seconds (busy
    averaged over the chips), per-kernel seconds, and the breakdown of
    the top device ops by self time and the longest idle gaps grouped
    by the host span they fall in."""
    lo, hi = window(trace.spans, window_span)
    n = max(len(trace.devices), 1)
    busy, kernels, selfs = 0.0, {}, {}
    gaps: Dict[str, float] = {}
    for evs in trace.devices.values():
        evs = clip(evs, lo, hi)
        busy += busy_ns(evs)
        for e in evs:
            k = kernel_name(e.name)
            if k is not None:
                kernels[k] = kernels.get(k, 0.0) + e.dur_ns / 1e9 / n
        for k, v in self_times(evs).items():
            selfs[k] = selfs.get(k, 0.0) + v / 1e9 / n
        # gaps between the ops of one program are sub-microsecond; only
        # stretches of 10 us or more are named
        for name, secs in idle_gaps(evs, trace.spans, lo, hi, 1e4):
            gaps[name] = max(gaps.get(name, 0.0), secs)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "kernel_s": kernels,
        "device_ops": sorted(selfs.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
        "n_events": sum(len(v) for v in trace.devices.values()),
    }
