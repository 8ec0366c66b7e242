"""Reduction of a ``jax.profiler`` trace to device metrics.

A trace is read into plain event lists (``read``): the device ops of
each chip and the host's annotations. The reduction then works on those
lists alone, so it is tested on events built by hand:

* busy time: the union of a chip's op intervals inside the window,
  averaged over the chips;
* idle share: 1 − busy ÷ window;
* per-op time: the summed durations of each op name inside the window;
* idle gaps: the stretches of the window in which no op ran, labelled by
  the innermost harness annotation that covers each gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
WINDOW = "window"
LABEL_PREFIXES = ("collect:", "update:", "load:")


@dataclasses.dataclass
class TraceEvents:
    devices: Dict[str, List[Event]]   # chip plane name → its op events
    host: List[Event]                 # harness annotations


def read(trace_dir: str) -> TraceEvents:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name == WINDOW
                            or e.name.startswith(LABEL_PREFIXES))
    return TraceEvents(devices, host)


def window(events: TraceEvents) -> Tuple[int, int]:
    """[start, end) of the harness's ``window`` annotation, in ns."""
    spans = [(s, s + d) for n, s, d in events.host if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, "
                         f"found {len(spans)}")
    return spans[0]


def _clip(ev: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in ev:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_ns(events: TraceEvents, lo: int, hi: int) -> float:
    """Union of op intervals inside [lo, hi), averaged over the chips."""
    if not events.devices:
        return 0.0
    per_chip = [sum(b - a for a, b in _union(_clip(ev, lo, hi)))
                for ev in events.devices.values()]
    return sum(per_chip) / len(per_chip)


def op_times(events: TraceEvents, lo: int, hi: int) -> Dict[str, float]:
    """Seconds per op (``short_op``) inside [lo, hi), summed over the
    chips."""
    out: Dict[str, float] = defaultdict(float)
    for ev in events.devices.values():
        for name, s, d in ev:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out[short_op(name)] += (b - a) * 1e-9
    return dict(out)


def idle_gaps(events: TraceEvents, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Stretches of [lo, hi) in which no op ran on the first chip."""
    if not events.devices:
        return [(lo, hi)]
    first = sorted(events.devices)[0]
    gaps, t = [], lo
    for a, b in _union(_clip(events.devices[first], lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(events: TraceEvents, t: int) -> str:
    """The innermost harness annotation covering instant ``t``."""
    best: Optional[Tuple[int, str]] = None
    for name, s, d in events.host:
        if name != WINDOW and s <= t < s + d and (best is None or
                                                  d < best[0]):
            best = (d, name)
    return best[1] if best else "other"


def idle_by_label(events: TraceEvents, lo: int, hi: int) -> Dict[str, float]:
    """Idle seconds inside [lo, hi), by what the harness was doing."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(events, lo, hi):
        out[label_at(events, (a + b) // 2)] += (b - a) * 1e-9
    return dict(out)


def short_op(name: str) -> str:
    """``%fusion.2 = f32[32768,128]{1,0:T(8,128)} fusion(...)`` →
    ``%fusion.2 = f32[32768,128]``: the op and its result type."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    if rest.startswith("("):
        result = rest[:rest.find(")") + 1]
    else:
        result = rest.split(" ", 1)[0]
    return f"{head} = {result}"


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
