"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes into a
small dict: for each device plane the operation events and their
``op_name``s (the program's scopes, ``bench/scopes.py``), and for the host
the spans that last at least ``HOST_MIN_NS``. The reductions below work on
that dict, so they are tested on a recorded one (``tests/bench``) and read
the chip's trace the same way in every run.

Device planes are those named ``/device:TPU:<n>``; their operation events
are on the line named ``XLA Ops`` (the ``Async XLA Ops`` line holds the DMA
copies that overlap them). A TPU op event is named by its HLO instruction
text (``%fusion.12 = f32[...] fusion(...)``); ``load`` keeps the
instruction's name (``fusion.12``). Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies. Times are
nanoseconds on the profiler's clock, which it aligns for host and device.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

HOST_MIN_NS = 100_000          # host spans shorter than 0.1 ms are dropped
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")
WINDOW_SPAN = "bench.window"

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Dict[str, Any]:
    """{"devices": {id: [event, ...]}, "host": [event, ...], "scopes": {id:
    [op_name, ...]}} from one ``.xplane.pb``. Each event is ``[name,
    start_ns, duration_ns]``; ``scopes`` holds each device operation
    event's ``op_name`` (``scopes.op_scopes``), in the order of
    ``devices``, ``""`` where the event has none."""
    from jax.profiler import ProfileData
    from bench.scopes import op_scopes
    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": [], "scopes": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append([op_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns)])
            out["devices"][m.group(1)] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= HOST_MIN_NS:
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    with open(path, "rb") as f:
        raw = op_scopes(f.read())
    for dev, evs in out["devices"].items():
        names, ops = raw.get(dev, ([], []))
        if [op_name(n) for n in names] != [e[0] for e in evs]:
            raise ValueError(f"device {dev}: the raw read of {path} does "
                             f"not match its operation events")
        out["scopes"][dev] = ops
    return out


def op_name(text: str) -> str:
    """``fusion.12`` from ``%fusion.12 = f32[8] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def window(trace: Dict[str, Any]) -> Tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    _, t0, dur = max(spans, key=lambda e: e[2])
    return t0, t0 + dur


def _clip(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def busy_ns(trace: Dict[str, Any]) -> float:
    """Union of the operation intervals inside the window, averaged over
    the devices that ran any."""
    t0, t1 = window(trace)
    per_dev = [sum(b - a for a, b in union(_clip(evs, t0, t1)))
               for evs in trace["devices"].values() if evs]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def op_ns(trace: Dict[str, Any], match: Callable[[str], bool]) -> float:
    """Summed device time of the ops whose name ``match``es, inside the
    window, averaged over the devices."""
    t0, t1 = window(trace)
    devs = [evs for evs in trace["devices"].values() if evs]
    if not devs:
        return 0.0
    total = sum(sum(b - a for a, b in _clip((e for e in evs if match(e[0])),
                                             t0, t1))
                for evs in devs)
    return total / len(devs)


def top_ops(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The ``n`` op names with the most device time in the window
    (seconds, averaged over devices), control-flow ops left out. Numbered
    HLO names (``fusion.12``) are pooled under their stem."""
    t0, t1 = window(trace)
    devs = [evs for evs in trace["devices"].values() if evs]
    tot: Dict[str, float] = {}
    for evs in devs:
        for name, s, d in evs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a and not CONTAINERS.match(name):
                key = op_stem(name)
                tot[key] = tot.get(key, 0.0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(devs) * 1e-9] for k, v in ranked]


def op_stem(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def idle_gaps(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The ``n`` longest gaps in which no op ran on the first device,
    inside the window, each named by the shortest host span that covers
    most of it (what the host was doing), in seconds."""
    t0, t1 = window(trace)
    devs = sorted((k for k, v in trace["devices"].items() if v), key=int)
    if not devs:
        return []
    busy = union(_clip(trace["devices"][devs[0]], t0, t1))
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:n]:
        out.append([host_label(trace, a, b), (b - a) * 1e-9])
    return out


def host_label(trace: Dict[str, Any], a: float, b: float) -> str:
    best: Optional[Tuple[float, str]] = None
    for name, s, d in trace["host"]:
        cover = min(s + d, b) - max(s, a)
        if cover >= 0.5 * (b - a) and (best is None or d < best[0]):
            best = (d, name)
    return best[1] if best else "(no host span)"


def window_ns(trace: Dict[str, Any]) -> float:
    t0, t1 = window(trace)
    return t1 - t0
