"""Reduction of a jax profiler trace to device busy time, op time and
idle gaps, attributed to what the host was doing.

Two halves: :func:`load` reads an ``.xplane.pb`` into plain tuples
(``(plane, line, name, start_ns, dur_ns)``), and everything else works
on those tuples, so that a small recorded trace, kept as JSON, tests
the arithmetic.
"""

from __future__ import annotations

import glob
import heapq
import itertools
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device line whose events are single operations (not modules)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str, host_names=None) -> list[tuple]:
    """Events of the newest ``.xplane.pb`` under ``log_dir``: every
    event, or, where ``host_names`` is given, only what :func:`reduce`
    and :func:`host_spans` read, the device planes' ``OPS_LINE`` and
    ``MODULES_LINE`` and the host events of those names.  A device
    plane's other lines keep their first event each, so that the plane
    counts in :func:`device_planes` as it does in the full load."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        pn = plane.name
        device = _DEVICE_PLANE.match(pn)
        for line in plane.lines:
            ln = line.name
            if host_names is None or (device and ln in (OPS_LINE,
                                                        MODULES_LINE)):
                evs = line.events
            elif device:
                evs = itertools.islice(line.events, 1)
            else:
                evs = (ev for ev in line.events if ev.name in host_names)
            for ev in evs:
                out.append((pn, ln, ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def device_planes(events) -> list[str]:
    return sorted({e[0] for e in events if _DEVICE_PLANE.match(e[0])})


def device_events(events, line: str = OPS_LINE) -> dict[str, list]:
    """Per device plane, ``(name, start_ns, end_ns)`` of one line."""
    out: dict[str, list] = {p: [] for p in device_planes(events)}
    for plane, ln, name, start, dur in events:
        if plane in out and ln == line:
            out[plane].append((name, start, start + dur))
    return out


def host_spans(events, name: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of every host event called ``name``."""
    return sorted((s, s + d) for plane, _, n, s, d in events
                  if n == name and not _DEVICE_PLANE.match(plane))


def union(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Busy length of the union of ``(start, end)`` clipped to
    [lo, hi], and the idle gaps between, as ``(start, end)``."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?(\S+) = (\([^()]*\)|\S+) ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``fusion.10 = f32[2304,602] fusion`` for an HLO op's full text
    (layouts and operands dropped); other names as they are."""
    m = _HLO.match(_LAYOUT.sub("", name))
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else name


def op_totals(ops, lo: float, hi: float) -> dict[str, float]:
    """Seconds per op name inside [lo, hi] (clipped)."""
    tot: dict[str, float] = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = short_name(name)
            tot[key] = tot.get(key, 0.0) + d * 1e-9
    return tot


def label_gaps(gaps, spans, default: str) -> list[tuple[str, float]]:
    """Name each idle gap by the innermost (shortest) host span that
    covers its middle, the smallest name among spans of one length;
    ``spans`` are ``(name, start_ns, end_ns)``.  One sweep over the
    gaps' middles in time order: a span joins a heap keyed by (length,
    name) once it has started, and leaves it once a middle passes its
    end."""
    mids = sorted((0.5 * (s + e), i) for i, (s, e) in enumerate(gaps))
    by_start = sorted((a, b, n) for n, a, b in spans)
    labels = [default] * len(gaps)
    heap: list[tuple] = []
    j = 0
    for mid, i in mids:
        while j < len(by_start) and by_start[j][0] <= mid:
            a, b, n = by_start[j]
            heapq.heappush(heap, (b - a, n, b))
            j += 1
        while heap and heap[0][2] < mid:
            heapq.heappop(heap)
        if heap:
            labels[i] = heap[0][1]
    return [(n, (e - s) * 1e-9) for n, (s, e) in zip(labels, gaps)]


def top(pairs, k: int = 10) -> list[list]:
    return [[n, v] for n, v in sorted(pairs, key=lambda p: -p[1])[:k]]


def reduce(events, *, window: tuple[float, float], spans,
           default_label: str = "host, outside any span") -> dict:
    """Busy and window seconds, and seconds per jitted program
    (``modules``), averaged over the device planes; the top ops and the
    idle time by what the host was doing."""
    lo, hi = window
    per_plane = device_events(events, OPS_LINE)
    if not per_plane:
        raise ValueError("the trace has no TPU device plane")
    busy_total, ops_all, gaps_all = 0.0, {}, []
    for plane, ops in per_plane.items():
        busy, gaps = union([(s, e) for _, s, e in ops], lo, hi)
        busy_total += busy
        for n, v in op_totals(ops, lo, hi).items():
            ops_all[n] = ops_all.get(n, 0.0) + v
        gaps_all += gaps
    n_dev = len(per_plane)
    modules: dict[str, float] = {}
    for ops in device_events(events, MODULES_LINE).values():
        for n, v in op_totals(ops, lo, hi).items():
            modules[n] = modules.get(n, 0.0) + v / n_dev
    idle: dict[str, float] = {}
    for n, v in label_gaps(gaps_all, spans, default_label):
        idle[n] = idle.get(n, 0.0) + v
    return {
        "busy_s": busy_total * 1e-9 / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "modules": modules,
        "device_ops": top(ops_all.items()),
        "idle_gaps": top(idle.items()),
    }
