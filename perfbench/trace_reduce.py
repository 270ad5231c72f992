"""Reduction of a profiler trace to the device's busy time, its busiest
operations, and its idle gaps named by what the host was doing.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device operations are the events of the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane.  The host's activities are the harness's
annotations on the host plane, whose names start with ``pb:``; the
annotations ``pb:mark start`` and ``pb:mark stop`` bound the traced
window.

  * busy: the union of the device operations' intervals inside the
    window, averaged over the devices that ran an operation;
  * device_ops: total device seconds per HLO op name (a Pallas kernel's
    custom call is named after its jitted function, e.g. ``%ssd.1``),
    largest first;
  * idle_gaps: device idle seconds inside the window, split by the
    innermost host annotation open during each part of the gap (or
    ``(no annotation)``), largest first.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

TOP = 10
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
MARK_START, MARK_STOP = "pb:mark start", "pb:mark stop"

Interval = Tuple[float, float]


def op_name(text: str) -> str:
    """An op event's HLO name: ``%ssd.1`` of ``%ssd.1 = (f32[...]) custom-call(...)``."""
    return text.split(" = ", 1)[0]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged, clipped) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """The host timeline as segments, each named by the innermost span
    open in it (the one opened last)."""
    ev = []
    for i, (a, b, _) in enumerate(spans):
        if b <= a:
            continue
        ev.append((a, 1, -b, i))
        ev.append((b, 0, -a, i))
    ev.sort()
    stack: List[int] = []
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, opening, _, i in ev:
        if prev is not None and t > prev and stack:
            out.append((prev, t, spans[stack[-1]][2]))
        if opening:
            stack.append(i)
        else:
            stack.remove(i)
        prev = t
    return out


def attribute(gap_list: List[Interval],
              spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of ``gap_list`` (sorted) per innermost open span."""
    out: Dict[str, float] = {}
    segs = innermost(spans)
    j = 0
    for g0, g1 in gap_list:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            out["(no annotation)"] = out.get("(no annotation)", 0.0) + rest
    return out


def top(table: Dict[str, float], n: int = TOP) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(profile) -> Dict:
    """Reduce a ``jax.profiler.ProfileData``; times in seconds."""
    host: List[Tuple[float, float, str]] = []
    device: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("pb:"):
                        host.append((e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     e.name[3:]))
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         op_name(e.name)) for e in line.events)
    starts = [a for a, _, n in host if n == MARK_START[3:]]
    stops = [a for a, _, n in host if n == MARK_STOP[3:]]
    if not starts or not stops:
        raise ValueError("trace holds no window marks (pb:mark start/stop)")
    lo, hi = min(starts), max(stops)
    spans = [s for s in host if s[2] not in (MARK_START[3:], MARK_STOP[3:])]
    busy_total, ops, idle = 0.0, {}, {}
    used = [d for d, evs in device.items() if clip([(a, b) for a, b, _ in evs], lo, hi)]
    for d in used:
        evs = device[d]
        merged = union(clip([(a, b) for a, b, _ in evs], lo, hi))
        busy_total += sum(b - a for a, b in merged)
        for a, b, name in evs:
            c = clip([(a, b)], lo, hi)
            if c:
                ops[name] = ops.get(name, 0.0) + (c[0][1] - c[0][0])
        for name, s in attribute(gaps(merged, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + s / len(used)
    n = max(1, len(used))
    return {"busy_s": busy_total / n, "window_s": hi - lo,
            "devices": len(used),
            "device_ops": top({k: v / n for k, v in ops.items()}),
            "idle_gaps": top(idle)}


def reduce_dir(path: str) -> Dict:
    """Reduce the one trace the profiler wrote under ``path``."""
    import jax
    files = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if len(files) != 1:
        raise ValueError(f"expected one trace under {path}, found {files}")
    return reduce(jax.profiler.ProfileData.from_file(files[0]))
