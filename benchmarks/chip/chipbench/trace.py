"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device,
  inside the traced window, averaged over the devices; idle share is
  1 - busy / window.
* device time per operation, summed over its events; operations are named
  by opcode and result shape (`op_label`).
* idle time by host span: every stretch of the window in which the device
  ran nothing, split at the benchmark's host spans (``TraceAnnotation``s
  whose names start with ``bench.``) and added to the innermost span that
  covers each piece; a piece no span covers is ``unattributed``.

The window is the host span named ``WINDOW_SPAN`` if the trace has one,
else the extent of all device events.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def op_label(text: str) -> str:
    """A device op's HLO text (``%fusion.3 = s32[8,128]{1,0:T(8,128)}
    fusion(...), kind=kLoop, ...``) as ``opcode[:custom target] shape``,
    without the numbered name and the layouts, which change from one
    program to the next."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    prev = None
    while prev != rest:
        prev, rest = rest, re.sub(r"\{[^{}]*\}", "", rest)
    m = re.match(r"(\([^()]*\)|\S+) ([a-z][\w-]*)\(", rest)
    if not m:
        return head.lstrip("%")[:120]
    target = re.search(r'custom_call_target="([^"]+)"', text)
    kind = m.group(2) + (f":{target.group(1)}" if target else "")
    return f"{kind} {m.group(1)}"


def newest_xplane(logdir: str) -> str | None:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "spans": [...]}``:
    device operations from each device plane's ops line (every line when
    the plane has none), and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [(op_label(ev.name), float(ev.start_ns),
                    float(ev.start_ns) + float(ev.duration_ns))
                   for ln in ops for ev in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.start_ns)
                                      + float(ev.duration_ns)))
    return {"devices": devices, "spans": spans}


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint sorted ``[a, b)`` covering the intervals, clipped to
    ``[lo, hi]``."""
    out: list = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    out, reach = [], lo
    for a, b in busy:
        if a > reach:
            out.append((reach, a))
        reach = max(reach, b)
    if hi > reach:
        out.append((reach, hi))
    return out


def _attribute(gap, spans, into: dict) -> None:
    """Split one idle gap at the span boundaries inside it and add each
    piece's length to the innermost span that covers it."""
    a, b = gap
    cuts = sorted({a, b} | {t for _, s, e in spans for t in (s, e)
                            if a < t < b})
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        best, width = "unattributed", float("inf")
        for name, s, e in spans:
            if name != WINDOW_SPAN and s <= lo and hi <= e and e - s < width:
                best, width = name, e - s
        into[best] = into.get(best, 0.0) + (hi - lo)


def reduce(trace: dict, top: int = 10) -> dict | None:
    """Busy seconds, window seconds, device time per op and idle seconds
    per host span; None when the trace holds no device operation."""
    devices, spans = trace["devices"], trace["spans"]
    if not devices:
        return None
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e in evs)
    busy_ns, op_ns, idle_ns = [], {}, {}
    for evs in devices.values():
        inside = [(n, s, e) for n, s, e in evs if e > lo and s < hi]
        cover = union([(s, e) for _, s, e in inside], lo, hi)
        busy_ns.append(sum(b - a for a, b in cover))
        for n, s, e in inside:
            op_ns[n] = op_ns.get(n, 0.0) + (min(e, hi) - max(s, lo))
        for gap in gaps(cover, lo, hi):
            _attribute(gap, spans, idle_ns)
    n_dev = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s = float(np.mean(busy_ns)) * 1e-9
    ops = sorted(((k, v * 1e-9 / n_dev) for k, v in op_ns.items()),
                 key=lambda kv: -kv[1])
    idle = sorted(((k, v * 1e-9 / n_dev) for k, v in idle_ns.items()),
                  key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s, "devices": n_dev,
            "op_seconds": dict(ops), "device_ops": [list(kv) for kv in ops[:top]],
            "idle_gaps": [list(kv) for kv in idle[:top]]}
