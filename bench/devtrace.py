"""From a profiler trace of the measured window to the device's numbers.

A traced run wraps its window in `jax.profiler` and in one host span,
`bench.window`. The reduction reads the trace with `jax.profiler.ProfileData`
alone:

  busy     the union of the intervals in which an operation ran on a
           device (plane `/device:TPU:<i>`, line `XLA Ops`), clipped to
           the window span; averaged over the chips used;
  idle     1 - busy / window;
  top ops  device seconds summed by operation name, less the time of
           the operations nested inside each;
  gaps     each stretch of the window in which device 0 ran nothing, put
           under the innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
TOP = 10

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


class Tracer:
    """Profiles the window into a temporary directory, reads it back and
    removes it. The host's Python tracer stays off: it would slow the host
    path that the window measures."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self, window_s: float, n_devices: int) -> dict:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError(f"no trace written under {self.dir}")
            pd = jax.profiler.ProfileData.from_file(paths[0])
            return reduce_trace(*load_events(pd), n_devices=n_devices,
                                window_s=window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load_events(pd) -> Tuple[Dict[int, List[Event]], List[Event]]:
    """(device ops by device index, host spans) of a ProfileData."""
    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            device[int(m.group(1))] = [
                (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for ln in ops for e in ln.events if e.duration_ns > 0]
        elif plane.name == HOST_PLANE:
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for ln in plane.lines for e in ln.events
                        if e.duration_ns > 0)
    return device, host


def op_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(events: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds each operation ran in [lo, hi], less the time of the
    operations nested inside it (a `while` holds its body's operations)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [name, end, nested seconds]

    def close(item):
        out[item[0]] -= item[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            close(stack.pop())          # ended, or not holding this one
        dur = (e - s) / 1e9
        out[name] += dur
        if stack:
            stack[-1][2] += dur
        stack.append([name, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of intervals clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(host: List[Event], points) -> List[Optional[str]]:
    """For each time in `points`, the name of the innermost (shortest)
    host span that covers it, or None."""
    points = np.asarray(points, dtype=np.float64)
    order = np.argsort(points)
    sorted_pts = points[order]
    label = np.full(points.size, -1, dtype=np.int64)
    names = [h[0] for h in host]
    # longest first, so a shorter span inside it overwrites its label
    for i in sorted(range(len(host)), key=lambda k: host[k][1] - host[k][2]):
        _, s, e = host[i]
        a = np.searchsorted(sorted_pts, s, side="left")
        b = np.searchsorted(sorted_pts, e, side="right")
        label[order[a:b]] = i
    return [names[k] if k >= 0 else None for k in label]


def reduce_trace(device: Dict[int, List[Event]], host: List[Event], *,
                 n_devices: int, window_s: float) -> dict:
    """busy_s, window_s and the breakdown of one traced window. The
    window is the `bench.window` host span where the trace has one, else
    the host clock's `window_s` ending at the last event."""
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
    else:
        ends = [e for evs in device.values() for _, _, e in evs]
        hi = max(ends, default=0.0)
        lo = hi - window_s * 1e9
    used = sorted(device)[:n_devices]
    busy_by_dev = {d: union(((s, e) for _, s, e in device[d]), lo, hi)
                   for d in used}
    busy_s = [sum(e - s for s, e in b) / 1e9 for b in busy_by_dev.values()]
    op_s: Dict[str, float] = defaultdict(float)
    for d in used:
        for name, secs in self_times(device[d], lo, hi).items():
            op_s[name] += secs
    gap_s: Dict[str, float] = defaultdict(float)
    inner = [h for h in host if h[0] != WINDOW_SPAN]
    if used:
        idle = gaps(busy_by_dev[used[0]], lo, hi)
        names = attribute(inner, [(s + e) / 2 for s, e in idle])
        for (s, e), name in zip(idle, names):
            gap_s[name or "(no host span)"] += (e - s) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:TOP]]

    return dict(busy_s=(sum(busy_s) / len(busy_s)) if busy_s else 0.0,
                window_s=(hi - lo) / 1e9, devices=len(used),
                breakdown=dict(device_ops=top(op_s), idle_gaps=top(gap_s)))


def idle_share_pct(run: dict) -> Optional[float]:
    """Share of the traced window in which the device ran nothing, in %;
    nothing where the trace saw no device."""
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
