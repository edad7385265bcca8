"""Profiler trace of one window, and its reduction to numbers.

``Tracer`` starts JAX's profiler (host tracing on, the Python tracer off,
so that tracing slows the host loop as little as it can), marks the
traced window with a host span named ``bench.window``, and stops.
``reduce`` reads the ``.xplane.pb`` it wrote and gives, over that
window:

* ``busy_s`` per device: the union of the intervals in which an operation
  ran (the device's "XLA Ops" line), clipped to the window;
* ``device_ops``: the ten operations (HLO instruction names) that took
  most device time, averaged over the devices traced; a loop's time
  includes the operations of its body, which appear too;
* ``idle_gaps``: the ten longest gaps on the first device in which no
  operation ran, each named by the innermost host span that covers its
  middle ("no host span" where the host ran untraced Python).

The window and the device timelines are on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


class Tracer:
    """Start and stop the profiler around a window; ``reduce()`` after."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, devices=None) -> dict:
        try:
            return reduce(find_xplane(self.dir), devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    return paths[0]


def _union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(path: str, devices=None) -> dict:
    """Numbers of the window marked ``bench.window`` in ``path``.
    ``devices``: the device ids to read (default: every TPU plane)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_events, window = [], None
    dev_ops = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops.setdefault(dev, []).extend(
                        (ev.name.split(" = ")[0], ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.duration_ns > 0:
                        host_events.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    if not dev_ops:
        raise RuntimeError("no device operation in the trace")
    w0, w1 = window
    busy, per_op = {}, {}
    first_union = None
    for dev in sorted(dev_ops):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in dev_ops[dev]
                   if e > w0 and s < w1]
        union = _union([[s, e] for _, s, e in clipped])
        if first_union is None:
            first_union = union
        busy[dev] = sum(e - s for s, e in union) / 1e9
        for n, s, e in clipped:
            per_op[n] = per_op.get(n, 0.0) + (e - s) / 1e9 / len(dev_ops)
    gaps, prev = [], w0
    for s, e in first_union:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    idle = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(he - hs, n) for n, hs, he in host_events
                 if hs <= mid <= he]
        idle.append([min(cover)[1] if cover else "no host span",
                     (e - s) / 1e9])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": idle}
