"""``trace.reduce`` on a small trace recorded on a TPU v5e
(``data/fixture.xplane.pb``, made by ``record_trace.py``: three
``snn_apply_batched`` calls with host sleeps between them), against a
brute-force count on a 1-microsecond grid read straight from the file."""
from __future__ import annotations

from pathlib import Path

import numpy as np

import bench_fixture  # noqa: F401  (puts the repo on sys.path)

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


def _raw():
    from jax.profiler import ProfileData

    from benchmarks.chip import trace
    pd = ProfileData.from_file(str(FIXTURE))
    window, ops = None, {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if m and line.name == trace.OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return window, ops


def test_busy_and_gaps_match_a_grid_count():
    from benchmarks.chip import trace
    red = trace.reduce(str(FIXTURE))
    (w0, w1), ops = _raw()
    assert ops, "the fixture holds device operations"
    assert red["window_s"] == (w1 - w0) / 1e9
    for dev, evs in ops.items():
        grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)
        for _, s, e in evs:
            lo, hi = max(s, w0), min(e, w1)
            if hi > lo:
                grid[int((lo - w0) // 1000):int(-(-(hi - w0) // 1000))] = True
        busy = grid.sum() * 1e-6
        # each interval's two ends round outward by under a microsecond
        assert abs(red["busy_s"][dev] - busy) <= 2e-6 * len(evs) + 1e-6
        assert 0 < red["busy_s"][dev] < red["window_s"]
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= trace.TOP
    first = min(red["busy_s"])
    assert sum(gaps) <= red["window_s"] - red["busy_s"][first] + 1e-9
    # the host sleeps between the calls are the longest gaps, and no
    # operation or host span covers them
    assert gaps[0] >= 0.005 and red["idle_gaps"][0][0] == "no host span"
    op_s = [s for _, s in red["device_ops"]]
    assert op_s == sorted(op_s, reverse=True) and len(op_s) == trace.TOP
    assert all(" = " not in n for n, _ in red["device_ops"])


def test_union_merges_overlaps():
    from benchmarks.chip.trace import _union
    assert _union([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    assert _union([]) == []
