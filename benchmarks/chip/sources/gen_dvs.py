"""Moving-edge DVS event traces (copy of the program's
``repro.data.dvs.dvs_moving_edges`` and ``events_to_frames``, kept here so
that the benchmark's traffic does not move when the program's does;
``tests/test_bench_generators.py`` holds the two equal).

An oriented band of ``band`` pixels sweeps the field of view over
``t_bins`` bins in one of ``classes`` directions: pixels it newly covers
emit ON events (polarity 1), pixels it uncovers OFF events (polarity 0),
plus a uniform noise floor of ``noise_rate`` events per pixel and bin.
Each trace is an (N_i, 4) int32 array of (t, y, x, polarity) rows in
shuffled order, as a sensor's arbiter emits them.
"""
from __future__ import annotations

import numpy as np

_DIRECTIONS = [(0, 1), (0, -1), (1, 0), (-1, 0),
               (1, 1), (-1, -1), (1, -1), (-1, 1)]


def dvs_moving_edges(n, t_bins, hw=(28, 28), *, classes=4, band=2,
                     noise_rate=0.01, seed=0):
    """``n`` traces and their direction labels: ``(traces, labels)``."""
    if not 1 <= classes <= len(_DIRECTIONS):
        raise ValueError(f"classes must be in [1, {len(_DIRECTIONS)}]")
    h, w = hw
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    labels = rng.integers(0, classes, size=n).astype(np.int32)
    traces = []
    for i in range(n):
        dy, dx = _DIRECTIONS[int(labels[i])]
        proj = dy * yy + dx * xx
        lo, hi = int(proj.min()), int(proj.max())
        speed = (hi - lo + band) / max(t_bins - 1, 1)
        speed *= rng.uniform(0.85, 1.15)
        start = lo - band + rng.uniform(-1.0, 1.0)
        rows = []
        prev = np.zeros((h, w), bool)
        for t in range(t_bins):
            front = start + speed * t
            cover = (proj >= front - band) & (proj < front)
            on = cover & ~prev
            off = prev & ~cover
            prev = cover
            for pol, mask in ((1, on), (0, off)):
                ys, xs = np.nonzero(mask)
                if ys.size:
                    rows.append(np.stack(
                        [np.full(ys.size, t), ys, xs,
                         np.full(ys.size, pol)], axis=-1))
            n_noise = rng.poisson(noise_rate * h * w)
            if n_noise:
                rows.append(np.stack(
                    [np.full(n_noise, t),
                     rng.integers(0, h, n_noise),
                     rng.integers(0, w, n_noise),
                     rng.integers(0, 2, n_noise)], axis=-1))
        ev = (np.concatenate(rows, axis=0) if rows
              else np.zeros((0, 4), np.int32)).astype(np.int32)
        rng.shuffle(ev, axis=0)
        traces.append(ev)
    return traces, labels


def events_to_frames(events, t_bins, hw, channels=2):
    """Bin events into dense (T, H, W, C) bool frames; events outside the
    window drop and duplicates merge."""
    h, w = hw
    ev = np.asarray(events, dtype=np.int64).reshape(-1, 4)
    frames = np.zeros((t_bins, h, w, channels), bool)
    if ev.size:
        t, y, x, p = ev.T
        ok = ((t >= 0) & (t < t_bins) & (y >= 0) & (y < h)
              & (x >= 0) & (x < w) & (p >= 0) & (p < channels))
        frames[t[ok], y[ok], x[ok], p[ok]] = True
    return frames
