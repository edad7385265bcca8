"""The general traffic generator: a mix's ``input`` kind and ``params``
in, the requests' payloads out, and the same inputs as the plain
reference and the weights' calibration take them.

Input kinds:

* ``events``: raw DVS traces, (N_i, 4) int32 (t, y, x, polarity) rows,
  from ``sources/gen_dvs.py``; 2 input channels;
* ``images``: digit-like (H, W, 1) float32 images from
  ``sources/gen_digits.py``, m-TTFS encoded inside the timed call.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.sources import gen_digits, gen_dvs

CHANNELS = {"events": 2, "images": 1}


def make(mix: dict, cfg: dict, n: int, seed: int):
    """``n`` payloads of the mix's kind: a list of traces, or an array."""
    hw, t = tuple(cfg["input_hw"]), cfg["t_steps"]
    kind, params = mix["input"], mix.get("params", {})
    if kind == "events":
        traces, _ = gen_dvs.dvs_moving_edges(n, t, hw, seed=seed, **params)
        return traces
    if kind == "images":
        images, _ = gen_digits.synth_digits(n, seed=seed, hw=hw, **params)
        return images
    raise ValueError(f"unknown input kind {kind!r}")


def frames(traces, cfg: dict) -> np.ndarray:
    hw, t = tuple(cfg["input_hw"]), cfg["t_steps"]
    return np.stack([gen_dvs.events_to_frames(tr, t, hw) for tr in traces])


def spikes(kind: str, payload, cfg: dict, reference) -> np.ndarray:
    """(N, T, H, W, C) bool input spikes, as the reference takes them."""
    if kind == "events":
        return frames(payload, cfg)
    return np.asarray(reference.encode_mttfs(np.asarray(payload),
                                             cfg["t_steps"]))


def ann_input(kind: str, payload, cfg: dict) -> np.ndarray:
    """(N, H, W, C) float32 in [0, 1] for the weights' normalisation:
    images as they are; DVS as per-pixel event counts divided by T."""
    if kind == "images":
        return np.asarray(payload, np.float32)
    return frames(payload, cfg).sum(axis=1, dtype=np.float32) / cfg["t_steps"]
