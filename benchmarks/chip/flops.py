"""Dense-equivalent operations of a convolutional SNN, from its shapes.

The work a frame-based implementation does, whatever implements it: per
time step each conv layer is a SAME convolution over its whole map,
``2 * H * W * k * k * C_in * C_out`` operations, and the head, once per
sample, ``2 * D * K``.  An event-driven path skips most of it; this
count stays the same, so a share of the peak built on it compares
implementations.
"""
from __future__ import annotations


def conv_step_flops(cfg: dict, c_in: int) -> int:
    """Operations of one time step of every conv layer, one sample."""
    hw, total = tuple(cfg["input_hw"]), 0
    for lay in cfg["layers"]:
        if "conv" not in lay:
            continue
        k, c = lay.get("kernel", 3), lay["conv"]
        total += 2 * hw[0] * hw[1] * k * k * c_in * c
        if lay.get("pool"):
            p = lay["pool"]
            hw = (-(-hw[0] // p), -(-hw[1] // p))
        c_in = c
    return total


def head_flops(cfg: dict) -> int:
    hw, c = tuple(cfg["input_hw"]), None
    for lay in cfg["layers"]:
        if "conv" in lay:
            c = lay["conv"]
            if lay.get("pool"):
                p = lay["pool"]
                hw = (-(-hw[0] // p), -(-hw[1] // p))
        else:
            return 2 * hw[0] * hw[1] * c * lay["fc"]
    raise ValueError("configuration has no fc head")


def sample_flops(cfg: dict, c_in: int) -> int:
    """Operations of one whole sample: T steps of the conv stack + head."""
    return cfg["t_steps"] * conv_step_flops(cfg, c_in) + head_flops(cfg)
