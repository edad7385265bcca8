"""BEYOND-PAPER: the AEQ idea applied to transformer FFN activation sparsity.

The paper's core move — compact sparse activations into a fixed-capacity
queue at runtime and do work proportional to the queue, not the tensor —
transfers directly to ReLU-family transformer FFNs, where post-activation
sparsity of 85-95 % is well documented (e.g. "ReLU Strikes Back", "Deja
Vu").  For one token:

    h = relu(x @ W_up)            # (d_ff,) — mostly zeros
    queue = top-k / threshold compaction of h (capacity k)
    y = sum_{i in queue} h_i * W_down[i, :]   # k rows gathered, not d_ff

Compute and W_down traffic scale with the queue capacity — the paper's
"processing time scales with the number of spikes", with the calibrated
capacity playing exactly the role of the AEQ depth (aeq.calibrate_capacity
works unchanged on per-token active counts).

This module is an opt-in replacement for the dense MLP (off by default:
the assigned configs use SiLU/GeGLU and are reproduced faithfully); it is
exercised by tests and the capacity-sweep benchmark, and its exact-match
property (capacity >= true active count => identical output) mirrors the
event-conv bit-exactness property of the paper.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec


def sparse_ffn_specs(d_model: int, d_ff: int) -> dict:
    return {
        "w_up": ParamSpec((d_model, d_ff), ("embed", "mlp"), "scaled"),
        "w_down": ParamSpec((d_ff, d_model), ("mlp", "embed"), "scaled"),
    }


def dense_relu_ffn(p: dict, x: jax.Array) -> jax.Array:
    """Oracle: the plain dense ReLU MLP."""
    return jax.nn.relu(x @ p["w_up"]) @ p["w_down"]


@partial(jax.jit, static_argnames=("capacity",))
def event_ffn(p: dict, x: jax.Array, *, capacity: int) -> jax.Array:
    """Event-driven FFN: per-token compaction of active hidden units.

    x: (..., d_model).  The top-``capacity`` hidden activations per token
    (its AEQ) select rows of W_down; everything below the queue is
    dropped, exactly like events past the queue depth in the paper.
    Output equals dense_relu_ffn whenever capacity >= #active units.
    """
    h = jax.nn.relu(x @ p["w_up"])                       # (..., d_ff)
    vals, idx = jax.lax.top_k(h, capacity)               # the token's AEQ
    rows = p["w_down"][idx]                              # (..., k, d_model)
    return jnp.einsum("...k,...kd->...d", vals, rows)


def active_counts(p: dict, x: jax.Array) -> jax.Array:
    """Per-token active hidden units — feed to aeq.calibrate_capacity."""
    return jnp.sum(jax.nn.relu(x @ p["w_up"]) > 0, axis=-1)


@partial(jax.jit, static_argnames=("capacity",))
def event_readout(drive: jax.Array, weights: jax.Array, *,
                  capacity: int) -> jax.Array:
    """AEQ-compacted classification-unit drive (the CSNN head connection).

    drive: (..., D) accumulated spike counts into the FC readout — mostly
    zeros, because only units under firing output spikes contribute.  The
    top-``capacity`` entries per sample are the head's event queue (the
    same top-k compaction as :func:`event_ffn`); they are scattered back
    into a zero (..., D) operand and the SAME dense contraction as the
    dense head runs on it.  Whenever ``capacity`` covers every nonzero
    entry the operand is value-identical to ``drive``, so the matmul is
    the identical dot_general and the logits are bit-exact vs the dense
    head — the paper's queue-deep-enough exactness property, transferred.
    (A gathered k-row einsum would change the reduction order and lose
    the last float bit; the scatter-back form trades nothing but the
    O(D - k) zero rows the hardware would skip.)
    """
    d = drive.shape[-1]
    if not 1 <= capacity <= d:
        raise ValueError(f"capacity={capacity} must be in [1, D={d}]")
    flat = drive.reshape(-1, d)
    vals, idx = jax.lax.top_k(flat, capacity)        # the head's AEQ
    rows = jnp.arange(flat.shape[0])[:, None]
    compact = jnp.zeros_like(flat).at[rows, idx].set(vals)
    from .scheduler import head_dot
    return head_dot(compact.reshape(drive.shape), weights)


def drive_active_counts(drive: jax.Array) -> jax.Array:
    """Per-sample nonzero drive entries — feed to aeq.calibrate_capacity
    to size :func:`event_readout`'s queue."""
    return jnp.sum(drive != 0, axis=-1)


def event_ffn_flops(d_model: int, d_ff: int, capacity: int) -> tuple[float, float]:
    """(dense flops, event flops) per token — the napkin the paper makes."""
    dense = 2.0 * d_model * d_ff * 2
    event = 2.0 * d_model * d_ff + 2.0 * capacity * d_model
    return dense, event
