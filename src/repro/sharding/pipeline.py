"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

Completes the parallelism matrix (DP/FSDP + TP + EP + SP + **PP**): layer
stages are placed along a mesh axis (canonically the "pod" axis of the
2x16x16 production mesh — inter-pod links are the slowest, and PP's
point-to-point `collective_permute` is the cheapest traffic to put
there), activations flow stage-to-stage with `ppermute`, and microbatches
keep every stage busy except the (n_stages - 1)-bubble.

Implementation: the classic shard_map round-robin schedule. With S stages
and M microbatches, the loop runs S+M-1 ticks; at tick t, stage s
processes microbatch t-s. All stages execute the same program on their
own parameter shard — stage placement is just the leading (stacked)
parameter axis sharded over the pipeline mesh axis.

The bubble fraction (S-1)/(S+M-1) and per-tick wire |activation| are the
napkin numbers recorded in EXPERIMENTS.md; correctness is tested against
the unpipelined stack on a forced multi-device host.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(stage_fn: Callable, stacked_params, x: jax.Array, *,
                   mesh: Mesh, axis: str, n_microbatches: int) -> jax.Array:
    """Run ``n_stages`` stacked stages over ``x`` with microbatch pipelining.

    stage_fn(params_slice, x_mb) -> x_mb     (one stage, one microbatch)
    stacked_params: pytree with leading dim n_stages == mesh.shape[axis],
        sharded (axis, ...) — each device holds its own stage's weights.
    x: (B, ...) global batch; B % n_microbatches == 0.

    Returns stage_{S-1}(...stage_0(x)) for the whole batch.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches

    def body(params_slice, x_all):
        # params_slice: this stage's weights (leading dim 1) ; x_all: full
        # batch, replicated along the pipeline axis (it is sharded on the
        # OTHER axes by the caller's in_specs).
        params_slice = jax.tree.map(lambda t: t[0], params_slice)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_stages + n_microbatches - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        x_mbs = x_all.reshape(n_microbatches, mb, *x_all.shape[1:])
        out_mbs = jnp.zeros_like(x_mbs)
        carry = jnp.zeros((mb, *x_all.shape[1:]), x_all.dtype)

        def tick(t, state):
            carry, out_mbs = state
            # stage 0 ingests microbatch t (if still in range)
            feed_idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = x_mbs[feed_idx]
            cur = jnp.where(stage == 0, inject, carry)
            valid = (t - stage >= 0) & (t - stage < n_microbatches)
            y = stage_fn(params_slice, cur)
            y = jnp.where(valid, y, carry)
            # the last stage banks its finished microbatch t - (S-1)
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            bank = (stage == n_stages - 1) & (t - (n_stages - 1) >= 0)
            out_mbs = jax.lax.cond(
                bank,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (done_idx,) + (0,) * y.ndim),
                lambda o: o, out_mbs)
            # hand activations to the next stage
            carry = jax.lax.ppermute(y, axis, perm)
            return carry, out_mbs

        _, out_mbs = jax.lax.fori_loop(0, n_ticks, tick, (carry, out_mbs))
        # finished microbatches live on the last stage: broadcast them back
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out_mbs, jnp.zeros_like(out_mbs)),
            axis)
        return out.reshape(b, *x_all.shape[1:])

    in_specs = (P(axis), P())
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(stacked_params, x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead — the napkin number for stage/microbatch sizing."""
    return (n_stages - 1) / (n_stages + n_microbatches - 1)
