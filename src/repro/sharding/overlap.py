"""Manual compute/communication overlap: collective matmuls via shard_map.

XLA inserts all-gathers *before* the matmuls that consume them; on a big
mesh that serializes wire time behind MXU time.  The classic fix
("collective matmul") decomposes the gather into a ring of ``ppermute``
steps, multiplying each arriving shard immediately — wire and MXU time
overlap to ~max(t_comm, t_compute) instead of their sum.

Two schedules:
* ``psum_matmul`` — Megatron row-parallel contraction with the reduction
  explicit (XLA latency-hides the async all-reduce);
* ``ring_weight_gather_matmul`` — FSDP-style: weights sharded over the
  data axis are streamed around a ring and consumed block-by-block, so
  the parameter all-gather of ZeRO-3 overlaps with the matmul itself.

Numerically validated against the unsharded product in
tests/test_distribution.py (multi-device subprocess).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def psum_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, axis: str):
    """Row-parallel TP matmul: y = psum(x_shard @ w_shard).

    x: (B, D) sharded (None, axis); w: (D, F) sharded (axis, None);
    returns y: (B, F) replicated over ``axis``.
    """
    def body(xs, ws):
        return jax.lax.psum(xs @ ws, axis)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None, axis), P(axis, None)),
                         out_specs=P())(x, w)


def ring_weight_gather_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, axis: str):
    """FSDP overlap: y = x @ w with w row-sharded over the *batch* axis.

    x: (B, D) sharded (axis, None) — batch shards (ZeRO data parallelism);
    w: (D, F) sharded (axis, None) — parameter shards (ZeRO-3);
    returns y: (B, F) sharded (axis, None).

    Instead of all-gathering w before the matmul (XLA's default), the ring
    rotates weight blocks; step i multiplies the matching D/n column slice
    of the local x block with the arriving rows.  Per-step wire = |w|/n
    runs concurrently with per-step compute = B·D·F/n² (on TPU, ppermute
    is async — the schedule is the overlap).
    """
    n = mesh.shape[axis]

    def body(x_blk, w_blk):
        idx = jax.lax.axis_index(axis)
        d_blk = w_blk.shape[0]
        perm = [(j, (j + 1) % n) for j in range(n)]

        def step(i, carry):
            acc, wb = carry
            src = (idx - i) % n  # which parameter rows just arrived
            x_cols = jax.lax.dynamic_slice_in_dim(x_blk, src * d_blk, d_blk, axis=1)
            acc = acc + x_cols @ wb
            wb = jax.lax.ppermute(wb, axis, perm)
            return acc, wb

        acc0 = jnp.zeros((x_blk.shape[0], w_blk.shape[1]),
                         jnp.promote_types(x_blk.dtype, w_blk.dtype))
        # mark device-varying for the carry
        acc0 = jax.lax.pcast(acc0, (axis,), to="varying")
        acc, _ = jax.lax.fori_loop(0, n, step, (acc0, w_blk))
        return acc.astype(x_blk.dtype)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis, None), P(axis, None)),
                         out_specs=P(axis, None))(x, w)
