"""Plain reference of the m-TTFS convolutional SNN family, and its weights.

Written from the paper (Sommer et al., TCAD 2022, Sec. III and VII) in
straightforward ``jax.numpy``; it imports nothing of the program.  A
configuration file (``configs/<name>.json``) describes the network:
``layers`` is a list of ``{"conv": C, "kernel": k, "pool": p?}`` and
``{"fc": K}`` entries, ``t_steps`` the number of algorithmic time steps,
``v_t`` the firing threshold.

* Conv layer: per step ``V += conv_SAME(x_t, w) + b`` in float32 at
  ``highest`` matmul precision; a neuron spikes when ``V > v_t`` and, by
  the m-TTFS code, on every later step too; an optional OR max-pool over
  non-overlapping ``p x p`` windows (the map padded up to a multiple).
* Head: the last layer's spikes summed over T, contracted with the FC
  weights in float64 on the host, plus ``T x bias``; never thresholded.

Weights are made here too, from the seed, on the device in one jitted
call: He-normal convolutions and a normal FC head (biases 0), then the
paper's conversion step, data-based normalisation, which rescales layer
``l`` by ``lambda_{l-1} / lambda_l`` with ``lambda_l`` the 99.9th
percentile of its clamped-ReLU activations on calibration inputs.

``precision="high"`` is the control: the same computation as the
``Precision.HIGH`` (three bf16 passes) contraction gives it.  With 0/1
spike operands the pass over the spikes' low half is zero, so it equals
carrying each weight as the sum of its two leading bf16 parts.
"""
from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def fc_index(cfg: dict) -> int:
    (idx,) = [i for i, lay in enumerate(cfg["layers"]) if "fc" in lay]
    return idx


def pooled_hw(hw, pool):
    return hw if not pool else (-(-hw[0] // pool), -(-hw[1] // pool))


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def _or_pool(s, p):
    """OR max-pool of (..., H, W, C) bool maps over p x p windows."""
    *lead, h, w, c = s.shape
    s = jnp.pad(s, [(0, 0)] * len(lead) + [(0, -h % p), (0, -w % p), (0, 0)])
    hh, ww = s.shape[-3:-1]
    s = s.reshape(*lead, hh // p, p, ww // p, p, c)
    return jnp.any(s, axis=(-4, -2))


def _max_pool(x, p):
    pads = [(0, 0), (0, -x.shape[1] % p), (0, -x.shape[2] % p), (0, 0)]
    x = jnp.pad(x, pads, constant_values=-jnp.inf)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, p, p, 1),
                                 (1, p, p, 1), "VALID")


def encode_mttfs(images, t_steps: int):
    """(N, H, W, C) floats in [0, 1] -> (N, T, H, W, C) bool m-TTFS input
    spikes (paper Sec. VII): T-1 thresholds evenly inside (0, 1), applied
    from the highest down, the last step again at the lowest, so a pixel
    spikes from the step its value first exceeds a threshold onwards."""
    thr = jnp.linspace(0.0, 1.0, t_steps + 1)[1:-1]
    order = jnp.concatenate([thr[::-1], thr[:1]])
    return images[:, None] > order.reshape((1, t_steps, 1, 1, 1))


def _init(key, cfg: dict, c_in: int) -> dict:
    params, hw = {}, tuple(cfg["input_hw"])
    for idx, lay in enumerate(cfg["layers"]):
        k = jax.random.fold_in(key, idx)
        if "conv" in lay:
            kk, c = lay.get("kernel", 3), lay["conv"]
            fan_in = kk * kk * c_in
            params[f"conv{idx}"] = {
                "w": jax.random.normal(k, (kk, kk, c_in, c), jnp.float32)
                * (2.0 / fan_in) ** 0.5,
                "b": jnp.zeros((c,), jnp.float32)}
            hw, c_in = pooled_hw(hw, lay.get("pool")), c
        else:
            d = hw[0] * hw[1] * c_in
            params[f"fc{idx}"] = {
                "w": jax.random.normal(k, (d, lay["fc"]), jnp.float32)
                * (1.0 / d) ** 0.5,
                "b": jnp.zeros((lay["fc"],), jnp.float32)}
    return params


def _normalize(params: dict, calib, cfg: dict) -> dict:
    """Data-based threshold balancing on the clamped-ReLU ANN."""
    pct = cfg["conversion"]["percentile"]
    out, x, prev = dict(params), calib, 1.0
    for idx, lay in enumerate(cfg["layers"]):
        if "conv" not in lay:
            continue
        p = params[f"conv{idx}"]
        x = jnp.clip(_conv(x, p["w"]) + p["b"], 0.0, cfg["relu_clamp"])
        lam = jnp.maximum(jnp.percentile(x, pct), 1e-6)
        # the ANN forward goes on with the raw layer, as the conversion
        # reads each layer's own activations
        out[f"conv{idx}"] = {"w": p["w"] * (prev / lam), "b": p["b"] / lam}
        prev = lam
        if lay.get("pool"):
            x = _max_pool(x, lay["pool"])
    return out


@partial(jax.jit, static_argnums=(2, 3))
def _make_params(key, calib, cfg_json, c_in):
    cfg = json.loads(cfg_json)
    return _normalize(_init(key, cfg, c_in), calib, cfg)


def make_params(key, calib, cfg: dict, c_in: int) -> dict:
    """Seeded, converted float32 weights in one jitted device call.
    ``calib``: (N, H, W, c_in) float ANN inputs in [0, 1]."""
    return _make_params(key, jnp.asarray(calib, jnp.float32),
                        json.dumps(cfg, sort_keys=True), c_in)


def _split_high(w):
    hi = w.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (w - hi).astype(jnp.bfloat16).astype(jnp.float32)


@partial(jax.jit, static_argnums=(2, 3))
def _forward(params, spikes, cfg_json, precision):
    """(B, T, H, W, C) bool -> (drive (B, D) int32, active share of each
    conv layer's input per row (B, n_conv))."""
    cfg = json.loads(cfg_json)
    x = spikes
    active = []
    for idx, lay in enumerate(cfg["layers"]):
        if "conv" not in lay:
            continue
        p = params[f"conv{idx}"]
        w = p["w"] if precision == "highest" else _split_high(p["w"])
        b = p["b"] if precision == "highest" else _split_high(p["b"])
        active.append(jnp.mean(x.astype(jnp.float32), axis=(1, 2, 3, 4)))
        bsz, _, h, wd, _ = x.shape

        def step(carry, x_t, w=w, b=b):
            vm, fired = carry
            vm = vm + _conv(x_t.astype(jnp.float32), w) + b
            s = (vm > cfg["v_t"]) | fired
            return (vm, s), s

        c = lay["conv"]
        init = (jnp.zeros((bsz, h, wd, c), jnp.float32),
                jnp.zeros((bsz, h, wd, c), jnp.bool_))
        _, s = jax.lax.scan(step, init, jnp.swapaxes(x, 0, 1))
        s = jnp.swapaxes(s, 0, 1)
        x = _or_pool(s, lay["pool"]) if lay.get("pool") else s
    drive = x.reshape(x.shape[0], x.shape[1], -1).sum(axis=1, dtype=jnp.int32)
    return drive, jnp.stack(active, axis=1)


def reference_logits(params, spikes, cfg: dict, *, precision="highest",
                     block: int = 256):
    """Logits (N, K) float64 and the active share of each conv layer's
    input (n_conv,), over ``spikes`` (N, T, H, W, C) bool, in blocks of
    ``block`` rows (the last padded) so that it fits beside anything."""
    cfg_json = json.dumps(cfg, sort_keys=True)
    n = spikes.shape[0]
    drives, act = [], []
    for s in range(0, n, block):
        blk = np.asarray(spikes[s:s + block])
        rows = blk.shape[0]
        if rows < block:
            blk = np.concatenate(
                [blk, np.zeros((block - rows,) + blk.shape[1:], bool)])
        d, a = _forward(params, jnp.asarray(blk), cfg_json, precision)
        drives.append(np.asarray(d)[:rows])
        act.append(np.asarray(a)[:rows])
    drive = np.concatenate(drives).astype(np.float64)
    fc = params[f"fc{fc_index(cfg)}"]
    w, b = np.asarray(fc["w"]), np.asarray(fc["b"])
    if precision != "highest":
        w = np.asarray(_split_high(jnp.asarray(w)))
        b = np.asarray(_split_high(jnp.asarray(b)))
    logits = (drive @ w.astype(np.float64)
              + cfg["t_steps"] * b.astype(np.float64))
    return logits, np.concatenate(act).mean(axis=0)
