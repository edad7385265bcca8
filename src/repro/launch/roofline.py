"""Roofline-term extraction from compiled dry-run artifacts (DESIGN.md Sec. 6).

Per (arch x shape x mesh) cell:
  compute term    = per-device HLO FLOPs / peak_FLOPs_per_chip
  memory term     = per-device HLO bytes  / HBM bandwidth per chip
  collective term = per-device wire bytes / (links_per_chip * link BW)

FLOPs/bytes come from ``compiled.cost_analysis()`` (XLA reports the
post-SPMD, per-partition module — i.e. already per-device; we cross-check
against MODEL_FLOPS/chips napkin math and report the ratio).
Collective bytes are NOT in cost_analysis: we parse the post-partitioning
HLO text and sum operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, converted to on-wire
bytes with ring-algorithm multipliers (all-reduce 2(N-1)/N, all-gather
(N-1)/N of the output, etc.).

Hardware constants come from ``PEAKS``, keyed by the ``device_kind`` jax
reports; a kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    bf16_flops: float   # FLOP/s, bf16 matmul
    hbm_bw: float       # bytes/s, HBM
    ici_bw: float       # bytes/s, all chip-to-chip links together


V5E = "TPU v5 lite"     # jax's device_kind of a TPU v5e chip

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # int8), 819 GB/s HBM, 1,600 Gbit/s ICI
    V5E: DevicePeaks(bf16_flops=197e12, hbm_bw=819e9, ici_bw=200e9),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; raises for a kind with no table entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak for {device_kind!r}: known kinds are "
                         f"{sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'f32[16,128]' -> bytes; tuples handled by summing every match."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, n_devices: int) -> int:
    """Participants per replica group of a collective op line."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)  # iota form
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float                 # per-device on-wire bytes (ring algs)
    payload_bytes: float              # raw operand bytes (no multipliers)
    counts: dict                      # op kind -> #ops
    by_kind: dict                     # op kind -> wire bytes

    def to_dict(self):
        return dataclasses.asdict(self)


def collective_bytes(hlo_text: str, n_devices: int) -> CollectiveStats:
    """Sum on-wire bytes of all collectives in a (partitioned) HLO module."""
    counts: dict[str, int] = {}
    by_kind: dict[str, float] = {}
    wire = payload = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears left of '=', op kind right: "%x = f32[..] all-reduce(...)"
        m = re.match(r"%?[\w.\-]+ = (.+?) ([a-z\-]+)\(", s)
        if not m:
            continue
        kind = m.group(2)
        if kind not in _COLLECTIVES:
            # async forms ("all-reduce-start"); "-done" carries no new data
            base = kind[: -len("-start")] if kind.endswith("-start") else None
            if base in _COLLECTIVES:
                kind = base
            else:
                continue
        out_bytes = _shape_bytes(m.group(1))
        n = max(_group_size(s, n_devices), 1)
        if n == 1:
            continue  # degenerate groups move no data
        frac = (n - 1) / n
        if kind == "all-reduce":
            w = 2.0 * out_bytes * frac          # reduce-scatter + all-gather
        elif kind == "all-gather":
            w = out_bytes * frac                # output is the gathered buffer
        elif kind == "reduce-scatter":
            w = out_bytes * (n - 1)             # output is the scattered shard
        elif kind == "all-to-all":
            w = out_bytes * frac
        else:  # collective-permute
            w = out_bytes
        counts[kind] = counts.get(kind, 0) + 1
        by_kind[kind] = by_kind.get(kind, 0.0) + w
        wire += w
        payload += out_bytes
    return CollectiveStats(wire, payload, counts, by_kind)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    n_devices: int
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: Optional[float] = None        # 6ND napkin (global)
    useful_flops_ratio: Optional[float] = None  # model / (hlo * devices)
    collectives: Optional[dict] = None
    memory_analysis: Optional[str] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, n_devices: int, *, device_kind: str,
            model_flops: Optional[float] = None,
            hlo_text: Optional[str] = None) -> Roofline:
    """Roofline terms from the compiled executable, against the peaks of
    ``device_kind``: the chip the compiled mesh stands for, which the
    caller names (a dry-run compiles on the host for a target it models).

    FLOPs/bytes/collectives come from launch/hlo_cost.py (HLO walk with
    while-trip-count multipliers) because XLA's HloCostAnalysis counts
    scan bodies once — a 22x undercount on our layer-scanned models.
    ``xla_cost_analysis_*`` fields keep the raw XLA numbers as the
    cross-check column.
    """
    from .hlo_cost import HloCostModel

    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = HloCostModel(text, n_devices).entry_cost()
    flops, byts = cost.flops, cost.bytes
    peaks = device_peaks(device_kind)
    t_c = flops / peaks.bf16_flops
    t_m = byts / peaks.hbm_bw
    t_x = cost.wire / peaks.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    try:
        mem = str(compiled.memory_analysis())
    except Exception as e:  # XLA:CPU may not implement it
        mem = f"unavailable on this backend: {e}"
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):
        xla_cost = xla_cost[0]
    ratio = None
    if model_flops:
        ratio = model_flops / max(flops * n_devices, 1.0)
    return Roofline(
        flops_per_device=flops, bytes_per_device=byts,
        wire_bytes_per_device=cost.wire, n_devices=n_devices,
        t_compute=t_c, t_memory=t_m, t_collective=t_x, bottleneck=bottleneck,
        model_flops=model_flops, useful_flops_ratio=ratio,
        collectives={"counts": cost.coll_counts, "wire_by_kind": cost.coll_wire,
                     "xla_flops_unscaled": float(xla_cost.get("flops", 0.0)),
                     "xla_bytes_unscaled": float(xla_cost.get("bytes accessed", 0.0))},
        memory_analysis=mem)


def model_flops_estimate(n_params_active: float, tokens: float, phase: str) -> float:
    """6*N*D for train, 2*N*D for inference forward passes."""
    return (6.0 if phase == "train" else 2.0) * n_params_active * tokens
