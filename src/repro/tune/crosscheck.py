"""Roofline cross-check of measured winners against the analytic model.

Every candidate's AOT-compiled HLO is costed through
``launch.hlo_cost.HloCostModel`` (trip-count-exact FLOPs/bytes) and
turned into a roofline time (``max(flops/peak FLOP/s, bytes/HBM BW)``)
with the peaks of the device the tuner measures on
(``launch.roofline.PEAKS``).  A device with no table entry — the CPU
among them — has no model time: the tuner reports "no peak for <kind>"
and ranks by measurement alone.
When the measured winner is not the model's pick — or the measured and
modelled times disagree by more than ``deviation_factor`` both ways —
the tuner logs it on the ``repro.tune`` logger.  The log line is the
design feedback loop: a systematic deviation on some backend means the
analytic VMEM/roofline priors mis-model that backend (exactly the
``planned_per_layer`` 0.89x story that motivated measuring at all), and
the priors should be revisited rather than silently out-voted forever.
"""
from __future__ import annotations

import logging
from typing import Optional

log = logging.getLogger("repro.tune")


_NO_PEAK_LOGGED: set[str] = set()


def model_microseconds(hlo_text: str, device_kind: str) -> Optional[float]:
    """Roofline time of one compiled candidate on ``device_kind``, in
    microseconds.  None when the peak table has no entry for that kind:
    the first such call per kind logs "no peak for <kind>"."""
    from repro.launch.hlo_cost import HloCostModel
    from repro.launch.roofline import PEAKS
    peaks = PEAKS.get(device_kind)
    if peaks is None:
        if device_kind not in _NO_PEAK_LOGGED:
            _NO_PEAK_LOGGED.add(device_kind)
            log.info("tune: no peak for %s; winners are ranked by "
                     "measurement alone, with no roofline cross-check",
                     device_kind)
        return None
    cost = HloCostModel(hlo_text, 1).entry_cost()
    return max(cost.flops / peaks.bf16_flops, cost.bytes / peaks.hbm_bw) * 1e6


def log_deviation(where: str, ranked: list, *,
                  deviation_factor: float = 4.0) -> None:
    """``ranked``: [(label, measured_us, model_us), ...] sorted by
    measured time; element 0 is the winner.  Logs when measurement and
    model disagree on the ranking or on the winner's magnitude; stays
    silent when there is no model time (no peak for the device)."""
    if not ranked or any(r[2] is None for r in ranked):
        return
    label, us, model_us = ranked[0]
    by_model = min(ranked, key=lambda r: r[2])
    if by_model[0] != label:
        log.info(
            "tune[%s]: measured winner %s (%.1f us) != model pick %s "
            "(model %.1f us vs %.1f us) — analytic prior mis-ranks this "
            "backend", where, label, us, by_model[0], model_us, by_model[2])
    if model_us > 0 and not (1 / deviation_factor
                             <= us / model_us <= deviation_factor):
        log.info(
            "tune[%s]: winner %s measured %.1f us vs %.1f us modelled "
            "(x%.2f) — outside the %.0fx roofline envelope for this "
            "device", where, label, us, model_us, us / model_us,
            deviation_factor)
