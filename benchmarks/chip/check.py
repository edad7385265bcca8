"""Whether what the timed path produced is correct.

Each answer (one request's or one sample's logits) is compared with the
plain reference's on the same input: its error is the largest
``|logit - reference logit|`` over the classes, the reference's head in
float64.  Three numbers are held to limits of their own, which the
workload file states; PERF.md gives the readings they were set from:

* ``logit_err_p90``: the 90th percentile of the answers' errors.  The
  program sums weights in float32 in its own order; the control (the
  reference at ``high`` precision) errs on every answer by some 5e-6 to
  1.2e-5, and so does a fault that reaches more than a tenth of them.
* ``unexplained_share``: the share of answers more than ``FLIP`` from
  the reference on an input whose reference answer stays the same with
  every threshold moved by ``MARGIN`` either way.  A neuron within
  rounding of its threshold may fire one step apart in the program and
  the reference, which moves that answer by about a whole weight (0.05);
  such an input's answer moves with the threshold, and is left out.  On
  every other input the program's sums lie far closer to the
  reference's than ``MARGIN``, so it fires the same spikes and its
  answer is within rounding.  A fault that reaches a single answer, such
  as one slot of the serving table or one row of a batch, shows here.
* ``unanswered``: answers due in the window that never came or came back
  as an error; the limit is 0.
"""
from __future__ import annotations

import sys

import numpy as np

# above the control's largest error (1.7e-5) and the rounding of sound
# answers (under 1e-6); below the move of one flipped spike (about 0.05)
FLIP = 1e-4
# ten times the float32 rounding of a membrane sum of a few hundred
# weights (about 1e-6); each run also logs the share at a tenth of it
MARGIN = 1e-5


def answer_errors(logits, ref_logits) -> np.ndarray:
    return np.max(np.abs(np.asarray(logits, np.float64) - ref_logits),
                  axis=1)


def threshold_robust(reference, params, spikes, cfg: dict, ref_logits,
                     margin: float = MARGIN) -> np.ndarray:
    """(N,) bool: the reference's answer to each input is the same, bit
    for bit, with the threshold ``v_t`` moved by ``-margin`` and by
    ``+margin``: no neuron that the answer depends on comes within
    ``margin`` of its threshold."""
    same = np.ones(len(ref_logits), bool)
    for d in (-margin, margin):
        moved, _ = reference.reference_logits(
            params, spikes, dict(cfg, v_t=cfg["v_t"] + d))
        same &= np.all(moved == ref_logits, axis=1)
    return same


def numbers(errors, robust, unanswered: int) -> dict:
    """``robust``: per answer, whether its input is threshold-robust."""
    if not len(errors):
        return {"logit_err_p90": float("inf"), "unexplained_share": 1.0,
                "unanswered": int(unanswered)}
    return {"logit_err_p90": float(np.percentile(errors, 90)),
            "unexplained_share": float(np.mean((errors > FLIP) & robust)),
            "unanswered": int(unanswered)}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a number is within its
    limit when it does not exceed it."""
    table = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    return all(v <= limits[k] for k, v in nums.items()), table


def report(table: dict) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for k, row in table.items():
        print(f"check {k}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
