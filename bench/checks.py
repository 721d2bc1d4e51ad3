"""The numbers that decide ``correct``, each against its limit.

A training cell is compared with the configuration's plain reference
(``bench/references``), which starts from the same weights (made by the
benchmark from the seed) and follows the same batches, to the window's
last step:

* ``loss_gap``: the largest relative gap between a side's loss and the
  reference's, over the first three steps and the program's two sides
  (its own reference and the candidate);
* ``loss_gap_first``: the same over the first step alone, where both
  start from the same weights and only rounding parts them;
* ``grad_gap``: the largest gap, over weight tensors, between the norm of
  the candidate's first gradient as the optimizer got it (Adam's first
  moment after one step over ``1 - b1``) and the reference's, relative to
  the reference's norm of that tensor or of the median tensor, whichever
  is larger;
* ``change_gap``: the same as ``grad_gap`` for the change of each weight
  tensor over the three steps, leaving out tensors whose reference
  gradient is under a thousandth of the median tensor's (they move by
  round-off alone);
* ``window_loss_gap``: the largest relative gap between a side's loss and
  the reference's over the window's steps, the reference run on to the
  window's last step;
* ``verdict_events``: flagged checks, watchdog, loud, rescued, lost and
  degraded events in the run (a clean cell has none);
* ``window_compiles``: compilations inside the measured window.
"""
from __future__ import annotations

import numpy as np

FLOOR = 1e-30


def norm_gap(prog: dict, ref: dict, skip=()) -> float:
    """The worst tensor's gap of norms, by the rule above."""
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - r) / max(r, med, FLOOR)
               for k, r in ref.items() if k not in skip)


def negligible(ref_grads: dict, share: float = 1e-3) -> set:
    """Tensors whose reference gradient norm is under ``share`` of the
    median tensor's."""
    med = float(np.median(list(ref_grads.values())))
    return {k for k, g in ref_grads.items() if g < share * med}


def loss_gap(prog_losses, ref_losses) -> float:
    return max(abs(p - r) / max(abs(r), FLOOR)
               for p, r in zip(prog_losses, ref_losses))
