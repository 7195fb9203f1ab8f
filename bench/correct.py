"""The comparison that decides ``correct``: every timed fit against the plain
reference's fit of the same rows, seed and length.

Three numbers, each with a limit of its own from the configuration's file
(``limits``; PERF.md says what each was set from):

``w_rel_gap``     ||w - w_ref|| / ||w_ref - w0||: the weights after the whole
                  fit, which every step's gradient and update feed.  The one
                  a lower precision moves.
``loss_max_gap``  max over steps of |loss - loss_ref| / max(|loss_ref|, 1e-3):
                  every step's loss.  Held against a part of the batch left
                  out or a wrong normalisation.
``dw_norm_gap``   | ||w - w0|| - ||w_ref - w0|| | / ||w_ref - w0||: the norm
                  of the parameters' change.  Held against a fit that returns
                  its state unchanged.

A fit whose history has another length than the configuration's iterations,
or holds a value that is not finite, fails whatever the numbers."""

import numpy as np

NUMBERS = ("w_rel_gap", "loss_max_gap", "dw_norm_gap")


def readings(w, losses, ref_w, ref_losses, w0) -> dict:
    w, ref_w = np.asarray(w, np.float64), np.asarray(ref_w, np.float64)
    losses = np.asarray(losses, np.float64)
    ref_losses = np.asarray(ref_losses, np.float64)
    change = np.linalg.norm(ref_w - w0)
    if (losses.shape != ref_losses.shape or not np.isfinite(losses).all()
            or not np.isfinite(w).all() or change == 0.0):
        return {name: float("inf") for name in NUMBERS}
    return {
        "w_rel_gap": float(np.linalg.norm(w - ref_w) / change),
        "loss_max_gap": float(np.max(
            np.abs(losses - ref_losses) / np.maximum(np.abs(ref_losses),
                                                     1e-3))),
        "dw_norm_gap": float(abs(np.linalg.norm(w - w0) - change) / change),
    }


def judge(fits, ref_w, ref_losses, w0, limits: dict):
    """``(failed, worst)``: how many of ``fits`` (``(w, losses)`` each) pass
    a limit, and the largest reading of each number."""
    missing = [n for n in NUMBERS if n not in limits]
    if missing:
        raise KeyError(f"the configuration's limits lack {missing}")
    failed, worst = 0, {name: 0.0 for name in NUMBERS}
    for w, losses in fits:
        got = readings(w, losses, ref_w, ref_losses, w0)
        failed += any(not got[n] <= limits[n] for n in NUMBERS)
        worst = {n: max(worst[n], got[n]) for n in NUMBERS}
    return failed, worst
