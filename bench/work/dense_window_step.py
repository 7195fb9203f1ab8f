"""Bytes and operations of one mini-batch step over one contiguous window of
dense rows (``sampling="sliced"``), from shapes.

``least``: what the algorithm needs, as ``dense_masked_step``'s: each of the
``round(fraction * rows)`` sampled rows read once, two matvecs over them.

``as_laid_out``: what the program's layout moves today: the WINDOW is read
twice (X[o:o+m] @ w, then coeff @ X[o:o+m]), its labels once, and the
coefficients are written and read between the two."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    item = np.dtype(_np_name(config)).itemsize
    batch = max(1, round(float(config["mini_batch_fraction"]) * rows))
    return {
        "least": {"bytes": batch * d * item + batch * 4,
                  "flops": 4 * batch * d},
        "as_laid_out": {"bytes": 2 * batch * d * item + 3 * batch * 4,
                        "flops": 4 * batch * d},
        "flops_peak": "bf16_flops_per_s",
    }
