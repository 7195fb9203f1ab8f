"""Bytes and operations of one iteration of EVERY micro-batch of a pass of a
logistic stream, from shapes: the harness's ``step_ms`` in a stream's cell is
a pass's busy time over the configuration's iterations, so that is what a
share of it is taken of.

``least``: logistic regression has no statistics to iterate on, so every
iteration has to read every REAL row of the pass once, and its label: the
pass's rows as they arrived, whatever array the program keeps them in.  A
program that reads the padding of a row capacity, or a tile past a
micro-batch's last row, reads more than this and its share of the roofline
pays for it.  The operations are the two matvecs an iteration,
``4 x rows x d``.

``as_laid_out``: the same (the one-read kernel over the real rows)."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    item = np.dtype(_np_name(config)).itemsize
    once = rows * d * item + rows * 4
    return {
        "least": {"bytes": once, "flops": 4 * rows * d},
        "as_laid_out": {"bytes": once, "flops": 4 * rows * d},
        "flops_peak": "bf16_flops_per_s",
    }
