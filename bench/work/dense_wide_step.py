"""Bytes and operations of one full-batch step of a linear model (a VECTOR of
weights) over dense rows too wide for the weights to lie along the lanes
beside a block of rows (RCV1 densified: 47,236 features), from shapes.

``least``: what the algorithm needs, as ``dense_masked_step`` reckons it:
every row read once with its label (margin, coefficient and its term of the
gradient can all be formed while the row is on the chip), two matvecs over
those rows.

``as_laid_out``: what the path that runs moves.  The one-read kernel in its
wide form (``ops/pallas_kernels.fused_wide_sums``) reads ``X.T`` once and the
labels as float32, and once a step the weights as ``WEIGHT_ROWS`` rows in X's
type (an f32 vector in three bf16 parts, padded to a packed register); it
writes the gradient's ``WEIGHT_ROWS`` rows in float32.  Both products run on
the matrix unit with that many rows, which is what it is issued.

A step at a mini-batch fraction under 1.0 would mask all of X; this
configuration's is 1.0."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401

#: rows the kernel's products are issued with: the weight vector's parts
#: padded to a packed bf16 register's sublanes (``ops/pallas_kernels.py``)
WEIGHT_ROWS = 16


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    item = np.dtype(_np_name(config)).itemsize
    batch = max(1, round(float(config["mini_batch_fraction"]) * rows))
    return {
        "least": {"bytes": batch * d * item + batch * 4,
                  "flops": 4 * batch * d},
        "as_laid_out": {"bytes": rows * d * item + rows * 4
                        + WEIGHT_ROWS * d * (item + 4),
                        "flops": 4 * rows * d * WEIGHT_ROWS},
        "flops_peak": "bf16_flops_per_s",
    }
