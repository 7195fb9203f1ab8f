"""Bytes and operations of one full-batch step of K-class logistic regression
over dense rows, from shapes.

``least``: what the algorithm needs.  Every row is read once (its K - 1
margins, its coefficients and its term of the gradient can all be formed
while the row is on the chip) with its label; two products of the
``(K-1, d)`` weights' shape over those rows: ``2 x rows x d x (K-1)``
operations each.

``as_laid_out``: what the path that runs moves.  The one-read kernel reads
``X.T`` once, the labels as float32, and the weights once a step; it writes
the gradient's and the loss's partials.  Its two products run with the class
rows padded to the 16 sublanes of a packed bf16 register, which is what the
matrix unit is issued.

A step at a mini-batch fraction under 1.0 would mask all of X as
``dense_masked_step`` says; this configuration's is 1.0."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401

#: class rows the kernel's products are issued with: C padded to a packed
#: bf16 register's sublanes (``ops/pallas_kernels.py``)
CLASS_ROWS = 16


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    c = int(config["classes"]) - 1
    item = np.dtype(_np_name(config)).itemsize
    batch = max(1, round(float(config["mini_batch_fraction"]) * rows))
    padded = -(-c // CLASS_ROWS) * CLASS_ROWS
    return {
        "least": {"bytes": batch * d * item + batch * 4,
                  "flops": 4 * batch * d * c},
        "as_laid_out": {"bytes": rows * d * item + rows * 4
                        + 2 * padded * d * 4,
                        "flops": 4 * rows * d * padded},
        "flops_peak": "bf16_flops_per_s",
    }
