"""Bytes and operations of one mini-batch step over dense rows, from shapes.

``least``: what the algorithm needs.  A step at fraction f touches f*n sampled
rows; each has to be read once (margin, coefficient and its gradient term can
all be formed while the row is on the chip).  Two matvecs over those rows.

``as_laid_out``: what the program's layout moves today: the Bernoulli mask is
applied to the whole resident X, which is read twice (X @ w, then
coeff @ X).  This is the "two-read floor" of the old records, kept for
PERF.md; the roofline share uses ``least``, which no program can beat."""

import numpy as np


def dataset_bytes(config: dict, rows: int) -> int:
    return rows * int(config["features"]) * np.dtype(_np_name(config)).itemsize


def _np_name(config: dict) -> str:
    return {"bfloat16": "uint16"}.get(config["x_dtype"], config["x_dtype"])


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    item = np.dtype(_np_name(config)).itemsize
    batch = max(1, round(float(config["mini_batch_fraction"]) * rows))
    return {
        "least": {"bytes": batch * d * item + batch * 4,
                  "flops": 4 * batch * d},
        "as_laid_out": {"bytes": 2 * rows * d * item + 3 * rows * 4,
                        "flops": 4 * rows * d},
        "flops_peak": "bf16_flops_per_s",
    }
