"""Bytes and operations of one iteration of EVERY micro-batch of a pass of a
least-squares stream, from shapes: the harness's ``step_ms`` in a stream's
cell is a pass's busy time over the configuration's iterations, so that is
what a share of it is taken of.

``least``: a bound that NO exact schedule of least squares beats.  Every row
of the pass has to come through the chip's memory once A PASS, whatever is
made of it there (the stock schedule reads it once an ITERATION; the
statistics schedule reads it once, for a Gram matrix, and iterates on that):
the pass's bytes over its iterations.  The operations are the stock
schedule's two matvecs an iteration, ``4 x rows x d``: the fewest of the
exact schedules (the statistics' ``2 x rows x d^2`` a pass is 10 times that
at d = 1000 and 50 iterations).  A ``least`` of one read an ITERATION would
read over 100% the day the statistics schedule runs.

``as_laid_out``: what the stock schedule moves, which is what the planner
chooses at this size (PERF.md section 4): the one-read kernel reads every
micro-batch's rows and labels once an iteration."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401


def step_work(config: dict, rows: int) -> dict:
    d, iterations = int(config["features"]), int(config["num_iterations"])
    item = np.dtype(_np_name(config)).itemsize
    once = rows * d * item + rows * 4
    return {
        "least": {"bytes": -(-once // iterations), "flops": 4 * rows * d},
        "as_laid_out": {"bytes": once, "flops": 4 * rows * d},
        "flops_peak": "bf16_flops_per_s",
    }
