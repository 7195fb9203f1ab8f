"""Bytes and operations of one mini-batch step over dense rows sharded by rows
over ``as_run.data_parallel`` chips, from shapes: ONE chip's share.

``step_ms`` is a per-device mean (``bench/trace.py`` averages the chips' busy
time) and ``bench/peaks.json`` holds one chip's peaks, so the work that
belongs beside them is what one chip does in a step: the whole batch over one
chip's 819 GB/s would read the roofline four times too high.  The chips run
the same program on equal shards, so one share is every chip's.

``least``: that chip's sampled rows (``round(fraction * rows / chips)``) read
once, two matvecs over them, as ``dense_masked_step``'s.

``as_laid_out``: what the program's layout moves today: the Bernoulli mask is
applied to the chip's whole shard, which the one-read kernel reads once, with
its labels; the mask is written by its fusion and read by the kernel.

The all-reduce is left out of both: a step sums ``features + 2`` floats across
the chips (4 KB at 1000 features), seven orders of magnitude under the rows'
bytes, and it moves over the interconnect, not through the memory whose peak
the roofline divides by.  ``psum_ms`` measures it.

``dataset_bytes`` is the WHOLE dataset's, over all the chips: the job's cap is
on that."""

import numpy as np

from bench.work.dense_masked_step import _np_name, dataset_bytes  # noqa: F401


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    item = np.dtype(_np_name(config)).itemsize
    local = rows // int(config["as_run"]["data_parallel"])
    batch = max(1, round(float(config["mini_batch_fraction"]) * local))
    return {
        "least": {"bytes": batch * d * item + batch * 4,
                  "flops": 4 * batch * d},
        "as_laid_out": {"bytes": local * d * item + 3 * local * 4,
                        "flops": 4 * local * d},
        "flops_peak": "bf16_flops_per_s",
    }
