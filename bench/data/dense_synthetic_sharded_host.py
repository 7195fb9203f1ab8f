"""``dense_synthetic_sharded``'s rows as ONE array in HOST memory: each chip's
rows are made on that chip by that generator's own program (shard ``s`` draws
its rows and its noise from the seed's keys folded with ``s``, one ``w_true``
for all), so a seed gives the rows that ``dense_synthetic_sharded`` gives for
it, and fetched from the chips side by side into rows ``[s n/S, (s + 1) n/S)``
of the host array; then the chips' arrays are deleted, and the chips hold
nothing of the dataset when the first fit starts (20 GB of rows have no home
on one chip: a fit brings them from the host).

The host array is Fortran-ordered (what the fetch of a device array that the
chip stores feature-major gives, as ``dense_synthetic_stream``'s), handed
over as ``HostRows``: the harness's ``place`` takes it as it takes a device
array."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.data import dense_synthetic_sharded
from bench.data.dense_synthetic_stream import HostRows

#: the chips' shards are fetched by this many threads, each a few columns of
#: one shard at a time, the shards in turn so that all the chips send at
#: once: the host's part of a fetch is the first touch of fresh pages
#: (``dense_synthetic_stream``), which threads take side by side.  The
#: columns in flight are device arrays beside the shard: 16 x 8 of 2,500,000
#: rows are 0.64 GB over four chips, 0.16 GB a chip, so set-up peaks at
#: 5.2 GB a chip, under what a fit holds (a shard, its labels and 16 blocks
#: in flight: 5.5 GB), and ``memory_peak_bytes`` stays the fit's own
FETCHERS, COLUMNS = 16, 8


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the HOST, the chips empty again."""
    Xd, yd = dense_synthetic_sharded.make(config, rows, seed)
    shards = [s.data for s in Xd.addressable_shards]
    local = rows // len(shards)
    X = np.empty(Xd.shape, Xd.dtype, order="F").view(HostRows)

    def columns(task):
        j, s = task
        X[s * local:(s + 1) * local, j:j + COLUMNS] = np.asarray(
            shards[s][:, j:j + COLUMNS])

    with ThreadPoolExecutor(FETCHERS) as pool:
        list(pool.map(columns, [(j, s) for j in range(0, X.shape[1], COLUMNS)
                                for s in range(len(shards))]))
    y = np.asarray(yd).view(HostRows)
    del shards
    Xd.delete()
    yd.delete()
    return X, y
