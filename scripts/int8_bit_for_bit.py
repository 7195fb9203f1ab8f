#!/usr/bin/env python3
"""The check that ties the int8 contract down, on the chip (PR 57; no cell):
at one of CIFAR-5m's parts, 1,000,448 x 3,072, the fit on int8 rows (3.07 GB)
and the fit on THE SAME VALUES as bfloat16 rows (6.15 GB beside them) through
the new cell's own entry, ``GradientDescent(MultinomialLogisticGradient(10),
SquaredL2Updater()).optimize_with_history``, give the same weights and the
same 100 losses BIT FOR BIT: every int8 is exact in bfloat16, both bodies
take bf16 operands, and full blocks are added in lane chunks of 1,024 rows
in order whatever the row tile (int8 2,048, bf16 1,024 at this width).

    chiprun --timeout 900 -- python3 scripts/int8_bit_for_bit.py [--rows N]

One JSON line; exit 1 where a bit differs.  On a CPU (``--rows 4096``) it
holds the two matmuls to the same: a rehearsal of the control flow."""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "cifar5m-int8-multinomial.resident-classes"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=1_000_448)
    parser.add_argument("--seed", type=int, default=57)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, correct
    from tpu_sgd.ops import pallas_kernels as pk

    cell = cells.Cell(CELL)
    config = cell.config
    X, y = cell.generator.make(config, args.rows, args.seed)
    n, d = X.shape
    fits, seconds, tiles = {}, {}, {}
    for name, rows in (("int8", X), ("bfloat16", X.astype(jnp.bfloat16))):
        fit = cell.entry.prepare(config, rows, y, int(config["sampling_seed"]))
        fit()  # compiles, or restores
        t = time.perf_counter()
        fits[name] = fit()
        seconds[name] = time.perf_counter() - t
        record = pk.one_read(n, d, rows.dtype.itemsize, False,
                             pk.class_rows_of(int(config["classes"]) - 1,
                                              rows.dtype))
        tiles[name] = record and {
            "row_tile": record.tile,
            "lane_chunk": pk._fm_lane_chunk(record.tile, record.class_rows)}
    (w8, l8), (wb, lb) = fits["int8"], fits["bfloat16"]
    same = bool(np.array_equal(w8, wb) and np.array_equal(l8, lb))
    w0 = np.zeros((d,), np.float32)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "rows": n, "features": d,
        "bit_for_bit": same, "tiles": tiles, "fit_s": seconds,
        "gaps_int8_against_bf16": correct.readings(w8, l8, wb, lb, w0),
        "a_tenth_of_each_limit": {k: v / 10
                                  for k, v in config["limits"].items()},
        "loss_first": float(l8[0]), "loss_last": float(l8[-1])}))
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
