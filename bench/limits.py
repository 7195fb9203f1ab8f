#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, several seeds in ONE
process (set-up is the long part of a run): for each seed the program's fit
against the reference (the sound reading), and the CONTROL — the reference put
in the program's place with every matmul operand rounded to the
configuration's ``control_operands``, the nearest precision below the one it
states — against the same reference.  Needs the cell's chip like a run.

    python3 bench/limits.py --workload <name> --seeds 11,12,13 [--control 1]

One JSON line a seed; not part of a benchmark run."""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def read_seed(cell, seed: int, control: bool) -> dict:
    import numpy as np

    from bench import correct, harness

    config = cell.config
    data_seed = harness.data_seed_of(seed)
    sgd_seed = int(config["sampling_seed"])
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows,
                                                     data_seed))
    w0 = np.zeros((int(config["features"]),), np.float32)
    t = time.perf_counter()
    w, losses = cell.entry.prepare(config, X, y, sgd_seed)()
    fit_s = time.perf_counter() - t
    w = np.asarray(w)
    t = time.perf_counter()
    ref = cell.reference.fit(config, X, y, w0, sgd_seed)
    out = {"workload": cell.name, "seed": seed, "fit_s": fit_s,
           "reference_s": time.perf_counter() - t,
           "program": correct.readings(w, losses, *ref, w0)}
    if control:
        # the reference at the operand precision the configuration STATES
        # (what a sound program may do), then at the one below it
        for key in ("matmul_operands", "control_operands"):
            low = cell.reference.fit(config, X, y, w0, sgd_seed,
                                     operands=config[key])
            out[key] = {"operands": config[key],
                        **correct.readings(*low, *ref, w0)}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = parser.parse_args(argv)

    import jax

    from bench import cells
    from bench.run import configure_compile_cache

    cell = cells.Cell(args.workload, cells.benchmark(with_prepared=True))
    configure_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU device(s)",
              file=sys.stderr)
        return 1
    for seed in args.seeds.split(","):
        print(json.dumps(read_seed(cell, int(seed), bool(args.control))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
