#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the machine this is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child; it never sets ``JAX_PLATFORMS``.  Anything but the
cell's number of TPU devices, or a device kind that ``bench/peaks.json`` does
not list, exits non-zero with no result on stdout (the last line of stderr
says why, as JSON).  The last line of stdout of a run that reached its end is
one JSON object: ``correct``, ``attempted``, ``failed`` (fits), ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` also ``breakdown``; ``run``
holds the rest of the record and is for people.

``setup_s`` is the PROGRAM's set-up, what a job that trains once pays before
its first timed row: ``import_s`` (the seconds of ``import tpu_sgd``, taken
below with JAX already imported) plus the stretch from the moment the
generator's arrays are where the job puts them to the end of the first fit
(``prepare``, the fit; in a checkout's first run also the fit that compiled,
``jax.clear_caches()`` and both again).  The interpreter's and JAX's start,
``jax.devices()``, the cell's files and the harness's generator are not the
program's and are not in it: ``run`` keeps them as ``data_s`` (process start
to the arrays placed) and ``process_s`` (process start to the end of the
first fit: what ``setup_s`` read before PR 53).

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` if it is set (JAX reads it, no
directory is set in code), else the fixed ``<checkout>/.jax_cache``; every
program is kept, however short its compile.  Traces go to
``<checkout>/.bench_trace/<workload>``."""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def fail(why: str, device=None) -> int:
    print(json.dumps({"ok": False, "error": why, "device": device}),
          file=sys.stderr, flush=True)
    return 1


def configure_compile_cache() -> str:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    import jax

    t = time.perf_counter()
    import tpu_sgd  # noqa: F401  (the system under test; absent -> no run)
    import_s = time.perf_counter() - t

    from bench import cells, harness, spans

    cell = cells.Cell(args.workload)
    cache_dir = configure_compile_cache()
    devices = jax.devices()  # read once; a backend that cannot start raises
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) != cell.chips:
        return fail(f"{cell.name} needs {cell.chips} TPU device(s)", device)
    with open(os.path.join(cells.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if device["kind"] not in peaks:
        return fail(f"bench/peaks.json has no device kind {device['kind']!r}",
                    device)

    run = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T0,
        harness.CompileCounter(), peaks=peaks[device["kind"]],
        trace_dir=os.path.join(REPO, ".bench_trace", cell.name),
        import_s=import_s)
    run["compile_cache_dir"] = cache_dir
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    line = {"correct": run["failed"] == 0, "attempted": run["attempted"],
            "failed": run["failed"],
            "metrics": harness.metrics_of(cell, run, bool(args.trace)),
            "device": device}
    if args.trace:
        reduced = run.pop("trace")
        device["busy_s"] = reduced["busy_ns"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        line["breakdown"] = spans.breakdown(reduced, run)
        # the shift that put each chip's lines on the host's clock before a
        # gap was named: the record's, not the driver's
        run["clock"] = line["breakdown"].pop("clock")
        run["traced_fits"] = reduced["fits"]
    line["run"] = run
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
