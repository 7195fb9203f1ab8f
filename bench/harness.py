"""One run of one cell: the data from the seed (the harness's own work, timed
apart as ``data_s``), the program's set-up (the entry point's object built
once and the first fit: ``setup_s``, with the program's import), the window
of back-to-back fits, and — outside all three — the plain reference's fit
that decides ``correct``.

``run_cell`` does not look for a chip: ``bench/run.py`` does, before it calls
this.  The tests call it tiny on the CPU."""

import glob
import os
import shutil
import time

import numpy as np

from bench import correct, trace as trace_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts compile requests and persistent-cache misses of this process
    through ``jax.monitoring``; installed once, never removed (the process
    ends with the run)."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        self.requests += event == COMPILE_EVENT

    def _event(self, event, **kw):
        self.misses += event == CACHE_MISS_EVENT


def data_seed_of(seed: int) -> int:
    """A seed under 2**31 for the generator from any whole ``--seed``.  The
    SAMPLING seed is the configuration's (``sampling_seed``, MLlib's 42): the
    program closes over it, so it is part of the compiled program, and a seed
    that changed with the run would compile the fit anew in every run."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def _peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def place(cell, X, y):
    """The job's placement of the generator's arrays."""
    if cell.job["placement"] == "device":
        return X, y
    if cell.job["placement"] != "host":
        raise ValueError(f"bench/jobs/{cell.job['name']}.json: placement "
                         f"{cell.job['placement']!r} is not device or host")
    host = np.asarray(X), np.asarray(y)
    for a in (X, y):  # the planner budgets from the memory that is free
        a.delete()
    return host


def _timed(fit):
    """``((weights, loss history), seconds)`` of one fit; the weights come to
    the host after the clock has stopped."""
    t = time.perf_counter()
    w, losses = fit()
    seconds = time.perf_counter() - t
    return (np.asarray(w), losses), seconds


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             counter: CompileCounter, peaks=None, trace_dir=None,
             log=print, import_s: float = 0.0) -> dict:
    """Returns the run's record; ``bench/run.py`` makes the last line of it.
    ``t0`` is the ``time.perf_counter()`` the process started at and
    ``import_s`` the seconds ``import tpu_sgd`` took there, JAX already in.

    ``setup_s`` is what the PROGRAM costs a job before its first timed row:
    ``import_s`` plus the stretch from ``place``'s return (the generator's
    arrays are where the job puts them) to the end of the first fit as every
    later run of the checkout takes it.  The interpreter's and the runtime's
    start, the cell's files, the generator and ``place`` are the harness's
    and the machine's: ``data_s`` ends where they do, and ``process_s`` is
    the whole stretch from ``t0`` (what ``setup_s`` read before PR 53)."""
    import jax

    config = cell.config
    iterations = int(config["num_iterations"])
    data_seed, sgd_seed = data_seed_of(seed), int(config["sampling_seed"])
    X, y = place(cell, *cell.generator.make(config, cell.rows, data_seed))
    placed = time.perf_counter()

    # the first fit of the process at the cell's config, compile cache warm
    fit = cell.entry.prepare(config, X, y, sgd_seed)
    prepared = time.perf_counter()
    misses = counter.misses
    first, first_fit_s = _timed(fit)
    cold_fit_s = None
    if counter.misses > misses:
        # this checkout's first run: the fit compiled.  Forget what the
        # process traced, build the object anew and take the first fit
        # again, now as every later run of the checkout takes it.
        cold_fit_s = first_fit_s
        jax.clear_caches()
        fit = cell.entry.prepare(config, X, y, sgd_seed)
        first, first_fit_s = _timed(fit)
    ready = time.perf_counter()
    # warm: import + prepare + the first fit are all of set-up
    prepare_s = prepared - placed if cold_fit_s is None else None

    # the window: that same object, fits back to back
    requests = counter.requests
    fits, fit_s, traced_fits = [first], [], int(cell.job["traced_fits"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(trace_mod.FIT):
            out, took = _timed(fit)
        fits.append(out)
        fit_s.append(took)
        elapsed = time.perf_counter() - start
        if elapsed >= seconds or (trace and len(fits) > traced_fits):
            break
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.requests - requests
    memory_peak = _peak_bytes(jax.devices()[:cell.chips])
    timed = len(fits) - 1
    batch = max(1, round(float(config["mini_batch_fraction"]) * cell.rows))

    # correct: every fit against the plain reference, outside set-up and
    # window (the program's object is dropped first)
    del fit
    t = time.perf_counter()
    w0 = np.zeros((int(config["features"]),), np.float32)
    ref_w, ref_losses = cell.reference.fit(config, X, y, w0, sgd_seed)
    reference_s = time.perf_counter() - t
    failed, worst = correct.judge(fits, ref_w, ref_losses, w0,
                                  config["limits"])
    for name in correct.NUMBERS:
        log(f"check {cell.name} {name} = {worst[name]:.6g} "
            f"(limit {config['limits'][name]:.6g}) over {len(fits)} fits")

    last = fits[-1][1]  # the last fit's loss history
    run = {
        "workload": cell.name, "seed": seed, "rows": cell.rows,
        "iterations": iterations, "batch_rows": batch, "fits": timed,
        "window_s": elapsed, "fit_s": fit_s, "data_s": placed - t0,
        "import_s": import_s, "prepare_s": prepare_s,
        "first_fit_s": first_fit_s,
        "cold_first_fit_s": cold_fit_s,
        "setup_s": import_s + (ready - placed), "process_s": ready - t0,
        "reference_s": reference_s, "compiles_in_window": compiles,
        "memory_peak_bytes": memory_peak, "checks": worst,
        "limits": config["limits"], "attempted": len(fits), "failed": failed,
        "loss_first": float(last[0]) if len(last) else None,
        "loss_last": float(last[-1]) if len(last) else None,
        "rows_per_s": timed * iterations * batch / elapsed,
        "work": cell.work.step_work(config, cell.rows), "peaks": peaks,
    }
    if trace:
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                               f"found {files}")
        run["trace"] = trace_mod.reduce(trace_mod.load(files[0]))
    return run


def metrics_of(cell, run: dict, trace: bool) -> dict:
    """The last line's ``metrics``: the cell's end-to-end metrics from the
    run's record, or its per-layer metrics from their readers; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    if not trace:
        for m in cell.metrics["end_to_end"]:
            out[m["name"]] = {"value": run[m["name"]], "unit": m["unit"]}
        return out
    for m in cell.metrics["per_layer"]:
        value = cell.readers[m["name"]].read(run["trace"], run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
