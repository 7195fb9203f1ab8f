"""Optimizer driver: what the traced window's sweeps BUILT, a sweep: the
``build.restore``, ``build.trace``, ``build.lower`` and ``build.compile``
spans (every restore from the store of exported runners, trace, lowering and
compile-or-cache-read JAX made) under the program's fits that started inside
them, with the short traces a root folds into a count.  A sweep is a tuning
grid's fits, one call of the static entry a point, each a new optimizer: a
program that keeps its step size and regulariser as constants builds every
point (at the least a restore, the restored call's lowering and its cache
read, a call); one that takes them as operands and finds its runner live in
the process builds nothing: 0.

Read from what the program keeps, tracing or not
(``tpu_sgd.obs.build_roots()``, as ``bench/first_fit.py`` reads it): the
roots that started inside one of the window's sweeps.  The program's record
is on ``time.time()`` and the profiler's trace is not, so the sweeps' bounds
are the ENTRY's: ``bench/entries/optimizer_resident_sweep.py`` appends each
sweep's ``(start, end)`` to ``SWEEPS`` here, and the window's are the last
``len(run["fit_s"])`` of them (in a traced run every fit of the window is
traced).  The program keeps its last 32 roots that built (``obs/builds.py``:
``KEPT``): three traced sweeps of eight points fit.  A NUMBER whatever
program ran: 0 where no root started in the window (nothing was built, or the
program keeps no such record); None only where the entry recorded no sweep
(another cell)."""

import importlib

#: ``(start, end)`` on ``time.time()`` of every sweep the entry has run in
#: this process, the newest last
SWEEPS = []


def window_roots(run: dict):
    """``(roots, sweeps)``: the program's kept roots that started inside the
    window's sweeps and how many sweeps those are; None where the entry
    recorded none.  The harness loads a reader from its file under a name of
    its own, so the list is read from the module the entry imported."""
    sweeps = importlib.import_module("bench.layers.sweep_builds").SWEEPS
    sweeps = sweeps[-len(run.get("fit_s") or ()):] if sweeps else []
    if not sweeps:
        return None
    from tpu_sgd import obs

    kept = getattr(obs, "build_roots", None)
    roots = [r for r in (kept() if kept is not None else [])
             if any(lo <= r["start"] < hi for lo, hi in sweeps)]
    return roots, len(sweeps)


def read(trace: dict, run: dict):
    found = window_roots(run)
    if found is None:
        return None
    roots, sweeps = found
    return sum(len(r["spans"]) + r["short_traces"] for r in roots) / sweeps
