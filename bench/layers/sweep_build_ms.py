"""Optimizer driver: the summed length of what the traced window's sweeps
built, a sweep, in ms: every ``build.restore``, ``build.trace``,
``build.lower`` and ``build.compile`` span ``sweep_builds`` counts (the short
traces by the sum their root keeps), each a duration on the host's clock.  A
sum, not a union: a trace inside a lowering is counted in both, as it is in
the count.  0 where nothing was built; None where ``sweep_builds`` is."""

from bench.layers import sweep_builds


def read(trace: dict, run: dict):
    found = sweep_builds.window_roots(run)
    if found is None:
        return None
    roots, sweeps = found
    return 1e3 * sum(
        sum(s["end"] - s["start"] for s in r["spans"]) + r["short_trace_s"]
        for r in roots) / sweeps
