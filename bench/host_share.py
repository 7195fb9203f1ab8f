"""The host's share of a traced fit, each reading taken on ONE clock: what
the readers ``fit_head_ms``, ``launch_wake_ms``, ``fit_tail_ms`` and
``fit_unspanned_ms`` under ``bench/layers/`` share.

The profiler's two clocks, the host's and the device's, differ by a constant
of up to ~2 ms that changes with the session (PERF.md section 3), so a time
on one less a time on the other reads that constant, and a device interval
cut at a host's bound books a piece of one fit to its neighbour.  Nothing
here does either.  A fit is cut on the HOST's clock at two of the program's
own marks, the start of ``train.dispatch`` and the end of ``train.fetch``:

    bench.fit |-- head --|------------ call ------------|-- tail --|
                         ^ train.dispatch starts        ^ train.fetch ends

and the one DEVICE time used is a duration: the fit's longest launch on the
``XLA Modules`` line (``sgd_run``), whole, the longest over the chips.
``call`` less that launch is the ``wake``: the runtime's launch and wake-up,
the copy of the loss history, from host the drain of the last blocks —
everything between the call and the answer during which the step's program
does not run.  ``head + wake + launch + tail`` is the fit, by construction.

A launch belongs to the fit whose ``bench.fit`` holds its midpoint, as
``bench/trace.py`` assigns ``programs``: that comparison across the clocks
picks a launch, it is in no reading.  Of a fit's launches the LONGEST is
taken, not the latest: the whole-run program outlasts every block write of
the hand-off (from host 133 launches a fit), so a short launch of the next
fit that the clocks' offset books to this one changes nothing.
``trace["fits"]`` holds clipped times, so the launches are taken whole from
what ``bench/spans.py`` keeps of the run's file (read once a process).

Times in nanoseconds, as in ``bench/trace.py``."""

from bench import spans, trace as trace_mod


def launches(path: str) -> list:
    """Per chip the ``(start_ns, duration_ns)`` of its program launches."""
    return [[(start, dur) for _, start, dur
             in trace_mod._events(plane, trace_mod.MODULES_LINE)]
            for plane in spans._reduced(path)["planes"]
            if trace_mod.DEVICE_PLANE.match(plane["name"])]


def longest_launch_ns(path: str, fit: dict):
    """The duration of the fit's longest launch over the chips; None where
    no chip launched a program in the fit."""
    return max((dur for chip in launches(path) for start, dur in chip
                if fit["start_ns"] <= start + dur / 2 < fit["end_ns"]),
               default=None)


def parts(fit: dict, path: str = None) -> dict:
    """``{"head", "wake", "launch", "tail"}`` of one fit of
    ``bench.spans.reduce``, each None where a mark it needs is absent
    (``train.dispatch``, ``train.fetch``; a launch, read only where the
    run's ``path`` is given)."""
    starts = [s["start_ns"] for s in fit["spans"]
              if s["name"] == "train.dispatch"]
    ends = [s["end_ns"] for s in fit["spans"] if s["name"] == "train.fetch"]
    called, answered = min(starts, default=None), max(ends, default=None)
    launch = None if path is None else longest_launch_ns(path, fit)
    return {
        "head": None if called is None else called - fit["start_ns"],
        "launch": launch,
        "wake": None if None in (called, answered, launch)
        else answered - called - launch,
        "tail": None if answered is None else fit["end_ns"] - answered}


def unspanned_ns(fit: dict):
    """Host time inside the fit, on its thread, under no leaf span of the
    program; None for a fit without a ``train.run``.  The fit's thread is
    known by that span: its leaves are those under the root span that holds
    it (a worker thread's spans have a root of their own)."""
    spans = fit["spans"]

    def root(i):
        while spans[i]["parent"] is not None:
            i = spans[i]["parent"]
        return i

    roots = {root(i) for i, s in enumerate(spans) if s["name"] == "train.run"}
    if not roots:
        return None
    parents = {s["parent"] for s in spans}
    covered = trace_mod._union(
        (s["start_ns"], s["end_ns"]) for i, s in enumerate(spans)
        if i not in parents and root(i) in roots)
    return fit["end_ns"] - fit["start_ns"] - sum(e - s for s, e in covered)


def part_ms(reduced, part: str, path: str = None):
    """Mean over the traced fits of one of ``parts``, in ms; None where the
    run's own trace resolved nothing (``reduced`` is ``bench.spans.of``'s)
    or no fit has the part."""
    if reduced is None:
        return None
    return mean_ms(parts(fit, path)[part] for fit in reduced["fits"])


def mean_ms(values):
    """Mean over the fits that have the reading, in ms; None where none."""
    values = [v for v in values if v is not None]
    return sum(values) / len(values) / 1e6 if values else None
