"""Model harness: the first fit's LOWERINGS: the union of the ``build.lower``
intervals of the last root that built something, less what of them is trace
(JAX traces the jitted rules it meets while it lowers; that time is
``first_fit_trace_ms``'s): jaxpr to StableHLO, the Mosaic call's text with it,
once a program.  ``bench/first_fit.py`` says how the root is cut.  On the
host's clock, inside set-up, tracing off.  None on a program without the
record."""

from bench import first_fit


def read(trace: dict, run: dict):
    return first_fit.read("lower_ms", run)
