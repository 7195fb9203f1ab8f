"""Model harness: the first fit's CACHE READS: the union of the
``build.compile`` intervals of the last root that built something, less what
of them is trace or lowering (nothing, on one thread): warm, the cache's key,
the read of the file and the executable's load, once a program.  A miss never
reaches this metric in a warm run: ``cold_first_fit_s`` is then set and the
root read is the fit taken again (``bench/first_fit.py``).  On the host's
clock, inside set-up, tracing off.  None on a program without the record."""

from bench import first_fit


def read(trace: dict, run: dict):
    return first_fit.read("cache_ms", run)
