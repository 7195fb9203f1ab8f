"""Model harness: the first fit LESS what it built: the duration of the last
root that built something less the union of ALL its ``build.*`` intervals, on
any thread (``bench/first_fit.py``): hand-off, dispatch, run, fetch.  To be
read against a steady fit (the median of ``run.fit_s``): where the two agree
the first fit's excess is build time and nothing else.  Where a worker builds
while the fit's thread waits or builds too (a stream's ``stream.stage``, a
meshed hand-off's issuing threads) the intervals overlap and are counted once;
``first_fit_trace_ms + first_fit_lower_ms + first_fit_cache_ms +
first_fit_rest_ms`` stays the root's duration.  None on a program without the
record."""

from bench import first_fit


def read(trace: dict, run: dict):
    return first_fit.read("rest_ms", run)
