"""The first fit split from inside the program: what the readers
``first_fit_trace_ms``, ``first_fit_lower_ms``, ``first_fit_cache_ms``,
``first_fit_programs`` and ``first_fit_rest_ms`` under ``bench/layers/``
share.

The program keeps, tracing or not, a record of every fit that BUILT something
(``tpu_sgd.obs.build_roots()``, ``tpu_sgd/obs/builds.py``): the root's start
and duration and a ``build.trace``, ``build.lower`` or ``build.compile`` span,
with its start and end on the host's clock, for every trace, lowering and
compile-or-cache-read JAX made under it, on any thread.  The readers run in
the run's own process after the window and take the LAST such root: in a warm
run the first fit, in a checkout's first run the fit taken again after
``jax.clear_caches()``: the one ``first_fit_s`` times.  No fit of the window
builds (``compiles_in_window`` is 0) and the plain reference is no root.

The root's duration is CUT into four, so that the four add up to it whatever
overlaps: ``trace`` is the union of the ``build.trace`` intervals (JAX fires
one for every jitted function traced inside another's trace); ``lower`` the
union of the ``build.lower`` intervals LESS what of them is trace (JAX traces
the jitted rules it meets while it lowers, the random key's among them);
``cache`` the union of the ``build.compile`` intervals less what of them is
either (nothing on one thread; where a stream's worker builds while the fit's
thread does, an instant is counted once, to the first of trace, lowering and
cache read that holds it); ``rest`` the root less the union of all three:
hand-off, dispatch, run, fetch, to be read against a steady fit.  Every
reading is a duration on the host's clock.

Where the root OUTLIVES the fit the harness timed it is cut at ``first_fit_s``
from its start: the uneven stream's entry runs ONE ``train_on`` for the whole
run (a fit of the harness is a pass of it), so its ``stream.run`` closes when
the run does; the first pass starts where the root does, and everything the
stream builds it builds in that pass.  In every other cell the root is the
fit, some tenths of a millisecond (the streams: ~13 ms) inside ``first_fit_s``.

None where the program keeps no such record (a program from before PR 55)."""

from bench.trace import _union

KINDS = ("build.trace", "build.lower", "build.compile")


def root():
    """The last root that built something, or None."""
    from tpu_sgd import obs

    roots = getattr(obs, "build_roots", None)
    roots = roots() if roots is not None else []
    return roots[-1] if roots else None


def split(root: dict, fit_s: float = None) -> dict:
    """``{trace_ms, lower_ms, cache_ms, rest_ms, programs}`` of one root, cut
    at ``fit_s`` seconds from its start where it is longer."""
    dur_s = root["dur_s"] if fit_s is None else min(root["dur_s"], fit_s)
    end = root["start"] + dur_s
    spans = [s for s in root["spans"] if s["start"] < end]
    covered, out, intervals = 0.0, {}, []
    for kind, name in zip(KINDS, ("trace_ms", "lower_ms", "cache_ms")):
        intervals += [(s["start"], min(s["end"], end)) for s in spans
                      if s["name"] == kind]
        upto = sum(e - s for s, e in _union(intervals))
        out[name], covered = (upto - covered) * 1e3, upto
    out["rest_ms"] = (dur_s - covered) * 1e3
    out["programs"] = sum(s["name"] == KINDS[2] for s in spans)
    return out


def read(name: str, run: dict):
    """One number of the last root's split; None without the record."""
    last = root()
    return None if last is None else split(last, run.get("first_fit_s"))[name]
