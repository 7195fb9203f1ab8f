"""What a fit BUILDS: every trace, lowering and compile-or-cache-read that
happens under one of the program's fits, kept as a ``build.*`` span.

A first fit spends 0.1 to 1.7 s above a steady one (PERF.md section 3), all
of it inside ONE span, ``train.dispatch``'s first call of a ``jax.jit``, and
before any profiler session.  JAX reports each piece of it through
``jax.monitoring``, with the function's name and a start and an end on
``time.time()``'s clock; this module listens and keeps:

=================  ==================================================  =====
span               ``jax.monitoring`` event                            attrs
=================  ==================================================  =====
``build.trace``    ``/jax/core/compile/jaxpr_trace_duration``          ``fun``, ``thread``
``build.lower``    ``/jax/core/compile/jaxpr_to_mlir_module_duration`` ``fun``, ``thread``
``build.compile``  ``/jax/core/compile/backend_compile_duration``      ``fun``, ``thread``, ``cache_hit``, ``cache_read_ms``
``build.restore``  none: ``optimize/run_store.py`` tells :func:`restored`  ``fun``, ``thread``, ``hit``, ``ms``, ``reason``
=================  ==================================================  =====

``cache_hit`` is 1 where the persistent cache gave the executable
(``/jax/compilation_cache/cache_hits``), 0 where it was compiled and written
there (``.../cache_misses``: what ``bench/harness.py`` takes a cold first fit
by) and None where no persistent cache took part; ``cache_read_ms`` is the
retrieval (``.../cache_retrieval_time_sec``) where there was one.  A
``build.restore`` is the first call of ``GradientDescent._runner``'s program
in the process, from the store's key to the program in hand: ``hit`` 1 where
the stored export was read back (no trace and no lowering of the package's
code follow, only the restored call's own), 0 where the runner was exported
and stored (its trace and lowering fire inside the span), None with the
``reason`` of a bypass, after which the runner builds as it always did.

A ROOT is the outermost of ``fit.run``, ``train.run`` and ``stream.run`` open
in the process (``root()``, entered beside the span).  The spans that fire
between its open and its close, on ANY thread (a stream's worker, a meshed
hand-off's issuing threads), are its; a root under which nothing was built
leaves nothing, one that built is kept among the last ``KEPT`` that
``tpu_sgd.obs.build_roots()`` returns.  A build outside any root (another
library's jits) is counted (``outside()``) and dropped.  JAX fires a trace
span for every jitted function traced INSIDE another's trace, before the
outer one, and traces the jitted rules it meets inside a LOWERING: a phase's
time is the union of its intervals, never their sum.  Trace spans under
``SHORT_TRACE_S`` are folded into a count and a sum a root (nearly all lie
inside a longer one).

Always on, tracing or not, and what that may cost: the listeners go in at the
first root's entry, not at import; a root's open and close are one read of
the clock and two stores of one module global, no allocation (the handle is a
singleton), no lock; JAX fires nothing without a build, so a steady fit makes
no listener call (pinned in ``tests/test_obs_builds.py``); a fit that builds
pays one tuple appended an event.  With ``obs.enable`` on a sink a root's
spans also go there at its close, as ``trace_span`` records whose
``parent_id`` is the root span's id."""

from __future__ import annotations

import collections
import threading
import time

from tpu_sgd.obs import spans

#: graftlint lock-discipline declaration: EMPTY on purpose, as in
#: ``obs.spans`` (the rule guards classes' attributes).  ``_OPEN`` is a
#: GIL-atomic single reference, ``_BUILT`` and ``_ROOTS`` take GIL-atomic
#: appends (a span is tagged with the root it fired under, so one that loses
#: a race with a close is dropped, not moved to the next root), the cache
#: read's answer is thread-local.  ``_OUTSIDE`` alone is read, added to and
#: written, under ``_LOCK``: taken only where NO root is open (and once to
#: register the listeners), so never by a thread that builds under a fit.
GRAFTLINT_LOCKS: dict = {}

KEPT = 32  # roots that built something, the newest last
SHORT_TRACE_S = 1e-3

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "build.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "build.lower",
    "/jax/core/compile/backend_compile_duration": "build.compile",
}
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

_OPEN = None  # the open root's start on time.time(); None outside any
_BUILT = []  # (root's start, name, start, end, fun, thread, hit, read ms)
_ROOTS = collections.deque(maxlen=KEPT)
_OUTSIDE = 0
_LOCK = threading.Lock()
_LISTENING = False
_TL = threading.local()  # .hit, .read_ms: the compile in progress here


def _on_span(event, start, end, fun_name="", **_):
    kind = _KINDS.get(event)
    if kind is None:
        return
    hit = read_ms = None
    if kind == "build.compile":
        hit, read_ms = getattr(_TL, "hit", None), getattr(_TL, "read_ms", None)
        _TL.hit = _TL.read_ms = None
    root = _OPEN
    if root is None:
        global _OUTSIDE
        with _LOCK:
            _OUTSIDE += 1
        return
    _BUILT.append((root, kind, start, end, fun_name,
                   threading.current_thread().name, hit, read_ms))


def restored(fun: str, hit, reason, start: float, end: float) -> None:
    """The store resolved ``fun`` between ``start`` and ``end`` (on
    ``time.time()``): kept under the open root, dropped outside any."""
    root = _OPEN
    if root is not None:
        _BUILT.append((root, "build.restore", start, end, fun,
                       threading.current_thread().name, hit, reason))


def _on_cache(event, seconds=0.0, **_):
    """The compile in progress on this thread asked the persistent cache."""
    if event == _HIT:
        _TL.hit = 1
    elif event == _MISS:
        _TL.hit = 0
    elif event == _READ:
        _TL.read_ms = seconds * 1e3


def _listen():
    """Once a process, at the first root's entry (two first roots at once
    on two threads register once)."""
    global _LISTENING
    from jax import monitoring

    with _LOCK:
        if _LISTENING:
            return
        monitoring.register_event_time_span_listener(_on_span)
        monitoring.register_event_listener(_on_cache)
        monitoring.register_event_duration_secs_listener(_on_cache)
        _LISTENING = True


class _Root:
    """The outermost root's handle: ONE object, the state is ``_OPEN``."""

    __slots__ = ("name", "span")

    def __enter__(self):
        global _OPEN
        _OPEN = time.time()

    def __exit__(self, *exc):
        global _OPEN
        start, _OPEN = _OPEN, None
        if _BUILT and start is not None:
            _keep(self.name, self.span, start, time.time())
        return False


_ROOT = _Root()


def root(name: str, sp):
    """Beside ``span(name)``, which is ``sp``: the root's handle where no
    root is open in the process, else a no-op (one global load, a branch)."""
    if _OPEN is not None:
        return spans.NO_SPAN
    if not _LISTENING:
        _listen()
    _ROOT.name, _ROOT.span = name, sp
    return _ROOT


def _keep(name, sp, start, end):
    global _BUILT
    built, _BUILT = _BUILT, []
    kept, short = [], []
    for b in built:
        if b[0] != start:
            continue  # fired under another root: it lost a race with a close
        if b[1] == "build.trace" and b[3] - b[2] < SHORT_TRACE_S:
            short.append(b[3] - b[2])
        else:
            kept.append(b[1:])
    if not kept and not short:
        return
    span_id = getattr(sp, "span_id", 0)
    _ROOTS.append((name, start, end - start, span_id, kept, len(short),
                   sum(short)))
    sink = spans._SINK
    if sink is None or not span_id:
        return
    try:
        for b in kept:
            sink.emit("trace_span", dict(
                _attrs(b), ts=b[1], t0_s=b[1] - sp.ts + sp.t0,
                dur_s=b[2] - b[1], span_id=next(spans._IDS),
                parent_id=span_id, error=None))
    except Exception:  # observability must never kill hot paths
        spans.logger.warning("trace sink raised; build spans dropped",
                             exc_info=True)


def _attrs(b) -> dict:
    out = {"name": b[0], "fun": b[3], "thread": b[4]}
    if b[0] == "build.compile":
        out.update(cache_hit=b[5], cache_read_ms=b[6])
    elif b[0] == "build.restore":
        out.update(hit=b[5], ms=(b[2] - b[1]) * 1e3)
        if b[6] is not None:
            out["reason"] = b[6]
    return out


def build_roots() -> list:
    """The last ``KEPT`` roots that built something, the newest last: each a
    dict of ``name``, ``start`` (``time.time()``), ``dur_s``, ``span_id`` (0
    with tracing off), ``spans`` (dicts of ``name``, ``fun``, ``thread``,
    ``start``, ``end``; a ``build.compile`` also ``cache_hit`` and
    ``cache_read_ms``, a ``build.restore`` ``hit``, ``ms`` and on a bypass
    ``reason``) and ``short_traces`` / ``short_trace_s``."""
    return [{"name": name, "start": start, "dur_s": dur_s,
             "span_id": span_id,
             "spans": [dict(_attrs(b), start=b[1], end=b[2]) for b in kept],
             "short_traces": short, "short_trace_s": short_s}
            for name, start, dur_s, span_id, kept, short, short_s
            in list(_ROOTS)]


def outside() -> int:
    """Builds that fired outside any root since the listeners went in."""
    return _OUTSIDE
