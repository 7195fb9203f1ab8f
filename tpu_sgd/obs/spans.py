"""Hierarchical span tracing: the low-overhead production half of the
observability layer.

The repo's timing signals were fragments — ``StepTimer`` wall clocks in
benches, ``wall_time_s`` on iteration events, ad-hoc ``perf_counter``
pairs in drivers.  A **span** unifies them: a named region with a
monotonic start/duration, a thread-local parent (so nested regions form
a tree), and arbitrary host-scalar attributes, emitted as one
``trace_span`` JSONL record through the shared event-log contract
(``tpu_sgd.utils.events.JsonLinesEventLog``; ``obs.report`` turns the
records into per-stage breakdowns, Chrome trace-event JSON, and SLO
verdicts)::

    from tpu_sgd.obs.spans import span, event

    with span("train.superstep", i0=i0, steps=steps):
        ...                       # device dispatch + host replay
    event("reliability.retry", attempt=2, error="FaultInjected")

Cost contract (the failpoints discipline, measured in
``tests/test_obs.py``): DISABLED — the only state a production process
runs in unless an operator opts in — is ONE module-global load and a
falsy branch (for ``span`` also one read of the profiler's session
flag); ``span(...)`` returns a shared no-op singleton, allocates
nothing, and formats nothing.  Enabling (``tpu_sgd.obs.enable``) routes
records to a sink; a raising sink drops the record and never kills the
observed hot path.

Thread-awareness: each thread keeps its own span stack, so the ingest
prefetch worker, the serving flush thread, and the io_callback thread
each nest their own spans correctly instead of parenting onto whatever
the main thread happens to be doing.  The current span's first dotted
segment (``train.superstep`` -> ``train``) is published as the thread's
*subsystem tag*, which ``obs.counters`` uses to attribute patch-counted
dispatches/syncs/transfers to the subsystem that caused them.

Timestamp truth contract (ADVICE.md "Span timestamps are attribution,
not truth"): spans time the HOST region only and must NEVER call
``block_until_ready`` (or any other sync) to "include device time" —
under async dispatch that would turn every traced hot loop back into
lockstep, which is precisely what the resident/superstep drivers exist
to avoid (and what graftlint's host-sync rule + the windows+3 sync pin
in ``tests/test_resident.py`` enforce).  Counts and bytes
(``obs.counters``) are the truth on this harness; span durations
attribute where host wall clock went.

Two consumers, one API.  While a ``jax.profiler`` session is active
(``jax.profiler.start_trace``, the profiler server — the switch is the
profiler's own ``TraceAnnotation.is_enabled()``, which the program can
observe) every span is ALSO entered as a
``jax.profiler.TraceAnnotation(name, **attrs)`` on the entering thread,
so it lands on the host plane of the same ``.xplane.pb`` as the device's
lines.  The same FILE, not the same clock: the host's clock and the
device's differ by a constant of up to ~2 ms that changes from one
profiler session to the next (PERF.md section 3, read off the ledger), so
a consumer compares DURATIONS — a span's on the host's clock, an
operation's or a launch's on the device's — and never a timestamp of one
with a timestamp of the other.  No ``obs.enable`` is needed for the
annotations: with the JSONL gate closed and a session active ``span()``
returns the bare annotation; with the gate open ``_Span`` enters the
annotation too, so both records carry the same name.

``span()`` returns one of three types, and ``live`` says of each whether
it keeps attributes: False on the no-op singleton, True on the
annotation and on ``_Span``.  A caller whose attributes cost something
to work out (``train.run``'s description of the step's kernel, the
hand-off's stall clock) works them out only where ``live`` holds, so
that the disabled path stays one global load and one flag read.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "event", "enable_tracing", "disable_tracing",
           "is_enabled", "current_subsystem"]

logger = logging.getLogger("tpu_sgd.obs")

#: graftlint lock-discipline declaration (tpu_sgd/analysis): EMPTY on
#: purpose, and load-bearing as documentation.  All mutable tracing
#: state is either thread-local (the per-thread span stack and
#: subsystem tag in ``_TL``) or a GIL-atomic single reference
#: (``_SINK``, swapped whole by enable/disable; ``_IDS`` is an atomic
#: ``itertools.count``).  Record serialization is the SINK's problem —
#: ``JsonLinesEventLog`` already lock-serializes its writes.  Adding
#: shared mutable state to this module means adding a lock AND
#: declaring it here.
GRAFTLINT_LOCKS: dict = {}

#: fast-path gate: ``span()``/``event()`` read this ONE module global
#: and return when falsy — the entire disabled-mode cost (the
#: failpoints discipline; measured no-op in tests/test_obs.py)
_ENABLED = False

_SINK = None                  # object with .emit(kind, payload)
_IDS = itertools.count(1)     # process-wide span ids (atomic under GIL)
_TL = threading.local()       # .stack: list of _Span; .tag: str

#: the windowed time-series hooks (``tpu_sgd.obs.timeseries`` installs
#: them): ``_ON_SPAN(name, dur_s, ts, attrs, error)`` fires on every
#: span close, ``_ON_EVENT(name, ts, attrs)`` on every instant event —
#: both GIL-atomic single references swapped whole like ``_SINK``, both
#: pure host work (the zero-added-runtime-events pin holds with the
#: time-series ON), and a raising hook is dropped, never propagated.
_ON_SPAN = None
_ON_EVENT = None


def _stack():
    st = getattr(_TL, "stack", None)
    if st is None:
        st = _TL.stack = []
    return st


def current_subsystem() -> str:
    """The accounting tag of the innermost open span on THIS thread
    (its first dotted name segment), or ``"untagged"`` — how
    ``obs.counters`` attributes patch-counted dispatches/syncs to the
    subsystem whose region caused them."""
    return getattr(_TL, "tag", "untagged")


class _NoopSpan:
    """The disabled-mode singleton: every ``span(...)`` call returns
    THIS object, so the disabled hot path allocates nothing."""

    __slots__ = ()

    #: nothing keeps what ``set`` is given: do not work it out
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()
#: what ``span()`` returns with tracing off: the default of a callee that is
#: handed its caller's span (``_stage_dense``, ``shard_dataset``)
NO_SPAN = _NOOP

#: the second consumer's switch: true while a ``jax.profiler`` session
#: is active in this process (one C++ flag read, ~40-150 ns)
_profiling = TraceAnnotation.is_enabled


class _Annotation(TraceAnnotation):
    """A span with the JSONL gate closed and a profiler session active:
    the profiler's own annotation, plus the span API's ``set``."""

    live = True

    def set(self, **attrs):
        self.set_metadata(**attrs)
        return self


class _Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "ts", "t0",
                 "_annotation")

    live = True

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.span_id = next(_IDS)
        self.parent_id = 0
        self.ts = 0.0
        self.t0 = 0.0
        self._annotation = None

    def set(self, **attrs):
        """Attach host-scalar attributes after entry (e.g. a batch size
        known only mid-region).  NEVER pass device values: formatting
        one forces a device->host sync (graftlint's obs-discipline
        check flags that statically)."""
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self

    def __enter__(self):
        st = _stack()
        self.parent_id = st[-1].span_id if st else 0
        st.append(self)
        _TL.tag = self.name.split(".", 1)[0]
        # epoch ts for cross-record joins (staleness SLOs), monotonic
        # t0 for durations and the Chrome trace timeline
        self.ts = time.time()
        if _profiling():
            self._annotation = TraceAnnotation(self.name, **self.attrs)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _TL.tag = st[-1].name.split(".", 1)[0] if st else "untagged"
        sink = _SINK
        if sink is not None:
            payload = {
                "name": self.name,
                "ts": self.ts,
                "t0_s": self.t0,
                "dur_s": dur,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "thread": threading.current_thread().name,
                "error": (exc_type.__name__
                          if exc_type is not None else None),
            }
            payload.update(self.attrs)
            try:
                sink.emit("trace_span", payload)
            except Exception:  # observability must never kill hot paths
                logger.warning("trace sink raised; span record dropped",
                               exc_info=True)
        hook = _ON_SPAN
        if hook is not None:
            try:
                hook(self.name, dur, self.ts, self.attrs,
                     exc_type.__name__ if exc_type is not None else None)
            except Exception:
                logger.warning("time-series span hook raised; dropped",
                               exc_info=True)
        return False


def span(name: str, **attrs):
    """Open a trace span.  No-op singleton when tracing is disabled and
    no profiler session is active (one global load + branch, one flag
    read); the profiler's annotation when only a session is active;
    otherwise a context manager that emits one ``trace_span`` record on
    exit (and enters the annotation too while a session is active).

    ``attrs`` must be HOST scalars/strings — a device value here forces
    a sync when the record serializes (statically flagged by
    graftlint)."""
    if not _ENABLED:
        return _Annotation(name, **attrs) if _profiling() else _NOOP
    return _Span(name, attrs)


def event(name: str, **attrs) -> None:
    """Emit one instant ``trace_event`` record (a point, not a region):
    retry attempts, breaker transitions, failpoint triggers, reload
    decisions.  Same cost/discipline contract as :func:`span`."""
    if not _ENABLED:
        return
    sink = _SINK
    if sink is None:
        return
    payload = {
        "name": name,
        "ts": time.time(),
        "t0_s": time.perf_counter(),
        "thread": threading.current_thread().name,
        "subsystem": current_subsystem(),
    }
    payload.update(attrs)
    try:
        sink.emit("trace_event", payload)
    except Exception:
        logger.warning("trace sink raised; event record dropped",
                       exc_info=True)
    hook = _ON_EVENT
    if hook is not None:
        try:
            hook(name, payload["ts"], attrs)
        except Exception:
            logger.warning("time-series event hook raised; dropped",
                           exc_info=True)


def enable_tracing(sink) -> None:
    """Route spans/events to ``sink`` (anything with ``emit(kind,
    payload)`` — a ``JsonLinesEventLog``) and open the gate.  Use the
    ``tpu_sgd.obs.enable`` facade unless you are wiring a custom sink."""
    global _SINK, _ENABLED
    _SINK = sink
    _ENABLED = True


def disable_tracing() -> None:
    """Close the gate and drop the sink reference (the caller owns the
    sink's lifecycle — a ``JsonLinesEventLog`` still needs ``close()``)."""
    global _SINK, _ENABLED
    _ENABLED = False
    _SINK = None


def is_enabled() -> bool:
    return _ENABLED
