"""tpu_sgd.obs: the unified observability layer.

Seven pieces, one opt-in switch (ROADMAP items 1 and 3 both presuppose
this surface: straggler detection for async replicas needs per-stage
timings that run in production, and the closed production loop needs
SLO assertions evaluated over a trace):

* **span tracing** (:mod:`tpu_sgd.obs.spans`) — hierarchical,
  thread-aware ``span("train.superstep")`` regions and instant
  ``event(...)`` records wired through every hot path (ingest prefetch,
  superstep/resident cadence windows, serve batcher flushes, registry
  reloads, checkpoint save/restore, retry/breaker/failpoint incidents),
  emitted as ``trace_*`` JSONL records on the shared
  ``JsonLinesEventLog`` contract;
* **what a fit builds** (:mod:`tpu_sgd.obs.builds`) — the one piece that
  is on without the switch: every trace, lowering and compile-or-cache-read
  JAX makes under a fit, kept in memory as ``build.*`` spans of that fit
  (``build_roots()``: which fit recompiled, which program, what it cost)
  and, with tracing on, written under the fit's root span;
* **runtime counters** (:mod:`tpu_sgd.obs.counters`) — the
  test-twin monkeypatch machinery (``tpu_sgd.analysis.runtime``)
  promoted to an always-on accounting layer: program dispatches,
  compiles, host syncs, h2d/d2h transfer counts and bytes, io_callback
  firings, tagged by the subsystem whose span caused them;
* **windowed time-series** (:mod:`tpu_sgd.obs.timeseries`) — the LIVE
  half: a bounded ring of fixed-width windows over the span / counter /
  event streams (per-window count, sum, max, p50/p99 via the shared
  nearest-rank rule), memory bounded by window count, never run
  length.  On by default whenever the layer is enabled; the
  ``Server.healthz()`` ``windows`` snapshot and the watch CLI read it;
* **anomaly detectors** (:mod:`tpu_sgd.obs.detect`) — declarative
  rules evaluated per window close (loss divergence, staleness creep,
  shed-rate spikes, replica straggler skew, wire-ratio collapse,
  dispatch regression), each trip a typed ``obs_alert`` record on the
  one event stream plus an ``obs.alert.<rule>`` counter;
* **the flight recorder** (:mod:`tpu_sgd.obs.flightrec`) — a bounded
  ring of recent trace records dumped to a standalone
  ``flightrec.jsonl`` on any alert, error unwind, or explicit trigger,
  so post-mortems start from the incident's tail, not the full trace;
* **the report pipeline** (:mod:`tpu_sgd.obs.report`) —
  ``python -m tpu_sgd.obs.report trace.jsonl`` renders per-stage
  breakdowns (``--window`` adds time-bucketed tables), an alerts
  section, Chrome trace-event JSON (Perfetto), and declarative SLO
  files with CI-able exit codes; ``python -m tpu_sgd.obs.watch``
  tails a RUNNING trace live.

Quickstart::

    from tpu_sgd import obs

    obs.enable("run_trace.jsonl")        # tracing + counters + windows
    obs.enable("t.jsonl", detect=True,   # + detectors + flight recorder
               flightrec="flightrec.jsonl")
    ...                                   # train / serve as usual
    obs.disable()                         # flushes windows+counters, closes log
    # then: python -m tpu_sgd.obs.report run_trace.jsonl --slo slo.json
    # live: python -m tpu_sgd.obs.watch run_trace.jsonl

Disabled (the default, forever, unless an operator opts in) every hook
is one module-global load and a falsy branch — the failpoints
discipline, measured in ``tests/test_obs.py``.  Enabled, the layer adds
wall-clock overhead but ZERO dispatches, compiles, or host syncs on the
warmed hot paths (the acceptance pin, re-measured with the time-series
ON; ``BENCH_OBS.json`` records both, and ``scripts/bench_gate.py``
gates the committed headline counts in CI).  Span timestamps never
force a device sync — see ADVICE.md "Span timestamps are attribution,
not truth"; alert semantics — ADVICE.md "Alerts are typed events, not
log lines".
"""

from __future__ import annotations

from typing import Optional

from tpu_sgd.obs import builds
from tpu_sgd.obs import counters
from tpu_sgd.obs import detect
from tpu_sgd.obs import flightrec
from tpu_sgd.obs import spans
from tpu_sgd.obs import timeseries
from tpu_sgd.obs.builds import build_roots
from tpu_sgd.obs.counters import RuntimeCounters, deltas, inc, snapshot
from tpu_sgd.obs.spans import (current_subsystem, disable_tracing,
                               enable_tracing, event, span)
from tpu_sgd.obs.timeseries import observe_scalar

__all__ = [
    "span", "event", "inc", "snapshot", "deltas", "RuntimeCounters",
    "enable", "disable", "flush_counters", "flush_windows", "is_enabled",
    "enable_tracing", "disable_tracing", "current_subsystem",
    "observe_scalar", "windows_snapshot", "detector_engine", "build_roots",
    "spans", "builds", "counters", "timeseries", "detect", "flightrec",
]

#: graftlint lock-discipline declaration (tpu_sgd/analysis): EMPTY on
#: purpose — the facade owns GIL-atomic module references only
#: (``_OWNED_LOG``/``_ENGINE``); all guarded state lives in the
#: submodules.
GRAFTLINT_LOCKS: dict = {}

_OWNED_LOG = None  # a JsonLinesEventLog this facade opened (and closes)
_ENGINE = None     # the live DetectorEngine (when detect was requested)


def enable(trace=None, *, with_counters: bool = True,
           fsync: bool = False, timeseries: bool = True,
           window_s: float = 1.0, max_windows: int = 64,
           detect: bool = False, detectors=None,
           flightrec: Optional[str] = None,
           flightrec_capacity: int = 512) -> None:
    """Turn the observability layer on.

    ``trace`` is a JSONL path (a ``JsonLinesEventLog`` is opened and
    owned — ``disable()`` closes it) or any sink with ``emit(kind,
    payload)`` (e.g. an event log shared with training/serving records,
    the chaos soak's spelling — caller keeps ownership).  ``None``
    enables counters only.  ``with_counters=False`` skips the runtime
    patches (tracing only).

    The windowed time-series ride along by default
    (``timeseries=True``; ``window_s``/``max_windows`` shape the
    bounded ring).  ``detect=True`` (or an explicit ``detectors``
    list) registers the anomaly-detector engine on window closes;
    ``flightrec=<path>`` arms the flight recorder — the trace sink is
    teed through its ring, every detector alert and error-closing span
    triggers a dump there."""
    # the boolean/path kwargs shadow the submodule names by design (the
    # caller-facing spelling is `obs.enable(log, detect=True,
    # flightrec="f.jsonl")`); alias the modules locally
    from tpu_sgd.obs import detect as _detect
    from tpu_sgd.obs import flightrec as _flightrec
    from tpu_sgd.obs import timeseries as _timeseries

    global _OWNED_LOG, _ENGINE
    sink = owned = None
    if trace is not None:
        if hasattr(trace, "emit"):
            sink = trace
        else:
            from tpu_sgd.utils.events import JsonLinesEventLog

            sink = owned = JsonLinesEventLog(str(trace), fsync=fsync)
    want_detect = detect or detectors is not None
    if want_detect and sink is None:
        import warnings

        warnings.warn(
            "obs.enable(detect=True) without a trace sink: the span/"
            "event-fed series (replica.step fanout, push staleness) "
            "never record — straggler and staleness rules cannot fire; "
            "only counter-fed rules (shed-rate, dispatch, wire) work",
            RuntimeWarning, stacklevel=2)
    store = None
    if timeseries or want_detect:  # detectors presuppose windows
        store = _timeseries.enable(width_s=window_s,
                                   max_windows=max_windows)
    rec = None
    if flightrec is not None:
        rec = _flightrec.enable(flightrec,
                                capacity=flightrec_capacity,
                                window_source=_timeseries.snapshot)
        if sink is not None:
            sink = _flightrec.TeeSink(sink, rec)
    else:
        # a re-enable that does NOT arm a flight recorder must drop a
        # previous enable's: its ring stops being fed at the sink swap,
        # so later alert dumps would overwrite the preserved incident
        # with a stale tail (no-op on a first enable)
        _flightrec.disable()

    def _on_alert(a, _rec=rec):
        if _rec is not None:
            _rec.trigger(f"alert:{a.rule}", detail=a.series)

    if want_detect and _ENGINE is None:
        _ENGINE = _detect.DetectorEngine(detectors, on_alert=_on_alert)
        store.add_close_listener(_ENGINE.on_window_close)
    elif _ENGINE is not None:
        # the engine (and its detector state) survives a re-enable, but
        # alert dumps must route to THIS enable's flight recorder (or
        # nowhere), never a closure over the previous one
        _ENGINE.on_alert = _on_alert
    if sink is not None:
        enable_tracing(sink)
        # re-enable with a NEW sink: close the log a previous enable()
        # opened (records already route to the new sink above) — a
        # second enable must not leak the first's file handle
        prev, _OWNED_LOG = _OWNED_LOG, owned
        if prev is not None and prev is not sink:
            prev.close()
    if with_counters:
        counters.enable()


def flush_counters() -> None:
    """Write the cumulative counter snapshot as one ``metric_counters``
    record on the trace sink (no-op without both sides enabled).  The
    report pipeline diffs consecutive flushes into window deltas."""
    sink = spans._SINK
    if sink is None or not counters.is_enabled():
        return
    import time

    try:
        sink.emit("metric_counters", {"ts": time.time(),
                                      "counters": counters.snapshot()})
    except Exception:
        import logging

        logging.getLogger("tpu_sgd.obs").warning(
            "trace sink raised; counter flush dropped", exc_info=True)


def flush_windows() -> None:
    """Close the open time-series window NOW so its data is visible to
    snapshots and the detectors evaluate it — the trailing window of a
    finished phase never sees a later observation otherwise.
    ``disable()`` calls this first."""
    timeseries.flush()


def windows_snapshot(prefix: Optional[str] = None,
                     last: Optional[int] = None):
    """The live windowed time-series (``None`` when off) — the facade
    spelling of ``timeseries.snapshot`` that ``healthz`` probes use."""
    return timeseries.snapshot(prefix=prefix, last=last)


def detector_engine():
    """The live :class:`~tpu_sgd.obs.detect.DetectorEngine` (or
    ``None``): ``active_alerts()``/``trip_counts()`` scrape surface."""
    return _ENGINE


def disable() -> None:
    """Turn everything off: evaluate the trailing window, flush
    counters into the trace (if both were on), unwind the runtime
    patches, drop the time-series/detector/flight-recorder hooks,
    close an owned trace log.  Idempotent."""
    global _OWNED_LOG, _ENGINE
    flush_windows()  # detectors see the trailing window BEFORE teardown
    flush_counters()
    counters.disable()
    disable_tracing()
    timeseries.disable()
    flightrec.disable()
    _ENGINE = None
    owned, _OWNED_LOG = _OWNED_LOG, None
    if owned is not None:
        owned.close()


def is_enabled() -> bool:
    return (spans.is_enabled() or counters.is_enabled()
            or timeseries.is_enabled())
