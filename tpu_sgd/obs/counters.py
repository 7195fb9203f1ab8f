"""Always-on runtime counters: dispatches, compiles, syncs, transfers.

``tpu_sgd.analysis.runtime`` proved the counting machinery — patch the
runtime's Python-level funnels (``ExecuteReplicated.__call__`` for
program launches, the ``ArrayImpl`` ``_value``/``item``/``__array__``
funnels for device→host materializations) and the counts are exact,
structural, and immune to the wall-clock noise this 2-core harness
drowns timings in.  But those twins are test-scoped context managers:
``count_dispatches`` cannot run in production because it is built to
bracket one region on one actor.  This module promotes the same
machinery into a long-lived, opt-in accounting layer:

* ``enable()`` installs the patches ONCE (plus a ``jax.monitoring``
  compile listener and a ``jax.device_put`` wrapper for h2d transfer
  counts/bytes) and they stay up until ``disable()`` — counters
  accumulate across threads, subsystems, and requests for the life of
  the process.
* every count is tagged with the **subsystem** whose span region caused
  it (``obs.spans.current_subsystem()`` — thread-local, so the serving
  flush thread's dispatches land under ``serve`` while the training
  thread's land under ``train``).
* explicit hook sites (``inc("serve.reject")``,
  ``inc("train.io_callback")``) ride the same registry for events the
  patches cannot see.

Cost contract: DISABLED is one module-global load and a falsy branch
per ``inc()`` call (the failpoints discipline; measured no-op in
``tests/test_obs.py``), and ZERO patches are installed — production
processes that never opt in run the stock runtime.  ENABLED is honest
but not free: counting launches requires declining jit's C++ fastpath
(warm effect-free programs otherwise execute entirely in C++, invisible
to any Python hook), so every dispatch takes the Python path — the
overhead is wall-clock only; the counter layer adds ZERO dispatches,
compiles, or host syncs of its own (the acceptance pin in
``tests/test_obs.py``, measured with the analysis twins, which nest
cleanly over these patches because both patch/restore LIFO).

Semantics (inherited from the twins, documented there in full): eager
jnp ops are dispatches AND compiles (one-op programs — the shape-trap
cost model); a ``lax.while_loop``/``scan`` program counts ONCE however
many trips it runs; ``np.asarray`` on the CPU backend is buffer-protocol
zero-copy and honestly invisible to the sync funnels; ``device_put``
h2d bytes are counted at the public ``jax.device_put`` spelling (the
one this codebase's feeds use), summing the argument's leaf ``nbytes``.
"""

from __future__ import annotations

import logging
import threading
import weakref
from typing import Dict, Optional

from tpu_sgd.obs import spans as _spans

__all__ = ["RuntimeCounters", "inc", "enable", "disable", "is_enabled",
           "record_wire", "snapshot", "reset", "deltas", "wire_ratios"]

logger = logging.getLogger("tpu_sgd.obs")

#: graftlint lock-discipline declaration (tpu_sgd/analysis): the counts
#: dict is written from every thread the patches observe (training,
#: prefetch worker, serving flush, io_callback) — `a += 1` on a dict
#: entry is a read-modify-write that loses updates without the lock.
GRAFTLINT_LOCKS = {
    "RuntimeCounters": {
        "_counts": "_lock",
    },
}

#: fast-path gate: ``inc()`` reads this ONE module global and returns
#: when falsy — the entire disabled-mode cost (failpoints discipline)
_ENABLED = False


class RuntimeCounters:
    """Thread-safe ``name -> {n, bytes}`` accumulator.  Names are
    dotted, leading segment = subsystem (``train.dispatch``,
    ``serve.host_sync``, ``ingest.h2d_bytes`` ride ``n``/``bytes``).

    ``forward`` (a GIL-atomic single reference, default ``None``) tees
    every inc to a second consumer — the windowed time-series store
    (``tpu_sgd.obs.timeseries``) installs it on THE global instance so
    per-window counter series exist without a second set of hook
    sites.  It is called OUTSIDE the lock (the forward target has its
    own lock; holding both would invert against the window store's
    close listeners) and is pure host work, so the zero-added-runtime
    pin holds with it installed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, Dict[str, int]] = {}
        self.forward = None

    def inc(self, name: str, n: int = 1, nbytes: int = 0) -> None:
        with self._lock:
            c = self._counts.get(name)
            if c is None:
                c = self._counts[name] = {"n": 0, "bytes": 0}
            c["n"] += n
            c["bytes"] += nbytes
        fwd = self.forward
        if fwd is not None:
            try:
                fwd(name, n, nbytes)
            except Exception:  # accounting must never kill the hot path
                logger.warning("counter forward raised; dropped",
                               exc_info=True)

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {k: dict(v) for k, v in self._counts.items()}

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


#: THE process-wide registry instance (tests may build private ones)
_GLOBAL = RuntimeCounters()


def inc(name: str, n: int = 1, nbytes: int = 0) -> None:
    """Hot-path hook: bump a named counter.  This function sits on
    per-request / per-window paths; keep the disabled branch to the
    single global check."""
    if not _ENABLED:
        return
    _GLOBAL.inc(name, n, nbytes)


def record_wire(fmt: str, logical_nbytes: int, physical_nbytes: int,
                tag: Optional[str] = None) -> None:
    """Tag one wire transfer by FORMAT (``dense-f32`` / ``bf16`` /
    ``bcoo`` / ``topk``): ``physical`` is what actually crosses the
    link, ``logical`` the dense-f32-equivalent payload it represents —
    the pair is what makes the per-stage compression ratio a measured
    number (``obs.report`` prints ``logical / physical``;
    :func:`wire_ratios` computes it).  Counter names:
    ``<subsystem>.wire.<fmt>`` carries the physical bytes,
    ``<subsystem>.wire.<fmt>.logical`` the logical bytes, both with one
    ``n`` per transfer.  ``tag`` fans the format out per-instance with
    the SAME bracket syntax the span/event fan-outs use
    (``<subsystem>.wire.<fmt>[<tag>]`` — e.g. the sharded store's
    per-shard wires tag ``s0..s{S-1}``); consumers that key on the
    format (the wire-ratio detector's exempt list) strip the bracket
    suffix before comparing.  Same disabled-mode cost contract as
    :func:`inc` — one global load + falsy branch."""
    if not _ENABLED:
        return
    base = f"{_tagged('wire')}.{fmt}"
    if tag is not None:
        base = f"{base}[{tag}]"
    _GLOBAL.inc(base, nbytes=int(physical_nbytes))
    _GLOBAL.inc(base + ".logical", nbytes=int(logical_nbytes))


def wire_ratios(counts: Optional[Dict[str, Dict[str, int]]] = None
                ) -> Dict[str, Dict[str, float]]:
    """Per-stage wire compression table from a counter snapshot:
    ``{"<subsystem>.wire.<fmt>": {n, physical_bytes, logical_bytes,
    ratio}}`` where ``ratio = logical / physical`` (>= 1 means the wire
    shipped fewer bytes than the dense-f32 payload it represents).  THE
    one definition shared by ``obs.report`` and the benches."""
    counts = snapshot() if counts is None else counts
    out: Dict[str, Dict[str, float]] = {}
    for name, c in counts.items():
        if ".wire." not in name or name.endswith(".logical"):
            continue
        logical = counts.get(name + ".logical", {"bytes": 0})["bytes"]
        phys = c["bytes"]
        out[name] = {
            "n": c["n"],
            "physical_bytes": phys,
            "logical_bytes": logical,
            "ratio": (logical / phys) if phys else float("inf"),
        }
    return out


def snapshot() -> Dict[str, Dict[str, int]]:
    """Cumulative counters since ``enable()``/``reset()`` — the scrape
    surface.  ``{name: {"n": count, "bytes": bytes}}``."""
    return _GLOBAL.snapshot()


def reset() -> None:
    _GLOBAL.reset()


class deltas:
    """Region helper over the GLOBAL registry: ``with deltas() as d:``
    then ``d.get()`` returns the per-name count/byte deltas the region
    produced — the production spelling of what the analysis twins pin
    in tests (requires counters already enabled)."""

    def __enter__(self):
        self._start = snapshot()
        return self

    def get(self) -> Dict[str, Dict[str, int]]:
        out = {}
        for name, c in snapshot().items():
            s = self._start.get(name, {"n": 0, "bytes": 0})
            dn, db = c["n"] - s["n"], c["bytes"] - s["bytes"]
            if dn or db:
                out[name] = {"n": dn, "bytes": db}
        return out

    def __exit__(self, *exc):
        return False


# -- runtime patches ---------------------------------------------------------

_PATCHES: Optional[dict] = None  # saved originals while enabled


def _tagged(kind: str) -> str:
    return f"{_spans.current_subsystem()}.{kind}"


def enable() -> None:
    """Install the accounting patches and open the ``inc`` gate.
    Idempotent.  Prefer the ``tpu_sgd.obs.enable`` facade, which also
    wires tracing and flushes counters into the trace on disable."""
    global _ENABLED, _PATCHES
    if _ENABLED:
        return
    from jax._src import array as _array
    from jax._src import monitoring as _monitoring
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla
    from jax._src.lib import xla_client as _xc
    import jax as _jax

    cls = _array.ArrayImpl
    saved = {
        "fastpath": _pjit._get_fastpath_data,
        "call": _pxla.ExecuteReplicated.__call__,
        "_value": cls._value,
        "item": cls.item,
        "__array__": cls.__array__,
        "device_put": _jax.device_put,
    }
    orig_call = saved["call"]
    orig_value, orig_item, orig_array = (saved["_value"], saved["item"],
                                         saved["__array__"])
    orig_put = saved["device_put"]
    depth = threading.local()

    def _no_fastpath(*a, **kw):
        return None

    def _counting_call(self, *args):
        _GLOBAL.inc(_tagged("dispatch"))
        return orig_call(self, *args)

    # arrays already materialized while enabled, by id with a weakref
    # keeping the id honest: a zero-copy fetch (the CPU backend's) caches
    # no ``_npy_value``, and re-reading the same ready buffer is as free
    # there as a cache hit is elsewhere (analysis/runtime.py's twin rule)
    seen: dict = {}

    def _tick_sync(arr):
        if getattr(depth, "d", 0) > 0:
            return  # inner funnel of an already-counted materialization
        if arr._npy_value is None and id(arr) not in seen:
            key = id(arr)
            seen[key] = weakref.ref(arr, lambda _, k=key: seen.pop(k, None))
            _GLOBAL.inc(_tagged("host_sync"),
                        nbytes=int(getattr(arr, "nbytes", 0) or 0))

    class _nested:
        def __enter__(self):
            depth.d = getattr(depth, "d", 0) + 1

        def __exit__(self, *exc):
            depth.d -= 1

    @property
    def _counting_value(self):
        _tick_sync(self)
        with _nested():
            return orig_value.fget(self)

    def _counting_item(self, *args):
        _tick_sync(self)
        with _nested():
            return orig_item(self, *args)

    def _counting_array(self, *args, **kwargs):
        _tick_sync(self)
        with _nested():
            return orig_array(self, *args, **kwargs)

    def _counting_device_put(x, *args, **kwargs):
        try:
            nbytes = sum(int(getattr(leaf, "nbytes", 0) or 0)
                         for leaf in _jax.tree_util.tree_leaves(x))
        except Exception:
            nbytes = 0
        _GLOBAL.inc(_tagged("h2d"), nbytes=nbytes)
        return orig_put(x, *args, **kwargs)

    def _compile_listener(name: str, dur: float, **kw):
        # one backend_compile per XLA program built — eager one-op
        # programs included, which is exactly the shape-trap cost model
        if name.endswith("backend_compile_duration"):
            _GLOBAL.inc(_tagged("compile"))

    def _clear_cpp_caches():
        _pjit._cpp_pjit_cache_fun_only.clear()
        _pjit._cpp_pjit_cache_explicit_attributes.clear()
        _xc._xla.PjitFunctionCache.clear_all()

    # install INSIDE the try: these touch deep-private jax internals,
    # and a renamed attribute on a future jax must unwind whatever DID
    # install rather than leave the process half-hook-routed (the same
    # containment count_dispatches documents)
    try:
        _pjit._get_fastpath_data = _no_fastpath
        _pxla.ExecuteReplicated.__call__ = _counting_call
        cls._value = _counting_value
        cls.item = _counting_item
        cls.__array__ = _counting_array
        _jax.device_put = _counting_device_put
        _monitoring.register_event_duration_secs_listener(_compile_listener)
        saved["compile_listener"] = _compile_listener
        # functions warmed BEFORE enable hold installed fastpaths that
        # would bypass the dispatch hook — drop them so their next call
        # re-enters the (now fastpath-less) Python path; the compiled
        # executables survive, so this costs a re-trace of the C++
        # cache entry, never an XLA recompile
        _clear_cpp_caches()
    except Exception:
        _restore(saved)
        raise
    _PATCHES = saved
    _ENABLED = True


def _restore(saved: dict) -> None:
    from jax._src import array as _array
    from jax._src import monitoring as _monitoring
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla
    from jax._src.lib import xla_client as _xc
    import jax as _jax

    _pjit._get_fastpath_data = saved["fastpath"]
    _pxla.ExecuteReplicated.__call__ = saved["call"]
    cls = _array.ArrayImpl
    cls._value = saved["_value"]
    cls.item = saved["item"]
    cls.__array__ = saved["__array__"]
    _jax.device_put = saved["device_put"]
    listener = saved.get("compile_listener")
    if listener is not None:
        try:
            _monitoring.unregister_event_duration_listener(
                listener)
        except Exception:
            logger.warning("could not unregister the compile listener",
                           exc_info=True)
    # entries cached while the fastpath was declined carry no fastpath
    # data and would stay on the slow path forever — drop them
    try:
        _pjit._cpp_pjit_cache_fun_only.clear()
        _pjit._cpp_pjit_cache_explicit_attributes.clear()
        _xc._xla.PjitFunctionCache.clear_all()
    except Exception:
        logger.warning("could not clear the C++ pjit caches",
                       exc_info=True)


def disable() -> None:
    """Unwind every patch and close the gate.  Idempotent.  Counter
    VALUES survive (scrape after disable is fine); ``reset()`` clears."""
    global _ENABLED, _PATCHES
    if not _ENABLED:
        return
    _ENABLED = False
    saved, _PATCHES = _PATCHES, None
    if saved is not None:
        _restore(saved)


def is_enabled() -> bool:
    return _ENABLED
