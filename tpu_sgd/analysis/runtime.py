"""Runtime counterparts of the static rules.

:func:`assert_compile_count` generalizes the ad-hoc ``fn._cache_size()``
asserts that ``tests/test_io.py`` grew: wrap any code region and pin
exactly how many NEW XLA programs it may compile, measured through any
combination of jitted functions and cache-size callables.  This is the
shape-trap rule's runtime twin — the static rule catches the eager-op
*pattern*, the context manager catches the *effect* (cache growth) for
paths the AST cannot see through.

:class:`InstrumentedLock` + :class:`LocksetRecorder` +
:func:`instrument_object` are the lock-discipline rule's runtime twin:
wrap a live object's declared locks, swap in a checking subclass, run a
real concurrent workload, and every guarded-attribute access that
happens WITHOUT the declared lock held by the accessing thread is
recorded (never raised — a checker must not kill the flush thread it is
observing).  ``tests/test_analysis.py`` validates the modules' actual
``GRAFTLINT_LOCKS`` declarations this way, including the helpers the
lexical rule must take on faith (a callee running under its caller's
lock passes here, because the lock really is held).

This module itself is stdlib-only — ``assert_compile_count`` works
through the ``_cache_size`` attribute jitted callables already expose
(though reaching it via ``tpu_sgd.analysis`` imports the parent package,
jax included, like everything else in this repo).
"""

from __future__ import annotations

import contextlib
import sys
import threading
import weakref
from typing import Callable, Dict, Iterable, List, Optional, Union

__all__ = [
    "CompileCountError", "DispatchCountError", "HostSyncError",
    "CallbackBufferError", "LockOrderError",
    "assert_compile_count", "assert_dispatch_count", "count_dispatches",
    "assert_no_host_sync", "count_host_syncs",
    "assert_bounded_callback_buffer",
    "InstrumentedLock", "LocksetRecorder", "LockViolation",
    "RaceReport", "instrument_object", "assert_lock_order",
]


class CompileCountError(AssertionError):
    """The wrapped region compiled a different number of programs than
    the contract allows."""


CacheSource = Union[Callable[[], int], object]


def _cache_size(of: CacheSource) -> int:
    """Current compiled-program count behind ``of``: a jitted function
    (``fn._cache_size()``), a zero-arg int callable, or an iterable of
    either (summed)."""
    size_fn = getattr(of, "_cache_size", None)
    if callable(size_fn):
        return int(size_fn())
    if callable(of):
        return int(of())
    if isinstance(of, Iterable):
        return sum(_cache_size(o) for o in of)
    raise TypeError(
        f"cannot read a compile-cache size from {of!r}: pass a jitted "
        "function, a zero-arg callable returning an int, or an "
        "iterable of those")


@contextlib.contextmanager
def assert_compile_count(expected: int, *, of: CacheSource,
                         at_most: bool = False):
    """Assert the region compiles exactly ``expected`` new programs.

    ``of`` names what to measure: a jitted function, a callable like
    ``tpu_sgd.ops.bucketed.program_cache_size`` (or
    ``lambda: engine.compile_count``), or an iterable mixing both —
    deltas are summed.  ``at_most=True`` relaxes equality to an upper
    bound (warm-loop guards: "no growth" is ``expected=0``).

    Replaces the hand-rolled pattern::

        fn = _streamed_stats_fn(B, "float32", False)
        ...build...
        assert fn._cache_size() == 1

    with::

        with assert_compile_count(1, of=_streamed_stats_fn(B, "float32",
                                                           False)):
            ...build...
    """
    if expected < 0:
        raise ValueError(f"expected must be >= 0, got {expected}")
    start = _cache_size(of)
    yield
    delta = _cache_size(of) - start
    if (delta > expected) if at_most else (delta != expected):
        bound = "at most" if at_most else "exactly"
        raise CompileCountError(
            f"region compiled {delta} new XLA program(s); the contract "
            f"allows {bound} {expected}.  A growing program cache on a "
            "hot path usually means an eager jnp op or dynamic slice on "
            "a batch-shaped value — pad/slice in host numpy instead "
            "(see the shape-trap rule, tpu_sgd/analysis)")


# -- dispatch counting ------------------------------------------------------

class DispatchCountError(AssertionError):
    """The wrapped region launched a different number of compiled
    programs than the contract allows."""


@contextlib.contextmanager
def count_dispatches():
    """Count XLA program LAUNCHES in a region — the execution twin of
    :func:`assert_compile_count`'s compile counting.

    Yields a one-key dict whose ``"n"`` entry is the number of compiled
    programs dispatched so far inside the region.  Counting hooks the
    runtime's one Python-level launch site
    (``pxla.ExecuteReplicated.__call__`` — every pjit execution passes
    through it on the Python dispatch path) and, for the duration of the
    region, disables jit's C++ fastpath (which executes warm effect-free
    programs entirely in C++, invisibly to any Python hook) by patching
    ``_get_fastpath_data`` to decline and clearing the C++ pjit caches on
    entry/exit.  Inside the region every call therefore takes the Python
    path and is counted exactly once per launch; ``device_put`` transfers
    and host callbacks are NOT launches and are not counted.  Slower than
    production dispatch — instrumentation for tests and benches, never
    for hot paths.

    Semantics to be aware of: EAGER jnp ops are dispatches too (each is
    its own one-op program — the same cost model behind the shape-trap
    rule), so a region that eagerly pads or slices will honestly count
    higher.  A ``lax.while_loop``/``scan`` program counts ONCE however
    many trips it runs — which is exactly the property the resident
    training driver's one-dispatch contract pins.

    Not reentrant; thread-compatible only for the counting thread (other
    threads' launches are counted too — keep the region single-actor).
    """
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla
    from jax._src.lib import xla_client as _xc

    counter = {"n": 0}
    orig_fastpath = _pjit._get_fastpath_data
    orig_call = _pxla.ExecuteReplicated.__call__

    def _no_fastpath(*a, **kw):
        return None

    def _counting_call(self, *args):
        counter["n"] += 1
        return orig_call(self, *args)

    def _clear_cpp_caches():
        _pjit._cpp_pjit_cache_fun_only.clear()
        _pjit._cpp_pjit_cache_explicit_attributes.clear()
        _xc._xla.PjitFunctionCache.clear_all()

    try:
        _pjit._get_fastpath_data = _no_fastpath
        _pxla.ExecuteReplicated.__call__ = _counting_call
        # functions warmed BEFORE the region hold installed fastpaths
        # that would bypass the hook — drop them so their next call
        # re-enters the (now fastpath-less) Python path.  Inside the
        # try: _clear_cpp_caches touches deep-private jax internals, and
        # a renamed attribute on a future jax must unwind the patches
        # above rather than leave the process permanently hook-routed
        _clear_cpp_caches()
        yield counter
    finally:
        _pjit._get_fastpath_data = orig_fastpath
        _pxla.ExecuteReplicated.__call__ = orig_call
        # entries cached during the region carry no fastpath data and
        # would stay on the slow path forever — drop them too
        _clear_cpp_caches()


@contextlib.contextmanager
def assert_dispatch_count(expected: int, *, at_most: bool = False):
    """Assert the region launches exactly ``expected`` compiled programs
    — the sibling of :func:`assert_compile_count`, pinning program
    LAUNCHES instead of program compiles (see :func:`count_dispatches`
    for how launches are observed and what counts as one).

    The resident training driver's contract is the motivating use: a
    whole converged-or-budget-exhausted run is ONE dispatch (its
    ``lax.while_loop`` trips and ``io_callback`` cadence hops are not
    launches), where the K-superstep driver pays one launch per
    superstep — ``assert_dispatch_count(1)`` around the run pins that
    structurally, not by timing.  ``at_most=True`` relaxes to an upper
    bound.
    """
    if expected < 0:
        raise ValueError(f"expected must be >= 0, got {expected}")
    with count_dispatches() as counter:
        yield counter
    if (counter["n"] > expected) if at_most else (counter["n"] != expected):
        bound = "at most" if at_most else "exactly"
        raise DispatchCountError(
            f"region launched {counter['n']} compiled program(s); the "
            f"contract allows {bound} {expected}.  Extra launches on a "
            "fused path usually mean an eager jnp op between dispatches "
            "or a loop that failed to stay device-resident (see "
            "optimize/resident_driver.py)")


# -- host-sync counting -----------------------------------------------------

class HostSyncError(AssertionError):
    """The wrapped region forced more device→host transfers than the
    contract allows."""


@contextlib.contextmanager
def count_host_syncs():
    """Count device→host materializations in a region — the runtime twin
    of the static ``host-sync`` rule (the rule catches the syncing
    *pattern*, this counts the *effect* on a live run).

    Yields a dict whose ``"n"`` entry is the number of jax arrays
    materialized to host so far inside the region, and whose
    ``"shapes"`` entry lists ``(shape, dtype)`` per materialization
    (the debugging breadcrumb: WHICH fetch fired).  Counting hooks the
    Python-level funnels on ``jax.Array`` — the ``_value`` property
    (``float()``/``int()``/``bool()`` scalar coercions route here),
    ``.item()``, and ``__array__`` — reentrancy-guarded so a funnel
    calling another counts once.  Only entries that actually COPY
    count: a re-read of an array whose host value is already cached
    (``_npy_value``) is free.

    Backend honesty: on the CPU backend ``np.asarray(arr)`` /
    ``jax.device_get`` convert through the C++ buffer protocol —
    zero-copy, invisible to these hooks, and genuinely free of DMA, so
    a zero count there is the truth, not a blind spot; on an
    accelerator backend the same spelling routes through ``__array__``
    and is counted.  ``block_until_ready`` is a barrier, not a
    transfer, and is never counted; use :func:`count_dispatches` for
    launch accounting.

    Not reentrant; the patch is process-global for the duration, so
    keep the region single-actor (a concurrent thread's fetches would
    be counted too — honestly, but confusingly).
    """
    from jax._src import array as _array

    counter = {"n": 0, "shapes": []}
    cls = _array.ArrayImpl
    depth = threading.local()

    # arrays already materialized inside this region, by id with a
    # weakref keeping the id honest: a backend whose fetch is a zero-copy
    # view (the CPU's) caches no ``_npy_value``, and re-reading the same
    # ready buffer is as free there as a cache hit is elsewhere
    seen: dict = {}

    def _tick(self):
        if getattr(depth, "d", 0) > 0:
            return  # inner funnel of an already-counted materialization
        if self._npy_value is None and id(self) not in seen:
            key = id(self)
            seen[key] = weakref.ref(self, lambda _, k=key: seen.pop(k, None))
            counter["n"] += 1
            counter["shapes"].append((tuple(self.shape), str(self.dtype)))

    @contextlib.contextmanager
    def _nested():
        depth.d = getattr(depth, "d", 0) + 1
        try:
            yield
        finally:
            depth.d -= 1

    orig_value, orig_item, orig_array = cls._value, cls.item, cls.__array__

    @property
    def _counting_value(self):
        _tick(self)
        with _nested():
            return orig_value.fget(self)

    def _counting_item(self, *args):
        _tick(self)
        with _nested():
            return orig_item(self, *args)

    def _counting_array(self, *args, **kwargs):
        _tick(self)
        with _nested():
            return orig_array(self, *args, **kwargs)

    try:
        cls._value = _counting_value
        cls.item = _counting_item
        cls.__array__ = _counting_array
        yield counter
    finally:
        cls._value = orig_value
        cls.item = orig_item
        cls.__array__ = orig_array


def assert_no_host_sync(fn: Optional[Callable] = None, *, allow: int = 0):
    """Assert a region (or ``fn()``) forces no device→host transfers.

    The resident training driver's steady-state contract: between
    dispatch and the cadence boundary the host touches NOTHING — one
    stray ``.item()`` / ``float()`` / ``np.asarray`` turns the
    device-resident loop back into per-trip lockstep, which is exactly
    what the static ``host-sync`` rule flags in source.  ``allow``
    admits the documented boundary fetches (e.g. the resident driver's
    three end-of-run scalars).

    Use as a context manager (``with assert_no_host_sync(): ...``) or
    call-through (``result = assert_no_host_sync(lambda: step(w))``).
    """
    if fn is not None:
        with _no_host_sync_region(allow):
            return fn()
    return _no_host_sync_region(allow)


@contextlib.contextmanager
def _no_host_sync_region(allow: int):
    with count_host_syncs() as counter:
        yield counter
    if counter["n"] > allow:
        shown = ", ".join(
            f"{s}:{d}" for s, d in counter["shapes"][:8])
        raise HostSyncError(
            f"region forced {counter['n']} device->host transfer(s); "
            f"the contract allows {allow}.  Transfers seen (first 8): "
            f"[{shown}].  A sync on a hot path usually means an "
            ".item()/float()/np.asarray on a device value — fetch at "
            "the cadence boundary instead (see the host-sync rule, "
            "tpu_sgd/analysis)")


# -- callback buffer bounds -------------------------------------------------

class CallbackBufferError(AssertionError):
    """A callback-carried host buffer grew beyond its declared bound."""


@contextlib.contextmanager
def assert_bounded_callback_buffer(buf, *, max_len: Optional[int] = None):
    """Assert a host buffer a callback feeds stays bounded across the
    region — the runtime twin of ``callback-discipline``'s bounded-
    buffer check (the static rule catches closure ``append``s in the
    callback body; this pins the live object's size over real firings).

    ``buf`` is the buffer itself or a zero-arg callable returning it
    (anything sized: list, deque, ndarray ring).  ``max_len`` defaults
    to the ENTRY length — the no-growth contract a preallocated ring
    satisfies and an append-per-firing history violates.
    """
    get = buf if callable(buf) else (lambda: buf)
    start = len(get())
    bound = start if max_len is None else max_len
    yield
    end = len(get())
    if end > bound:
        raise CallbackBufferError(
            f"callback buffer grew to {end} element(s); the bound is "
            f"{bound} (entry length {start}).  An unbounded host buffer "
            "pinned by a compiled program's callback accumulates for "
            "the whole run — hand windows to a bookkeeper with a "
            "documented bound instead (see optimize/resident_driver.py)")


# -- lock instrumentation ---------------------------------------------------

class LockViolation:
    """One guarded-attribute access without its declared lock held."""

    __slots__ = ("cls_name", "attr", "op", "thread", "function", "line")

    def __init__(self, cls_name: str, attr: str, op: str, thread: str,
                 function: str, line: int):
        self.cls_name = cls_name
        self.attr = attr
        self.op = op            # "read" | "write"
        self.thread = thread
        self.function = function  # code object name of the accessor
        self.line = line

    def __repr__(self) -> str:
        return (f"LockViolation({self.cls_name}.{self.attr} {self.op} in "
                f"{self.function}:{self.line} on thread {self.thread})")


class RaceReport:
    """One Eraser-style runtime race: a written attribute whose observed
    accesses from >= 2 threads share NO common lock.  Carries one
    representative site per thread (writes preferred) — the two stacks
    a human needs to see the schedule."""

    __slots__ = ("cls_name", "attr", "threads", "sites")

    def __init__(self, cls_name: str, attr: str, threads: set,
                 sites: List[tuple]):
        self.cls_name = cls_name
        self.attr = attr
        self.threads = threads
        self.sites = sites  # [(thread, op, function, line), ...]

    def __repr__(self) -> str:
        shown = "; ".join(f"{t}: {op} in {fn}:{ln}"
                          for t, op, fn, ln in self.sites)
        return (f"RaceReport({self.cls_name}.{self.attr} written from "
                f"{len(self.threads)} threads with empty common lockset"
                f" — {shown})")


class _AttrState:
    """Per-(object, attribute) Eraser state: the candidate lockset is
    the intersection of locksets held across every observed access."""

    __slots__ = ("cls_name", "attr", "candidate", "threads", "written",
                 "site_by_thread")

    def __init__(self, cls_name: str, attr: str):
        self.cls_name = cls_name
        self.attr = attr
        self.candidate = None  # None = no access observed yet
        self.threads: set = set()
        self.written = False
        #: thread name -> (thread, op, function, line); a write replaces
        #: a read site so the report shows the racing mutation
        self.site_by_thread: Dict[str, tuple] = {}


class LocksetRecorder:
    """Thread-aware ledger: which instrumented locks does each thread
    hold right now, which guarded accesses happened without the declared
    lock, which attribute locksets intersect to empty across threads
    (Eraser), and which acquisition ORDER pairs were observed."""

    def __init__(self):
        self._held = threading.local()
        self._mu = threading.Lock()
        self.violations: List[LockViolation] = []
        self.checked_accesses = 0
        #: id(lock) -> qualified name ("Class.lockattr")
        self._by_id: Dict[int, str] = {}
        #: (outer name, inner name) -> first-seen acquisition site
        #: (thread, function, line)
        self.order_pairs: Dict[tuple, tuple] = {}
        #: (id(obj), attr) -> Eraser state
        self._eraser: Dict[tuple, _AttrState] = {}

    # -- lockset -----------------------------------------------------------
    def _counts(self) -> Dict[int, int]:
        counts = getattr(self._held, "counts", None)
        if counts is None:
            counts = self._held.counts = {}
        return counts

    def acquired(self, lock: "InstrumentedLock") -> None:
        c = self._counts()
        prev = [i for i, n in c.items() if n > 0 and i != id(lock)]
        c[id(lock)] = c.get(id(lock), 0) + 1
        first = c[id(lock)] == 1
        with self._mu:
            self._by_id[id(lock)] = lock.name
            if not (first and prev):
                return  # reentrant re-acquire adds no ordering fact
            try:
                frame = sys._getframe(2)
                site = (threading.current_thread().name,
                        frame.f_code.co_name, frame.f_lineno)
            except ValueError:  # shallow stack (direct test calls)
                site = (threading.current_thread().name, "?", 0)
            for i in prev:
                outer = self._by_id.get(i)
                if outer is not None and outer != lock.name:
                    self.order_pairs.setdefault((outer, lock.name), site)

    def released(self, lock: "InstrumentedLock") -> None:
        c = self._counts()
        n = c.get(id(lock), 0) - 1
        if n <= 0:
            c.pop(id(lock), None)
        else:
            c[id(lock)] = n

    def holds(self, lock: "InstrumentedLock") -> bool:
        return self._counts().get(id(lock), 0) > 0

    def held_names(self) -> set:
        """Qualified names of every instrumented lock the CURRENT thread
        holds right now — the Eraser lockset."""
        c = self._counts()
        held = [i for i, n in c.items() if n > 0]
        with self._mu:
            return {self._by_id[i] for i in held if i in self._by_id}

    # -- violations --------------------------------------------------------
    def count_checked(self) -> None:
        # under _mu: += from concurrent checked threads loses updates,
        # a sloppiness a lock-discipline validator cannot afford itself
        with self._mu:
            self.checked_accesses += 1

    def record(self, violation: LockViolation) -> None:
        with self._mu:
            self.violations.append(violation)

    def violating_functions(self) -> set:
        with self._mu:
            return {v.function for v in self.violations}

    # -- Eraser ------------------------------------------------------------
    def eraser_access(self, obj_id: int, cls_name: str, attr: str,
                      op: str, held: set, site: tuple) -> None:
        """Fold one guarded access into the per-attribute candidate
        lockset: ``C(attr) ∩= locks held at this access``.  Called by
        the ``instrument_object`` hooks; ``site`` is ``(thread,
        function, line)``."""
        thread = site[0]
        with self._mu:
            st = self._eraser.get((obj_id, attr))
            if st is None:
                st = self._eraser[(obj_id, attr)] = _AttrState(
                    cls_name, attr)
            st.threads.add(thread)
            if op != "read":
                st.written = True
            if st.candidate is None:
                st.candidate = set(held)
            else:
                st.candidate &= held
            old = st.site_by_thread.get(thread)
            if old is None or (op != "read" and old[1] == "read"):
                st.site_by_thread[thread] = (thread, op, site[1], site[2])

    def races(self) -> List[RaceReport]:
        """Every WRITTEN attribute observed from >= 2 threads whose
        candidate lockset intersected to empty — the Eraser verdict.
        Sites: one per thread (writes preferred), so a report names both
        sides of the racing schedule."""
        out = []
        with self._mu:
            for st in self._eraser.values():
                if (st.written and len(st.threads) >= 2
                        and not st.candidate):
                    sites = sorted(st.site_by_thread.values())
                    writes = [s for s in sites if s[1] != "read"]
                    others = [s for s in sites if s[1] == "read"]
                    out.append(RaceReport(
                        st.cls_name, st.attr, set(st.threads),
                        (writes + others)[:4]))
        return sorted(out, key=lambda r: (r.cls_name, r.attr))


class InstrumentedLock:
    """Wrap a Lock / RLock / Condition so acquisitions register in a
    :class:`LocksetRecorder`.  Proxies everything else (``notify_all``,
    ``wait_for``, ...) to the inner primitive; ``wait`` is intercepted
    because a Condition.wait RELEASES the lock while blocked — the
    recorder must not count the waiter as a holder."""

    def __init__(self, inner, *, name: str = "?",
                 recorder: Optional[LocksetRecorder] = None):
        self._inner = inner
        self.name = name
        self.recorder = recorder or LocksetRecorder()

    def held_by_current_thread(self) -> bool:
        return self.recorder.holds(self)

    def acquire(self, *a, **kw):
        got = self._inner.acquire(*a, **kw)
        if got is not False:  # Lock.acquire() returns True; timeouts False
            self.recorder.acquired(self)
        return got

    def release(self):
        self._inner.release()
        self.recorder.released(self)

    def __enter__(self):
        self._inner.__enter__()
        self.recorder.acquired(self)
        return self

    def __exit__(self, *exc):
        out = self._inner.__exit__(*exc)
        self.recorder.released(self)
        return out

    def wait(self, timeout=None):
        self.recorder.released(self)
        try:
            return self._inner.wait(timeout)
        finally:
            self.recorder.acquired(self)

    def wait_for(self, predicate, timeout=None):
        self.recorder.released(self)
        try:
            return self._inner.wait_for(predicate, timeout)
        finally:
            self.recorder.acquired(self)

    def __getattr__(self, item):
        return getattr(self._inner, item)


def instrument_object(obj, lock_map: Dict[str, str],
                      recorder: Optional[LocksetRecorder] = None,
                      *, owner: Optional[str] = None) -> LocksetRecorder:
    """Arm ``obj`` with the runtime lock-discipline + Eraser check.

    ``lock_map`` is one class's entry of a module ``GRAFTLINT_LOCKS``
    declaration: ``{attr: "lock_attr[:w]"}``.  Each named lock attribute
    on ``obj`` is wrapped in an :class:`InstrumentedLock` (idempotent)
    named ``<owner>.<lock_attr>`` — ``owner`` defaults to the object's
    class name and should be passed explicitly when instrumenting a
    SUBCLASS with its base's declaration (``ShardedParameterStore`` under
    ``GRAFTLINT_LOCKS["ParameterStore"]``), so acquisition-order pairs
    match the committed ``GRAFTLINT_LOCK_ORDER`` node names.  ``obj``'s
    class is swapped for a dynamically-built checking subclass whose
    ``__getattribute__`` / ``__setattr__``:

    * verify the DECLARED lock is held by the accessing thread
      (recorded as :class:`LockViolation`, never raised — a checker
      must not kill the flush thread it is observing), and
    * fold the access into the Eraser candidate lockset
      (``C(attr) ∩= locks held``): :meth:`LocksetRecorder.races` then
      reports every written attribute whose accesses from >= 2 threads
      share no lock at all — the race class the declaration check
      misses when the declaration itself names the wrong lock.

    ``:w`` attrs participate with writes only (the atomic-reference
    idiom sanctions lock-free reads).  Accesses from within this
    module's own machinery (the lock wrappers) are not counted.
    """
    from tpu_sgd.analysis.core import parse_guard

    recorder = recorder or LocksetRecorder()
    base = type(obj)
    base_name = base.__name__
    if base_name.endswith("LockChecked"):  # re-instrumenting
        base_name = base_name[: -len("LockChecked")]
    owner = owner or base_name
    guards = {attr: parse_guard(spec) for attr, spec in lock_map.items()}
    for lock_name in {ln for ln, _ in guards.values()}:
        inner = getattr(obj, lock_name)
        if not isinstance(inner, InstrumentedLock):
            object.__setattr__(
                obj, lock_name,
                InstrumentedLock(inner, name=f"{owner}.{lock_name}",
                                 recorder=recorder))
        else:
            inner.recorder = recorder
            inner.name = f"{owner}.{lock_name}"

    def _check(self, attr: str, op: str) -> None:
        lock_name, mode = guards[attr]
        if mode == "w" and op == "read":
            return
        lock = object.__getattribute__(self, lock_name)
        recorder.count_checked()
        held = isinstance(lock, InstrumentedLock) and \
            lock.held_by_current_thread()
        frame = sys._getframe(2)
        site = (threading.current_thread().name,
                frame.f_code.co_name, frame.f_lineno)
        recorder.eraser_access(id(self), owner, attr, op,
                               recorder.held_names(), site)
        if held:
            return
        recorder.record(LockViolation(
            base.__name__, attr, op, site[0], site[1], site[2]))

    class _Checked(base):  # type: ignore[misc, valid-type]
        def __getattribute__(self, name):
            if name in guards:
                _check(self, name, "read")
            return object.__getattribute__(self, name)

        def __setattr__(self, name, value):
            if name in guards:
                _check(self, name, "write")
            object.__setattr__(self, name, value)

        def __delattr__(self, name):
            if name in guards:
                _check(self, name, "write")
            object.__delattr__(self, name)

    _Checked.__name__ = base.__name__ + "LockChecked"
    _Checked.__qualname__ = _Checked.__name__
    obj.__class__ = _Checked
    return recorder


# -- lock-order replay ------------------------------------------------------

class LockOrderError(AssertionError):
    """A recorded acquisition sequence inverted the committed
    ``GRAFTLINT_LOCK_ORDER``."""


def assert_lock_order(recorder: LocksetRecorder, order=None) -> None:
    """Replay the acquisition pairs a :class:`LocksetRecorder` observed
    against the committed ``GRAFTLINT_LOCK_ORDER`` — the runtime twin of
    the static lock-order graph (``rules_order.py``), covering the
    acquisitions static analysis cannot resolve (callback hooks like the
    HA ``set_replication(log.append)`` replication path).

    An observed pair ``(A held, B acquired)`` whose INVERSE is reachable
    in the transitively-closed declared order (B before A) raises
    :class:`LockOrderError` naming the observed site and the declared
    chain.  Pairs the declaration does not relate pass — the static rule
    is the side that forces new nestings INTO the declaration.
    """
    if order is None:
        from tpu_sgd.analysis import GRAFTLINT_LOCK_ORDER as order
    adj: Dict[str, set] = {}
    for a, b in order:
        adj.setdefault(a, set()).add(b)
    reach_memo: Dict[str, set] = {}

    def reach(a: str) -> set:
        if a in reach_memo:
            return reach_memo[a]
        out: set = set()
        stack = list(adj.get(a, ()))
        while stack:
            v = stack.pop()
            if v in out:
                continue
            out.add(v)
            stack.extend(adj.get(v, ()))
        reach_memo[a] = out
        return out

    with recorder._mu:
        observed = dict(recorder.order_pairs)
    for (outer, inner), site in sorted(observed.items()):
        if outer in reach(inner):
            thread, fn, line = site
            raise LockOrderError(
                f"observed acquisition {outer} -> {inner} (thread "
                f"{thread}, {fn}:{line}) INVERTS the committed "
                f"GRAFTLINT_LOCK_ORDER, which orders {inner} before "
                f"{outer}.  Either this code path is a deadlock with "
                "the declared-direction path, or the order declaration "
                "in tpu_sgd/analysis/__init__.py is stale — fix the "
                "code or re-run the static lock-order rule and update "
                "the declaration")
