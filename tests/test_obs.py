"""Unified observability layer tests (ISSUE 8): span tracing, runtime
counters, and the trace/SLO report pipeline.

The two load-bearing contracts pinned here:

* DISABLED is a measured no-op — one module-global load and a falsy
  branch per hook, the failpoints discipline (`span()` returns the one
  shared singleton, `inc()` bumps nothing, zero runtime patches
  installed).
* ENABLED adds ZERO dispatches, compiles, or host syncs on the warmed
  superstep and resident hot paths (the acceptance criterion), measured
  both by the analysis twins (disabled baseline) and by the promoted
  counters themselves (enabled run) — the numbers must agree exactly.
"""

import json
import threading
import time

import numpy as np
import pytest

from tpu_sgd import obs
from tpu_sgd.obs import counters as obs_counters
from tpu_sgd.obs import report as obs_report
from tpu_sgd.obs import spans as obs_spans
from tpu_sgd.obs.spans import disable_tracing, enable_tracing
from tpu_sgd.utils.events import JsonLinesEventLog


class ListSink:
    """In-memory sink on the ``emit(kind, payload)`` contract."""

    def __init__(self, raising: bool = False):
        self.records = []
        self.raising = raising

    def emit(self, kind, payload):
        if self.raising:
            raise RuntimeError("sink intentionally broken")
        self.records.append((kind, dict(payload)))

    def spans(self, name=None):
        return [p for k, p in self.records if k == "trace_span"
                and (name is None or p["name"] == name)]

    def events(self, name=None):
        return [p for k, p in self.records if k == "trace_event"
                and (name is None or p["name"] == name)]


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with the layer fully disabled."""
    obs.disable()
    obs_counters.reset()
    yield
    obs.disable()
    obs_counters.reset()


# -- disabled-mode cost contract --------------------------------------------

def test_disabled_span_is_the_shared_noop_singleton():
    """`span(...)` disabled returns ONE shared object — no allocation,
    no formatting; `event`/`inc` return before touching anything."""
    s1 = obs_spans.span("train.superstep", i0=1)
    s2 = obs_spans.span("serve.batch")
    assert s1 is s2  # the singleton, not a fresh object per call
    with s1 as s:
        assert s.set(anything=1) is s  # set() is a no-op that chains
    obs_spans.event("reliability.retry", attempt=1)  # must not raise
    obs_counters.inc("serve.reject")
    assert obs_counters.snapshot() == {}


def test_disabled_hooks_are_measured_noops():
    """The failpoints discipline, measured: sub-microsecond per call on
    this noisy 2-core host (bound ~20x the measured mean for CI
    headroom).  `span()` pays one kwargs dict + global load + branch;
    `inc()` pays the global load + branch."""
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs_spans.span("train.step")
    per_span = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        obs_counters.inc("train.io_callback")
    per_inc = (time.perf_counter() - t0) / n
    assert per_span < 2e-6, f"disabled span costs {per_span*1e9:.0f}ns"
    assert per_inc < 2e-6, f"disabled inc costs {per_inc*1e9:.0f}ns"


def test_disabled_installs_zero_runtime_patches():
    """A production process that never opts in runs the STOCK runtime:
    enabling installs the patches, disabling restores the originals."""
    import jax

    orig_put = jax.device_put
    obs_counters.enable()
    try:
        assert jax.device_put is not orig_put
    finally:
        obs_counters.disable()
    assert jax.device_put is orig_put


# -- span mechanics ----------------------------------------------------------

def test_span_nesting_and_attrs():
    sink = ListSink()
    enable_tracing(sink)
    try:
        with obs_spans.span("train.superstep", i0=5) as outer:
            with obs_spans.span("train.replay"):
                pass
            outer.set(steps=4)
    finally:
        disable_tracing()
    inner, = sink.spans("train.replay")
    outer, = sink.spans("train.superstep")
    assert inner["parent_id"] == outer["span_id"]  # child closed first
    assert outer["parent_id"] == 0
    assert outer["i0"] == 5 and outer["steps"] == 4
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0
    assert outer["error"] is None


def test_span_records_error_class_and_propagates():
    sink = ListSink()
    enable_tracing(sink)
    try:
        with pytest.raises(ValueError):
            with obs_spans.span("checkpoint.save"):
                raise ValueError("boom")
    finally:
        disable_tracing()
    rec, = sink.spans("checkpoint.save")
    assert rec["error"] == "ValueError"


def test_spans_are_thread_aware():
    """Each thread keeps its own stack: a worker's span must not parent
    onto whatever the main thread has open (the prefetch-worker /
    flush-thread contract), and the subsystem tag is per-thread too."""
    sink = ListSink()
    enable_tracing(sink)
    tags = {}
    try:
        def worker():
            with obs_spans.span("ingest.produce"):
                tags["worker"] = obs_spans.current_subsystem()
                time.sleep(0.005)

        with obs_spans.span("train.superstep"):
            t = threading.Thread(target=worker, name="w0")
            t.start()
            tags["main"] = obs_spans.current_subsystem()
            t.join()
    finally:
        disable_tracing()
    produce, = sink.spans("ingest.produce")
    assert produce["parent_id"] == 0  # NOT nested under train.superstep
    assert produce["thread"] == "w0"
    assert tags == {"worker": "ingest", "main": "train"}
    assert obs_spans.current_subsystem() == "untagged"


def test_raising_sink_never_kills_the_hot_path():
    enable_tracing(ListSink(raising=True))
    try:
        with obs_spans.span("train.step", i=1):
            pass  # span exit swallows the sink error
        obs_spans.event("reliability.retry")  # ditto
    finally:
        disable_tracing()


# -- the second consumer: the profiler's own trace ---------------------------

@pytest.fixture
def profiler_session(tmp_path):
    """``stop() -> {span name: [(start_ns, end_ns, stats)]}`` of a
    ``jax.profiler`` session started here, read back from its
    ``.xplane.pb`` with nothing but JAX."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    stopped = []

    def stop():
        jax.profiler.stop_trace()
        stopped.append(True)
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        found = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("fit.", "train.", "demo.")):
                        found.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
        return {name: sorted(spans, key=lambda s: s[0])
                for name, spans in found.items()}

    yield stop
    if not stopped:
        jax.profiler.stop_trace()


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_session_with_the_gate_closed_puts_the_span_in_the_trace(
        profiler_session):
    assert not obs_spans.is_enabled()
    with obs_spans.span("demo.outer", rows=7, path="fused") as sp:
        assert sp is not obs_spans._NOOP
        assert sp.set(cached=True) is sp  # set() chains, as on _Span
    found = profiler_session()
    (_, _, stats), = found["demo.outer"]
    assert stats == {"rows": 7, "path": "fused", "cached": 1}
    # the session over, the gate still closed: the singleton again
    assert obs_spans.span("demo.outer") is obs_spans._NOOP


def test_gate_open_and_session_give_one_record_and_one_event(
        profiler_session):
    sink = ListSink()
    enable_tracing(sink)
    try:
        with obs_spans.span("demo.both", i=3) as sp:
            sp.set(steps=4)
    finally:
        disable_tracing()
    found = profiler_session()
    rec, = sink.spans("demo.both")
    assert rec["i"] == 3 and rec["steps"] == 4
    (_, _, stats), = found["demo.both"]
    assert stats == {"i": 3, "steps": 4}


def test_fit_path_spans_round_trip_through_the_profiler(profiler_session,
                                                        rng):
    """A tiny ``LogisticRegressionWithSGD.run`` on host arrays, twice, and
    a device-array fit at the Optimizer boundary, under one session with
    no ``obs.enable``: the fit path's spans nest as the program runs and
    carry their counts."""
    import jax.numpy as jnp

    import tpu_sgd

    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = (rng.random(256) > 0.5).astype(np.float32)
    alg = tpu_sgd.LogisticRegressionWithSGD(1.0, 4, reg_param=0.0,
                                            mini_batch_fraction=0.5)
    alg.run((X, y))
    alg.run((X, y))
    opt = tpu_sgd.GradientDescent(
        tpu_sgd.LogisticGradient(),
        tpu_sgd.SquaredL2Updater()).set_num_iterations(3)
    opt.optimize_with_history((jnp.asarray(X), jnp.asarray(y)),
                              np.zeros(8, np.float32))
    # a meshed fit: from host arrays, then on what the placement returned
    import jax

    mesh = tpu_sgd.data_mesh(jax.devices()[:4])
    opt.set_mesh(mesh)
    opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    Xd, yd, _ = tpu_sgd.parallel.shard_dataset(mesh, X, y)
    opt.optimize_with_history((Xd, yd), np.zeros(8, np.float32))
    found = profiler_session()

    assert len(found["fit.run"]) == 2 and len(found["train.run"]) == 5
    assert "fit.prepare" not in found  # no scaling, no intercept: no span
    for i, fit in enumerate(found["fit.run"]):
        assert fit[2] == {"rows": 256, "features": 8, "sparse": 0}
        for name in ("fit.validate", "fit.plan", "train.run"):
            assert _inside(found[name][i], fit), name
        run = found["train.run"][i]
        assert run[2] == {"iterations": 4, "rows": 256, "path": "fused",
                          "shards": 1, "classes": 2,
                          "labels_prepared": 0,  # 1 on a TPU alone (PR 33)
                          # the kernel's blocks, on a TPU alone (PR 34)
                          "row_tile": 0, "feature_blocks": 1,
                          # the draw in the kernel, on a TPU alone (PR 36)
                          "mask_in_kernel": 0,
                          # the by-rows kernel, on a TPU alone (PR 39)
                          "by_rows": 0,
                          # a matrix's padded class rows in the kernel, on
                          # a TPU alone (PR 48)
                          "class_rows": 0,
                          # the class body's chunks ahead, past 128 class
                          # rows on a TPU alone (PR 50)
                          "ahead": 0,
                          # a feature's bytes as the step reads it and the
                          # products' operand type, on both paths (PR 57)
                          "row_item_bytes": 4, "operand": "float32",
                          # from the totals of its rows (PR 41): logistic
                          "stats": 0}
        # the leaves tile the fit in this order (PR 37: train.select
        # between the hand-off and the call, fit.finish after the optimizer)
        leaves = [found[name][i] for name in (
            "fit.validate", "fit.plan", "train.h2d", "train.select",
            "train.dispatch", "train.fetch", "fit.finish")]
        for earlier, later in zip(leaves, leaves[1:]):
            assert earlier[1] <= later[0]
        for name in ("train.h2d", "train.select", "train.dispatch",
                     "train.fetch"):
            assert _inside(found[name][i], run), name
        assert _inside(found["fit.finish"][i], fit)
        assert run[1] <= found["fit.finish"][i][0]
        assert found["train.h2d"][i][2]["bytes"] == X.nbytes + y.nbytes
        assert found["train.fetch"][i][2]["recorded"] == 4
        assert found["fit.validate"][i][2] == {"rows": 256}
    assert [p[2]["cached"] for p in found["fit.plan"]] == [0, 1]
    assert found["fit.plan"][0][2]["schedule"] == "resident_stock"
    # the fit that builds its runner names itself
    assert [d[2]["built"] for d in found["train.dispatch"]] == [1, 0, 1, 1,
                                                                0]
    # the selection before it says which form the step's kernel is (PR 39:
    # 0 on a CPU) and where ``_runner``'s program came from (no compile
    # cache directory here: the runner as it was); whether the optimizer
    # made a new entry for it is ``built``'s to say
    assert [s[2] for s in found["train.select"]] == [
        {"runner": "as_was", "by_rows": 0, "class_rows": 0, "ahead": 0,
         "stats": 0}] * 5
    assert len(found["fit.finish"]) == 2  # run() alone has a model to make
    # device arrays at the Optimizer boundary: nothing to copy
    assert found["train.h2d"][2][2]["bytes"] == 0
    assert found["train.run"][2][2]["iterations"] == 3
    # the meshed fits: ``train.place`` is a leaf of ``train.run`` between
    # the copy and the dispatch; both train the arrays where they lie
    assert "train.place" in found and len(found["train.place"]) == 2
    for place, run, h2d, select, dispatch in zip(
            found["train.place"], found["train.run"][3:],
            found["train.h2d"][3:], found["train.select"][3:],
            found["train.dispatch"][3:]):
        assert _inside(place, run) and h2d[1] <= place[0]
        assert place[1] <= select[0] and select[1] <= dispatch[0]
        assert run[2]["path"] == "mesh" and run[2]["shards"] == 4
    # from host arrays the hand-off itself lays the rows out (PR 42: every
    # block to the device that owns it), so the placement moves nothing
    # either time (tests/test_shard_place.py has the dataset it re-lays)
    assert [p[2] for p in found["train.place"]] == [
        {"shards": 4, "in_place": 1, "bytes": 0}] * 2
    assert [h[2]["shards"] for h in found["train.h2d"]] == [1, 1, 0, 4, 0]


def test_fit_prepare_span_only_when_scaling_or_intercept_runs(
        profiler_session, rng):
    import tpu_sgd

    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = X @ np.ones(4, np.float32)
    alg = tpu_sgd.LinearRegressionWithSGD(0.1, 2)
    alg.set_intercept(True)
    alg.run((X, y))
    found = profiler_session()
    (prepare,), (fit,) = found["fit.prepare"], found["fit.run"]
    assert _inside(prepare, fit) and prepare[2] == {"rows": 64}
    assert prepare[1] <= found["fit.plan"][0][0]  # planned on the final X


# -- counters ----------------------------------------------------------------

def test_counters_inc_snapshot_deltas_reset():
    obs_counters.enable()
    try:
        obs_counters.inc("serve.reject")
        with obs_counters.deltas() as d:
            obs_counters.inc("serve.reject", 2)
            obs_counters.inc("ingest.wire", nbytes=128)
        got = d.get()
        assert got == {"serve.reject": {"n": 2, "bytes": 0},
                       "ingest.wire": {"n": 1, "bytes": 128}}
        snap = obs_counters.snapshot()
        assert snap["serve.reject"]["n"] == 3
    finally:
        obs_counters.disable()
    # values survive disable (scrape-after-stop); reset clears
    assert obs_counters.snapshot()["serve.reject"]["n"] == 3
    obs_counters.reset()
    assert obs_counters.snapshot() == {}


def test_counters_attribute_runtime_events_to_the_open_subsystem():
    """Dispatches/compiles/syncs/h2d land under the span-derived tag of
    the thread that caused them — the straggler-attribution surface.
    Tagging rides the span stack, so tracing must be on too (the facade
    enables both)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()  # warm BEFORE enabling
    enable_tracing(ListSink())
    obs_counters.enable()
    try:
        with obs_spans.span("train.superstep"):
            y = f(x)
            v = float(y[0, 0])  # eager slice + scalar fetch
        with obs_spans.span("ingest.produce"):
            jax.device_put(np.ones((4, 4), np.float32))
        snap = obs_counters.snapshot()
    finally:
        obs_counters.disable()
    assert v == 3.0
    assert snap["train.dispatch"]["n"] >= 1     # the warmed f(x) launch
    assert snap["train.host_sync"]["n"] >= 1    # the float() fetch
    assert snap["train.host_sync"]["bytes"] >= 4
    assert snap["ingest.h2d"]["n"] == 1
    assert snap["ingest.h2d"]["bytes"] == 64
    # no compile of the WARMED function; the eager slice may compile
    assert snap.get("untagged.dispatch", {"n": 0})["n"] == 0


def test_counters_enable_disable_roundtrip_under_twins():
    """The analysis twins nest over the promoted patches (both
    patch/restore LIFO) and agree with them on a warmed function."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.analysis.runtime import count_dispatches

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((4,))
    f(x).block_until_ready()
    obs_counters.enable()
    try:
        obs_counters.reset()
        with count_dispatches() as twin:
            f(x).block_until_ready()
        snap = obs_counters.snapshot()
    finally:
        obs_counters.disable()
    assert twin["n"] == 1
    assert snap["untagged.dispatch"]["n"] == 1  # no span open: untagged


# -- facade ------------------------------------------------------------------

def test_facade_owns_trace_log_and_flushes_counters(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.enable(path)
    with obs.span("train.superstep", i0=1):
        obs.inc("train.io_callback")
    obs.flush_counters()
    obs.disable()  # flushes once more + closes the owned log
    records = JsonLinesEventLog.read(path)
    kinds = [r["kind"] for r in records]
    assert "trace_span" in kinds
    assert kinds.count("metric_counters") == 2
    last = [r for r in records if r["kind"] == "metric_counters"][-1]
    assert last["counters"]["train.io_callback"]["n"] == 1


def test_facade_shares_a_listener_event_log(tmp_path):
    """Traces interleave with listener events on ONE JSONL stream — the
    chaos-soak spelling (caller keeps ownership)."""
    from tpu_sgd.utils.events import IterationEvent

    path = str(tmp_path / "shared.jsonl")
    log = JsonLinesEventLog(path)
    obs.enable(log, with_counters=False)
    log.on_iteration(IterationEvent(1, 0.5, 0.1, 32, 0.01))
    with obs.span("train.step", i=1):
        pass
    obs.disable()  # caller-owned: must NOT close it
    log.on_iteration(IterationEvent(2, 0.4, 0.1, 32, 0.01))
    log.close()
    kinds = [r["kind"] for r in JsonLinesEventLog.read(path)]
    assert kinds == ["iteration", "trace_span", "iteration"]


def test_reenable_with_new_path_closes_previous_owned_log(tmp_path):
    """A second enable() must not leak the first's file handle: the
    previously owned log is closed (tail flushed) when the sink swaps."""
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    obs.enable(a)
    first = obs._OWNED_LOG
    with obs.span("train.step", i=1):
        pass
    obs.enable(b)  # swap without an intervening disable()
    assert first._f.closed  # the leak the review caught
    with obs.span("train.step", i=2):
        pass
    obs.disable()
    ka = [r for r in JsonLinesEventLog.read(a) if r["kind"] == "trace_span"]
    kb = [r for r in JsonLinesEventLog.read(b) if r["kind"] == "trace_span"]
    assert [r["i"] for r in ka] == [1]
    assert [r["i"] for r in kb] == [2]


# -- the acceptance pin: enabled obs adds ZERO runtime events ---------------

def _data(rng, n=400, d=6):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ w).astype(np.float32)
    return X, y


def _opt(iters=24, k=4, c=0):
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.utils.events import SGDListener

    o = (GradientDescent().set_num_iterations(iters).set_step_size(0.1)
         .set_mini_batch_fraction(0.5).set_sampling("sliced")
         .set_convergence_tol(0.0).set_seed(7).set_superstep(k)
         .set_listener(SGDListener()))
    if c:
        o.set_residency(c)
    return o


def test_enabled_obs_superstep_driver_zero_added_runtime_events(rng):
    """ISSUE 8 acceptance: tracing+counters ENABLED, the warmed
    superstep driver shows ZERO additional compiles, dispatches, or
    host syncs versus disabled — the disabled baseline measured by the
    analysis twins, the enabled run measured by the promoted counters
    themselves, and the numbers must agree exactly."""
    from tpu_sgd.analysis.runtime import count_dispatches, count_host_syncs

    X, y = _data(rng)
    w0 = np.zeros(6, np.float32)
    o = _opt()
    o.optimize_with_history((X, y), w0)  # warm every program
    with count_host_syncs() as sc, count_dispatches() as dc:
        o.optimize_with_history((X, y), w0)
    base_dispatch, base_sync = dc["n"], sc["n"]

    sink = ListSink()
    obs.enable(sink)  # tracing + counters + TIME-SERIES, the full config
    try:
        obs_counters.reset()
        o.optimize_with_history((X, y), w0)
        snap = obs_counters.snapshot()
        wins = obs.windows_snapshot()
    finally:
        obs.disable()

    def total(kind):
        return sum(v["n"] for k, v in snap.items()
                   if k.endswith("." + kind))

    assert total("dispatch") == base_dispatch
    assert total("host_sync") == base_sync
    assert total("compile") == 0  # warmed: nothing recompiles
    # and the trace really observed the run: one span per superstep
    assert len(sink.spans("train.superstep")) == 24 // 4
    assert all(s["i0"] % 4 == 1 for s in sink.spans("train.superstep"))
    # ISSUE 13 re-pin: the counts above were measured with the windowed
    # time-series ON (obs.enable default), and it really recorded — the
    # span durations, the per-step loss scalars, and the counter series
    # all landed in the live window ring at ZERO added runtime events
    series = {name for w in wins for name in w["series"]}
    assert "train.superstep" in series
    assert "train.loss" in series
    assert "train.dispatch" in series


def test_enabled_obs_compressed_wire_zero_added_runtime_events(rng):
    """ISSUE 9 satellite: the warmed COMPRESSED host-streamed path
    (top-k + error-feedback wire, fused K) shows ZERO additional
    dispatches or host syncs with tracing+counters enabled — same
    methodology as the superstep pin above — and the wire counters tag
    the feed's bytes by format."""
    from tpu_sgd.analysis.runtime import count_dispatches, count_host_syncs
    from tpu_sgd.optimize.gradient_descent import GradientDescent

    X, y = _data(rng)
    w0 = np.zeros(6, np.float32)

    def mk():
        return (GradientDescent().set_num_iterations(16)
                .set_step_size(0.1).set_mini_batch_fraction(0.5)
                .set_convergence_tol(0.0).set_seed(7)
                .set_host_streaming(True).set_superstep(4)
                .set_ingest_options(wire_compress="topk:0.5"))

    mk().optimize_with_history((X, y), w0)  # warm the fused program
    # disabled compile baseline via the same jax.monitoring funnel the
    # counters use (bench_obs.py methodology): the streamed driver
    # re-jits its per-run fused wrapper, a pre-existing warmed cost the
    # enabled delta must not blame on obs
    from jax._src import monitoring as _monitoring

    base_compiles = [0]

    def _listener(ev_name, dur, **kw):
        if ev_name.endswith("backend_compile_duration"):
            base_compiles[0] += 1

    _monitoring.register_event_duration_secs_listener(_listener)
    try:
        with count_host_syncs() as sc, count_dispatches() as dc:
            mk().optimize_with_history((X, y), w0)
    finally:
        _monitoring.unregister_event_duration_listener(
            _listener)
    base_dispatch, base_sync = dc["n"], sc["n"]

    sink = ListSink()
    obs.enable(sink)
    try:
        obs_counters.reset()
        mk().optimize_with_history((X, y), w0)
        snap = obs_counters.snapshot()
    finally:
        obs.disable()
        obs_counters.reset()

    def total(kind):
        return sum(v["n"] for k, v in snap.items()
                   if k.endswith("." + kind))

    assert total("dispatch") == base_dispatch
    assert total("host_sync") == base_sync
    # enabled-minus-disabled compile delta is ZERO (the absolute count
    # is the streamed driver's pre-existing per-run re-jit, measured by
    # the same funnel disabled)
    assert total("compile") == base_compiles[0]
    # the feed's wire bytes are format-tagged (dense-f32 batches here;
    # the compressed segments ride inside the traced program)
    from tpu_sgd.obs.counters import wire_ratios

    ratios = wire_ratios(snap)
    dense_wire = [r for n_, r in ratios.items()
                  if n_.endswith(".dense-f32")]
    assert dense_wire and dense_wire[0]["n"] == 16 // 4
    assert len(sink.spans("train.superstep")) == 16 // 4


def test_enabled_obs_resident_driver_pins_one_dispatch_windows_syncs(rng):
    """The resident acceptance pin via the promoted counters: a warmed
    whole-run dispatch is exactly ONE train.dispatch, host syncs are
    exactly windows+3 scalars (the same pin the analysis twin holds
    with tracing OFF — tests/test_resident.py), compiles are zero, and
    every one lands under the `train` tag."""
    import jax.numpy as jnp

    from tpu_sgd.optimize.resident_driver import ResidentBookkeeper

    X, y = _data(rng)
    w0 = np.zeros(6, np.float32)
    iters, k, c = 64, 4, 2
    o = _opt(iters=iters, k=k, c=c)
    o.optimize_with_history((X, y), w0)  # warm the one compiled program
    key = ("resident", o.gradient, o.updater, o.config.structure(), k, c)
    loop = o._run_cache[key]
    windows = iters // (k * c)

    sink = ListSink()
    obs.enable(sink)
    try:
        obs_counters.reset()
        hooks = ResidentBookkeeper(o.config, k, c, losses=[], reg_val=0.0,
                                   start_iter=1)
        loop.run(jnp.asarray(w0), 0.0, 1,
                 (o._hyper(), jnp.asarray(X), jnp.asarray(y)), hooks)
        snap = obs_counters.snapshot()
    finally:
        obs.disable()
    assert snap["train.dispatch"]["n"] == 1          # the whole-run program
    assert snap["train.host_sync"]["n"] == windows + 3
    assert sum(v["n"] for n, v in snap.items()
               if n.endswith(".compile")) == 0
    assert snap["train.io_callback"]["n"] == windows
    # every window emitted its span on the callback thread, i0 attrs in
    # cadence order
    wins = sink.spans("train.window")
    assert [w["i0"] for w in wins] == [1 + i * k * c for i in range(windows)]
    assert len(sink.spans("train.resident_dispatch")) == 1


# -- serving: the satellite fields -------------------------------------------

def test_serve_batch_event_carries_enqueue_depth_and_deadline_slack(tmp_path):
    """ISSUE 8 satellite: the batcher records queue depth at enqueue and
    deadline slack at flush; both ride the serve_batch JSONL record and
    old positional constructors keep working."""
    from tpu_sgd.serve.batcher import MicroBatcher
    from tpu_sgd.serve.metrics import ServingMetrics
    from tpu_sgd.utils.events import ServeBatchEvent

    # backward compat: the pre-ISSUE positional constructor still works
    legacy = ServeBatchEvent(3, 2, 4, 0.01, 0, 7)
    assert legacy.enqueue_depth == 0 and legacy.deadline_slack_s == 0.0

    path = str(tmp_path / "serve.jsonl")
    log = JsonLinesEventLog(path)
    metrics = ServingMetrics(listener=log)
    b = MicroBatcher(lambda X: np.asarray(X).sum(axis=1),
                     max_batch=8, max_latency_s=0.01, metrics=metrics)
    futs = [b.submit(np.ones((4,), np.float32)) for _ in range(3)]
    b.stop(drain=True)  # synchronous drain: deterministic single flush
    assert [f.result(1.0) for f in futs] == [4.0] * 3
    log.close()
    rec, = [r for r in JsonLinesEventLog.read(path)
            if r["kind"] == "serve_batch"]
    assert rec["batch_size"] == 3
    # the OLDEST request saw an empty queue at its own enqueue
    assert rec["enqueue_depth"] == 0
    # stop() drained before the 10ms deadline ran out -> positive slack
    # is possible but not guaranteed on a loaded CI box; the field just
    # has to be present and finite
    assert np.isfinite(rec["deadline_slack_s"])


def test_enqueue_depth_reflects_queue_at_each_requests_enqueue():
    from tpu_sgd.serve.batcher import MicroBatcher

    seen = {}

    class Capture:
        def record_reject(self):
            pass

        def record_batch(self, **kw):
            seen.update(kw)

    b = MicroBatcher(lambda X: np.zeros((np.asarray(X).shape[0],)),
                     max_batch=8, max_latency_s=0.01, metrics=Capture())
    for _ in range(4):
        b.submit(np.ones((2,), np.float32))
    b.stop(drain=True)
    # oldest request enqueued into an empty queue; the record carries
    # ITS depth (0), not the last request's (3)
    assert seen["enqueue_depth"] == 0
    assert seen["batch_size"] == 4
    assert "deadline_slack_s" in seen


# -- report pipeline ---------------------------------------------------------

def _mk_trace(tmp_path, name="t.jsonl"):
    """A small synthetic trace with spans, counters, a checkpoint save,
    and a reload — enough surface for every report feature."""
    path = str(tmp_path / name)
    log = JsonLinesEventLog(path)
    log.emit("metric_counters", {"ts": 1.0, "counters": {
        "train.dispatch": {"n": 10, "bytes": 0},
        "serve.reject": {"n": 1, "bytes": 0}}})
    for i, dur in enumerate([0.010, 0.012, 0.011, 0.200]):
        log.emit("trace_span", {
            "name": "serve.batch", "ts": 10.0 + i, "t0_s": 1.0 + i,
            "dur_s": dur, "span_id": i + 1, "parent_id": 0,
            "thread": "flush", "error": None, "batch": 4})
    log.emit("trace_span", {
        "name": "checkpoint.save", "ts": 100.0, "t0_s": 50.0,
        "dur_s": 0.05, "span_id": 90, "parent_id": 0,
        "thread": "MainThread", "error": None, "iteration": 40})
    log.emit("trace_event", {
        "name": "reliability.retry", "ts": 101.0, "t0_s": 51.0,
        "thread": "MainThread", "subsystem": "ingest", "attempt": 1})
    log.emit("serve_reload", {"ts": 130.0, "event": "reloaded",
                              "version": 40, "previous_version": None})
    log.emit("obs_alert", {
        "ts": 131.0, "rule": "shed-rate", "series": "serve.lane.batch",
        "value": 0.6, "bound": 0.3, "window_index": 131,
        "t_start": 131.0, "t_end": 132.0, "detail": "test alert"})
    log.emit("metric_counters", {"ts": 200.0, "counters": {
        "train.dispatch": {"n": 25, "bytes": 0},
        "serve.reject": {"n": 1, "bytes": 0}}})
    log.close()
    return path


def test_report_span_stats_counters_and_staleness(tmp_path):
    records = obs_report.load_trace(_mk_trace(tmp_path))
    stats = obs_report.span_stats(records)
    sb = stats["serve.batch"]
    assert sb["count"] == 4
    assert sb["p50_s"] == 0.011   # nearest-rank over [.010,.011,.012,.200]
    assert sb["p99_s"] == 0.200
    assert sb["max_s"] == 0.200
    deltas = obs_report.counter_deltas(records)
    assert deltas == {"train.dispatch": {"n": 15, "bytes": 0}}  # 25-10; 0-delta dropped
    stale, = obs_report.staleness_samples(records)
    assert stale == {"version": 40, "staleness_s": 30.0}


def test_report_chrome_trace_export(tmp_path):
    records = obs_report.load_trace(_mk_trace(tmp_path))
    doc = obs_report.to_chrome_trace(records)
    evs = doc["traceEvents"]
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(complete) == 5 and len(instants) == 1
    assert {m["args"]["name"] for m in metas} == {"flush", "MainThread"}
    sb = [e for e in complete if e["name"] == "serve.batch"][0]
    assert sb["ts"] == pytest.approx(1.0 * 1e6)
    assert sb["dur"] == pytest.approx(0.010 * 1e6)
    assert sb["args"]["batch"] == 4  # non-core fields ride args
    assert json.dumps(doc)  # serializable as-is


def test_slo_evaluation_pass_fail_and_malformed(tmp_path):
    records = obs_report.load_trace(_mk_trace(tmp_path))
    verdicts = obs_report.evaluate_slos(records, {"slos": [
        {"name": "p50", "metric": "span_p50_s", "span": "serve.batch",
         "max": 0.05},
        {"name": "p99", "metric": "span_p99_s", "span": "serve.batch",
         "max": 0.05},
        {"name": "no-drops", "metric": "counter", "counter": "serve.reject",
         "max": 0},
        {"name": "fresh", "metric": "staleness_s", "max": 60.0},
        {"name": "absent-count", "metric": "span_count",
         "span": "never.fired", "max": 0},
        {"name": "absent-latency", "metric": "span_p99_s",
         "span": "never.fired", "max": 1.0},
    ]})
    by = {v["name"]: v for v in verdicts}
    assert by["p50"]["ok"] and not by["p99"]["ok"]
    assert by["no-drops"]["ok"]          # counter DELTA is 0 across the trace
    assert by["fresh"]["ok"] and by["fresh"]["value"] == 30.0
    assert by["absent-count"]["ok"]      # count bound of 0 passes on absence
    assert not by["absent-latency"]["ok"]  # unevaluable latency ≠ free pass
    with pytest.raises(ValueError):
        obs_report.evaluate_slos(records, {"slos": [
            {"name": "typo", "metric": "span_p42_s", "span": "x", "max": 1}]})
    with pytest.raises(ValueError):
        obs_report.evaluate_slos(records, {"slos": [
            {"name": "no-bound", "metric": "staleness_s"}]})


def test_report_cli_exit_codes_and_chrome_file(tmp_path, capsys):
    trace = _mk_trace(tmp_path)
    slo_ok = tmp_path / "ok.json"
    slo_ok.write_text(json.dumps({"slos": [
        {"name": "p50", "metric": "span_p50_s", "span": "serve.batch",
         "max": 0.05}]}))
    slo_bad = tmp_path / "bad.json"
    slo_bad.write_text(json.dumps({"slos": [
        {"name": "p99", "metric": "span_p99_s", "span": "serve.batch",
         "max": 0.05}]}))
    chrome = str(tmp_path / "chrome.json")
    assert obs_report.main([trace, "--slo", str(slo_ok),
                            "--chrome", chrome]) == 0
    out = capsys.readouterr().out
    assert "SLO PASS: p50" in out and "per-stage breakdown" in out
    with open(chrome) as f:
        assert len(json.load(f)["traceEvents"]) > 0
    assert obs_report.main([trace, "--slo", str(slo_bad)]) == 1
    assert "SLO FAIL: p99" in capsys.readouterr().out
    # usage errors are 2, distinct from violations
    assert obs_report.main([str(tmp_path / "missing.jsonl")]) == 2
    # ... including an unwritable --chrome export path
    assert obs_report.main(
        [trace, "--chrome", str(tmp_path / "no_dir" / "t.json")]) == 2
    assert "cannot write Chrome trace" in capsys.readouterr().err
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    assert obs_report.main([trace, "--slo", str(garbage)]) == 2
    # --json emits one machine-readable object
    assert obs_report.main([trace, "--json", "--slo", str(slo_ok)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["spans"]["serve.batch"]["count"] == 4
    assert doc["slos"][0]["ok"] is True


def test_chaos_soak_default_slos_are_well_formed():
    """The soak's built-in SLO doc must stay on the report schema: every
    entry evaluates (no ValueError) — on an empty trace the structural
    min-bounds simply FAIL, they never error or vacuously pass."""
    from scripts.chaos_soak import DEFAULT_SLOS

    verdicts = obs_report.evaluate_slos([], DEFAULT_SLOS)
    assert len(verdicts) == len(DEFAULT_SLOS["slos"])
    by = {v["name"]: v for v in verdicts}
    # a soak that emitted nothing fails its count gates loudly
    assert not by["train-windows-fired"]["ok"]
    assert not by["callback-windows-counted"]["ok"]


def test_report_tolerates_crash_torn_tail(tmp_path):
    """The soak/crash forensics contract, inherited from read(): a torn
    trailing line is skipped, an interior malformed line still raises."""
    trace = _mk_trace(tmp_path)
    with open(trace, "a") as f:
        f.write('{"kind": "trace_span", "name": "torn')  # no newline
    records = obs_report.load_trace(trace)
    assert len(obs_report.span_stats(records)["serve.batch"]) > 0
    with open(trace, "a") as f:
        f.write('ed"}\n{"interior": garbage}\n{"kind": "x"}\n')
    with pytest.raises(json.JSONDecodeError):
        obs_report.load_trace(trace)


# -- windowed time-series (ISSUE 13) -----------------------------------------

def _mk_store(width=1.0, **kw):
    """A WindowStore on a synthetic clock (no sleeping in tests)."""
    from tpu_sgd.obs.timeseries import WindowStore

    clock = {"t": 0.0}
    store = WindowStore(width_s=width, clock=lambda: clock["t"], **kw)
    return store, clock


def test_window_store_aggregates_and_nearest_rank_parity():
    """Per-window count/sum/max are exact and the window p50/p99 agree
    with ServingMetrics' live scrape — ONE percentile rule everywhere
    (serve.metrics.nearest_rank)."""
    from tpu_sgd.serve.metrics import ServingMetrics

    store, clock = _mk_store()
    samples = [0.010, 0.012, 0.011, 0.200, 0.003, 0.050, 0.007]
    for v in samples:
        store.observe("serve.batch", value=v)
    clock["t"] = 1.5  # roll the window
    store.observe("serve.batch", value=1.0)
    w0 = store.snapshot()[0]
    assert w0["closed"] is True
    s = w0["series"]["serve.batch"]
    assert s["count"] == len(samples)
    assert s["sum"] == pytest.approx(sum(samples))
    assert s["max"] == 0.200
    metrics = ServingMetrics()
    metrics.record_batch(queue_depth=0, batch_size=len(samples),
                         padded_size=8, latencies=samples,
                         reject_count=0)
    assert s["p50"] == metrics.latency_percentile(50)
    assert s["p99"] == metrics.latency_percentile(99)


def test_window_store_ring_and_sample_bounds_under_long_run():
    """The acceptance bound: memory is bounded by WINDOW COUNT, never
    run length — a 10k-window synthetic run retains max_windows closed
    windows, and a 10k-observation window caps its sample buffer while
    count/sum/max stay exact."""
    store, clock = _mk_store(width=1.0, max_windows=32,
                             samples_per_series=64)
    for i in range(10_000):
        clock["t"] = float(i)
        store.observe("train.loss", value=float(i % 7))
    assert len(store._windows) == 32          # the ring, full and bounded
    assert len(store.snapshot()) == 33        # + the open window
    # one giant window: samples capped, exact aggregates kept
    store2, _ = _mk_store(samples_per_series=64)
    for i in range(10_000):
        store2.observe("x", value=float(i))
    s = store2.snapshot()[0]["series"]["x"]
    assert s["count"] == 10_000
    assert s["samples_capped"] is True
    assert s["max"] == 9999.0
    assert s["sum"] == pytest.approx(sum(range(10_000)))


def test_window_store_flush_and_late_records():
    """flush() closes the open window (fires listeners) so a finished
    run's trailing data evaluates; a record with an OLDER ts than the
    open window folds into the open window, never reopens a closed
    one."""
    store, clock = _mk_store()
    closed = []
    store.add_close_listener(lambda w: closed.append(w))
    clock["t"] = 5.5
    store.observe("a", value=1.0)
    store.observe("b", ts=4.2)  # late cross-thread record: folds in
    store.flush()
    assert len(closed) == 1
    assert closed[0]["series"]["a"]["count"] == 1
    assert closed[0]["series"]["b"]["count"] == 1
    assert store.snapshot() == [closed[0]]  # flush closed it into the ring
    # a mid-run flush must not duplicate a ring index: the rest of the
    # same wall-clock second lands in the NEXT window
    store.observe("a", value=2.0)  # clock still inside flushed window 5
    store.flush()
    assert [w["index"] for w in store.snapshot()] == [5, 6]


def test_window_store_concurrent_close_joins_dispatch_thread():
    """The racing schedule the ISSUE 19 fix pins: ``close()`` snapshots
    the dispatch-thread handle UNDER ``_dispatch_cv`` (an unlocked read
    raced ``add_close_listener``'s lazy spawn and could miss the thread
    entirely), then joins OUTSIDE the cv — so N concurrent closers all
    return with the dispatch thread really dead, never deadlocked on
    the loop's finally-block."""
    store, _ = _mk_store()
    store.add_close_listener(lambda snap: None)
    t = store._dispatch_thread
    assert t is not None and t.is_alive()
    closers = [threading.Thread(target=store.close, name=f"close{i}")
               for i in range(3)]
    for c in closers:
        c.start()
    for c in closers:
        c.join(timeout=30)
    assert not any(c.is_alive() for c in closers)  # no deadlock
    assert not t.is_alive()  # really joined, not leaked as a daemon


# -- detectors: trip / no-trip fixtures per rule -----------------------------

def _run_detector(detector, feeds, width=1.0):
    """Drive windows through a private store+engine: ``feeds`` is one
    dict per window, series -> list of observe kwargs."""
    from tpu_sgd.obs.detect import DetectorEngine
    from tpu_sgd.obs.timeseries import WindowStore

    clock = {"t": 0.5}
    store = WindowStore(width_s=width, clock=lambda: clock["t"])
    engine = DetectorEngine([detector])
    store.add_close_listener(engine.on_window_close)
    for wi, feed in enumerate(feeds):
        clock["t"] = wi + 0.5
        for series, obs_list in feed.items():
            for kw in obs_list:
                store.observe(series, **kw)
    store.flush()
    return engine


def _vals(v, n=1):
    return [{"value": v}] * n


def test_detector_loss_divergence_trip_and_no_trip():
    from tpu_sgd.obs.detect import LossDivergenceDetector

    steady = [{"train.loss": _vals(1.0, 4)}] * 3
    eng = _run_detector(LossDivergenceDetector(),
                        steady + [{"train.loss": _vals(10.0, 4)}])
    assert eng.trip_counts() == {"loss-divergence": 1}
    # a converging run never trips
    eng = _run_detector(LossDivergenceDetector(), [
        {"train.loss": _vals(1.0 / (i + 1), 4)} for i in range(6)])
    assert eng.trip_counts() == {}


def test_detector_loss_plateau_trip_and_not_in_defaults():
    from tpu_sgd.obs.detect import LossPlateauDetector, default_detectors

    flat = [{"train.loss": _vals(0.5, 4)}] * 5
    eng = _run_detector(LossPlateauDetector(), flat)
    assert eng.trip_counts() == {"loss-plateau": 1}
    falling = [{"train.loss": _vals(1.0 / (i + 1), 4)} for i in range(5)]
    eng = _run_detector(LossPlateauDetector(), falling)
    assert eng.trip_counts() == {}
    # a converged run plateaus legitimately: the rule is control-plane
    # opt-in, NOT part of the default anomaly set
    assert "loss-plateau" not in {d.rule for d in default_detectors()}


def test_detector_staleness_creep_trip_and_no_trip():
    from tpu_sgd.obs.detect import StalenessCreepDetector

    eng = _run_detector(StalenessCreepDetector(max_staleness=8),
                        [{"replica.push.staleness": _vals(2.0, 5)}])
    assert eng.trip_counts() == {}
    eng = _run_detector(StalenessCreepDetector(max_staleness=8),
                        [{"replica.push.staleness": _vals(2.0, 5)},
                         {"replica.push.staleness": _vals(12.0, 1)}])
    assert eng.trip_counts() == {"staleness-creep": 1}


def test_detector_shed_rate_trip_no_trip_and_min_offered():
    from tpu_sgd.obs.detect import LaneRejectionDetector

    def lane_feed(admitted, shed):
        return {"serve.admitted.interactive": [{}] * admitted,
                "serve.shed.interactive": [{}] * shed}

    eng = _run_detector(LaneRejectionDetector(), [lane_feed(30, 30)])
    assert eng.trip_counts() == {"shed-rate": 1}
    # healthy lane: rate under threshold
    eng = _run_detector(LaneRejectionDetector(), [lane_feed(30, 2)])
    assert eng.trip_counts() == {}
    # a tiny window cannot trip on 3 requests (min_offered)
    eng = _run_detector(LaneRejectionDetector(), [lane_feed(1, 2)])
    assert eng.trip_counts() == {}


def test_detector_straggler_trip_no_trip_and_fleet_silence():
    """The rule is cumulative over fleet PROGRESS, not wall clock: a
    silent worker trips once its peers accumulate min_fleet_steps
    steps — however many windows that takes — so ambient load that
    slows the whole fleet down equally can never flake it."""
    from tpu_sgd.obs.detect import StragglerDetector

    def fleet(*counts):
        return {f"replica.step[w{i}]": _vals(0.01, c)
                for i, c in enumerate(counts) if c}

    active = [fleet(5, 5, 5)]
    # w1 goes silent while the others accumulate 10 peer steps: trip —
    # whether the progress arrives fast (one window) or slow (many)
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        active + [fleet(5, 0, 5)])
    assert eng.trip_counts() == {"replica-straggler": 1}
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        active + [fleet(1, 0, 1)] * 5)
    assert eng.trip_counts() == {"replica-straggler": 1}
    # a lagging-but-alive worker under the threshold: no trip (the SSP
    # progress bound caps live lag at ~(n-1)*tau peer steps)
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        active + [fleet(2, 0, 2), fleet(2, 1, 2)] * 3)
    assert eng.trip_counts() == {}
    # the whole fleet goes silent (round ended): NOT a straggler
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        active + [fleet(0, 0, 0)] * 6)
    assert eng.trip_counts() == {}


def test_detector_straggler_membership_events_drive_the_roster():
    """Membership is event-driven (the replica.join/rejoin/leave
    fan-out): a CLEAN leave removes the worker — its residual deficit
    cannot false-trip the NEXT fleet sharing this engine — while a
    death-leave (the .error twin) keeps accumulating until the rejoin,
    and a joined-but-never-stepped worker is tracked from its join."""
    from tpu_sgd.obs.detect import StragglerDetector

    def fleet(*counts, extra=None):
        d = {f"replica.step[w{i}]": _vals(0.01, c)
             for i, c in enumerate(counts) if c}
        d.update(extra or {})
        return d

    # run A ends with w1 slightly behind, leaves CLEANLY; run B's
    # early windows must not inherit the deficit
    run_a_end = fleet(4, 0, 4, extra={
        "replica.leave[w0]": [{}], "replica.leave[w1]": [{}],
        "replica.leave[w2]": [{}]})
    run_b = [fleet(0, 0, 0, extra={f"replica.join[w{i}]": [{}]
                                   for i in range(3)}),
             fleet(4, 0, 4), fleet(2, 1, 2)]
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        [fleet(3, 3, 3), run_a_end] + run_b)
    assert eng.trip_counts() == {}
    # a DEATH-leave keeps the entry hunting: the deficit crosses the
    # threshold while the worker is gone
    death = [fleet(3, 3, 3),
             fleet(3, 0, 3, extra={"replica.leave.error[w1]": [{}]}),
             fleet(3, 0, 3)]
    eng = _run_detector(StragglerDetector(min_fleet_steps=10), death)
    assert eng.trip_counts() == {"replica-straggler": 1}
    # a worker that JOINED but never stepped is tracked from the join:
    # peers moving on without it trips the rule
    spawn_dead = [fleet(0, 0, extra={"replica.join[w0]": [{}],
                                     "replica.join[w1]": [{}]}),
                  fleet(6, 0), fleet(6, 0)]
    eng = _run_detector(StragglerDetector(min_fleet_steps=10),
                        spawn_dead)
    assert eng.trip_counts() == {"replica-straggler": 1}


def test_detector_wire_ratio_collapse_trip_exempt_and_no_trip():
    from tpu_sgd.obs.detect import WireRatioDetector

    def wire(fmt, phys, logical):
        return {f"replica.wire.{fmt}": [{"nbytes": phys}],
                f"replica.wire.{fmt}.logical": [{"nbytes": logical}]}

    # compression collapsed: topk shipping nearly-dense bytes
    eng = _run_detector(WireRatioDetector(), [wire("topk", 100_000,
                                                  105_000)])
    assert eng.trip_counts() == {"wire-ratio-collapse": 1}
    # healthy compression
    eng = _run_detector(WireRatioDetector(), [wire("topk", 10_000,
                                                  500_000)])
    assert eng.trip_counts() == {}
    # dense-f32's 1.0x ratio is BY CONSTRUCTION, never a collapse
    eng = _run_detector(WireRatioDetector(), [wire("dense-f32", 100_000,
                                                  100_000)])
    assert eng.trip_counts() == {}


def test_detector_dispatch_regression_trip_no_trip_and_floor():
    from tpu_sgd.obs.detect import DispatchRegressionDetector

    steady = [{"train.dispatch": [{"n": 100}]}] * 4
    eng = _run_detector(DispatchRegressionDetector(),
                        steady + [{"train.dispatch": [{"n": 400}]}])
    assert eng.trip_counts() == {"dispatch-regression": 1}
    eng = _run_detector(DispatchRegressionDetector(), steady * 2)
    assert eng.trip_counts() == {}
    # idle-phase noise (median under the floor) cannot trip
    tiny = [{"train.dispatch": [{"n": 2}]}] * 4
    eng = _run_detector(DispatchRegressionDetector(),
                        tiny + [{"train.dispatch": [{"n": 12}]}])
    assert eng.trip_counts() == {}


def test_detector_engine_transition_dedup_and_rearm():
    """A rule that stays tripped across consecutive windows emits ONE
    alert; after a clean window it re-arms and a new episode emits a
    new alert."""
    from tpu_sgd.obs.detect import StalenessCreepDetector

    hot = {"replica.push.staleness": _vals(12.0, 2)}
    cool = {"replica.push.staleness": _vals(1.0, 2)}
    eng = _run_detector(StalenessCreepDetector(max_staleness=8),
                        [hot, hot, hot, cool, hot])
    assert eng.trip_counts() == {"staleness-creep": 2}


def test_detector_alert_is_typed_record_counter_and_flightrec(tmp_path):
    """The full alert contract end-to-end through the facade: a shed
    spike trips the rule, the trip is a typed obs_alert record on the
    trace sink, an obs.alert.<rule> counter, an active alert on the
    engine, and a flight-recorder dump."""
    import os

    fr = str(tmp_path / "fr.jsonl")
    sink = ListSink()
    obs.enable(sink, detect=True, window_s=0.05, flightrec=fr)
    try:
        for _ in range(30):
            obs_counters.inc("serve.admitted.interactive")
            obs_counters.inc("serve.shed.interactive")
        time.sleep(0.06)
        obs_counters.inc("serve.admitted.interactive")
        obs.flush_windows()
        alerts = [p for k, p in sink.records if k == "obs_alert"]
        assert alerts and alerts[0]["rule"] == "shed-rate"
        assert alerts[0]["series"] == "serve.lane.interactive"
        assert obs_counters.snapshot()["obs.alert.shed-rate"]["n"] >= 1
        eng = obs.detector_engine()
        assert eng is not None
        assert eng.trip_counts().get("shed-rate", 0) >= 1
        assert os.path.exists(fr)
        recs = JsonLinesEventLog.read(fr)
        assert recs[0]["kind"] == "flightrec_meta"
        assert recs[0]["reason"].startswith("alert:shed-rate")
        assert any(r["kind"] == "obs_window" for r in recs)
    finally:
        obs.disable()
    assert obs.detector_engine() is None  # torn down with the layer


def test_clean_seeded_run_trips_no_detectors(rng):
    """The no-false-positive pin: a fault-free seeded train + serve
    flow under the DEFAULT detector set raises zero alerts."""
    from tpu_sgd.models import LinearRegressionModel
    from tpu_sgd.serve import Server

    X, y = _data(rng)
    w0 = np.zeros(6, np.float32)
    o = _opt()
    o.optimize_with_history((X, y), w0)  # warm before enabling
    sink = ListSink()
    obs.enable(sink, detect=True, window_s=0.25)
    try:
        w, _ = o.optimize_with_history((X, y), w0)
        with Server(LinearRegressionModel(np.asarray(w), 0.0),
                    max_latency_s=0.002) as srv:
            futs = [srv.submit(X[i]) for i in range(64)]
            for f in futs:
                f.result(timeout=30)
        obs.flush_windows()
        assert [k for k, _ in sink.records if k == "obs_alert"] == []
        assert obs.detector_engine().trip_counts() == {}
    finally:
        obs.disable()


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_dumps_on_error_unwind(tmp_path):
    """An error crossing a span boundary triggers a dump: the ring
    holds the erroring span record itself, the meta header names the
    span, and the run keeps going (the recorder never re-raises)."""
    trace = str(tmp_path / "t.jsonl")
    fr = str(tmp_path / "fr.jsonl")
    obs.enable(trace, flightrec=fr)
    try:
        with obs.span("serve.batch", batch=4):
            pass  # a healthy span first: it must be IN the ring
        with pytest.raises(ValueError):
            with obs.span("train.superstep", i0=9):
                raise ValueError("boom")
    finally:
        obs.disable()
    recs = JsonLinesEventLog.read(fr)
    meta = recs[0]
    assert meta["kind"] == "flightrec_meta"
    assert meta["reason"] == "span-error:train.superstep"
    assert meta["detail"] == "ValueError"
    spans = [r for r in recs if r["kind"] == "trace_span"]
    assert [s["name"] for s in spans] == ["serve.batch",
                                          "train.superstep"]
    assert spans[1]["error"] == "ValueError"


def test_flight_recorder_ring_is_bounded_and_dump_replaces(tmp_path):
    from tpu_sgd.obs.flightrec import FlightRecorder

    fr = FlightRecorder(str(tmp_path / "fr.jsonl"), capacity=8)
    for i in range(100):
        fr.record("trace_event", {"name": "e", "i": i})
    assert fr.trigger("first") is not None
    recs = JsonLinesEventLog.read(fr.path)
    assert len(recs) == 1 + 8  # meta + the BOUNDED ring tail
    assert [r["i"] for r in recs[1:]] == list(range(92, 100))
    fr.record("trace_event", {"name": "e", "i": 100})
    fr.trigger("second", detail="why")
    recs = JsonLinesEventLog.read(fr.path)  # replaced, not appended
    assert recs[0]["reason"] == "second"
    assert recs[0]["dump_ordinal"] == 2
    assert recs[-1]["i"] == 100


# -- live series feeds -------------------------------------------------------

def test_server_healthz_carries_windows_snapshot(rng):
    from tpu_sgd.models import LinearRegressionModel
    from tpu_sgd.serve import Server

    X, _ = _data(rng)
    model = LinearRegressionModel(np.zeros(6, np.float32), 0.0)
    with Server(model, max_latency_s=0.002) as srv:
        srv.predict(X[0], timeout=30)
        assert srv.healthz()["windows"] is None  # layer off: honest None
    sink = ListSink()
    obs.enable(sink, window_s=0.05)
    try:
        with Server(model, max_latency_s=0.002) as srv:
            for i in range(8):
                srv.predict(X[i], timeout=30)
            wins = srv.healthz()["windows"]
    finally:
        obs.disable()
    assert wins, "no serve windows recorded"
    names = {n for w in wins for n in w["series"]}
    assert any(n.startswith("serve.") for n in names)


def test_replica_driver_windows_snapshot(rng):
    from tpu_sgd.replica import ReplicaDriver

    X, y = _data(rng, n=64)
    w0 = np.zeros(6, np.float32)
    sink = ListSink()
    obs.enable(sink, window_s=0.05)
    try:
        drv = (ReplicaDriver().set_num_iterations(8).set_step_size(0.1)
               .set_mini_batch_fraction(1.0).set_convergence_tol(0.0)
               .set_seed(3).set_workers(2).set_staleness(0))
        drv.optimize_with_history((X, y), w0)
        wins = drv.last_windows_snapshot
    finally:
        obs.disable()
    assert wins, "no replica windows recorded"
    names = {n for w in wins for n in w["series"]}
    assert any(n.startswith("replica.step[") for n in names)
    assert "replica.push.staleness" in names  # the version-gap series
    assert drv.windows() is None  # layer off again: honest None


# -- report: windows, alerts, window SLO metrics -----------------------------

def test_report_windowed_stats_alerts_and_staleness_buckets(tmp_path):
    records = obs_report.load_trace(_mk_trace(tmp_path))
    wins = obs_report.windowed_stats(records, 1.0)
    by_idx = {w["index"]: w for w in wins}
    # the four serve.batch spans land one per second at ts 10..13
    for i in range(10, 14):
        assert by_idx[i]["spans"]["serve.batch"]["count"] == 1
    assert by_idx[131]["alerts"][0]["rule"] == "shed-rate"
    # the staleness join gains its time dimension: bucketed at reload ts
    assert by_idx[130]["staleness"] == [
        {"version": 40, "staleness_s": 30.0}]
    txt = obs_report.render_windows(wins)
    assert "window 10" in txt and "ALERT [shed-rate]" in txt
    stats = obs_report.alert_stats(records)
    assert stats["count"] == 1 and stats["by_rule"] == {"shed-rate": 1}
    # a foreign/drifted obs_alert missing value/bound degrades the
    # render, never crashes the report or the live watcher
    weird = records + [{"kind": "obs_alert", "ts": 132.0,
                        "rule": "custom", "series": "x"}]
    assert "value=?" in obs_report.render_report(weird)
    assert "value=?" in obs_report.render_windows(
        obs_report.windowed_stats(weird, 1.0))


def test_report_window_slo_metrics_absent_is_violation(tmp_path):
    records = obs_report.load_trace(_mk_trace(tmp_path))
    verdicts = obs_report.evaluate_slos(records, {"slos": [
        {"name": "w-p99-bad", "metric": "window_span_p99_s",
         "span": "serve.batch", "window_s": 1.0, "max": 0.05},
        {"name": "w-p99-ok", "metric": "window_span_p99_s",
         "span": "serve.batch", "window_s": 1.0, "max": 0.5},
        {"name": "w-absent", "metric": "window_span_p99_s",
         "span": "never.fired", "window_s": 1.0, "max": 10.0},
        {"name": "w-gap", "metric": "window_span_count_min",
         "span": "serve.batch", "window_s": 1.0, "min": 1},
        {"name": "alerts-any", "metric": "alert_count", "max": 0},
        {"name": "alerts-rule", "metric": "alert_count",
         "rule": "shed-rate", "min": 1},
        {"name": "alerts-other", "metric": "alert_count",
         "rule": "replica-straggler", "max": 0},
    ]})
    by = {v["name"]: v for v in verdicts}
    # the ts-13 window holds the 0.200s span: worst window p99
    assert not by["w-p99-bad"]["ok"] and by["w-p99-bad"]["value"] == 0.200
    assert by["w-p99-ok"]["ok"]
    # a windowed latency bound over a span that never fired: violation
    assert not by["w-absent"]["ok"] and by["w-absent"]["value"] is None
    # the grid spans ts 10..131 — the gap windows count ZERO, never
    # silent green
    assert not by["w-gap"]["ok"] and by["w-gap"]["value"] == 0
    assert not by["alerts-any"]["ok"]  # the trace carries one alert
    assert by["alerts-rule"]["ok"]
    assert by["alerts-other"]["ok"]    # absent rule counts 0, max 0 holds
    with pytest.raises(ValueError):
        obs_report.evaluate_slos(records, {"slos": [
            {"name": "no-width", "metric": "window_span_p99_s",
             "span": "serve.batch", "max": 1.0}]})


def test_report_cli_window_flag_and_json(tmp_path, capsys):
    trace = _mk_trace(tmp_path)
    assert obs_report.main([trace, "--window", "1.0"]) == 0
    out = capsys.readouterr().out
    assert "time-bucketed tables" in out and "window 10" in out
    assert "alerts (1 typed obs_alert trips)" in out
    assert obs_report.main([trace, "--window", "1.0", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["alerts"]["by_rule"] == {"shed-rate": 1}
    assert any(w["index"] == 131 for w in doc["windows"])


# -- the watch CLI -----------------------------------------------------------

def test_watch_once_renders_windows_and_alerts(tmp_path, capsys):
    from tpu_sgd.obs import watch as obs_watch

    trace = _mk_trace(tmp_path)
    with open(trace, "a") as f:
        f.write('{"kind": "torn_mid')  # a live producer mid-write
    assert obs_watch.main([trace, "--once", "--window", "1.0",
                           "--active-s", "1000"]) == 0
    out = capsys.readouterr().out
    assert "window 10" in out
    assert "ACTIVE ALERTS" in out and "shed-rate" in out
    assert "parse_errors" not in out  # the torn tail is buffered, not
    #                                   an error
    assert obs_watch.main([str(tmp_path / "missing.jsonl"),
                           "--once"]) == 2


def test_watch_tail_is_incremental_and_tolerant(tmp_path):
    from tpu_sgd.obs.watch import TraceTail

    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"kind": "trace_event", "name": "a", "ts": 1.0}\n')
        f.write('{"kind": "trace_')  # torn mid-write
    tail = TraceTail(path)
    recs = tail.poll()
    assert [r["name"] for r in recs] == ["a"]
    with open(path, "a") as f:  # the producer finishes the line
        f.write('event", "name": "b", "ts": 2.0}\n')
        f.write('garbage line\n')  # malformed interior: skipped, counted
        f.write('{"kind": "trace_event", "name": "c", "ts": 3.0}\n')
    recs = tail.poll()
    assert [r["name"] for r in recs] == ["b", "c"]
    assert tail.parse_errors == 1
    assert tail.poll() == []  # EOF: nothing new
    tail.close()


# -- the bench regression gate -----------------------------------------------

def test_bench_gate_self_check_perturbed_and_missing(tmp_path, capsys):
    """The CI contract: exit 0 on the committed baselines, 1 on a
    deliberately perturbed candidate (the gate provably fails bad
    numbers), 1 on a candidate missing a headline metric, 2 on an
    unreadable baseline."""
    import os
    import shutil

    from scripts import bench_gate

    assert bench_gate.main([]) == 0  # the committed files gate green
    capsys.readouterr()
    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(bench_gate.__file__)))
    cand = tmp_path / "cand"
    cand.mkdir()
    for fname in bench_gate.GATES:
        shutil.copy(os.path.join(repo, fname), cand / fname)
    with open(cand / "BENCH_OBS.json") as f:
        doc = json.load(f)
    doc["headline"]["superstep_count_deltas"]["dispatches"] = 3
    with open(cand / "BENCH_OBS.json", "w") as f:
        json.dump(doc, f)
    assert bench_gate.main(["--candidate-dir", str(cand)]) == 1
    assert "GATE FAIL" in capsys.readouterr().out
    # a vanished candidate metric is a regression, not a skip
    del doc["headline"]["superstep_count_deltas"]
    doc["headline"]["superstep_count_deltas"] = {}
    with open(cand / "BENCH_OBS.json", "w") as f:
        json.dump(doc, f)
    assert bench_gate.main(["--candidate-dir", str(cand)]) == 1
    capsys.readouterr()
    # unreadable baseline = usage-error class
    assert bench_gate.main(["--baseline-dir",
                            str(tmp_path / "nope")]) == 2


def test_bench_gate_direction_semantics():
    from scripts.bench_gate import Gate, check_gate

    base = {"x": {"ratio": 100.0, "count": 10}}
    # higher-is-better: improvement passes, collapse beyond band fails
    g = Gate("x/ratio", "higher", rel_tol=0.1)
    assert check_gate(g, base, {"x": {"ratio": 150.0}})["ok"]
    assert check_gate(g, base, {"x": {"ratio": 91.0}})["ok"]
    assert not check_gate(g, base, {"x": {"ratio": 85.0}})["ok"]
    # lower-is-better: fewer dispatches always pass
    g = Gate("x/count", "lower", rel_tol=0.1)
    assert check_gate(g, base, {"x": {"count": 5}})["ok"]
    assert not check_gate(g, base, {"x": {"count": 12}})["ok"]
    # equal: drift either way beyond the band fails
    g = Gate("x/count", "equal")
    assert check_gate(g, base, {"x": {"count": 10}})["ok"]
    assert not check_gate(g, base, {"x": {"count": 9}})["ok"]
