"""The record of what a fit BUILDS (``tpu_sgd/obs/builds.py``): every trace,
lowering and compile-or-cache-read JAX makes under ``fit.run``, ``train.run``
or ``stream.run`` is kept as a ``build.*`` span of that root, tracing or not;
a fit that builds nothing leaves nothing and costs nothing.  Beside them
stands ``build.restore``: the first call of ``_runner``'s program through the
store of exported runners (``tpu_sgd/optimize/run_store.py``).

The ``jax.monitoring`` names the record depends on are the six in
``builds._KINDS``, ``_HIT``, ``_MISS`` and ``_READ``: a JAX that renames one
fails here (``test_a_first_fit_leaves_one_root...``, ``test_the_cache_read...``)
and not in a metric that reads None."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_sgd
from tpu_sgd import obs
from tpu_sgd.obs import builds, spans as obs_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("build.trace", "build.lower", "build.compile")
RESTORE = "build.restore"


@pytest.fixture(autouse=True)
def _fresh():
    """Every test starts with no root kept, none open and tracing off."""
    obs.disable()
    builds._ROOTS.clear()
    del builds._BUILT[:]
    assert builds._OPEN is None
    yield
    obs.disable()
    assert builds._OPEN is None


def _data(rows=256, features=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, features)).astype(np.float32)
    y = (X @ rng.standard_normal((features,)) > 0).astype(np.float32)
    return X, y


def _optimizer(step_size=0.5):
    return (tpu_sgd.GradientDescent(tpu_sgd.LogisticGradient(),
                                    tpu_sgd.SquaredL2Updater())
            .set_step_size(step_size).set_num_iterations(4)
            .set_mini_batch_fraction(0.5).set_convergence_tol(0.0)
            .set_seed(42))


def _fit(opt, X, y):
    return opt.optimize_with_history(
        (jnp.asarray(X), jnp.asarray(y)), np.zeros((X.shape[1],), np.float32))


def _named(root, kind, fun="sgd_run"):
    return [s for s in root["spans"] if s["name"] == kind and fun in s["fun"]]


class _Listeners:
    """This test's own listeners on ``jax.monitoring``: what JAX fires."""

    def __init__(self):
        self.calls = 0

    def __call__(self, event, *args, **kwargs):
        self.calls += 1

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_time_span_listener(self)
        monitoring.register_event_listener(self)
        monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_time_span_listener(self)
        monitoring.unregister_event_listener(self)
        monitoring.unregister_event_duration_listener(self)


class _Sink:
    def __init__(self, broken=False):
        self.records, self.broken = [], broken

    def emit(self, kind, payload):
        if self.broken and payload["name"].startswith("build."):
            raise RuntimeError("sink intentionally broken")
        self.records.append((kind, dict(payload)))

    def spans(self, prefix):
        return [p for k, p in self.records
                if k == "trace_span" and p["name"].startswith(prefix)]


# -- what a fit leaves ---------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_first_fit_leaves_one_root_with_sgd_runs_trace_lowering_and_compile(
        kind):
    X, y = _data()
    _fit(_optimizer(), X, y)
    (root,) = obs.build_roots()
    assert root["name"] == "train.run" and root["span_id"] == 0
    assert _named(root, kind), [s["fun"] for s in root["spans"]]
    for s in root["spans"]:
        assert s["name"] in KINDS + (RESTORE,) \
            and s["thread"] == "MainThread"
        # on the root's clock and inside it
        assert root["start"] <= s["start"] <= s["end"] \
            <= root["start"] + root["dur_s"]
        assert ("cache_hit" in s) == (s["name"] == "build.compile")


def test_short_traces_are_folded_into_a_count_and_a_sum():
    X, y = _data()
    _fit(_optimizer(), X, y)
    (root,) = obs.build_roots()
    traces = [s for s in root["spans"] if s["name"] == "build.trace"]
    assert all(s["end"] - s["start"] >= builds.SHORT_TRACE_S for s in traces)
    # a first fit traces hundreds of small jitted functions inside sgd_run
    assert root["short_traces"] > len(traces)
    assert 0 < root["short_trace_s"] < root["short_traces"] \
        * builds.SHORT_TRACE_S


def test_steady_fits_leave_nothing_and_fire_no_listener_call():
    X, y = _data()
    opt = _optimizer()
    _fit(opt, X, y)
    Xd, yd, w0 = jnp.asarray(X), jnp.asarray(y), np.zeros((16,), np.float32)
    opt.optimize_with_history((Xd, yd), w0)  # the arrays as they now are
    kept = obs.build_roots()
    with _Listeners() as heard:
        for _ in range(10):
            opt.optimize_with_history((Xd, yd), w0)
    assert heard.calls == 0
    assert obs.build_roots() == kept and builds._BUILT == []


def test_a_new_step_size_builds_nothing():
    """Mended (ROADMAP Speed 4(a), PR 62): the step size and the regulariser
    are operands of ``sgd_run`` (``config.Hyper``), so a changed value of
    either runs the program the first fit built: no second root, no second
    ``_run_cache`` entry, no listener call."""
    X, y = _data()
    opt = _optimizer()
    _fit(opt, X, y)
    kept = obs.build_roots()
    with _Listeners() as heard:
        _fit(opt.set_step_size(0.25), X, y)
        _fit(opt.set_reg_param(0.5), X, y)
    assert heard.calls == 0
    assert obs.build_roots() == kept and len(kept) == 1
    assert len(opt._run_cache) == 1


@pytest.fixture
def cache_dir(compile_cache):
    return compile_cache


def test_the_cache_read_is_told_by_program(cache_dir):
    X, y = _data(features=24)  # a shape no other test of the process caches
    _fit(_optimizer(), X, y)
    jax.clear_caches()
    _fit(_optimizer(), X, y)
    cold, warm = obs.build_roots()
    (missed,), (read,) = (_named(r, "build.compile") for r in (cold, warm))
    assert missed["cache_hit"] == 0 and missed["cache_read_ms"] is None
    assert read["cache_hit"] == 1 and 0 < read["cache_read_ms"] \
        <= (read["end"] - read["start"]) * 1e3
    # the directory was empty: nothing of the first fit was read from it
    assert {s["cache_hit"] for s in cold["spans"]
            if s["name"] == "build.compile"} == {0}


def test_the_store_is_told_by_a_restore_span_under_the_root(cache_dir):
    """``hit`` 0 where the runner was exported and stored (its trace and
    lowering fire inside the span), 1 where it was read back: then nothing
    of the package is traced under the root."""
    X, y = _data(features=40)
    _fit(_optimizer(), X, y)
    jax.clear_caches()
    _fit(_optimizer(), X, y)
    cold, warm = obs.build_roots()
    (stored,), (restored,) = (_named(r, RESTORE) for r in (cold, warm))
    assert stored["hit"] == 0 and restored["hit"] == 1
    assert "reason" not in stored and "reason" not in restored
    for s, root in ((stored, cold), (restored, warm)):
        assert s["fun"] == "sgd_run" and s["thread"] == "MainThread"
        assert s["ms"] == pytest.approx((s["end"] - s["start"]) * 1e3)
        assert root["start"] <= s["start"] <= s["end"] \
            <= root["start"] + root["dur_s"]
    inside = [t for t in _named(cold, "build.trace")
              if stored["start"] <= t["start"] and t["end"] <= stored["end"]]
    assert inside and restored["ms"] < stored["ms"]
    assert not [t for t in warm["spans"] if t["name"] == "build.trace"
                and t["end"] - t["start"] > 0.02]
    assert _named(warm, "build.compile")[0]["cache_hit"] == 1


def test_a_bypass_of_the_store_says_why():
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("this process has a persistent cache")
    sink = _Sink()
    obs.enable_tracing(sink)
    X, y = _data()
    _fit(_optimizer(), X, y)
    obs.disable_tracing()
    (root,) = obs.build_roots()
    (bypass,) = _named(root, RESTORE)
    assert bypass["hit"] is None \
        and bypass["reason"] == "no compile cache directory"
    (record,) = sink.spans(RESTORE)
    assert record["reason"] == bypass["reason"] and record["hit"] is None \
        and record["fun"] == "sgd_run"


def test_without_a_persistent_cache_the_hit_is_none():
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("this process has a persistent cache")
    X, y = _data()
    _fit(_optimizer(), X, y)
    (root,) = obs.build_roots()
    assert {(s["cache_hit"], s["cache_read_ms"]) for s in root["spans"]
            if s["name"] == "build.compile"} == {(None, None)}


def test_the_outermost_root_takes_the_builds_of_the_ones_inside():
    X, y = _data()
    tpu_sgd.LogisticRegressionWithSGD(0.5, 4, mini_batch_fraction=0.5).run(
        (X, y))
    (root,) = obs.build_roots()
    assert root["name"] == "fit.run" and _named(root, "build.compile")


def test_a_build_on_a_worker_thread_lands_under_the_streams_root():
    from tpu_sgd.models.streaming import StreamingLogisticRegressionWithSGD

    alg = StreamingLogisticRegressionWithSGD(step_size=0.3, num_iterations=4)
    alg.set_initial_weights(np.zeros((12,), np.float32))
    alg.algorithm.set_schedule("off")  # the worker stages from the first on
    jax.clear_caches()  # what the worker stages with is built anew
    alg.train_on(_data(512, 12, seed=i) for i in range(2))
    (root,) = obs.build_roots()
    assert root["name"] == "stream.run" and _named(root, "build.compile")
    threads = {s["thread"] for s in root["spans"]}
    assert "MainThread" in threads
    assert any(t.startswith("tpu-sgd-stream") for t in threads), threads


def test_a_build_outside_any_root_is_counted_and_not_kept():
    with builds.root("train.run", obs_spans.NO_SPAN):
        pass  # the listeners are in; nothing was built, nothing is kept
    assert obs.build_roots() == []
    before = builds.outside()
    jax.jit(lambda x: x * 3.0 + before)(1.0)  # a new function: built
    assert builds.outside() >= before + 3  # its trace, lowering, compile
    assert obs.build_roots() == [] and builds._BUILT == []


def test_the_list_of_roots_is_bounded():
    for i in range(builds.KEPT + 4):
        with builds.root("train.run", obs_spans.NO_SPAN):
            jax.jit(lambda x, i=i: x + i)(1.0)
    roots = obs.build_roots()
    assert len(roots) == builds.KEPT
    assert [r["start"] for r in roots] == sorted(r["start"] for r in roots)
    assert all(len(r["spans"]) + r["short_traces"] >= 3 for r in roots)


def test_a_root_that_raises_is_closed_and_keeps_what_it_built():
    with pytest.raises(ZeroDivisionError):
        with builds.root("fit.run", obs_spans.NO_SPAN):
            jax.jit(lambda x: x - 7.0)(1.0)
            1 / 0
    assert builds._OPEN is None
    (root,) = obs.build_roots()
    assert root["name"] == "fit.run"


def _hammer(threads=16, each=2000):
    """``threads`` threads fire ``each`` lowering events apiece at the
    listener; returns once all are done (or fails)."""
    import threading

    def fire(k):
        for i in range(each):
            builds._on_span("/jax/core/compile/jaxpr_to_mlir_module_duration",
                            float(i), float(i) + 1.0, fun_name=f"f{k}")

    workers = [threading.Thread(target=fire, args=(k,))
               for k in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    return threads * each


def test_many_threads_lose_no_event_outside_or_under_a_root():
    before = builds.outside()
    fired = _hammer()
    assert builds.outside() == before + fired and builds._BUILT == []
    with builds.root("stream.run", obs_spans.NO_SPAN):
        fired = _hammer()
    (root,) = obs.build_roots()
    assert len(root["spans"]) == fired and builds.outside() == before + fired
    assert len({s["thread"] for s in root["spans"]}) == 16


# -- tracing on ---------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_with_a_sink_the_build_records_hang_under_the_roots_span(kind):
    sink = _Sink()
    obs.enable_tracing(sink)
    X, y = _data()
    _fit(_optimizer(), X, y)
    obs.disable_tracing()
    (run,) = sink.spans("train.run")
    (root,) = obs.build_roots()
    assert root["span_id"] == run["span_id"]
    records = sink.spans(kind)
    assert len(records) == sum(s["name"] == kind for s in root["spans"]) > 0
    ids = [p["span_id"] for _, p in sink.records]
    assert len(set(ids)) == len(ids)  # one id space
    for p in records:
        assert p["parent_id"] == run["span_id"] and p["error"] is None
        assert p["fun"] and p["thread"] == "MainThread"
        # on the root's two clocks, inside it (the clocks' reads lie apart)
        assert run["t0_s"] - 1e-3 <= p["t0_s"] \
            and p["t0_s"] + p["dur_s"] <= run["t0_s"] + run["dur_s"] + 1e-3
        assert run["ts"] - 1e-3 <= p["ts"]
        assert ("cache_hit" in p) == (kind == "build.compile")


def test_the_report_shows_the_build_spans(tmp_path):
    from tpu_sgd.obs import report

    obs.enable(str(tmp_path / "trace.jsonl"), with_counters=False)
    X, y = _data()
    _fit(_optimizer(), X, y)
    obs.disable()
    records = report.load_trace(str(tmp_path / "trace.jsonl"))
    stats = report.span_stats(records)
    assert all(stats[kind]["count"] >= 1 for kind in KINDS)
    chrome = report.to_chrome_trace(records)
    assert any(e["name"] == "build.compile" and "sgd_run" in e["args"]["fun"]
               for e in chrome["traceEvents"])


def test_a_sink_that_raises_drops_the_records_and_not_the_fit():
    obs.enable_tracing(_Sink(broken=True))
    X, y = _data()
    w, losses = _fit(_optimizer(), X, y)
    obs.disable_tracing()
    assert len(losses) == 4
    (root,) = obs.build_roots()  # the record in memory is whole
    assert _named(root, "build.compile")


# -- what it costs ---------------------------------------------------------------

def test_disabled_span_is_still_one_global_load_and_a_branch():
    """``tests/test_obs.py``'s pin holds beside a root: ``span()`` disabled
    is the shared no-op, and a root INSIDE a root is that same object."""
    assert obs_spans.span("fit.run") is obs_spans.NO_SPAN
    with builds.root("fit.run", obs_spans.NO_SPAN) as handle:
        assert handle is None  # nothing to hold
        assert builds.root("train.run", obs_spans.NO_SPAN) \
            is obs_spans.NO_SPAN
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs_spans.span("train.step")
    assert (time.perf_counter() - t0) / n < 2e-6


def test_a_roots_open_and_close_allocate_nothing_and_cost_a_microsecond():
    first = builds.root("fit.run", obs_spans.NO_SPAN)
    assert builds.root("train.run", obs_spans.NO_SPAN) is first  # ONE handle
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with builds.root("fit.run", obs_spans.NO_SPAN):
            pass
    per_root = (time.perf_counter() - t0) / n
    assert per_root < 5e-6, f"a root costs {per_root * 1e9:.0f} ns"
    assert obs.build_roots() == [] and builds._BUILT == []


IMPORT = """
import json, sys
import jax, jax.numpy, numpy
from jax._src import monitoring
def listeners():
    return (len(monitoring.get_event_listeners())
            + len(monitoring.get_event_duration_listeners())
            + len(monitoring.get_event_time_span_listeners()))
before, heard = set(sys.modules), listeners()
import tpu_sgd
new = sorted(set(sys.modules) - before)
at_import = listeners() - heard
from tpu_sgd.obs import builds, spans
with builds.root("fit.run", spans.NO_SPAN):
    pass
print("REPORT " + json.dumps({
    "new": new, "at_import": at_import, "at_root": listeners() - heard,
    "had": [m in before for m in ("collections", "threading", "time")]}))
"""


def test_import_registers_no_listener_and_imports_nothing_new():
    """``import tpu_sgd`` in a FRESH interpreter registers no listener (the
    first root's entry registers the three), and the record's module imports
    the standard library's ``collections``, ``threading``, ``time`` and
    ``obs.spans`` alone: all loaded before it, so nothing new is."""
    out = subprocess.run(
        [sys.executable, "-c", IMPORT], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.split("REPORT ", 1)[1])
    assert report["at_import"] == 0 and report["at_root"] == 3
    assert "tpu_sgd.obs.builds" in report["new"]
    # the record's own imports: the standard library and obs.spans, all
    # loaded by JAX or by the package before it
    src = open(os.path.join(REPO, "tpu_sgd", "obs", "builds.py")).read()
    imports = [line for line in src.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import collections", "import threading",
                       "import time", "from tpu_sgd.obs import spans"]
    assert report["had"] == [True, True, True]  # JAX had loaded the three
