"""A fused fit's leaf spans tile it, and tracing that is off costs a fit one
call of ``span()`` a span and nothing else (PR 37): ``train.select`` between
the hand-off and the call, ``fit.finish`` after the optimizer, every leaf
once and in order under ``train.run`` or ``fit.run``; ``_step_kernel`` only
where a span will carry what it says; the hand-off's stall counter on
``train.h2d`` and no clock read in its loop without a live span; the traced
fit the untraced fit's bit for bit.  Tiny, CPU, a memory sink."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_sgd
from tpu_sgd.obs import spans as obs_spans
from tpu_sgd.obs.spans import disable_tracing, enable_tracing
from tpu_sgd.optimize import gradient_descent as gd

ROWS, D = 3 * gd._STAGE_ROWS + 100, 8
RUN_LEAVES = ["fit.validate", "fit.plan", "train.h2d", "train.select",
              "train.dispatch", "train.fetch", "fit.finish"]
OPTIMIZER_LEAVES = ["train.h2d", "train.select", "train.dispatch",
                    "train.fetch"]


class Sink:
    """The fit's own spans; what a first fit BUILT (``build.*``, PR 55:
    records under the root that lie over ``train.dispatch`` and the eager
    programs around it, no part of the tiling) is kept apart."""

    def __init__(self):
        self.records, self.builds = [], []

    def emit(self, kind, payload):
        if kind == "trace_span":
            (self.builds if payload["name"].startswith("build.")
             else self.records).append(dict(payload))

    def named(self, name):
        return [p for p in self.records if p["name"] == name]


@pytest.fixture
def sink():
    sink = Sink()
    enable_tracing(sink)
    yield sink
    disable_tracing()


@pytest.fixture
def data():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(ROWS, D)).astype(np.float32)
    y = (X @ rng.uniform(-1, 1, D).astype(np.float32) > 0).astype(np.float32)
    return X, y


def _alg():
    return tpu_sgd.LogisticRegressionWithSGD(0.5, 6, mini_batch_fraction=0.5)


def _opt():
    return (tpu_sgd.GradientDescent(tpu_sgd.LogisticGradient(),
                                    tpu_sgd.SquaredL2Updater())
            .set_step_size(0.5).set_num_iterations(6)
            .set_mini_batch_fraction(0.5).set_convergence_tol(0.0))


def _small_blocks(monkeypatch, in_flight=2):
    """Blocks of ``_STAGE_ROWS`` rows: ``ROWS`` go in four pieces."""
    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES", gd._STAGE_ROWS * D * 4)
    monkeypatch.setattr(gd, "_STAGE_IN_FLIGHT", in_flight)


def _leaves(records):
    """The spans that hold no span, in the order they were entered."""
    parents = {p["parent_id"] for p in records}
    return sorted((p for p in records if p["span_id"] not in parents),
                  key=lambda p: p["t0_s"])


def _check_tiling(records, expected, roots):
    leaves = _leaves(records)
    assert [p["name"] for p in leaves] == expected  # each once, in order
    by_id = {p["span_id"]: p for p in records}
    for leaf in leaves:
        assert by_id[leaf["parent_id"]]["name"] in roots, leaf["name"]
    for earlier, later in zip(leaves, leaves[1:]):
        assert earlier["t0_s"] + earlier["dur_s"] <= later["t0_s"]


# -- the leaves --------------------------------------------------------------

def test_a_fit_through_run_emits_each_leaf_once_in_order(sink, data):
    alg = _alg()
    alg.run(data)
    _check_tiling(sink.records, RUN_LEAVES, {"fit.run", "train.run"})
    select, = sink.named("train.select")
    dispatch, = sink.named("train.dispatch")
    # train.select says which form the step's kernel is (PR 39), nothing
    # else: whether the cache held the runner is train.dispatch's ``built``
    assert "cached" not in select and dispatch["built"] == 1
    assert select["by_rows"] == 0
    # fit.finish is fit.run's own, after the optimizer has returned
    finish, = sink.named("fit.finish")
    run, = sink.named("train.run")
    fit, = sink.named("fit.run")
    assert finish["parent_id"] == fit["span_id"]
    assert run["t0_s"] + run["dur_s"] <= finish["t0_s"]
    # what the first fit built hangs under the OUTERMOST root, fit.run
    assert {b["parent_id"] for b in sink.builds} == {fit["span_id"]}
    # the second fit of the object finds its runner where the first left it
    del sink.records[:], sink.builds[:]
    alg.run(data)
    _check_tiling(sink.records, RUN_LEAVES, {"fit.run", "train.run"})
    assert sink.named("train.dispatch")[0]["built"] == 0 and not sink.builds


def test_a_fit_at_the_optimizer_boundary_emits_each_leaf_once_in_order(
        sink, data):
    X, y = data
    opt = _opt().set_check_numerics(True)  # read inside train.fetch
    opt.optimize_with_history((jnp.asarray(X), jnp.asarray(y)),
                              np.zeros(D, np.float32))
    _check_tiling(sink.records, OPTIMIZER_LEAVES, {"train.run"})
    assert [p["name"] for p in sink.records
            if p["parent_id"] == 0] == ["train.run"]


def test_under_a_mesh_the_placement_comes_before_the_selection(sink, data):
    opt = _opt().set_mesh(tpu_sgd.data_mesh(jax.devices()[:4]))
    opt.optimize_with_history(data, np.zeros(D, np.float32))
    _check_tiling(sink.records, ["train.h2d", "train.place", "train.select",
                                 "train.dispatch", "train.fetch"],
                  {"train.run"})
    run, = sink.named("train.run")
    assert (run["path"], run["shards"]) == ("mesh", 4)


@pytest.mark.parametrize("form", ["totals", "prefix"])
def test_the_statistics_build_is_a_leaf_before_the_selection(sink, form):
    """``_maybe_gram`` builds its statistics under ``train.stats`` (PR 41: a
    leaf of its own between the hand-off and ``train.select``, which it was
    inside), and the fit runs the substituted gradient as it did.  ``stats``
    says whether it ran from the totals (a full batch) or, 0, from the
    prefix form (sliced windows)."""
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(512, D)).astype(np.float32))
    y = X @ jnp.arange(D, dtype=jnp.float32)
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_step_size(0.1).set_num_iterations(5)
           .set_convergence_tol(0.0).set_sufficient_stats(True))
    if form == "prefix":
        opt.set_mini_batch_fraction(0.5).set_sampling("sliced")
    asked = []
    real = opt._maybe_gram
    opt._maybe_gram = lambda *a: asked.append(
        obs_spans._stack()[-1].name) or real(*a)
    gradient = opt.gradient
    opt.optimize_with_history((X, y), np.zeros(D, np.float32))
    assert asked == ["train.run"] and opt.gradient is gradient
    run, = sink.named("train.run")
    select, = sink.named("train.select")
    build, = sink.named("train.stats")
    assert run["path"] == "gram"
    assert run["stats"] == select["stats"] == int(form == "totals")
    assert (build["bytes"], build["rows"]) == (X.nbytes + y.nbytes, 512)
    _check_tiling(sink.records,
                  ["train.h2d", "train.stats", "train.select",
                   "train.dispatch", "train.fetch"], {"train.run"})


def test_a_fit_that_builds_no_statistics_has_no_such_leaf(sink, data):
    X, y = data
    _opt().optimize_with_history((jnp.asarray(X), jnp.asarray(y)),
                                 np.zeros(D, np.float32))
    assert not sink.named("train.stats")
    run, = sink.named("train.run")
    assert run["stats"] == 0 and run["path"] == "fused"


# -- tracing off ---------------------------------------------------------------

class _Counted:
    def __init__(self, real):
        self.real, self.calls = real, 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.real(*args, **kwargs)


def test_live_says_whether_a_span_keeps_attributes(sink):
    assert obs_spans.span("demo.on").live is True
    disable_tracing()
    assert obs_spans.span("demo.off") is obs_spans._NOOP
    assert obs_spans._NOOP.live is False
    assert obs_spans._Annotation.live is True


def test_with_tracing_off_a_fit_calls_span_and_nothing_else_of_the_tracing(
        monkeypatch, data):
    """``_step_kernel`` is not evaluated, ``span()`` hands out the one no-op
    object every time, and the hand-off's loop reads no clock."""
    assert not obs_spans.is_enabled()
    kernel = _Counted(gd.GradientDescent._step_kernel)
    monkeypatch.setattr(gd.GradientDescent, "_step_kernel",
                        lambda self, *a: kernel(self, *a))
    handed = []
    real_span = gd.span
    monkeypatch.setattr(gd, "span", lambda *a, **kw: handed.append(
        real_span(*a, **kw)) or handed[-1])

    class NoClock:
        @staticmethod
        def perf_counter():
            raise AssertionError("a clock was read with tracing off")

    monkeypatch.setattr(gd, "time", NoClock)
    _small_blocks(monkeypatch)
    w, losses = _opt().optimize_with_history(data, np.zeros(D, np.float32))
    assert kernel.calls == 0 and len(losses) == 6
    assert len(handed) == 5  # train.run and its four leaves
    assert all(s is obs_spans._NOOP for s in handed)


def test_with_tracing_on_the_step_is_described_once_a_fit(monkeypatch, sink,
                                                          data):
    kernel = _Counted(gd.GradientDescent._step_kernel)
    monkeypatch.setattr(gd.GradientDescent, "_step_kernel",
                        lambda self, *a: kernel(self, *a))
    _opt().optimize_with_history(data, np.zeros(D, np.float32))
    assert kernel.calls == 1
    run, = sink.named("train.run")
    assert (run["path"], run["shards"], run["labels_prepared"],
            run["row_tile"], run["feature_blocks"],
            run["mask_in_kernel"]) == ("fused", 1, 0, 0, 1, 0)


# -- the stall counter -----------------------------------------------------------

def test_train_h2d_carries_the_stall_counter(monkeypatch, sink, data):
    X, y = data
    w0 = np.zeros(D, np.float32)
    clock = _Counted(gd.time.perf_counter)

    class Clock:
        perf_counter = clock

    monkeypatch.setattr(gd, "time", Clock)
    _opt().optimize_with_history((X, y), w0)  # under the real block: a piece
    assert clock.calls == 0
    _small_blocks(monkeypatch, in_flight=1)
    _opt().optimize_with_history((X, y), w0)
    # four blocks, one in flight: two readings a wait (three waits) and,
    # since PR 46, four a block and the loop's first and last
    assert clock.calls == 2 * 3 + 4 * 4 + 2
    _opt().optimize_with_history((jnp.asarray(X), jnp.asarray(y)), w0)
    one, many, device = sink.named("train.h2d")
    assert (many["blocks"], many["stalls"]) == (4, 3)
    assert many["stalls"] >= 1 and 0 <= many["stall_ms"] < many["dur_s"] * 1e3
    for span in (one, device):
        assert (span["stalls"], span["stall_ms"]) == (0, 0)


# -- the fit ---------------------------------------------------------------------

@pytest.mark.parametrize("entry", ["run", "optimizer"])
def test_a_traced_fit_is_the_untraced_fit_bit_for_bit(monkeypatch, data,
                                                      entry):
    X, y = data
    _small_blocks(monkeypatch)

    def fit():
        if entry == "run":
            alg = _alg()
            return (np.asarray(alg.run((X, y)).weights),
                    np.asarray(alg.optimizer.loss_history))
        w, losses = _opt().optimize_with_history((X, y),
                                                 np.zeros(D, np.float32))
        return np.asarray(w), np.asarray(losses)

    w_off, loss_off = fit()
    sink = Sink()
    enable_tracing(sink)
    try:
        w_on, loss_on = fit()
    finally:
        disable_tracing()
    assert sink.named("train.select") and sink.named("train.h2d")[0]["stalls"]
    assert len(loss_off) == 6 and np.isfinite(loss_off).all()
    np.testing.assert_array_equal(w_on, w_off)
    np.testing.assert_array_equal(loss_on, loss_off)
