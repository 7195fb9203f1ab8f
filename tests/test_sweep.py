"""A tuning job's grid trains through ONE program: the step size and the
regulariser are operands of every compiled program made from ``make_step``
(``config.Hyper``), the memo keys and the store's key hold the config's
structure alone (``SGDConfig.structure``), and a new optimizer of the process
finds the runner it needs live (``optimize/run_store.py``).  Tiny, on the
CPU."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_sgd
from tpu_sgd import obs
from tpu_sgd.config import Hyper, SGDConfig
from tpu_sgd.obs import builds
from tpu_sgd.obs.spans import disable_tracing, enable_tracing
from tpu_sgd.optimize import run_store

GRID = [(s, r) for s in (1.25, 2.5, 5.0, 10.0) for r in (1e-4, 1e-3)]


@pytest.fixture(autouse=True)
def _no_roots():
    builds._ROOTS.clear()
    del builds._BUILT[:]
    yield


def _rows(n=512, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    return jnp.asarray(X, jnp.bfloat16), jnp.asarray(y)


def _optimizer(step_size=1.0, reg_param=0.0):
    return (tpu_sgd.GradientDescent(tpu_sgd.LogisticGradient(),
                                    tpu_sgd.SquaredL2Updater())
            .set_step_size(step_size).set_reg_param(reg_param)
            .set_num_iterations(12).set_mini_batch_fraction(0.25)
            .set_sampling("sliced").set_convergence_tol(0.0).set_seed(42))


def _call(X, y, step_size, reg_param, **more):
    return tpu_sgd.run_mini_batch_sgd(
        (X, y), tpu_sgd.LogisticGradient(), tpu_sgd.SquaredL2Updater(),
        step_size, 12, reg_param, 0.25, np.zeros((X.shape[1],), np.float32),
        convergence_tol=0.0, seed=42, sampling="sliced", **more)


class _Sink:
    def __init__(self):
        self.spans = []

    def emit(self, kind, payload):
        if kind == "trace_span":
            self.spans.append(dict(payload))


# -- the config says which is which, in one place -------------------------------

def test_the_config_names_its_operands_and_the_rest_is_structure():
    assert Hyper._fields == ("step_size", "reg_param")
    cfg = SGDConfig(step_size=2.5, reg_param=0.01, num_iterations=7, seed=3,
                    sampling="sliced", mini_batch_fraction=0.5,
                    convergence_tol=0.0)
    assert cfg.hyper() == Hyper(2.5, 0.01)
    assert all(type(v) is float for v in cfg.hyper())
    other = cfg.replace(step_size=0.1, reg_param=0.5)
    assert cfg.structure() == other.structure() and cfg != other
    for field in dataclasses.fields(SGDConfig):
        if field.name in Hyper._fields:
            continue
        moved = cfg.replace(**{field.name: {
            "num_iterations": 8, "seed": 4, "sampling": "bernoulli",
            "mini_batch_fraction": 0.75, "convergence_tol": 0.5}[field.name]})
        assert moved.structure() != cfg.structure(), field.name


# -- one object -----------------------------------------------------------------

def test_eight_pairs_through_one_object_leave_one_run_cache_entry():
    X, y = _rows()
    w0 = np.zeros((16,), np.float32)
    opt = _optimizer()
    fits = [opt.set_step_size(s).set_reg_param(r)
            .optimize_with_history((X, y), w0) for s, r in GRID]
    assert len(opt._run_cache) == 1
    (root,) = obs.build_roots()  # the first fit's, and no other
    assert any(s["name"] == "build.compile" for s in root["spans"])
    # eight different models, each the fit a fresh optimizer gives there
    finals = [float(l[-1]) for _, l in fits]
    assert len(set(finals)) == 8
    for (s, r), (w, losses) in zip(GRID, fits):
        w_own, l_own = _optimizer(s, r).optimize_with_history((X, y), w0)
        np.testing.assert_array_equal(np.asarray(w), np.asarray(w_own))
        np.testing.assert_array_equal(losses, l_own)


def test_a_steady_fit_sends_no_operand_again():
    X, y = _rows()
    opt = _optimizer(2.5, 1e-3)
    first = opt._hyper()
    assert opt._hyper() is first
    assert all(isinstance(v, jax.Array) and v.weak_type and v.shape == ()
               for v in first)
    assert opt.set_step_size(5.0)._hyper() is not first
    assert [float(v) for v in opt._hyper()] == [5.0, float(np.float32(1e-3))]


# -- eight calls of the static entry --------------------------------------------

def test_eight_calls_trace_once_store_one_file_and_find_the_runner_live(
        compile_cache):
    X, y = _rows()
    sink = _Sink()
    enable_tracing(sink)
    try:
        fits = [_call(X, y, s, r) for s, r in GRID]
    finally:
        disable_tracing()
    assert len(os.listdir(os.path.join(compile_cache, run_store.FOLDER))) == 1
    (root,) = obs.build_roots()
    (restore,) = [s for s in root["spans"] if s["name"] == "build.restore"]
    assert restore["hit"] == 0  # the first call exported and stored it
    runners = [s["runner"] for s in sink.spans if s["name"] == "train.select"]
    assert runners == ["stored"] + ["live"] * 7
    assert len({float(l[-1]) for _, l in fits}) == 8
    # another process: the file is read, once, and the rest is live again
    run_store._LIVE.clear()
    sink = _Sink()
    enable_tracing(sink)
    try:
        again = [_call(X, y, s, r) for s, r in GRID]
    finally:
        disable_tracing()
    runners = [s["runner"] for s in sink.spans if s["name"] == "train.select"]
    assert runners == ["restored"] + ["live"] * 7
    for (w, l), (w2, l2) in zip(fits, again):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
        np.testing.assert_array_equal(l, l2)


def test_without_a_store_the_runner_as_it_was_is_found_live():
    X, y = _rows()
    sink = _Sink()
    enable_tracing(sink)
    try:
        for s, r in GRID[:3]:
            _call(X, y, s, r)
    finally:
        disable_tracing()
    runners = [s["runner"] for s in sink.spans if s["name"] == "train.select"]
    assert runners == ["as_was", "live", "live"]
    assert len(obs.build_roots()) == 1


def test_a_runner_whose_executable_is_gone_is_not_live(compile_cache):
    X, y = _rows()
    _call(X, y, *GRID[0])
    jax.clear_caches()
    sink = _Sink()
    enable_tracing(sink)
    try:
        _call(X, y, *GRID[1])
        _call(X, y, *GRID[2])
    finally:
        disable_tracing()
    runners = [s["runner"] for s in sink.spans if s["name"] == "train.select"]
    assert runners == ["restored", "live"]


def test_another_structure_is_another_program(compile_cache):
    X, y = _rows()
    _call(X, y, *GRID[0])
    tpu_sgd.run_mini_batch_sgd(
        (X, y), tpu_sgd.LogisticGradient(), tpu_sgd.SquaredL2Updater(),
        1.25, 12, 1e-4, 0.25, np.zeros((16,), np.float32),
        convergence_tol=0.0, seed=43, sampling="sliced")  # another seed
    assert len(os.listdir(os.path.join(compile_cache, run_store.FOLDER))) == 2
    assert len(obs.build_roots()) == 2


# -- the updaters' contract -------------------------------------------------------

UPDATERS = {"simple": tpu_sgd.SimpleUpdater, "l1": tpu_sgd.L1Updater,
            "squared_l2": tpu_sgd.SquaredL2Updater}


@pytest.mark.parametrize("name", UPDATERS)
def test_an_updater_at_a_traced_pair_is_its_concrete_valued_result(name):
    updater = UPDATERS[name]()
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(32), jnp.float32)
    g = jnp.asarray(rng.standard_normal(32), jnp.float32)
    traced = jax.jit(lambda w, g, hyper, i: updater.compute(
        w, g, hyper.step_size, i, hyper.reg_param))
    for step_size, reg_param in ((5.0, 0.001), (0.1, 0.0), (1.25, 0.5)):
        closed = jax.jit(lambda w, g, i: updater.compute(
            w, g, step_size, i, reg_param))
        for i in (1, 7):
            want = closed(w, g, jnp.asarray(i, jnp.int32))
            got = traced(w, g, SGDConfig(step_size=step_size,
                                         reg_param=reg_param).hyper(),
                         jnp.asarray(i, jnp.int32))
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert traced._cache_size() == 1  # one program served every pair


class _NeedsConcrete(tpu_sgd.SquaredL2Updater):
    def compute(self, weights_old, gradient, step_size, iter_num, reg_param):
        if reg_param > 0:  # a Python branch on an operand
            return super().compute(weights_old, gradient, step_size,
                                   iter_num, reg_param)
        return weights_old, jnp.zeros((), weights_old.dtype)


def test_an_updater_that_needs_a_concrete_value_fails_at_trace_by_name():
    X, y = _rows()
    opt = tpu_sgd.GradientDescent(tpu_sgd.LogisticGradient(),
                                  _NeedsConcrete()).set_num_iterations(3)
    with pytest.raises(TypeError) as raised:
        opt.optimize_with_history((X, y), np.zeros((16,), np.float32))
    message = str(raised.value)
    assert "_NeedsConcrete.compute" in message
    assert "tpu_sgd/ops/updaters.py" in message and "TRACED" in message


# -- a mesh and a stream ------------------------------------------------------------

def test_a_mesh_takes_a_changed_step_size_without_a_second_program():
    X, y = _rows()
    w0 = np.zeros((16,), np.float32)
    mesh = tpu_sgd.data_mesh(jax.devices()[:4])
    opt = _optimizer().set_mesh(mesh)
    a = opt.optimize_with_history((X, y), w0)
    kept = obs.build_roots()
    b = opt.set_step_size(2.5).set_reg_param(1e-3) \
        .optimize_with_history((X, y), w0)
    assert len(opt._run_cache) == 1 and obs.build_roots() == kept
    assert float(a[1][-1]) != float(b[1][-1])
    # the operands are replicated over the mesh, and the fit is a new meshed
    # optimizer's fit of the same pair
    assert all(v.sharding.is_fully_replicated
               and len(v.sharding.device_set) == 4 for v in opt._hyper())
    w_own, l_own = _optimizer(2.5, 1e-3).set_mesh(mesh) \
        .optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(b[0]), np.asarray(w_own))
    np.testing.assert_array_equal(b[1], l_own)


def test_dp_run_fn_is_one_program_for_two_pairs():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_run_fn

    X, y = _rows()
    mesh = tpu_sgd.data_mesh(jax.devices()[:4])
    cfg = _optimizer().config
    fn = dp_run_fn(tpu_sgd.LogisticGradient(), tpu_sgd.SquaredL2Updater(),
                   cfg.structure(), mesh, with_valid=False)
    Xd = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    yd = jax.device_put(y, NamedSharding(mesh, P("data")))
    w0 = jnp.zeros((16,), jnp.float32)
    outs = [fn(w0, Xd, yd, cfg.replace(step_size=s, reg_param=r).hyper())
            for s, r in GRID[:3]]
    assert fn._cache_size() == 1
    assert len({float(o[1][-1]) for o in outs}) == 3


def test_a_streams_train_on_takes_a_changed_step_size_without_a_second_program():
    rng = np.random.default_rng(3)
    batches = [(rng.standard_normal((256, 8)).astype(np.float32),
                rng.standard_normal(256).astype(np.float32))
               for _ in range(4)]
    alg = tpu_sgd.StreamingLinearRegressionWithSGD(
        step_size=0.1, num_iterations=5).set_initial_weights(np.zeros(8))
    alg.train_on(iter(batches[:2]))
    opt = alg.algorithm.optimizer
    programs = dict(opt._run_cache)
    kept = obs.build_roots()
    before = np.asarray(alg.latest_model().weights).copy()
    opt.set_step_size(0.05)
    alg.train_on(iter(batches[2:]))
    assert dict(opt._run_cache) == programs
    assert [r for r in obs.build_roots() if r not in kept] == []
    assert not np.array_equal(before, np.asarray(alg.latest_model().weights))
