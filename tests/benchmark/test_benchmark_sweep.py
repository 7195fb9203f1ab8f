"""``dense1000-logistic-sweep.resident-sweep`` tiny on the CPU through
``harness.run_cell``: a fit of the harness is ONE SWEEP of the grid's eight
models through ``run_mini_batch_sgd``, judged all eight against the plain
reference's eight; the float8 control fails every limit, one model fitted at
its neighbour's step size fails ``w_rel_gap`` though seven are right, the
rate counts every model's every step, and the two readers of what the
sweeps built give a NUMBER on a record with no ``build.*`` span and with
eight a sweep."""

import json
import os
import time

import numpy as np
import pytest

from bench import cells, correct, harness
from bench.layers import sweep_builds

NAME = "dense1000-logistic-sweep.resident-sweep"
TWIN = "dense1000-logistic-sliced.resident"


def _cell(name=NAME):
    cell = cells.Cell(name)
    tiny = dict(cell.config["tiny"])
    tiny.pop("what")
    return cells.Cell(name, overrides=tiny)


@pytest.fixture(scope="module")
def counter():
    return harness.CompileCounter()


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """A compile cache directory, as ``bench/run.py`` always sets one: the
    switch of the store of exported runners, in which a second optimizer of
    the process finds its runner live."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("cache")))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def timed(counter, store):
    cell = _cell()
    run = harness.run_cell(cell, 2**31 + 62, 0.2, False, time.perf_counter(),
                           counter, log=lambda line: None)
    return cell, run


def test_the_file_says_what_a_fit_of_the_harness_is():
    config = _cell().config
    points = [(s, r) for s in config["grid"]["step_size"]
              for r in config["grid"]["reg_param"]]
    assert points == [(1.25, 1e-4), (1.25, 1e-3), (2.5, 1e-4), (2.5, 1e-3),
                      (5.0, 1e-4), (5.0, 1e-3), (10.0, 1e-4), (10.0, 1e-3)]
    assert config["iterations_a_model"] == 100
    assert config["num_iterations"] == len(points) * 100
    assert "step_size" not in config and "reg_param" not in config
    assert {"each_model", "between_models", "held_by"} \
        <= set(config["guarantees"])


def test_the_configuration_is_its_twins_with_a_grid_alone_added():
    config, twin = _cell().config, _cell(TWIN).config
    own = {"name", "source", "source_section", "grid", "grid_order",
           "iterations_a_model", "num_iterations", "num_iterations_is",
           "initial_weights", "reference", "guarantees", "assumed", "as_run",
           "limits", "step_size", "reg_param"}
    for key in (set(config) | set(twin)) - own:
        assert config[key] == twin[key], key
    assert (5.0, 0.001) == (twin["step_size"], twin["reg_param"])
    assert twin["step_size"] in config["grid"]["step_size"]
    assert twin["reg_param"] in config["grid"]["reg_param"]
    assert config["iterations_a_model"] == twin["num_iterations"]
    assert config["reduced"] == ["rows", "data_parallel"]


def test_the_cell_fills_the_chip_as_its_twin_does():
    cell, twin = cells.Cell(NAME), cells.Cell(TWIN)
    assert cell.rows == twin.rows == 4_194_304 and cell.chips == 1
    assert cell.work.dataset_bytes(cell.config, cell.rows) == 8_388_608_000
    assert cell.job["traced_fits"] == 3 and cell.job["placement"] == "device"
    assert cell.work.step_work(cell.config, cell.rows) \
        == twin.work.step_work(twin.config, twin.rows)


def test_a_sweep_is_correct_and_every_model_is_judged(timed):
    cell, run = timed
    assert run["failed"] == 0 and run["attempted"] == run["fits"] + 1
    for name in correct.NUMBERS:
        assert run["checks"][name] <= cell.config["limits"][name]
    assert run["compiles_in_window"] == 0


def test_rows_per_s_counts_every_models_every_step(timed):
    cell, run = timed
    batch = round(0.1 * cell.rows)
    assert (run["iterations"], run["batch_rows"]) == (800, batch)
    assert run["rows_per_s"] == pytest.approx(
        run["fits"] * 8 * 100 * batch / run["window_s"])


def test_each_model_is_its_twins_fit_at_that_pair(timed):
    """The stack's rows are the twin's reference at each pair, in the
    grid's order, and the joined history is theirs one after another."""
    cell, _ = timed
    twin = _cell(TWIN)
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, 7)
    w0 = np.zeros((config["features"],), np.float32)
    W, losses = cell.reference.fit(config, X, y, w0, 42)
    assert W.shape == (8, config["features"]) and losses.shape == (800,)
    for k, (s, r) in enumerate((s, r) for s in config["grid"]["step_size"]
                               for r in config["grid"]["reg_param"]):
        w, hist = twin.reference.fit(
            dict(twin.config, step_size=s, reg_param=r), X, y, w0, 42)
        np.testing.assert_array_equal(W[k], w)
        np.testing.assert_array_equal(losses[100 * k:100 * (k + 1)], hist)
    # and the program's sweep is within the limits of every one of them
    w_fit, l_fit = cell.entry.prepare(config, X, y, 42)()
    assert w_fit.shape == W.shape and l_fit.shape == losses.shape
    assert correct.judge([(w_fit, l_fit)], W, losses, w0,
                         config["limits"])[0] == 0


def test_one_model_at_its_neighbours_step_size_fails_w_rel_gap():
    """The stack must not hide one wrong point: seven models right and one
    fitted at the next step size of the grid is not correct."""
    cell = _cell()
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, 11)
    w0 = np.zeros((config["features"],), np.float32)
    ref = cell.reference.fit(config, X, y, w0, 42)
    for wrong in range(8):
        steps = [s for s in config["grid"]["step_size"] for _ in (0, 1)]
        neighbour = steps[wrong + 2] if wrong < 6 else steps[wrong - 2]
        W, losses = (a.copy() for a in ref)
        moved = dict(config, grid={
            "step_size": [neighbour],
            "reg_param": [config["grid"]["reg_param"][wrong % 2]]})
        W[wrong], losses[100 * wrong:100 * (wrong + 1)] = (
            a[0] if a.ndim == 2 else a
            for a in cell.reference.fit(moved, X, y, w0, 42))
        got = correct.readings(W, losses, *ref, w0)
        assert got["w_rel_gap"] > config["limits"]["w_rel_gap"], (wrong, got)
        assert correct.judge([(W, losses)], *ref, w0,
                             config["limits"])[0] == 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_each_number(seed):
    import jax.numpy as jnp

    cell = _cell()
    config, limits = cell.config, cell.config["limits"]
    X, y = cell.generator.make(config, cell.rows, seed)
    w0 = np.zeros((config["features"],), np.float32)
    ref = cell.reference.fit(config, X, y, w0, 42)
    low = cell.reference.fit(config, jnp.array(X), y, w0, 42,
                             operands=config["control_operands"])
    got = correct.readings(*low, *ref, w0)
    for name in correct.NUMBERS:
        assert got[name] > limits[name], (name, got)


# -- the two readers of what the sweeps built ----------------------------------

def _root(start, spans, short=0, short_s=0.0):
    return {"name": "train.run", "start": start, "dur_s": 0.1, "span_id": 0,
            "spans": [{"name": n, "fun": "sgd_run", "thread": "MainThread",
                       "start": start + a, "end": start + b}
                      for n, a, b in spans],
            "short_traces": short, "short_trace_s": short_s}


@pytest.fixture
def record(monkeypatch):
    """``sweeps`` recorded by the entry and ``roots`` kept by the program,
    both the test's."""
    from tpu_sgd import obs

    def install(sweeps, roots):
        monkeypatch.setattr(sweep_builds, "SWEEPS", list(sweeps))
        monkeypatch.setattr(obs, "build_roots", lambda: list(roots))
        cell = _cell()
        return {m: cell.readers[m].read({}, {"fit_s": [1.0] * 2})
                for m in ("sweep_builds", "sweep_build_ms")}

    return install


def test_the_readers_are_loaded_for_this_cell_alone():
    assert {"sweep_builds", "sweep_build_ms"} <= set(_cell().readers)
    assert not {"sweep_builds", "sweep_build_ms"} & set(_cell(TWIN).readers)
    with open(os.path.join(cells.REPO, "BENCHMARK.json")) as f:
        last_two = json.load(f)["per_layer"]
    by_name = {m["name"]: m for m in last_two}
    for name in ("sweep_builds", "sweep_build_ms"):
        assert by_name[name]["workloads"] == [NAME]
        assert by_name[name]["layer"] == "optimizer driver"
        assert by_name[name]["moves"] == "rows_per_s"


def test_a_record_with_no_build_span_reads_zero_not_none(record):
    sweeps = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
    assert record(sweeps, []) == {"sweep_builds": 0.0, "sweep_build_ms": 0.0}
    # set-up's root, before the window: not the window's
    setup = _root(9.5, [("build.restore", 0.0, 0.01)])
    assert record(sweeps, [setup]) == {"sweep_builds": 0.0,
                                      "sweep_build_ms": 0.0}


def test_a_record_with_eight_builds_a_sweep_reads_them(record):
    sweeps = [(10.0, 11.0), (11.0, 12.0), (12.0, 13.0)]
    kinds = ("build.restore", "build.trace", "build.lower", "build.compile")
    roots = [_root(9.0, [("build.restore", 0.0, 0.5)])]  # the first sweep's
    for lo, _ in sweeps[-2:]:  # the window: the last two fits
        for k in range(8):
            roots.append(_root(lo + k / 8, [(kinds[k % 4], 0.0, 0.002)],
                               short=1, short_s=0.0005))
    got = record(sweeps, roots)
    assert got["sweep_builds"] == 16.0  # a span and a short trace a point
    assert got["sweep_build_ms"] == pytest.approx(8 * 2.5)


def test_without_a_recorded_sweep_there_is_nothing_to_read(record):
    assert record([], []) == {"sweep_builds": None, "sweep_build_ms": None}


def test_a_traced_run_gives_both_numbers(counter, store, tmp_path):
    """Through ``run_cell`` itself, tracing: the entry records its sweeps,
    the program keeps its roots, and with the step size and the regulariser
    operands and the runner live the window's sweeps build nothing."""
    cell = _cell()
    run = harness.run_cell(cell, 5, 0.2, True, time.perf_counter(),
                           counter, trace_dir=str(tmp_path / "trace"),
                           log=lambda line: None)
    metrics = harness.metrics_of(cell, run, trace=True)
    assert run["failed"] == 0 and run["compiles_in_window"] == 0
    assert metrics["sweep_builds"] == {"value": 0.0, "unit": "count"}
    assert metrics["sweep_build_ms"] == {"value": 0.0, "unit": "ms"}
    assert metrics["first_fit_restored"]["value"] in (0, 1)
