"""Every cell's phases tiny on the CPU, through the functions a run on the
chip goes through (``harness.run_cell`` does not look for a chip; only
``bench/run.py`` does): set-up, window, the reference's fit and ``correct``.
Then the same with the timed path broken underneath, and the CONTROL: the
reference put in the program's place at the precision below the one the
configuration states.  Both have to come out as not correct, by the limits
the configuration's file commits."""

import json
import time

import numpy as np
import pytest

from bench import cells, correct, harness

def _tiny(name):
    """The sizes the cell's configuration names for this rehearsal."""
    tiny = dict(cells.Cell(name, ALL).config["tiny"])
    tiny.pop("what")
    return tiny


ALL = cells.benchmark(with_prepared=True)
CELLS = [w["name"] for w in ALL["workloads"]]


@pytest.fixture(scope="module")
def counter():
    return harness.CompileCounter()


def _run(name, counter, trace=False, tmp=None, entry=None, seed=2**31 + 11):
    cell = cells.Cell(name, ALL, _tiny(name))
    if entry is not None:
        cell.entry = entry
    lines = []
    run = harness.run_cell(cell, seed, 0.2, trace, time.perf_counter(),
                           counter, trace_dir=str(tmp) if tmp else None,
                           log=lines.append)
    return cell, run, lines


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_has_a_tiny_size(name):
    tiny = _tiny(name)
    assert tiny["rows"] <= 65536 and tiny["features"] <= 1024


@pytest.fixture(scope="module", params=CELLS)
def timed(request, counter):
    return _run(request.param, counter)


def test_run_is_correct_and_counts_its_fits(timed):
    cell, run, lines = timed
    assert run["failed"] == 0 and run["attempted"] == run["fits"] + 1
    assert run["fits"] == len(run["fit_s"]) >= 1 and run["window_s"] >= 0.2
    assert sum(run["fit_s"]) <= run["window_s"]
    assert run["compiles_in_window"] == 0
    assert json.loads(json.dumps(run))["workload"] == cell.name


def test_rows_per_s_is_fits_times_iterations_times_nominal_batch(timed):
    cell, run, _ = timed
    batch = round(cell.config["mini_batch_fraction"] * cell.rows)
    assert run["batch_rows"] == batch
    assert run["rows_per_s"] == pytest.approx(
        run["fits"] * cell.config["num_iterations"] * batch / run["window_s"])


def test_first_fit_is_inside_setup_and_the_reference_is_not(timed):
    """``setup_s`` is the program's: the generator's ``data_s`` lies in front
    of it and the reference's fit behind the window, both outside."""
    _, run, _ = timed
    assert 0 < run["first_fit_s"] <= run["setup_s"] < run["process_s"]
    assert run["data_s"] > 0 and run["reference_s"] > 0
    assert run["process_s"] == pytest.approx(
        run["data_s"] + run["setup_s"] - run["import_s"])
    if run["cold_first_fit_s"] is None:  # nothing compiled: no second fit
        assert run["setup_s"] == pytest.approx(
            run["import_s"] + run["prepare_s"] + run["first_fit_s"],
            abs=5e-3)


# -- setup_s's clock ------------------------------------------------------------

class _Clock:
    """``time`` for the harness: every reading costs a millisecond and
    nothing else passes but what a test's ``sleep`` adds, so two runs of one
    cell read the same ``setup_s`` to rounding."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        self.now += 1e-3
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def _clocked(monkeypatch, counter, slow=None, import_s=0.0):
    """One run of the windowed cell on a ``_Clock``; ``slow`` names the part
    (``generator`` or ``entry``) that sleeps five seconds of it."""
    clock = _Clock()
    monkeypatch.setattr(harness, "time", clock)
    name = "dense1000-logistic-sliced.resident"
    cell = cells.Cell(name, ALL, _tiny(name))
    real = {"generator": cell.generator.make, "entry": cell.entry.prepare}

    class Slow:
        @staticmethod
        def make(*args):
            clock.sleep(5.0)
            return real["generator"](*args)

        @staticmethod
        def prepare(*args):
            clock.sleep(5.0)
            return real["entry"](*args)

    if slow is not None:
        setattr(cell, slow, Slow)
    run = harness.run_cell(cell, 2**31 + 11, 0.01, False,
                           clock.perf_counter(), counter,
                           log=lambda line: None, import_s=import_s)
    assert run["failed"] == 0 and run["cold_first_fit_s"] is None
    return run


CLOCKS = ("data_s", "import_s", "prepare_s", "first_fit_s", "setup_s",
          "process_s")


@pytest.mark.parametrize("slow, import_s, moved", [
    ("generator", 0.0, {"data_s": 5.0, "process_s": 5.0}),
    ("entry", 0.0, {"prepare_s": 5.0, "setup_s": 5.0, "process_s": 5.0}),
    (None, 0.75, {"import_s": 0.75, "setup_s": 0.75}),
], ids=["a_slow_generator_is_not_in_setup_s", "a_slow_prepare_is",
        "the_programs_import_is"])
def test_setup_s_moves_with_the_programs_work_alone(
        slow, import_s, moved, monkeypatch, counter):
    base = _clocked(monkeypatch, counter)
    run = _clocked(monkeypatch, counter, slow, import_s)
    for key in CLOCKS:
        assert run[key] - base[key] == pytest.approx(moved.get(key, 0.0),
                                                     abs=1e-9), key
    assert run["rows_per_s"] == pytest.approx(base["rows_per_s"])
    metrics = harness.metrics_of(cells.Cell(run["workload"], ALL), run,
                                 trace=False)
    assert metrics["setup_s"] == {"value": run["setup_s"], "unit": "s"}


def test_every_number_compared_is_printed_beside_its_limit(timed):
    cell, run, lines = timed
    assert len(lines) == len(correct.NUMBERS)
    for name, line in zip(correct.NUMBERS, lines):
        assert line.startswith(f"check {cell.name} {name} = ")
        assert f"(limit {cell.config['limits'][name]:.6g})" in line
        assert run["checks"][name] <= cell.config["limits"][name]


def test_end_to_end_metrics_are_the_cells(timed):
    cell, run, _ = timed
    metrics = harness.metrics_of(cell, run, trace=False)
    assert set(metrics) == {m["name"] for m in cell.metrics["end_to_end"]}
    assert "setup_s" in metrics and len(metrics) >= 2
    for name, m in metrics.items():
        assert m["value"] > 0 and m["unit"]


def test_the_fit_learns(timed):
    _, run, _ = timed
    assert run["loss_last"] < run["loss_first"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_what_its_readers_find(name, counter, tmp_path):
    cell, run, _ = _run(name, counter, trace=True, tmp=tmp_path / "trace")
    assert run["failed"] == 0 and 1 <= run["fits"] <= cell.job["traced_fits"]
    metrics = harness.metrics_of(cell, run, trace=True)
    # no chip here: the trace's readers find nothing and are left out, the
    # counter's is there
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert metrics["first_fit_ms"]["value"] == run["first_fit_s"] * 1e3
    assert set(metrics) <= {m["name"] for m in cell.metrics["per_layer"]}
    assert run["trace"]["devices"] == 0


def test_the_data_and_not_the_sampling_follow_the_seed(counter):
    name = "dense1000-logistic.resident"
    a = _run(name, counter, seed=5)[1]
    b = _run(name, counter, seed=5)[1]
    c = _run(name, counter, seed=6)[1]
    assert a["loss_last"] == b["loss_last"] != c["loss_last"]
    assert harness.data_seed_of(2**31 + 99) < 2**31


def test_a_cold_compile_cache_takes_the_first_fit_again(counter, tmp_path):
    """The first run of a checkout compiles the fit: its time is kept apart
    and ``first_fit_s`` is taken again from a new object."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    wanted = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {key: getattr(jax.config, key) for key in wanted}
    for key, value in wanted.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    try:
        overrides = dict(_tiny("dense1000-logistic.resident"), rows=4096)
        cell = cells.Cell("dense1000-logistic.resident", overrides=overrides)
        prepared = []
        real = cell.entry.prepare

        class Entry:
            @staticmethod
            def prepare(*args):
                prepared.append(args)
                return real(*args)

        cell.entry = Entry
        run = harness.run_cell(cell, 1, 0.05, False, time.perf_counter(),
                               counter, log=lambda line: None)
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    assert run["cold_first_fit_s"] is not None and len(prepared) == 2
    assert run["failed"] == 0 and run["compiles_in_window"] == 0
    # both fits, the one that compiled too, are inside set-up
    assert run["cold_first_fit_s"] + run["first_fit_s"] < run["setup_s"]
    assert run["setup_s"] < run["process_s"] and run["prepare_s"] is None


# -- the timed path broken underneath ----------------------------------------

def _broken_entry(cell_name, how):
    real = cells.Cell(cell_name, ALL).entry

    class Entry:
        @staticmethod
        def prepare(config, X, y, seed):
            if how == "part of the batch left out":
                half = X.shape[0] // 2
                return real.prepare(config, X[:half], y[:half], seed)
            fit = real.prepare(config, X, y, seed)

            def broken():
                w, losses = fit()
                if how == "state returned unchanged":
                    return np.zeros_like(np.asarray(w)), losses
                if how == "a loss altered where it is produced":
                    return w, np.concatenate([losses[:-1], losses[-1:] * 1.5])
                if how == "history cut short":
                    return w, losses[:-1]
                raise AssertionError(how)
            return broken

    return Entry


@pytest.mark.parametrize("how", ["state returned unchanged",
                                 "part of the batch left out",
                                 "a loss altered where it is produced",
                                 "history cut short"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, how, counter):
    _, run, _ = _run(name, counter, entry=_broken_entry(name, how))
    assert run["failed"] == run["attempted"] > 0


# -- the control --------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name, seed):
    """The reference at the precision BELOW the configuration's fails a
    limit; at the precision the configuration states it passes them all."""
    import jax.numpy as jnp

    cell = cells.Cell(name, ALL, _tiny(name))
    config, limits = cell.config, cell.config["limits"]
    X, y = cell.generator.make(config, cell.rows, seed)
    w0 = np.zeros((config["features"],), np.float32)
    seed42 = config["sampling_seed"]
    ref = cell.reference.fit(config, X, y, w0, seed42)

    def judged(operands):
        data = jnp.array(X) if config["storage"] == "dense" else X
        low = cell.reference.fit(config, data, y, w0, seed42,
                                 operands=operands)
        return correct.judge([low], *ref, w0, limits)

    failed, worst = judged(config["control_operands"])
    assert failed == 1, worst
    failed, worst = judged(config["matmul_operands"])
    assert failed == 0, worst


def test_judge_counts_each_fit_and_keeps_the_worst_reading():
    ref_w, ref_l, w0 = np.array([1.0, 2.0]), np.array([0.7, 0.5]), np.zeros(2)
    limits = {"w_rel_gap": 1e-3, "loss_max_gap": 1e-3, "dw_norm_gap": 1e-3}
    good = (ref_w * (1 + 1e-5), ref_l)
    bad = (ref_w * 1.01, ref_l)
    nan = (np.array([np.nan, 2.0]), ref_l)
    failed, worst = correct.judge([good, bad, good, nan], ref_w, ref_l, w0,
                                  limits)
    assert failed == 2 and worst["w_rel_gap"] == float("inf")
    failed, worst = correct.judge([good, bad], ref_w, ref_l, w0, limits)
    assert failed == 1 and worst["w_rel_gap"] == pytest.approx(0.01)
    with pytest.raises(KeyError, match="dw_norm_gap"):
        correct.judge([good], ref_w, ref_l, w0, {"w_rel_gap": 1.0,
                                                 "loss_max_gap": 1.0})
