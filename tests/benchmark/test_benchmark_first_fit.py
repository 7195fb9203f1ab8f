"""What PR 55 brings to the benchmark: five per-layer metrics of every cell
that say what the first fit, and so ``setup_s``, is made of:
``first_fit_trace_ms``, ``first_fit_lower_ms``, ``first_fit_cache_ms``,
``first_fit_programs`` and ``first_fit_rest_ms``.  Their readers take the
program's own record of what its fits built (``tpu_sgd.obs.build_roots()``)
through ``bench/first_fit.py``: the entries, appended; the cut of a root into
four that add up to it, on roots written by hand; then tiny runs through
``harness.run_cell`` on the CPU, warm and with an empty cache directory; and
a program without the record."""

import time

import pytest

from bench import cells, first_fit, harness

METRICS = {"first_fit_trace_ms": "trace_ms", "first_fit_lower_ms": "lower_ms",
           "first_fit_cache_ms": "cache_ms", "first_fit_programs": "programs",
           "first_fit_rest_ms": "rest_ms"}
DURATIONS = ("trace_ms", "lower_ms", "cache_ms", "rest_ms")
CELLS = [w["name"] for w in cells.benchmark()["workloads"]]
RESIDENT = "dense1000-logistic.resident"


# -- the entries -----------------------------------------------------------------

@pytest.mark.parametrize("metric", METRICS)
def test_the_entry_moves_setup_s_in_every_cell(metric):
    bench = cells.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert bench["per_layer"][names.index(metric)] == {
        "name": metric, "unit": "count" if metric.endswith("programs")
        else "ms", "better": "lower", "source": "program_span",
        "layer": "model harness", "moves": "setup_s"}
    assert cells.load_module("layers", metric).__doc__.startswith(
        "Model harness")


def test_the_five_stand_in_order_behind_what_the_benchmark_had():
    """By ``index``, never by the list's end: a later PR appends its own
    entries behind these with no edit here."""
    names = [m["name"] for m in cells.benchmark()["per_layer"]]
    at = [names.index(m) for m in METRICS]
    assert at == sorted(at) and at[0] > names.index("stream_whole_ms")
    end_to_end = {m["name"] for m in cells.benchmark()["end_to_end"]}
    assert "setup_s" in end_to_end


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_the_five_readers(cell):
    loaded = cells.Cell(cell)
    reported = [m["name"] for m in loaded.metrics["per_layer"]]
    at = [reported.index(m) for m in METRICS]
    assert at == sorted(at)
    assert all(callable(loaded.readers[m].read) for m in METRICS)


# -- the cut ---------------------------------------------------------------------

def _root(dur_s, *spans):
    return {"name": "train.run", "start": 100.0, "dur_s": dur_s,
            "span_id": 0, "short_traces": 0, "short_trace_s": 0.0,
            "spans": [{"name": f"build.{kind}", "start": 100.0 + start,
                       "end": 100.0 + end, "fun": "f", "thread": thread}
                      for kind, start, end, thread in spans]}


ROOTS = {
    # one program on the fit's thread: trace, lowering, cache read in turn
    "in_turn": (_root(1.0, ("trace", 0.1, 0.4, "m"), ("lower", 0.4, 0.6, "m"),
                      ("compile", 0.6, 0.65, "m")),
                (300.0, 200.0, 50.0, 450.0, 1)),
    # JAX fires the inner traces too: a union, never a sum
    "nested_traces": (_root(1.0, ("trace", 0.2, 0.3, "m"),
                            ("trace", 0.15, 0.35, "m"),
                            ("trace", 0.1, 0.4, "m")),
                      (300.0, 0.0, 0.0, 700.0, 0)),
    # a jitted rule traced while JAX lowers: that stretch is trace
    "trace_inside_a_lowering": (
        _root(1.0, ("trace", 0.1, 0.3, "m"), ("trace", 0.35, 0.4, "m"),
              ("lower", 0.3, 0.5, "m"), ("compile", 0.5, 0.6, "m")),
        (250.0, 150.0, 100.0, 500.0, 1)),
    # a worker reads the cache while the fit's thread traces: counted once
    "a_worker_beside": (
        _root(2.0, ("trace", 0.0, 1.0, "m"), ("lower", 1.0, 1.2, "m"),
              ("compile", 0.9, 1.3, "w"), ("compile", 1.4, 1.5, "m")),
        (1000.0, 200.0, 200.0, 600.0, 2)),
    "three_programs": (
        _root(0.5, ("lower", 0.0, 0.1, "m"), ("compile", 0.1, 0.11, "m"),
              ("trace", 0.12, 0.13, "m"), ("lower", 0.13, 0.14, "m"),
              ("compile", 0.14, 0.15, "m"), ("trace", 0.2, 0.3, "m"),
              ("lower", 0.3, 0.35, "m"), ("compile", 0.35, 0.4, "m")),
        (110.0, 160.0, 70.0, 160.0, 3)),
}
# the uneven stream's entry: ONE ``train_on`` for the whole run, a fit of the
# harness its first pass; the root is cut where that fit ended
OUTLIVES = _root(7.0, ("trace", 0.1, 0.4, "s"), ("lower", 0.4, 0.6, "s"),
                 ("compile", 0.6, 1.0, "s"), ("compile", 1.9, 2.2, "s"),
                 ("compile", 5.0, 5.1, "s"))


@pytest.mark.parametrize("case", ROOTS)
def test_a_root_is_cut_into_four_that_add_up_to_it(case):
    root, expected = ROOTS[case]
    got = first_fit.split(root)
    for name, value in zip(DURATIONS + ("programs",), expected):
        assert got[name] == pytest.approx(value, abs=1e-6), name
    assert sum(got[name] for name in DURATIONS) == pytest.approx(
        root["dur_s"] * 1e3, abs=1e-6)


def test_a_root_that_outlives_the_fit_is_cut_where_the_fit_ended():
    got = first_fit.split(OUTLIVES, fit_s=2.0)
    assert [got[name] for name in DURATIONS + ("programs",)] == pytest.approx(
        [300.0, 200.0, 500.0, 1000.0, 2], abs=1e-6)
    # a fit LONGER than its root (every other cell) leaves the root as it is
    assert first_fit.split(OUTLIVES, fit_s=7.5) == first_fit.split(OUTLIVES)
    assert first_fit.split(OUTLIVES)["programs"] == 3


# -- tiny runs --------------------------------------------------------------------

def _tiny(name, **more):
    tiny = dict(cells.Cell(name).config["tiny"], **more)
    tiny.pop("what")
    return tiny


@pytest.fixture(scope="module")
def counter():
    return harness.CompileCounter()


@pytest.fixture(scope="module")
def resident(counter, tmp_path_factory):
    """A tiny traced run of a cell that trains rows already on the device,
    and the roots the program kept by its end."""
    from tpu_sgd import obs

    cell = cells.Cell(RESIDENT, overrides=_tiny(RESIDENT))
    run = harness.run_cell(
        cell, 2**31 + 55, 0.2, True, time.perf_counter(), counter,
        trace_dir=str(tmp_path_factory.mktemp("trace")),
        log=lambda line: None)
    return cell, run, obs.build_roots()


@pytest.mark.parametrize("metric", METRICS)
def test_a_traced_run_reports_the_last_roots_number(resident, metric):
    cell, run, roots = resident
    metrics = harness.metrics_of(cell, run, trace=True)
    entry = {m["name"]: m for m in cell.metrics["per_layer"]}[metric]
    assert metrics[metric] == {
        "value": first_fit.split(roots[-1], run["first_fit_s"])[
            METRICS[metric]],
        "unit": entry["unit"]}
    assert metrics[metric]["value"] > 0


def test_the_four_durations_are_the_first_fit(resident):
    cell, run, roots = resident
    assert run["failed"] == 0 and run["compiles_in_window"] == 0
    metrics = harness.metrics_of(cell, run, trace=True)
    four = sum(metrics[m]["value"] for m in METRICS
               if m != "first_fit_programs")
    root = roots[-1]
    # to the microsecond: the root is CUT, nothing is counted twice
    assert four == pytest.approx(root["dur_s"] * 1e3, abs=1e-3)
    # and the root is the fit ``first_fit_s`` times, from inside the entry
    assert 0 <= run["first_fit_s"] - root["dur_s"] < 0.05
    assert root["name"] == "train.run"
    assert any("sgd_run" in s["fun"] for s in root["spans"]
               if s["name"] == "build.compile")
    assert metrics["first_fit_programs"]["value"] >= 1
    # no fit of the window built anything: the last root is set-up's
    assert root["start"] + root["dur_s"] <= time.time() - run["window_s"]


def test_the_uneven_streams_root_is_cut_at_its_first_pass(counter, tmp_path):
    """The uneven stream's entry runs ONE ``train_on`` for the whole run on a
    thread of its own: ``stream.run`` closes when the harness drops ``fit``,
    passes later; the four durations are the first PASS's."""
    from tpu_sgd import obs

    name = "dense1000-logistic-stream.stream-uneven-from-host"
    cell = cells.Cell(name, overrides=_tiny(name))
    run = harness.run_cell(cell, 2**31 + 56, 0.2, True, time.perf_counter(),
                           counter, trace_dir=str(tmp_path / "trace"),
                           log=lambda line: None)
    root = obs.build_roots()[-1]
    assert run["failed"] == 0 and root["name"] == "stream.run"
    assert root["dur_s"] > run["first_fit_s"] + sum(run["fit_s"][:-1])
    assert {s["thread"] for s in root["spans"]} >= {"bench-stream"}
    metrics = harness.metrics_of(cell, run, trace=True)
    four = sum(metrics[m]["value"] for m in METRICS
               if m != "first_fit_programs")
    assert four == pytest.approx(run["first_fit_s"] * 1e3, abs=1e-3)
    assert 0 < metrics["first_fit_rest_ms"]["value"] < run["first_fit_s"] * 1e3


@pytest.mark.parametrize("metric", METRICS)
def test_a_cold_cache_reads_the_fit_taken_again(counter, tmp_path, metric):
    """An empty cache directory: the first fit compiles, the harness takes it
    again from a new object after ``jax.clear_caches()``; the readers read
    THAT root, whose ``sgd_run`` was read from the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tpu_sgd import obs

    wanted = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {key: getattr(jax.config, key) for key in wanted}
    for key, value in wanted.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    try:
        cell = cells.Cell(RESIDENT, overrides=_tiny(RESIDENT, rows=4096))
        run = harness.run_cell(cell, 1, 0.05, False, time.perf_counter(),
                               counter, log=lambda line: None)
        roots = obs.build_roots()
        value = cell.readers[metric].read(None, run)
    finally:
        for key, value_ in before.items():
            jax.config.update(key, value_)
        compilation_cache.reset_cache()
    assert run["cold_first_fit_s"] is not None
    cold, again = roots[-2:]

    def hits(root):
        return [s["cache_hit"] for s in root["spans"]
                if s["name"] == "build.compile" and "sgd_run" in s["fun"]]

    assert hits(cold) == [0] and hits(again) == [1]
    assert value == first_fit.split(again)[METRICS[metric]]
    assert 0 <= run["first_fit_s"] - again["dur_s"] < 0.05
    assert 0 <= run["cold_first_fit_s"] - cold["dur_s"] < 0.05


# -- the parent --------------------------------------------------------------------

@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_record_reads_nothing(monkeypatch, metric):
    from tpu_sgd import obs

    reader = cells.load_module("layers", metric)
    monkeypatch.delattr(obs, "build_roots")
    assert reader.read({"fits": [], "devices": 0}, {"first_fit_s": 1.0}) \
        is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_that_built_nothing_reads_nothing(monkeypatch, metric):
    from tpu_sgd import obs

    monkeypatch.setattr(obs, "build_roots", lambda: [])
    assert cells.load_module("layers", metric).read(None, {}) is None
