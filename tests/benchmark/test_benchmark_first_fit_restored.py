"""What PR 56 brings to the benchmark: the reader of ``first_fit_restored``,
the ``build.restore`` spans with ``hit`` 1 under the first fit's root
(``bench/layers/first_fit_restored.py``): on roots written by hand, through
tiny runs of ``harness.run_cell`` on the CPU with a compile cache directory
(a checkout's first run stores, the fit taken again restores) and without
one (a bypass reads 0), and on a program without the span (None, no raise).
Its ``BENCHMARK.json`` entry waits for a ``benchmark`` PR (PERF.md section 7)."""

import time

import pytest

from bench import cells, harness

RESIDENT = "dense1000-logistic.resident"
STREAM = "dense1000-lsq-stream.stream-from-host"


@pytest.fixture(scope="module")
def reader():
    return cells.load_module("layers", "first_fit_restored")


def test_the_reader_is_the_model_harness(reader):
    assert reader.__doc__.startswith("Model harness")


def _root(dur_s, *spans):
    return {"name": "train.run", "start": 100.0, "dur_s": dur_s,
            "span_id": 0, "short_traces": 0, "short_trace_s": 0.0,
            "spans": [dict(more, name=f"build.{kind}", start=100.0 + start,
                           end=100.0 + end, fun="sgd_run", thread="m")
                      for kind, start, end, more in spans]}


ROOTS = {
    "restored": (_root(1.0, ("restore", 0.1, 0.102, {"hit": 1, "ms": 2.0}),
                       ("lower", 0.11, 0.2, {}), ("compile", 0.2, 0.22, {})),
                 None, 1),
    "stored": (_root(1.0, ("trace", 0.1, 0.4, {}),
                     ("restore", 0.1, 0.6, {"hit": 0, "ms": 500.0})),
               None, 0),
    "bypassed": (_root(1.0, ("restore", 0.1, 0.1, {
        "hit": None, "ms": 0.01, "reason": "no compile cache directory"}),
        ("trace", 0.1, 0.4, {})), None, 0),
    "two_programs": (_root(1.0, ("restore", 0.1, 0.11, {"hit": 1, "ms": 1}),
                           ("restore", 0.5, 0.51, {"hit": 1, "ms": 1})),
                     None, 2),
    # a root that outlives the timed fit is cut where the fit ended
    "cut_at_the_fit": (_root(7.0, ("restore", 0.1, 0.11, {"hit": 1, "ms": 1}),
                             ("restore", 3.0, 3.01, {"hit": 1, "ms": 1})),
                       2.0, 1),
    "no_such_span": (_root(1.0, ("trace", 0.1, 0.4, {}),
                           ("compile", 0.4, 0.5, {})), None, None),
}


@pytest.mark.parametrize("case", ROOTS)
def test_the_hits_under_the_last_root_are_counted(monkeypatch, reader, case):
    from tpu_sgd import obs

    root, fit_s, expected = ROOTS[case]
    monkeypatch.setattr(obs, "build_roots", lambda: [_root(1.0), root])
    run = {} if fit_s is None else {"first_fit_s": fit_s}
    assert reader.read(None, run) == expected


def test_a_program_without_the_record_reads_nothing(monkeypatch, reader):
    from tpu_sgd import obs

    monkeypatch.delattr(obs, "build_roots")
    assert reader.read({"fits": [], "devices": 0}, {"first_fit_s": 1.0}) \
        is None


def test_a_program_that_built_nothing_reads_nothing(monkeypatch, reader):
    from tpu_sgd import obs

    monkeypatch.setattr(obs, "build_roots", lambda: [])
    assert reader.read(None, {}) is None


def _tiny(name, **more):
    tiny = dict(cells.Cell(name).config["tiny"], **more)
    tiny.pop("what")
    return tiny


@pytest.fixture(scope="module")
def counter():
    return harness.CompileCounter()


@pytest.fixture
def cache_dir(compile_cache):
    return compile_cache


@pytest.mark.parametrize("cell_name, rows", [(RESIDENT, 4096), (STREAM, None)])
def test_a_checkouts_first_run_stores_and_the_fit_taken_again_restores(
        counter, cache_dir, reader, cell_name, rows):
    from tpu_sgd import obs

    more = {} if rows is None else {"rows": rows}
    cell = cells.Cell(cell_name, overrides=_tiny(cell_name, **more))
    run = harness.run_cell(cell, 2**31 + 56, 0.05, False, time.perf_counter(),
                           counter, log=lambda line: None)
    assert run["failed"] == 0 and run["cold_first_fit_s"] is not None
    assert run["compiles_in_window"] == 0
    cold, again = obs.build_roots()[-2:]

    def hits(root):
        return [s["hit"] for s in root["spans"]
                if s["name"] == "build.restore"]

    assert hits(cold) == [0] and hits(again) == [1]
    assert reader.read(None, run) == 1


def test_without_a_cache_directory_the_bypass_reads_zero(counter, reader):
    import jax

    if jax.config.jax_compilation_cache_dir:
        pytest.skip("this process has a persistent cache")
    cell = cells.Cell(RESIDENT, overrides=_tiny(RESIDENT, rows=2048))
    run = harness.run_cell(cell, 2**31 + 57, 0.05, False, time.perf_counter(),
                           counter, log=lambda line: None)
    assert run["failed"] == 0 and reader.read(None, run) == 0
