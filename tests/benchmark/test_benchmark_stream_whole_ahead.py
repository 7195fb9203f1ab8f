"""What PR 60 brings to the benchmark: one per-layer metric of the uneven
logistic stream's cell, ``stream_whole_ahead`` (the ``ahead`` attribute of
the passes' ``stream.whole`` spans: 1 where a micro-batch's join was
dispatched BEHIND the running fit of the one before it, 0 where in turn),
its reader on traces written by hand, its entry, appended, and the attribute
the program sets."""

import importlib.util
import os

import pytest

from bench import cells

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NAME = "dense1000-logistic-stream.stream-uneven-from-host"
METRIC = "stream_whole_ahead"


def _host(ahead):
    """Two passes of two micro-batches: (name, start ms, length ms, stats).
    ``ahead[i]`` is what the ``i``-th ``stream.whole`` says: 1 lies inside
    the fit BEFORE its own (the pass's first: before the pass, inside the
    fit that ends the pass before; so a pass holds the join of the NEXT
    pass's first), behind ``train.dispatch`` and in front of
    ``train.fetch``; 0 inside its own ``stream.wait``; None in the wait with
    no attribute (the parent's span)."""
    out, joins = [], iter(ahead)
    for base in (0, 100):
        out.append(("bench.fit", base, 100, {}))
        for k in (0, 1):
            at, says = base + 1 + 48 * k, next(joins)
            stats = {"blocks": 4} if says is None else {"blocks": 4,
                                                        "ahead": says}
            out += [("stream.wait", at, 6, {}),
                    ("stream.batch", at + 6, 40, {"index": k, "rows": 64,
                                                  "ahead": 1}),
                    ("fit.run", at + 7, 34, {"rows": 64}),
                    ("train.run", at + 8, 32, {"path": "fused"}),
                    ("train.dispatch", at + 10, 2, {"built": 0}),
                    ("train.fetch", at + 16, 22, {"recorded": 10}),
                    ("stream.publish", at + 42, 2, {})]
            out.append(("stream.whole", at + 12, 3, stats) if says
                       else ("stream.whole", at + 2, 3, stats))
    return out


@pytest.mark.parametrize("ahead,expected", [
    ((1, 1, 1, 1), 1.0),
    ((0, 0, 0, 0), 0.0),  # every take came late: every join in turn
    ((0, 1, 1, 1), 0.75),  # the window's first in turn
    ((0, 1, None, 1), 2 / 3),  # a mean over the spans that say it
    ((None, None, None, None), None),  # the parent: ``blocks`` alone
], ids=["every_join", "none", "a_mean", "over_those_that_say", "parent"])
def test_stream_whole_ahead_reads_the_joins_spans(checkout, ahead, expected):
    got = H._read(METRIC, *checkout(H._text(host=_host(ahead))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_stream_whole_ahead_is_nothing_without_the_span_or_a_device(checkout):
    assert H._read(METRIC, *checkout(H._text())) is None
    no_join = [e for e in _host((1, 1, 1, 1)) if e[0] != "stream.whole"]
    assert H._read(METRIC, *checkout(H._text(host=no_join))) is None
    from bench.layers import stream_whole_ahead

    assert stream_whole_ahead.read({"fits": [], "devices": 0},
                                   {"workload": NAME,
                                    "iterations": 50}) is None


def test_the_readers_beside_it_read_a_join_behind_a_fit_as_before(checkout):
    """``stream.whole`` keeps its name and its ``blocks`` wherever it lies:
    ``stream_whole_ms`` reads the host's time in it, and
    ``stream_wait_ms`` no longer holds it where it went behind a fit."""
    behind = checkout(H._text(host=_host((1, 1, 1, 1))))
    assert H._read("stream_whole_ms", *behind) == pytest.approx(3.0)
    assert H._read("stream_wait_ms", *behind) == pytest.approx(6.0)
    in_turn = checkout(H._text(host=_host((0, 0, 0, 0))))
    assert H._read("stream_whole_ms", *in_turn) == pytest.approx(3.0)
    assert H._read("stream_wait_ms", *in_turn) == pytest.approx(6.0)


def test_the_metric_is_the_uneven_cells_appended_and_moves_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries[METRIC] == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_span", "layer": "stream fold",
        "moves": "rows_per_s", "workloads": [NAME]}
    names = [m["name"] for m in bench["per_layer"]]
    # behind everything PR 58 left (a later PR's entries go behind it)
    assert names.index(METRIC) > names.index("stream_join_ms")
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"].startswith("stream_")}
    assert layers == {"stream fold"}  # the layer's name, letter for letter
    assert os.path.exists(os.path.join(cells.BENCH, "layers",
                                       METRIC + ".py"))


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_the_uneven_cell_alone_reports_it(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert (METRIC in reported) == (cell == NAME)


def test_the_program_sets_the_attribute_the_reader_reads(tmp_path):
    """``stream.whole`` of a logistic stream of unequal micro-batches says
    ``ahead`` beside ``blocks``: 0 for the join dispatched in turn (the
    second micro-batch's: the first is copied inside its fit and the second
    taken after it), 0 or 1 after it (1 where the worker's take was done
    before the fit before it had ended: ``tests/test_streaming.py`` sets the
    pace), and a join behind a fit lies inside that fit's ``train.run``."""
    import json

    import numpy as np

    from tpu_sgd import StreamingLogisticRegressionWithSGD, obs

    rng = np.random.default_rng(3)
    stream = []
    for rows in (1500, 1300, 1700, 1100):
        X = rng.normal(size=(rows, 8)).astype(np.float32)
        stream.append((X, (X @ np.ones(8, np.float32) > 0)
                       .astype(np.float32)))
    alg = StreamingLogisticRegressionWithSGD(step_size=0.1, num_iterations=3)
    alg.set_initial_weights(np.zeros(8, np.float32))
    path = tmp_path / "spans.jsonl"
    obs.enable(str(path))
    try:
        alg.train_on(stream)
    finally:
        obs.disable()
    with open(path) as f:
        spans = [json.loads(line) for line in f]
    fits = {s["span_id"] for s in spans if s.get("name") == "train.run"}
    waits = {s["span_id"] for s in spans if s.get("name") == "stream.wait"}
    joins = sorted((s for s in spans if s.get("name") == "stream.whole"),
                   key=lambda s: s["t0_s"])
    assert len(joins) == 3 and joins[0]["ahead"] == 0
    for join in joins:
        assert join["blocks"] == 1 and join["ahead"] in (0, 1)
        assert join["parent_id"] in (fits if join["ahead"] else waits)
