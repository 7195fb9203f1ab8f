"""What PR 44 brings to the benchmark: one per-layer metric of the stream
cell, ``stream_folded`` (``folded`` over ``blocks`` of the passes'
``stream.stage`` spans: the share of the row blocks whose part of the totals
was folded in under the copy), its reader on traces written by hand, and its
entry, appended."""

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

NAME = "dense1000-lsq-stream.stream-from-host"


def _host(staged):
    """Two passes of two micro-batches: (name, start ms, length ms, stats);
    ``staged[i]`` are the stats of the worker's ``i``-th ``stream.stage``
    that took a micro-batch (the one that finds the stream's end says
    nothing)."""
    out, seen = [], 0
    for base in (0, 100):
        out += [("bench.fit", base, 100, {}),
                ("stream.run", base, 99, {})]
        for k in (0, 1):
            at = base + 1 + 48 * k
            out += [("stream.stage", at, 20, staged[seen]),
                    ("stream.wait", at + 20, 2, {}),
                    ("stream.batch", at + 22, 24,
                     {"index": k, "rows": 64, "ahead": k}),
                    ("fit.run", at + 23, 20, {"rows": 64})]
            seen += 1
        out.append(("stream.stage", base + 97, 1, {}))
    return out


def _staged(folded, blocks=(128, 128, 128, 128)):
    return [{"bytes": 4096 * b, "blocks": b, "folded": f}
            for b, f in zip(blocks, folded)]


@pytest.mark.parametrize("staged,expected", [
    (_staged((128, 128, 128, 128)), 1.0),
    (_staged((0, 0, 0, 0)), 0.0),  # staged for a join, every one
    (_staged((0, 128, 128, 128)), 0.75),
    (_staged((0, 4, 100, 100), blocks=(4, 4, 100, 100)), 204 / 208),
    # the parent's spans: ``bytes`` and ``blocks``, no ``folded``
    ([{"bytes": 4096, "blocks": 2}] * 4, None),
    ([{}] * 4, None),  # nothing went ahead in blocks
    (_staged((0, 0, 0, 0), blocks=(0, 0, 0, 0)), None),
], ids=["every_block", "joined", "a_share", "by_blocks_not_by_batches",
        "parent", "nothing_staged", "no_blocks"])
def test_stream_folded_reads_the_workers_spans(checkout, staged, expected):
    got = H._read("stream_folded", *checkout(H._text(host=_host(staged))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_stream_folded_is_nothing_without_the_span_or_a_device(checkout):
    assert H._read("stream_folded", *checkout(H._text())) is None
    no_stage = [e for e in _host(_staged((128,) * 4))
                if e[0] != "stream.stage"]
    assert H._read("stream_folded",
                   *checkout(H._text(host=no_stage))) is None
    from bench.layers import stream_folded

    assert stream_folded.read({"fits": [], "devices": 0},
                              {"workload": NAME, "iterations": 50}) is None


def test_the_metric_is_the_stream_cells_appended_and_moves_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["stream_folded"] == {
        "name": "stream_folded", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "stream fold",
        "moves": "rows_per_s", "workloads": [NAME]}
    names = [m["name"] for m in bench["per_layer"]]
    # behind everything PR 42 left (a later PR's entries go behind it)
    assert names.index("stream_folded") > names.index("stage_ms")
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"].startswith("stream_")}
    assert layers == {"stream fold"}  # the layer's name, letter for letter


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_the_stream_cell_alone_reports_it(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert ("stream_folded" in reported) == (cell == NAME)


def test_the_program_sets_the_attribute_the_reader_reads(tmp_path):
    """``stream.stage`` of a micro-batch that went ahead on the statistics
    schedule says ``folded`` beside ``bytes`` and ``blocks``, and
    ``stream.batch`` says ``totals``."""
    import json
    import warnings

    import numpy as np

    from tpu_sgd import StreamingLinearRegressionWithSGD, obs

    rng = np.random.default_rng(3)
    stream = []
    for _ in range(3):
        X = rng.normal(size=(256, 8)).astype(np.float32)
        stream.append((X, (X @ np.ones(8, np.float32)).astype(np.float32)))
    alg = StreamingLinearRegressionWithSGD(step_size=0.1, num_iterations=3)
    alg.set_initial_weights(np.zeros(8, np.float32))
    alg.algorithm.set_schedule("resident_gram")
    path = tmp_path / "spans.jsonl"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # forced: a net loss at these sizes
        alg.train_on(stream)  # the first fit plans
        obs.enable(str(path))
        try:
            alg.train_on(stream)
        finally:
            obs.disable()
    with open(path) as f:
        spans = [json.loads(line) for line in f]
    staged = [s for s in spans if s.get("name") == "stream.stage"
              and "blocks" in s]
    assert [(s["blocks"], s["folded"], s["bytes"]) for s in staged] \
        == [(1, 1, 256 * 8 * 4)] * 3
    assert [s["totals"] for s in spans
            if s.get("name") == "stream.batch"] == [1, 1, 1]
