"""What PR 41 brings to the benchmark: two per-layer metrics of the stream
cell, ``stats_build_ms`` (own time of the operations under the scope
``sgd.stats_build``, a micro-batch trained) and ``stats_fits`` (the ``stats``
attribute of the passes' ``train.run`` spans: 1 where the fit ran from the
totals of its rows), their readers on traces written by hand, and their
entries, appended."""

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


def _host(stats_of=lambda i: {}, batches=2):
    """Two passes of ``batches`` micro-batches: (name, start ms, length ms,
    stats); each micro-batch's ``train.run`` carries ``stats_of(i)``."""
    out, seen = [], 0
    for base in (0, 100):
        out.append(("bench.fit", base, 100, {}))
        out.append(("stream.run", base, 100, {}))
        for k in range(batches):
            at = base + 1 + k * (96 // batches)
            out += [("stream.wait", at, 4, {}),
                    ("stream.batch", at + 4, 40 // batches * 2, {"ahead": k}),
                    ("fit.run", at + 5, 30 // batches * 2, {"rows": 64}),
                    ("train.run", at + 6, 28 // batches * 2,
                     {"path": "gram", **stats_of(seen)})]
            seen += 1
    return out


BUILD = "%convolution.5 = convolution(X, X)"
PARTS = "%multiply_reduce_fusion = fusion(y)"
FOLD = "%fusion.6 = fusion(X, y1, y2, y3)"
JOIN = "%fusion.127 = fusion(blocks)"
SCOPE = "jit(_stats_build)/sgd.stats_build/"
TF_OPS = {BUILD: SCOPE + "dot_general:", PARTS: SCOPE + "reduce_precision:",
          FOLD: SCOPE + "reduce_sum:",
          JOIN: "jit(_stage_join)/sgd.stage/concatenate:",
          H.WHILE: "jit(sgd_run)/while:"}
#: pass 0: the join's 3 ms, then the build's 1 + 20 + 5 and the run; and a
#: second build of 22 + 5; pass 1: two builds of 20 bare
OPS = [(JOIN, 2, 3), (PARTS, 6, 1), (BUILD, 7, 20), (FOLD, 27, 5),
       (H.WHILE, 33, 4), (BUILD, 55, 22), (FOLD, 77, 5),
       (BUILD, 110, 20), (BUILD, 160, 20)]


def test_stats_build_ms_reads_the_builds_scope_a_micro_batch(checkout):
    reduced, run = checkout(H._text(host=_host(), ops=OPS, tf_ops=TF_OPS))
    # (1 + 20 + 5 + 22 + 5 + 20 + 20) ms over four micro-batches trained
    assert H._read("stats_build_ms", reduced, run) == pytest.approx(23.25)
    assert H._read("fused_sums_ms", reduced, run) is None


def test_stats_build_ms_counts_the_fits_not_the_iterations(checkout):
    run_of = {"workload": H.WORKLOAD, "iterations": 50}
    reduced, _ = checkout(H._text(host=_host(batches=3), ops=OPS,
                                  tf_ops=TF_OPS))
    from bench.layers import stats_build_ms

    assert stats_build_ms.read(reduced, run_of) == pytest.approx(93 / 6)


def test_stats_build_ms_leaves_itself_out_where_nothing_carries_the_scope(
        checkout):
    # the parent: the one-read kernel under sgd.fused_sums, no such scope
    assert H._read("stats_build_ms", *checkout(H._text())) is None
    # the scope without a fit's span around it
    bare = [e for e in _host() if e[0] != "train.run"]
    assert H._read("stats_build_ms", *checkout(
        H._text(host=bare, ops=OPS, tf_ops=TF_OPS))) is None
    # no device in the trace: the CPU rehearsal
    from bench.layers import stats_build_ms

    assert stats_build_ms.read({"fits": [], "devices": 0},
                               {"workload": NAME, "iterations": 50}) is None


@pytest.mark.parametrize("stats_of,expected", [
    (lambda i: {"stats": 1}, 1),
    (lambda i: {"stats": 0}, 0),  # every fit read its rows
    (lambda i: {"stats": (1, 0, 1, 1)[i]}, 0.75),
    (lambda i: {"row_tile": 2048}, None),  # the parent's span: no attribute
], ids=["totals", "stock", "mean_over_micro_batches", "parent"])
def test_stats_fits_reads_the_spans_attribute(checkout, stats_of, expected):
    got = H._read("stats_fits", *checkout(H._text(host=_host(stats_of))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_stats_fits_is_nothing_without_the_span(checkout):
    no_run = [e for e in _host(lambda i: {"stats": 1})
              if e[0] != "train.run"]
    assert H._read("stats_fits", *checkout(H._text(host=no_run))) is None


def test_the_two_metrics_are_the_stream_cells_and_move_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["stats_build_ms"] == {
        "name": "stats_build_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step", "moves": "rows_per_s",
        "workloads": [NAME]}
    assert entries["stats_fits"] == {
        "name": "stats_fits", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "model harness",
        "moves": "rows_per_s", "workloads": [NAME]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("stats_build_ms") > names.index("stream_publish_ms")
    assert names.index("stats_fits") > names.index("stream_publish_ms")


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_each_cell_reports_them_where_a_fit_may_run_from_its_totals(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert ({"stats_build_ms", "stats_fits"} <= reported) == (cell == NAME)
    assert ("stats_fits" in reported) == ("stats_build_ms" in reported)


def test_the_program_has_the_scope_and_the_attribute_the_readers_read():
    """The scope's name in the jitted build and the span's attribute in the
    optimizer: what the two readers rest on."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops import gram

    text = gram._stats_build.lower(
        jax.ShapeDtypeStruct((256, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((256,), jnp.float32)).as_text(debug_info=True)
    assert "sgd.stats_build" in text and "jit(_stats_build)" in text
