"""What PR 36 brings to the benchmark: one per-layer metric,
``mask_in_kernel`` (the ``mask_in_kernel`` attribute of the fits'
``train.run`` spans: 1 where the one-read kernel draws the step's Bernoulli
mask itself), its reader on traces written by hand, and its entry: the three
masked cells', appended."""

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

MASKED = ["dense1000-logistic.resident", "dense1000-logistic.from-host",
          "dense1000-lsq-dp4.resident-sharded"]


def _host(stats_of):
    """``H.HOST`` with each fit's ``train.run`` stats from ``stats_of(i)``."""
    seen, out = 0, []
    for name, start, length, stats in H.HOST:
        if name == "train.run":
            stats, seen = {**stats, **stats_of(seen)}, seen + 1
        out.append((name, start, length, stats))
    return out


@pytest.mark.parametrize("stats_of,expected", [
    (lambda i: {"mask_in_kernel": 1, "row_tile": 2048}, 1),
    (lambda i: {"mask_in_kernel": 0, "row_tile": 2048}, 0),  # an array
    (lambda i: {"mask_in_kernel": (1, 0)[i]}, 0.5),
    (lambda i: {"row_tile": 2048}, None),  # the parent's span: no attribute
], ids=["in_kernel", "array", "mean_over_fits", "parent"])
def test_mask_in_kernel_reads_the_spans_attribute(checkout, stats_of,
                                                  expected):
    got = H._read("mask_in_kernel", *checkout(H._text(host=_host(stats_of))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_mask_in_kernel_is_nothing_without_the_span(checkout):
    no_run = [e for e in H.HOST if e[0] != "train.run"]
    assert H._read("mask_in_kernel", *checkout(H._text(host=no_run))) is None


def test_the_metric_is_the_masked_cells_and_moves_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["mask_in_kernel"] == {
        "name": "mask_in_kernel", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "step", "moves": "rows_per_s",
        "workloads": MASKED}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("mask_in_kernel") > names.index("row_tile")


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_each_cell_reports_it_where_its_step_draws_a_mask(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert ("mask_in_kernel" in reported) == (cell in MASKED)
