"""``bench/layers/h2d_blocks.py`` (PR 29) on traces written by hand, through
the helpers of ``test_benchmark_spans.py``: the mean over the traced fits of
the pieces their ``train.h2d`` spans say the dense host array went in; 1 for a
span that says nothing (the parent's one ``jnp.asarray``: its column is a
number); nothing where no fit has the span."""

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


def _host(stats_of):
    """``H.HOST`` with each fit's ``train.h2d`` stats from ``stats_of(i)``."""
    seen, out = 0, []
    for name, start, length, stats in H.HOST:
        if name == "train.h2d":
            stats, seen = stats_of(seen), seen + 1
        out.append((name, start, length, stats))
    return out


@pytest.mark.parametrize("stats_of,expected", [
    (lambda i: {"bytes": 4096, "blocks": 131, "block_bytes": 32}, 131),
    (lambda i: {"bytes": 4096, "blocks": (131, 1)[i], "block_bytes": 32}, 66),
    (lambda i: {"bytes": 4096}, 1),  # the parent's span
    (lambda i: {"bytes": 0, "blocks": 0, "block_bytes": 0}, 0),  # on device
], ids=["blocks", "mean_over_fits", "parent", "device_array"])
def test_h2d_blocks_reads_the_spans_blocks(checkout, stats_of, expected):
    got = H._read("h2d_blocks", *checkout(H._text(host=_host(stats_of))))
    assert got == pytest.approx(expected)


def test_h2d_blocks_is_nothing_without_the_span(checkout):
    no_h2d = [e for e in H.HOST if e[0] != "train.h2d"]
    assert H._read("h2d_blocks", *checkout(H._text(host=no_h2d))) is None


def test_h2d_blocks_is_the_from_host_cells_and_moves_rows_per_s():
    bench = cells.benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == "h2d_blocks")
    assert entry == {
        "name": "h2d_blocks", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "model harness",
        "moves": "rows_per_s",
        "workloads": ["dense1000-logistic.from-host"]}
