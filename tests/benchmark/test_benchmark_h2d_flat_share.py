"""What PR 49 brings to the benchmark: one per-layer metric of the two cells
that train from a host array, ``h2d_flat_share`` (``flat`` over ``blocks`` of
the fits' ``train.h2d`` spans: the share of the hand-off's pieces that crossed
in a form the runtime does not re-tile), its reader on traces written by hand
through the helpers of ``test_benchmark_spans.py``, its entry, appended, and
the attribute the program sets."""

import importlib.util
import os

import numpy as np
import pytest

from bench import cells

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

METRIC = "h2d_flat_share"
ONE = "dense1000-logistic.from-host"
FOUR = "dense1000-lsq-dp4-run.from-host-sharded"


def _host(stats_of):
    """``H.HOST`` with each fit's ``train.h2d`` stats from ``stats_of(i)``."""
    seen, out = 0, []
    for name, start, length, stats in H.HOST:
        if name == "train.h2d":
            stats, seen = stats_of(seen), seen + 1
        out.append((name, start, length, stats))
    return out


@pytest.mark.parametrize("stats_of,expected", [
    (lambda i: {"bytes": 4096, "blocks": 612, "flat": 612, "shards": 4}, 1.0),
    # the strided fallback: the attribute is there and says none
    (lambda i: {"bytes": 4096, "blocks": 131, "flat": 0, "shards": 1}, 0.0),
    # the parent: ``blocks`` and no ``flat``
    (lambda i: {"bytes": 4096, "blocks": 131, "block_bytes": 32}, 0.0),
    # a mean over the fits' shares, not over their pieces
    (lambda i: {"bytes": 4096, "blocks": (100, 4)[i], "flat": (100, 1)[i]},
     (1.0 + 0.25) / 2),
    # a device array sends no piece; a span from before ``blocks`` says none
    (lambda i: {"bytes": 0, "blocks": 0, "block_bytes": 0, "flat": 0}, None),
    (lambda i: {"bytes": 4096}, None),
    # one fit of two sent pieces: the mean is over the fits that did
    (lambda i: {"bytes": 4096, "blocks": (8, 0)[i], "flat": (8, 0)[i]}, 1.0),
], ids=["every_piece", "fallback", "parent", "mean_over_fits",
        "device_array", "no_blocks", "one_fit_sent"])
def test_h2d_flat_share_reads_flat_over_blocks(checkout, stats_of, expected):
    got = H._read(METRIC, *checkout(H._text(host=_host(stats_of))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_h2d_flat_share_is_nothing_without_the_span_or_a_device(checkout):
    no_h2d = [e for e in H.HOST if e[0] != "train.h2d"]
    assert H._read(METRIC, *checkout(H._text(host=no_h2d))) is None
    assert H._read(METRIC, *checkout(H._text())) is None  # ``bytes`` alone
    assert cells.load_module("layers", METRIC).read(
        {"fits": [], "devices": 0}, {"workload": FOUR}) is None


def test_the_entry_names_both_cells_appended_and_moves_rows_per_s():
    bench = cells.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert bench["per_layer"][names.index(METRIC)] == {
        "name": METRIC, "unit": "share", "better": "higher",
        "source": "program_span", "layer": "model harness",
        "moves": "rows_per_s", "workloads": [ONE, FOUR]}
    # behind everything PR 48 left (a later PR's entries go behind it)
    assert names.index(METRIC) > names.index("class_kernel_ms")
    assert METRIC in H.SPAN_METRICS  # held to test_benchmark_spans' rules
    assert cells.load_module("layers", METRIC).__doc__.startswith(
        "Model harness")


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_the_two_cells_that_train_from_a_host_array_report_it(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert (METRIC in reported) == (cell in (ONE, FOUR))


# -- the program --------------------------------------------------------------------

@pytest.mark.parametrize("order,dtype,flat", [
    ("C", "float32", True), ("F", "bfloat16", True), ("F", "float32", False)],
    ids=["c_ordered_flat", "fortran_two_byte_words", "fortran_f32_strided"])
@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh"])
def test_the_program_sets_the_attribute_the_reader_reads(monkeypatch, meshed,
                                                         order, dtype, flat):
    """A fit from a host array in blocks, tracing on: ``train.h2d`` says
    ``flat`` beside ``blocks``: every block of a C-ordered array and of a
    Fortran-ordered array of 2-byte items, none of one that takes the
    strided fallback."""
    import jax
    import ml_dtypes

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing
    from tpu_sgd.optimize import gradient_descent as gd

    dtype = np.dtype(getattr(ml_dtypes, dtype, dtype))
    shards = 4 if meshed else 1
    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES",
                        gd._STAGE_ROWS * 8 * dtype.itemsize)
    monkeypatch.setattr(gd, "_STAGE_IN_FLIGHT", 2)
    rng = np.random.default_rng(5)
    X = np.asarray(rng.normal(size=(shards * 3 * gd._STAGE_ROWS, 8)),
                   dtype=dtype, order=order)
    y = X.astype(np.float32) @ np.arange(8, dtype=np.float32)
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_num_iterations(3).set_mini_batch_fraction(0.5))
    if meshed:
        opt.set_mesh(tpu_sgd.data_mesh(jax.devices()[:shards]))
    records = []

    class Sink:
        @staticmethod
        def emit(kind, payload):
            records.append(dict(payload))

    enable_tracing(Sink)
    try:
        opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    finally:
        disable_tracing()
    h2d, = [r for r in records if r["name"] == "train.h2d"]
    assert (h2d["shards"], h2d["blocks"]) == (shards, shards * 3)
    assert h2d["flat"] == (shards * 3 if flat else 0)
    # the name is no ``put_`` part (``test_benchmark_handoff_calls.py``)
    assert not [k for k in h2d if k.startswith("put_") and k != "put_ms"]
