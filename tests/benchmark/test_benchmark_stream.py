"""What ``dense1000-lsq-stream`` brings to the benchmark (PR 40): BASELINE
config 5, ``StreamingLinearRegressionWithSGD.train_on`` over micro-batches
from the host.  The job, the cut and the work module from shapes, the
generator, the reference by hand, the cell's tiny rehearsal through its own
entry with a micro-batch dropped or trained twice, and the three readers
(``stream_wait_ms``, ``stream_ahead``, ``stream_publish_ms``) on traces
written by hand."""

import importlib.util
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, correct, harness

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NAME = "dense1000-lsq-stream.stream-from-host"
ROWS, BATCH, D = 6_291_456, 2_097_152, 1000
METRICS = ["stream_wait_ms", "stream_ahead", "stream_publish_ms"]


def _tiny_cell(**more):
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides={**tiny, **more})


# -- the job, the cut and the work module ----------------------------------------

def test_the_job_cuts_the_stream_to_three_micro_batches():
    cell = cells.Cell(NAME)
    config = cell.config
    assert cell.rows == ROWS == 3 * BATCH == 3 * cell.job["rows_step"]
    assert config["micro_batch_rows"] == BATCH
    assert cell.work.dataset_bytes(config, BATCH) == 4_194_304_000 < 2**32
    # in turn, a micro-batch with its labels and the hand-off's 16 blocks
    # of 16,384 rows in flight clears a quarter of a 16 GiB chip
    assert BATCH * D * 2 + 4 * BATCH + 16 * 16384 * D * 2 > 2**34 / 4
    assert cell.work.dataset_bytes(config, ROWS) <= \
        cell.job["dataset_bytes_cap"] < cell.work.dataset_bytes(
            config, ROWS + BATCH)
    # the rows are cut and nothing else; the defaults are upstream's
    assert config["reduced"] == ["rows"] and config["rows"] == 10_000_000
    assert config["as_run"]["rows"] == {"stream-from-host": ROWS}
    assert (config["features"], config["step_size"], config["num_iterations"],
            config["mini_batch_fraction"], config["reg_param"]) \
        == (D, 0.1, 50, 1.0, 0.0)
    assert (config["model"], config["gradient"], config["updater"]) == (
        "StreamingLinearRegressionWithSGD", "LeastSquaresGradient",
        "SimpleUpdater")
    assert config["schedule"] == "auto" and cell.chips == 1
    assert cell.job["placement"] == "host"
    assert cell.job["entry"] == {"dense": "stream_train_on"}


def test_work_from_shapes_by_hand():
    cell = cells.Cell(NAME)
    work = cell.work.step_work(cell.config, cell.rows)
    once = ROWS * D * 2 + ROWS * 4  # every row of a pass, with its label
    # least: once a PASS, over the pass's 50 iterations; the stock
    # schedule's two matvecs an iteration
    assert work["least"] == {"bytes": -(-once // 50), "flops": 4 * ROWS * D}
    # as laid out: the stock schedule reads every row once an ITERATION
    assert work["as_laid_out"] == {"bytes": once, "flops": 4 * ROWS * D}
    assert work["least"]["bytes"] / 819e9 == pytest.approx(0.30789e-3,
                                                           rel=1e-3)
    # no exact schedule of least squares beats it: the statistics schedule
    # reads every row once a pass and does 2 x rows x d^2 operations
    gram = {"bytes": once, "flops": 2 * ROWS * D * D}
    for other in (gram, {k: 50 * v for k, v in work["as_laid_out"].items()}):
        a_pass = max(other["bytes"] / 819e9, other["flops"] / 197e12)
        least = max(work["least"]["bytes"] / 819e9,
                    work["least"]["flops"] / 197e12)
        assert 50 * least <= a_pass


def test_the_three_metrics_are_this_cells_and_move_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    for metric, unit, better in zip(METRICS, ("ms", "count", "ms"),
                                    ("lower", "higher", "lower")):
        # the cell the metric came with stands first on its list; a later
        # stream cell whose passes carry the span is appended behind it
        assert {**entries[metric],
                "workloads": entries[metric]["workloads"][:1]} == {
            "name": metric, "unit": unit, "better": better,
            "source": "program_span", "layer": "stream fold",
            "moves": "rows_per_s", "workloads": [NAME]}
        assert names.index(metric) > names.index("h2d_stall_ms")
    reported = {m["name"] for m in cells.Cell(NAME).metrics["per_layer"]}
    assert set(METRICS) <= reported and "step_roofline" in reported
    assert "h2d_ms" not in reported  # the from-host cell's own


# -- the generator ------------------------------------------------------------------

def test_the_generator_makes_a_stationary_stream_on_the_host():
    cell = _tiny_cell()
    config, m = cell.config, cell.config["micro_batch_rows"]
    X, y = cell.generator.make(config, cell.rows, 2_147_483_000)
    assert X.shape == (12288, 64) and y.shape == (12288,)
    assert isinstance(X, np.ndarray) and X.flags.f_contiguous
    assert jnp.asarray(X[:8]).dtype == jnp.bfloat16 and y.dtype == np.float32
    assert type(np.asarray(X)) is np.ndarray  # what ``place`` keeps
    X.delete(), y.delete()  # and what it calls: nothing to free
    again = cell.generator.make(config, cell.rows, 2_147_483_000)
    np.testing.assert_array_equal(np.asarray(X).view(np.uint16),
                                  np.asarray(again[0]).view(np.uint16))
    other = cell.generator.make(config, cell.rows, 7)
    assert not np.array_equal(np.asarray(y), np.asarray(other[1]))
    # one w_true for the whole stream: each micro-batch alone gives it back
    Xf = np.asarray(X).astype(np.float32)
    fits = [np.linalg.lstsq(Xf[a:a + m], np.asarray(y)[a:a + m],
                            rcond=None)[0] for a in range(0, 12288, m)]
    assert np.abs(fits[0] - fits[2]).max() < 0.02 and \
        np.abs(fits[0]).max() > 0.5
    # micro-batches are different rows
    assert not np.array_equal(Xf[:m], Xf[m:2 * m])
    # a stream that ends inside a micro-batch: the last one is cut
    short = cell.generator.make(config, m + 100, 2_147_483_000)
    assert short[0].shape == (m + 100, 64)
    np.testing.assert_array_equal(np.asarray(short[1]),
                                  np.asarray(y)[:m + 100])


# -- the reference --------------------------------------------------------------------

def test_stream_reference_follows_two_micro_batches_of_two_steps_by_hand():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(12, 3)).astype(np.float32)
    y = rng.normal(size=(12,)).astype(np.float32)
    config = {"gradient": "LeastSquaresGradient", "updater": "SimpleUpdater",
              "mini_batch_fraction": 1.0, "step_size": 0.5, "reg_param": 0.0,
              "num_iterations": 2, "micro_batch_rows": 6}
    ref = cells.load_module("reference", "glm_dense_stream")
    w, losses = ref.fit(config, X, y, np.zeros(3, np.float32), 42)
    want_w, want = np.zeros(3), []
    for a in (0, 6):  # each micro-batch: t from 1 again, from the last w
        Xb, yb = X[a:a + 6].astype(np.float64), y[a:a + 6]
        for t in (1, 2):
            diff = Xb @ want_w - yb
            want.append(0.5 * np.mean(diff * diff))  # at the old weights
            want_w = want_w - 0.5 / np.sqrt(t) * (diff @ Xb) / 6
    np.testing.assert_allclose(w, want_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    assert losses.shape == (4,)
    # a last micro-batch of what is left
    w9, losses9 = ref.fit(config, X[:9], y[:9], np.zeros(3, np.float32), 42)
    assert losses9.shape == (4,) and losses9[1] == losses[1]
    assert not np.allclose(w9, w)


# -- the cell's own entry at the tiny sizes ---------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    cell = _tiny_cell()
    run = harness.run_cell(cell, 2**31 + 40, 0.2, False, time.perf_counter(),
                           harness.CompileCounter(), log=lambda line: None)
    return cell, run


def test_the_rehearsal_is_correct_and_counts_every_row_trained(rehearsal):
    cell, run = rehearsal
    assert run["failed"] == 0 and run["attempted"] == run["fits"] + 1
    assert run["compiles_in_window"] == 0
    assert run["batch_rows"] == cell.rows == 12288
    assert run["rows_per_s"] == pytest.approx(
        run["fits"] * 50 * 12288 / run["window_s"])
    assert run["loss_last"] < 0.01 * run["loss_first"]


@pytest.mark.parametrize("how", ["dropped", "trained twice"])
def test_a_pass_with_a_micro_batch_dropped_or_repeated_is_not_correct(how):
    cell = _tiny_cell()
    config, m = cell.config, cell.config["micro_batch_rows"]
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 5))
    order = {"dropped": [0, 2], "trained twice": [0, 1, 1, 2]}[how]
    rows = np.concatenate([np.arange(k * m, (k + 1) * m) for k in order])
    w0 = np.zeros((config["features"],), np.float32)
    ref = cell.reference.fit(config, X, y, w0, 42)
    whole = cell.entry.prepare(config, X, y, 42)()
    assert correct.judge([whole], *ref, w0, config["limits"])[0] == 0
    broken = cell.entry.prepare(config, X[rows], y[rows], 42)()
    assert correct.judge([broken], *ref, w0, config["limits"])[0] == 1


def test_the_entry_publishes_every_micro_batch_in_order():
    import tpu_sgd

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 9))
    seen, real = [], tpu_sgd.StreamingLinearRegressionWithSGD

    class Watched(real):
        def on_model_update(self):
            seen.append((self._batch_count,
                         len(self.algorithm.optimizer.loss_history)))
            super().on_model_update()

    tpu_sgd.StreamingLinearRegressionWithSGD = Watched
    try:
        fit = cell.entry.prepare(config, X, y, 42)
    finally:
        tpu_sgd.StreamingLinearRegressionWithSGD = real
    w, losses = fit()
    assert seen == [(1, 50), (2, 50), (3, 50)] and losses.shape == (150,)
    w2, losses2 = fit()  # a pass starts from the initial weights again
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    np.testing.assert_array_equal(losses, losses2)
    assert [s[0] for s in seen[3:]] == [4, 5, 6]


# -- the readers ------------------------------------------------------------------------

#: two passes of two micro-batches: (name, start ms, length ms, stats); the
#: worker's ``stream.stage`` lies on the same line here and changes nothing
def _host(ahead=(0, 1, 0, 1)):
    out = []
    for p, base in enumerate((0, 100)):
        out += [("bench.fit", base, 100, {}),
                ("stream.wait", base + 1, 20, {}),
                ("stream.batch", base + 21, 30,
                 {"index": 2 * p, "rows": 64, "ahead": ahead[2 * p]}),
                ("stream.stage", base + 22, 25, {"bytes": 4096, "blocks": 2}),
                ("fit.run", base + 22, 26, {"rows": 64}),
                ("stream.publish", base + 48, 3, {}),
                ("stream.wait", base + 51, 4, {}),
                ("stream.batch", base + 55, 40,
                 {"index": 2 * p + 1, "rows": 64, "ahead": ahead[2 * p + 1]}),
                ("fit.run", base + 56, 30, {"rows": 64}),
                ("stream.publish", base + 90, 5, {}),
                ("stream.wait", base + 96, 2, {})]  # the stream's end
    return out


@pytest.mark.parametrize("metric,expected", [
    ("stream_wait_ms", (20 + 4 + 2) / 2), ("stream_ahead", 0.5),
    ("stream_publish_ms", (3 + 5) / 2)])
def test_the_readers_read_the_folds_spans(checkout, metric, expected):
    got = H._read(metric, *checkout(H._text(host=_host())))
    assert got == pytest.approx(expected)


def test_stream_ahead_is_the_mean_over_the_micro_batches(checkout):
    got = H._read("stream_ahead",
                  *checkout(H._text(host=_host((0, 1, 0, 0)))))
    assert got == pytest.approx(0.25)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_whose_fold_has_no_spans_gives_nothing(checkout, metric):
    """The parent: ``train_on`` in turn, ``fit.run`` and its leaves alone."""
    assert H._read(metric, *checkout(H._text())) is None
    no_attr = [(n, s, d, {k: v for k, v in st.items() if k != "ahead"})
               for n, s, d, st in _host()]
    got = H._read(metric, *checkout(H._text(host=no_attr)))
    assert (got is None) == (metric == "stream_ahead")
