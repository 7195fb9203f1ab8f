"""What ``dense1000-logistic-stream`` brings to the benchmark (PR 52): MLlib's
``StreamingLogisticRegressionWithSGD.train_on`` over micro-batches of unequal,
never-repeating sizes from the host.  The job, the cut and the work module
from shapes, the generator and its boundaries (a pure function of the data
seed, the same for the entry and the reference), the reference by hand, the
cell's tiny rehearsal through its own entry with a micro-batch dropped,
trained twice or re-cut, and the three readers (``stream_programs``,
``stream_pad_share``, ``stream_whole_ms``) on traces written by hand."""

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

NAME = "dense1000-logistic-stream.stream-uneven-from-host"
CONFIG = "dense1000-logistic-stream"
ROWS, LO, HI, D = 6_291_456, 1_048_576, 2_097_152, 1000
METRICS = ["stream_programs", "stream_pad_share", "stream_whole_ms"]
NEW_FILES = ["bench/configs/dense1000-logistic-stream.json",
             "bench/jobs/stream-uneven-from-host.json",
             "bench/data/dense_synthetic_stream_uneven.py",
             "bench/entries/stream_train_on_uneven.py",
             "bench/reference/glm_dense_stream_uneven.py",
             "bench/work/dense_stream_uneven_step.py"] + [
                 f"bench/layers/{m}.py" for m in METRICS]


def _tiny_cell(**more):
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides={**tiny, **more})


# -- the entries, the job, the cut and the work module ---------------------------

def test_the_cell_its_configuration_and_its_metrics_are_appended_entries():
    """Behind everything the benchmark had (``index(...) >``: a later PR
    appends behind these in turn), with files of their own."""
    bench = cells.benchmark()
    for kind, name, before in (
            ("configs", CONFIG, "imagenet1k-r50-multinomial"),
            ("workloads", NAME,
             "imagenet1k-r50-multinomial.resident-classes")):
        names = [e["name"] for e in bench[kind]]
        assert names.index(name) > names.index(before)
        assert names.count(name) == 1
    names = [m["name"] for m in bench["per_layer"]]
    for metric in METRICS:
        assert names.index(metric) > names.index("h2d_flat_share")
    for path in NEW_FILES:
        assert os.path.isfile(os.path.join(cells.REPO, path)), path
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["file"] == NEW_FILES[0] and entry["reduced"] == ["rows"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    workload = {w["name"]: w for w in bench["workloads"]}[NAME]
    assert workload == {"name": NAME, "config": CONFIG,
                        "traffic": "stream-uneven-from-host", "chips": 1,
                        "why": workload["why"]}
    assert len(workload["why"]) <= 200
    # an eleventh cell opens no third four-chip cell
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_the_three_metrics_are_this_cells_and_move_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit in zip(METRICS, ("count", "%", "ms")):
        assert entries[metric] == {
            "name": metric, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "stream fold",
            "moves": "rows_per_s", "workloads": [NAME]}
    reported = {m["name"] for m in cells.Cell(NAME).metrics["per_layer"]}
    assert set(METRICS) <= reported and "step_roofline" in reported
    # of the lsq stream's own metrics the cell is on the lists of those whose
    # spans its passes carry too (PR 58, each after a traced chip run); it
    # folds no totals and copies nothing inside a fit
    assert {"stream_wait_ms", "stream_ahead", "stream_publish_ms",
            "stream_block_ms", "stream_join_ms", "fused_sums_ms"} <= reported
    assert not {"stream_folded", "stats_fits", "h2d_ms"} & reported
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != NAME):
        assert not set(METRICS) & {
            m["name"] for m in cells.Cell(cell).metrics["per_layer"]}


def test_the_job_cuts_the_stream_and_the_sizes_are_the_sources():
    cell = cells.Cell(NAME)
    config = cell.config
    assert cell.rows == ROWS == 3 * cell.job["rows_step"]
    assert cell.work.dataset_bytes(config, ROWS) <= \
        cell.job["dataset_bytes_cap"] < cell.work.dataset_bytes(
            config, ROWS + cell.job["rows_step"])
    assert (config["micro_batches"], config["micro_batch_rows_min"],
            config["micro_batch_rows_max"]) == (4, LO, HI)
    assert not [key for key in config if "share" in key or "jitter" in key]
    assert 4 * LO <= ROWS <= 4 * HI and ROWS // 4 == 1_572_864
    # every micro-batch under the 4 GiB one-piece edge
    assert cell.work.dataset_bytes(config, HI) == 4_194_304_000 < 2**32
    # the rows are cut and nothing else; the defaults are upstream's
    assert config["reduced"] == ["rows"] and config["rows"] == 10_000_000
    assert config["as_run"]["rows"] == {"stream-uneven-from-host": ROWS}
    assert (config["features"], config["step_size"], config["num_iterations"],
            config["mini_batch_fraction"], config["reg_param"],
            config["convergence_tol"]) == (D, 0.1, 50, 1.0, 0.0, 0.0)
    assert (config["model"], config["gradient"], config["updater"]) == (
        "StreamingLogisticRegressionWithSGD", "LogisticGradient",
        "SquaredL2Updater")
    assert (config["x_dtype"], config["weights_dtype"],
            config["accumulation"]) == ("bfloat16", "float32", "float32")
    assert config["schedule"] == "auto" and cell.chips == 1
    assert config["model_update_listeners"] == 1
    assert cell.job["placement"] == "host" and cell.job["traced_fits"] == 3
    assert cell.job["entry"] == {"dense": "stream_train_on_uneven"}
    assert cell.job["dataset_bytes_cap"] == cells.load_json(
        "jobs", "stream-from-host")["dataset_bytes_cap"]
    for word in ("arrival order", "exactly once", "dropped", "split",
                 "before the next", "padding rows are never trained",
                 "divisor", "wire"):
        assert word in config["guarantees"], word
    assert set(config["limits"]) == set(correct.NUMBERS)
    assert "arrivals" in config["assumed"]


def test_step_work_counts_the_real_rows_and_nothing_of_a_capacity():
    cell = cells.Cell(NAME)
    work = cell.work.step_work(cell.config, cell.rows)
    once = ROWS * D * 2 + ROWS * 4  # every real row of a pass and its label
    assert work["least"] == work["as_laid_out"] \
        == {"bytes": once, "flops": 4 * ROWS * D}
    assert work["flops_peak"] == "bf16_flops_per_s"
    # a step that read four capacities of 2,097,152 rows would read a third
    # more than this: its share of the roofline pays for the padding
    assert 4 * HI * (D * 2 + 4) / once == pytest.approx(4 / 3)
    assert work["least"]["bytes"] / 819e9 == pytest.approx(15.394e-3,
                                                           rel=1e-3)
    # it scales with the rows it is asked about, nothing else
    assert cell.work.step_work(cell.config, 1000)["least"]["bytes"] \
        == 1000 * (D * 2 + 4)


# -- the generator and its boundaries ----------------------------------------------

def test_the_generator_makes_a_logistic_stream_on_the_host():
    cell = _tiny_cell()
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, 2_147_483_000)
    assert X.shape == (18000, 64) and y.shape == (18000,)
    assert isinstance(X, np.ndarray) and X.flags.f_contiguous
    assert jnp.asarray(X[:8]).dtype == jnp.bfloat16 and y.dtype == np.float32
    assert set(np.unique(np.asarray(y))) == {0.0, 1.0}
    assert type(np.asarray(X)) is np.ndarray  # what ``place`` keeps
    X.delete(), y.delete()  # and what it calls: nothing to free
    again = cell.generator.make(config, cell.rows, 2_147_483_000)
    np.testing.assert_array_equal(np.asarray(X).view(np.uint16),
                                  np.asarray(again[0]).view(np.uint16))
    other = cell.generator.make(config, cell.rows, 7)
    assert not np.array_equal(np.asarray(y), np.asarray(other[1]))
    # one w_true for the whole stream: the labels lean the same way in
    # every chunk the rows were made in
    Xf, yf = np.asarray(X).astype(np.float32), np.asarray(y)
    lean = [Xf[a:a + 6000].T @ (yf[a:a + 6000] - 0.5)
            for a in range(0, 18000, 6000)]
    assert np.corrcoef(lean[0], lean[2])[0, 1] > 0.9
    assert not np.array_equal(Xf[:6000], Xf[6000:12000])


def test_the_boundaries_are_a_pure_function_of_the_data_seed():
    """Four ranges that tile the pass in order, at a granularity of one row,
    every size inside the configuration's range; the same for every caller
    that holds the same rows (the entry and the reference import the ONE
    function and share no state), another cut for another data seed."""
    cell = _tiny_cell()
    config = cell.config
    cuts = {}
    for seed in (5, 6, 2**31 + 40, 3_000_000_017):
        X, _ = cell.generator.make(config, cell.rows,
                                   harness.data_seed_of(seed))
        ranges = cell.generator.boundaries(config, X)
        assert [a for a, _ in ranges] == [0] + [b for _, b in ranges[:-1]]
        assert ranges[-1][1] == 18000 and len(ranges) == 4
        assert all(3000 <= b - a <= 6000 for a, b in ranges)
        assert ranges == cell.generator.boundaries(config, np.asarray(X))
        assert ranges == cell.generator.boundaries(config, np.array(X))
        cuts[seed] = tuple(ranges)
    assert len(set(cuts.values())) == 4  # every run has its own
    assert any((b - a) % 128 for a, b in cuts[5])  # no convenient multiple
    # the entry and the reference import the ONE function (the harness
    # loads each named file as a module of its own: no state is shared)
    assert cell.reference.boundaries is cell.entry.boundaries
    assert cell.entry.boundaries.__code__.co_filename \
        == cell.generator.boundaries.__code__.co_filename
    assert cell.entry.boundaries(config, X) == ranges
    # a pass of another length keeps the range about its own mean
    short = cell.generator.boundaries(config, np.asarray(X)[:9000])
    assert short[-1][1] == 9000 and len(short) == 4
    assert all(1500 <= b - a <= 3000 for a, b in short)


def test_the_published_sizes_draw_as_the_issue_fixed_them():
    """At the published sizes, from a first row alone (the draw reads
    nothing else of the data): three sizes uniform on 1,048,576..2,097,152
    and the fourth the remainder, inside the same range; the draw is NOT
    narrowed to a pattern (the first micro-batch's size, whose copy is
    exposed, covers the range), and no size ever repeats, so a program
    compiled for a row count is cold in every run."""
    cell = cells.Cell(NAME)

    class Rows:  # 6,291,456 rows of which only the first is ever read
        shape = (ROWS, D)

        def __init__(self, seed):
            self.first = np.random.default_rng(seed).integers(
                0, 2**16, size=(1, D)).astype(np.uint16)

        def __getitem__(self, key):
            assert key == slice(None, 1)
            return self.first

    runs = []
    for seed in range(200):
        ranges = cell.generator.boundaries(cell.config, Rows(seed))
        runs.append([b - a for a, b in ranges])
        assert sum(runs[-1]) == ROWS
    sizes = np.array(runs)
    assert LO <= sizes.min() and sizes.max() <= HI
    assert len(set(sizes.ravel().tolist())) == sizes.size  # none repeats
    # every position covers the range: over 200 draws the first size's
    # quartiles lie a quarter of the range and more apart
    for k in range(4):
        q1, q3 = np.percentile(sizes[:, k], [25, 75])
        assert q3 - q1 > (HI - LO) / 4, (k, q1, q3)
        assert sizes[:, k].min() < LO + (HI - LO) / 5
        assert sizes[:, k].max() > HI - (HI - LO) / 5


# -- the reference --------------------------------------------------------------------

def test_stream_reference_follows_two_uneven_micro_batches_by_hand(
        monkeypatch):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(12, 3)).astype(np.float32)
    y = (rng.uniform(size=(12,)) < 0.5).astype(np.float32)
    config = {"gradient": "LogisticGradient", "updater": "SquaredL2Updater",
              "mini_batch_fraction": 1.0, "step_size": 0.5, "reg_param": 0.0,
              "num_iterations": 2}
    ref = cells.load_module("reference", "glm_dense_stream_uneven")
    monkeypatch.setattr(ref, "boundaries",
                        lambda config, X: [(0, 5), (5, 12)])
    w, losses = ref.fit(config, X, y, np.zeros(3, np.float32), 42)
    want_w, want = np.zeros(3), []
    for a, b in ((0, 5), (5, 12)):  # t from 1 again, from the last w
        Xb, yb = X[a:b].astype(np.float64), y[a:b]
        for t in (1, 2):
            m = Xb @ want_w
            want.append(np.mean(np.log1p(np.exp(m)) - yb * m))
            want_w = want_w - 0.5 / np.sqrt(t) * (
                (1 / (1 + np.exp(-m)) - yb) @ Xb) / (b - a)  # its REAL count
    np.testing.assert_allclose(w, want_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    assert losses.shape == (4,)
    # it imports nothing of the program
    with open(os.path.join(cells.BENCH, "reference",
                           "glm_dense_stream_uneven.py")) as f:
        assert "tpu_sgd" not in f.read()


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
    assert run["batch_rows"] == cell.rows == 18000
    assert run["rows_per_s"] == pytest.approx(
        run["fits"] * 50 * 18000 / run["window_s"])
    assert run["loss_first"] == pytest.approx(np.log(2.0), rel=1e-5)
    assert run["loss_last"] < 0.7 * run["loss_first"]
    assert run["work"]["least"]["bytes"] == 18000 * (64 * 2 + 4)


@pytest.mark.parametrize("how", ["dropped", "trained twice", "merged",
                                 "padding trained"])
def test_a_pass_that_breaks_a_guarantee_is_not_correct(how):
    """A micro-batch dropped, trained twice, two merged into one fit, or a
    capacity's zero rows trained and counted with the real ones: each fails
    a limit of the cell."""
    import tpu_sgd

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 5))
    ranges = cell.generator.boundaries(config, X)
    w0 = np.zeros((config["features"],), np.float32)
    ref = cell.reference.fit(config, X, y, w0, 42)
    whole = cell.entry.prepare(config, X, y, 42)()
    assert correct.judge([whole], *ref, w0, config["limits"])[0] == 0
    batches = [(X[a:b], y[a:b]) for a, b in ranges]
    if how == "dropped":
        batches = [batches[0], batches[2], batches[3]]
    elif how == "trained twice":
        batches = batches[:2] + batches[1:]
    elif how == "merged":
        a, b = ranges[1][0], ranges[2][1]
        batches = [batches[0], (X[a:b], y[a:b]), batches[3]]
    else:
        def padded(Xb, yb):
            fill = 8192 - Xb.shape[0]
            return (np.concatenate([Xb, np.zeros((fill, 64), Xb.dtype)]),
                    np.concatenate([yb, np.zeros(fill, np.float32)]))
        batches = [padded(*batch) for batch in batches]
    alg = tpu_sgd.StreamingLogisticRegressionWithSGD(0.1, 50, 1.0, 0.0)
    alg.algorithm.optimizer.set_convergence_tol(0.0)
    alg.set_initial_weights(w0)
    losses = []
    alg.add_model_update_listener(lambda model, count: losses.append(
        np.asarray(alg.algorithm.optimizer.loss_history)))
    model = alg.train_on(iter(batches))
    broken = np.asarray(model.weights), np.concatenate(losses)
    assert correct.judge([broken], *ref, w0, config["limits"])[0] == 1


def test_the_entry_publishes_every_micro_batch_in_order():
    import tpu_sgd

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 9))
    ranges = cell.generator.boundaries(config, X)
    seen, real = [], tpu_sgd.StreamingLogisticRegressionWithSGD

    class Watched(real):
        def _fit(self, X, y):
            seen.append((self._batch_count, getattr(X, "rows", None),
                         getattr(X, "capacity", None)))
            return super()._fit(X, y)

    tpu_sgd.StreamingLogisticRegressionWithSGD = Watched
    try:
        fit = cell.entry.prepare(config, X, y, 42)
    finally:
        tpu_sgd.StreamingLogisticRegressionWithSGD = real
    w, losses = fit()
    # each micro-batch once, in arrival order, its own rows, ONE capacity
    assert [s[0] for s in seen] == [0, 1, 2, 3] and losses.shape == (200,)
    assert [s[1] for s in seen] == [b - a for a, b in ranges]
    assert {s[2] for s in seen} == {8192}
    w2, losses2 = fit()  # a pass starts from the initial weights again
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    np.testing.assert_array_equal(losses, losses2)
    assert [s[0] for s in seen[4:]] == [4, 5, 6, 7]


def test_the_stream_is_one_and_ends_where_the_harness_drops_the_fit():
    """Pass after pass through ONE ``train_on`` on a thread of the entry's:
    while the harness holds no fit open nothing is trained (the fold stands
    in the listener, behind the pass's last publish), the worker has taken
    the next pass's micro-batches ahead meanwhile, and dropping ``fit`` ends
    the stream and its thread."""
    import threading

    import tpu_sgd

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 11))
    fits, real = [], tpu_sgd.StreamingLogisticRegressionWithSGD
    calls = []

    class Watched(real):
        def _fit(self, X, y):
            fits.append(self._batch_count)
            return super()._fit(X, y)

        def train_on(self, stream, skip=None):
            calls.append(1)
            return super().train_on(stream, skip)

    tpu_sgd.StreamingLogisticRegressionWithSGD = Watched
    try:
        fit = cell.entry.prepare(config, X, y, 42)
    finally:
        tpu_sgd.StreamingLogisticRegressionWithSGD = real

    def streams():
        return [t for t in threading.enumerate() if t.name == "bench-stream"]

    before = len(streams())
    assert before >= 1 and fits == []  # nothing is trained before a fit
    first = fit()
    time.sleep(0.2)
    assert fits == [0, 1, 2, 3]  # and nothing between two fits
    second = fit()
    assert fits == list(range(8)) and calls == [1]
    np.testing.assert_array_equal(first[0], second[0])
    np.testing.assert_array_equal(first[1], second[1])
    del fit
    assert len(streams()) == before - 1


def test_a_program_with_no_row_capacity_cannot_run_the_configuration(
        monkeypatch):
    """The configuration states ``row_count``: an operand.  A program that
    has no row capacity (the parent of the PR that brought it: every
    micro-batch a program of its own) fails as the cell's files are loaded,
    before any device is touched; one that trains at another capacity than
    the sizes' fails at the pass's end."""
    import tpu_sgd as gd

    cell = _tiny_cell()
    assert cell.config["row_count"] == "operand"
    X, y = harness.place(cell, *cell.generator.make(cell.config, cell.rows,
                                                    13))
    real = gd.row_capacity
    monkeypatch.setattr(gd, "row_capacity",
                        lambda X, held=0: 2 * real(X, held))
    entry = cells.load_module("entries", "stream_train_on_uneven")
    monkeypatch.setattr(gd, "row_capacity", real)
    with pytest.raises(RuntimeError, match="row_count 'operand'"):
        entry.prepare(cell.config, X, y, 42)()
    monkeypatch.delattr(gd, "row_capacity")
    with pytest.raises(ImportError, match="row_capacity"):
        cells.Cell(NAME)


def test_nothing_new_is_loaded_for_another_cell():
    """The new modules load for the new cell alone: no other cell's
    configuration or job names one of them, and neither ``bench/cells.py``
    nor ``bench/harness.py`` does."""
    bench = cells.benchmark()
    mine = {"dense_synthetic_stream_uneven", "stream_train_on_uneven",
            "glm_dense_stream_uneven", "dense_stream_uneven_step"}
    for workload in bench["workloads"]:
        if workload["name"] == NAME:
            continue
        cell = cells.Cell(workload["name"])
        named = {cell.config["generator"], cell.config["reference"],
                 cell.config["work"], *cell.job["entry"].values()}
        assert not named & mine, workload["name"]
    for module in ("cells.py", "harness.py", "run.py", "spans.py",
                   "trace.py", "correct.py"):
        with open(os.path.join(cells.BENCH, module)) as f:
            text = f.read()
        assert not any(name in text for name in mine), module


# -- the readers ------------------------------------------------------------------------

#: two passes of two micro-batches: (name, start ms, length ms, stats)
def _host(capacity=True):
    out = []
    for p, base in enumerate((0, 100)):
        for k, (rows, at, took) in enumerate(((3000, 1, 36), (5000, 48, 36))):
            says = {"index": 2 * p + k, "rows": rows, "ahead": k}
            if capacity:
                says.update(capacity=8192, rows_read=-(-rows // 2048) * 2048)
            out += [("stream.wait", base + at, 8, {}),
                    ("stream.whole", base + at + 2, 3 + k, {"blocks": 4}),
                    ("stream.batch", base + at + 9, took, says),
                    ("fit.run", base + at + 10, took - 6, {"rows": rows}),
                    ("stream.publish", base + at + took + 5, 2, {})]
        out += [("bench.fit", base, 100, {}),
                ("stream.wait", base + 97, 1, {})]  # the stream's end
    return out


@pytest.mark.parametrize("metric,expected", [
    ("stream_programs", 1),
    ("stream_pad_share", 100 * (1 - 8000 / (4096 + 6144))),
    ("stream_whole_ms", (3 + 4) / 2)])
def test_the_readers_read_the_folds_spans(checkout, metric, expected):
    got = H._read(metric, *checkout(H._text(host=_host())))
    assert got == pytest.approx(expected)


def test_a_pass_whose_last_publish_ends_behind_the_fit_counts_its_whole(
        checkout):
    """The cell's passes are one stream: a pass's last ``stream.batch``
    stands in the entry's listener until the harness asks for the next pass,
    so it ends behind the fit and is not among the fit's spans; its
    ``stream.whole`` is, and the micro-batches are then the wholes."""
    host = [e for e in _host()
            if not (e[0] == "stream.batch" and e[3]["index"] % 2)]
    assert sum(e[0] == "stream.batch" for e in host) == 2
    trace = checkout(H._text(host=host))
    assert H._read("stream_whole_ms", *trace) == pytest.approx((3 + 4) / 2)
    assert H._read("stream_programs", *trace) == 1
    assert H._read("stream_pad_share", *trace) == pytest.approx(
        100 * (1 - 3000 / 4096))


def test_a_program_that_compiles_a_size_reads_as_many_programs_as_sizes(
        checkout):
    """The parent: ``stream.batch`` says ``rows`` and no ``capacity``, its
    join has no span of its own, it reads no padding."""
    host = [e for e in _host(capacity=False) if e[0] != "stream.whole"]
    trace = checkout(H._text(host=host))
    assert H._read("stream_programs", *trace) == 2  # 3,000 and 5,000 rows
    assert H._read("stream_pad_share", *trace) is None
    assert H._read("stream_whole_ms", *trace) is None
    # a capacity raised between two passes: two programs
    raised = [(n, s, d, dict(st, capacity=16384)
               if n == "stream.batch" and s > 100 else st)
              for n, s, d, st in _host()]
    assert H._read("stream_programs",
                   *checkout(H._text(host=raised))) == 2


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_whose_fold_has_no_spans_gives_nothing(checkout, metric):
    assert H._read(metric, *checkout(H._text())) is None
