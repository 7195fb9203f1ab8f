"""What ``cifar5m-int8-multinomial`` brings to the benchmark (PR 57):
CIFAR-5m's rows in the byte a pixel they are published in, int8 at the
Optimizer boundary, four of the six parts in the bytes that hold two as
bfloat16.  Its entries, appended; the configuration's contract (what is
published, what is cut, what is assumed, the arithmetic of the cut); the
generator; the program through the cell's entry at the tiny sizes against
the configuration's own reference, the ``float8_e4m3fn`` control failing
every limit; the work at one byte a feature; the cell's own reader
(``row_item_bytes``: its entry waited prepared until PR 58 pasted it) and
the accepted readers of the class kernel, which find the cell's kernel as
they find the twin's, on traces written by hand and, since PR 58, in the
cell's own traced runs."""

import importlib.util
import json
import os

import numpy as np
import pytest

from bench import cells, correct

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

CONFIG = "cifar5m-int8-multinomial"
TWIN = "cifar5m-multinomial"
NAME = CONFIG + ".resident-classes"
BENCH = cells.benchmark()
READERS = ("row_item_bytes",)
#: the class kernel's readers, which list the cell since PR 58
SHARED = ("class_kernel_ms", "class_sums_ms", "class_rows", "row_tile")
with open(os.path.join(cells.BENCH, "peaks.json")) as _f:
    PEAKS = json.load(_f)["TPU v5 lite"]
_cell = cells.Cell(NAME)  # before any fixture moves ``cells.REPO``
WORK = _cell.work.step_work(_cell.config, _cell.rows)


def _tiny_cell():
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides=tiny)


def _twin():
    with open(os.path.join(cells.BENCH, "configs", TWIN + ".json")) as f:
        return json.load(f)


# -- the entries ---------------------------------------------------------------

def _index(kind, name):
    return [e["name"] for e in BENCH[kind]].index(name)


def test_the_entries_are_appended_behind_what_the_benchmark_had():
    assert _index("configs", CONFIG) > _index("configs",
                                              "dense1000-logistic-stream")
    assert _index("workloads", NAME) > _index(
        "workloads", "dense1000-logistic-stream.stream-uneven-from-host")
    entry = BENCH["configs"][_index("configs", CONFIG)]
    assert entry["reduced"] == ["rows"] and len(entry["source"]) <= 200
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    for word in ("CIFAR-5m", "github.com/preetum/cifar5m", "uint8",
                 "int8 (pixel-128)", "6,002,688 x 3,072", "4,001,792",
                 "LogisticGradient(10)"):
        assert word in entry["source"], word
    cell = BENCH["workloads"][_index("workloads", NAME)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-classes", 1)
    assert "4,001,792 x 3,072 int8" in cell["why"] and len(cell["why"]) <= 200
    # the cell took none of the four-chip quota: up to it the two cells
    # that had it (by index: a later PR's four-chip cell stands behind)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][
        :_index("workloads", NAME) + 1]) == 2


def test_the_cells_own_entry_is_appended_and_the_kernels_readers_list_it():
    """``row_item_bytes`` behind everything the benchmark had when the cell
    came (by ``index``: what a later PR appends is none of this test's), and
    the cell on the lists of the readers that find its kernel."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("row_item_bytes") > names.index("first_fit_rest_ms")
    reported = [m["name"] for m in cells.Cell(NAME).metrics["per_layer"]]
    for metric in ("step_ms", "step_roofline", "peak_hbm_gb",
                   "device_idle_share", "programs_per_fit",
                   "compiles_in_window", "first_fit_ms") + READERS + SHARED:
        assert metric in reported, metric
    listed = sorted(m["name"] for m in BENCH["per_layer"]
                    if NAME in m.get("workloads", []))
    assert listed == sorted(READERS + SHARED)
    assert not os.path.exists(
        os.path.join(cells.BENCH, "prepared", CONFIG + ".json"))


# -- the configuration -----------------------------------------------------------

def test_the_configuration_is_on_cifar5ms_keys_and_cuts_the_rows_alone():
    config, twin = cells.Cell(NAME).config, _twin()
    assert set(config) - set(twin) == {"limits_from"}  # the limits' reasons
    assert set(twin) <= set(config)
    assert set(config["limits_from"]) == {"what", *correct.NUMBERS}
    same = ("model", "gradient", "classes", "updater", "storage", "features",
            "matmul_operands", "accumulation", "weights_dtype",
            "mini_batch_fraction", "sampling", "sampling_seed",
            "num_iterations", "convergence_tol", "control_operands", "work",
            "reduced", "tiny")
    for key in same:
        assert config[key] == twin[key], key
    assert (config["x_dtype"], config["matmul_operands"],
            config["accumulation"], config["weights_dtype"],
            config["control_operands"]) == (
        "int8", "bfloat16", "float32", "float32", "float8_e4m3fn")
    assert (config["generator"], config["reference"], config["work"]) == (
        "dense_int8_classes", "glm_dense_classes_int8", "dense_classes_step")
    assert config["reduced"] == ["rows"]
    published = config["published"]
    assert (published["rows"], published["features"], published["classes"],
            published["feature_bytes"]) == (6_002_688, 3072, 10, 1)
    assert (config["features"], config["classes"]) == (3072, 10)
    assert config["rows"] == 4 * 1_000_448 == 4_001_792
    assert published["rows"] == 6 * 1_000_448
    assert {"x", "true_weights", "labels", "step_size", "reg_param",
            "source_sizes"} <= set(config["assumed"])
    assert "clip(round(64 z), -128, 127)" in config["assumed"]["x"]
    assert "uint8" in config["assumed"]["source_sizes"]
    for words in ("ALL rows", "int8 rows, exact", "bf16 matmul operands",
                  "re-quantised", "never stored under float32"):
        assert words in config["guarantees"], words


def test_the_step_size_and_the_regulariser_are_the_twins_under_the_scale():
    """With ``w' = w / 64`` the fit on ``q`` at (2^-12, 4.096) is the fit on
    ``x = q / 64`` at the twin's (1.0, 0.001), step for step: ``eta' = eta
    s^2``, ``reg' = reg / s^2``, ``s = 2^-6``."""
    config, twin = cells.Cell(NAME).config, _twin()
    s = 2.0 ** -6
    assert config["step_size"] == twin["step_size"] * s * s == 2.0 ** -12
    assert config["reg_param"] == pytest.approx(twin["reg_param"] / (s * s),
                                                rel=1e-12)
    assert config["reg_param"] == 4.096
    assert config["step_size"] * config["reg_param"] == pytest.approx(
        twin["step_size"] * twin["reg_param"], rel=1e-12)
    assert "2^-12" in config["assumed"]["step_size"]


def test_the_cut_is_written_down_with_its_arithmetic():
    cell = cells.Cell(NAME)
    assert cell.rows == 4_001_792
    size = cell.work.dataset_bytes(cell.config, cell.rows)
    assert size == 4_001_792 * 3072 * 1 == 12_293_505_024
    twin = cells.Cell(TWIN + ".resident-classes")
    assert size == twin.work.dataset_bytes(twin.config, twin.rows)
    assert cell.rows == 2 * twin.rows
    assert round(size / 2**30, 2) == 11.45
    assert round(100 * size / 2**34, 1) == 71.6
    assert size >= 0.25 * 2**34  # the driver's floor
    assert size <= cell.job["dataset_bytes_cap"] == 12_750_000_000
    assert 5 * 1_000_448 * 3072 > cell.job["dataset_bytes_cap"]  # five parts
    assert 6 * 1_000_448 * 3072 > 2**34  # all six fit no one chip
    why = cell.config["as_run"]["why"]
    for words in ("12,293,505,024", "11.45 GiB", "71.6%", "12.75 GB",
                  "15.4 GB", "18.4 GB", "16.0 MB", "four of the source's six"):
        assert words in why, words
    assert cell.config["as_run"]["rows"]["resident-classes"] == cell.rows


def test_the_work_counts_one_byte_a_feature():
    """``dense_classes_step``, unedited: ``least`` follows ``x_dtype``."""
    from bench.layers import step_roofline

    cell = cells.Cell(NAME)
    work = cell.work.step_work(cell.config, cell.rows)
    assert work["least"] == {"bytes": 4_001_792 * 3072 + 4_001_792 * 4,
                             "flops": 4 * 4_001_792 * 3072 * 9}
    assert work["as_laid_out"] == {
        "bytes": 4_001_792 * 3072 + 4_001_792 * 4 + 2 * 16 * 3072 * 4,
        "flops": 4 * 4_001_792 * 3072 * 16}
    assert work["flops_peak"] == "bf16_flops_per_s"
    ms, bound = step_roofline.least_ms({"work": work, "peaks": PEAKS})
    assert bound == "bytes" and ms == pytest.approx(15.03, abs=0.01)
    twin = cells.Cell(TWIN + ".resident-classes")
    twin_work = twin.work.step_work(twin.config, twin.rows)
    # the same bytes of rows, twice the rows, so twice the labels
    assert work["least"]["bytes"] - twin_work["least"]["bytes"] \
        == 4 * twin.rows


# -- the generator ----------------------------------------------------------------

def test_the_generator_makes_int8_rows_in_range_from_the_seed():
    cell = _tiny_cell()
    X, y = cell.generator.make(cell.config, cell.rows, 11)
    assert X.shape == (16384, 128) and str(X.dtype) == "int8"
    q = np.asarray(X).astype(np.int32)
    assert q.min() == -128 and q.max() == 127
    assert abs(q.mean()) < 0.5 and 58 < q.std() < 64  # 64 z, clipped
    labels = np.asarray(y).astype(int)
    assert labels.min() == 0 and labels.max() == 9
    counts = np.bincount(labels, minlength=10)
    assert (counts > 0).all() and counts[0] == counts.min()  # the pivot
    again = cell.generator.make(cell.config, cell.rows, 11)
    other = cell.generator.make(cell.config, cell.rows, 12)
    np.testing.assert_array_equal(q, np.asarray(again[0]))
    np.testing.assert_array_equal(labels, np.asarray(again[1]).astype(int))
    assert (q != np.asarray(other[0])).any()
    with pytest.raises(ValueError, match="int8"):
        cell.generator.make(dict(cell.config, x_dtype="bfloat16"), 64, 1)


def test_the_generators_last_block_overlaps_the_one_before_it():
    """Every block has one shape: the last starts at ``n - block`` and
    overwrites what it overlaps; rows before the overlap are the blocks'."""
    import jax

    cell = _tiny_cell()
    gen = cell.generator.generator
    key = jax.random.PRNGKey(5)
    whole = np.asarray(gen(1000, 128, 10, 256)(key)[0])
    three = np.asarray(gen(768, 128, 10, 256)(key)[0])
    assert whole.shape == (1000, 128) and whole.dtype == np.int8
    np.testing.assert_array_equal(whole[:744], three[:744])
    assert (whole[744:768] != three[744:768]).any()  # block 3 of 4 wrote them
    assert np.abs(whole[768:].astype(np.int32)).max() > 0


# -- the program, through the cell's own entry, at the tiny sizes -----------------

@pytest.mark.parametrize("seed", [1, 2])
def test_the_program_follows_the_reference_and_the_control_does_not(seed):
    cell = _tiny_cell()
    config = cell.config
    assert (cell.rows, config["features"], config["classes"]) == (
        16384, 128, 10)
    X, y = cell.generator.make(config, cell.rows, seed)
    w, losses = cell.entry.prepare(config, X, y, config["sampling_seed"])()
    assert isinstance(w, np.ndarray) and w.shape == (9, 128)
    assert losses.shape == (config["num_iterations"],)
    w0 = np.zeros((config["features"],), np.float32)  # as the harness does
    ref = cell.reference.fit(config, X, y, w0, config["sampling_seed"])
    got = correct.readings(w, losses, *ref, w0)
    for name in correct.NUMBERS:
        assert got[name] <= config["limits"][name], (name, got)
    assert losses[0] == pytest.approx(np.log(10), rel=1e-5)
    assert losses[-1] < 0.7 * losses[0]
    # the stated precision passes; the one below it fails EVERY limit
    stated = cell.reference.fit(config, X, y, w0, config["sampling_seed"],
                                operands=config["matmul_operands"])
    low = cell.reference.fit(config, X, y, w0, config["sampling_seed"],
                             operands=config["control_operands"])
    stated, low = (correct.readings(*fit, *ref, w0) for fit in (stated, low))
    for name in correct.NUMBERS:
        assert stated[name] <= config["limits"][name], (name, stated)
        assert low[name] > config["limits"][name], (name, low)
    assert X.dtype == np.int8  # nothing rounded the rows in place


def test_the_reference_takes_int8_rows_and_a_full_batch_alone():
    cell = _tiny_cell()
    X, y = cell.generator.make(cell.config, 256, 3)
    w0 = np.zeros((128,), np.float32)
    with pytest.raises(ValueError, match="int8 rows"):
        cell.reference.fit(cell.config, np.asarray(X, np.float32), y, w0, 42)
    with pytest.raises(ValueError, match="mini_batch_fraction"):
        cell.reference.fit(dict(cell.config, mini_batch_fraction=0.5), X, y,
                           w0, 42)
    # the sums over row blocks are the sums over all rows
    short = dict(cell.config, num_iterations=3)
    one = cell.reference.fit(short, X, y, w0, 42)
    blocks = cell.reference.fit(short, X, y, w0, 42, block_rows=100)
    np.testing.assert_allclose(blocks[0], one[0], rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(blocks[1], one[1], rtol=1e-6)


def test_the_fit_on_the_integers_is_the_twins_fit_on_the_scaled_rows():
    """The derivation under ``assumed.step_size``, run: the reference on
    ``q`` at (2^-12, 4.096) and ``glm_dense_classes`` on ``x = q / 64`` at
    (1.0, 0.001) give ``W' = W / 64`` and the same losses."""
    cell, twin = _tiny_cell(), cells.Cell(TWIN + ".resident-classes")
    X, y = cell.generator.make(cell.config, 2048, 4)
    w0 = np.zeros((128,), np.float32)
    short = dict(cell.config, num_iterations=20)
    W_q, loss_q = cell.reference.fit(short, X, y, w0, 42)
    x = np.asarray(X, np.float32) / 64.0
    W_x, loss_x = twin.reference.fit(
        dict(twin.config, num_iterations=20), x, y, w0, 42)
    np.testing.assert_allclose(64.0 * W_q, W_x, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(loss_q, loss_x, rtol=1e-5)


# -- the readers, on traces written by hand -----------------------------------

KERNEL = "%_fused_rows_class_sums.11 = custom-call(X, y, W)"
CAST, FOLD = "%pad.14 = pad(reshape(W))", "%reduce_sum.32 = reduce(call.24)"
UPDATE = "%multiply_reduce_fusion.3 = fusion(reshape.74)"
SCOPE = "jit(sgd_run)/while/body/sgd.class_sums/cond/branch_0_fun/"
ONE_READ = {KERNEL: SCOPE + "jit(_fused_rows_class_sums)/pallas_call:",
            CAST: SCOPE + "jit(_fused_rows_class_sums)/pad:",
            FOLD: SCOPE + "jit(_fused_rows_class_sums)/reduce_sum:",
            UPDATE: "jit(sgd_run)/while/body/sgd.update/mul:",
            H.WHILE: "jit(sgd_run)/while:"}
#: fit 0: a while of 60 ms holding the cast's 1, the kernel's 50, the fold's
#: 2, the update's 3; fit 1: the kernel's 20 bare
OPS = [(H.WHILE, 30, 60), (CAST, 30.5, 1), (KERNEL, 32, 50), (FOLD, 83, 2),
       (UPDATE, 86, 3), (KERNEL, 110, 20)]


def _host(**stats):
    """The hand-written host events with ``stats`` on every ``train.run``."""
    return [(n, s, d, {**st, **stats} if n == "train.run" else st)
            for n, s, d, st in H.HOST]


def _read(metric, reduced, run):
    return cells.load_module("layers", metric).read(reduced, run)


def test_row_item_bytes_reads_train_runs_attribute(checkout):
    reduced, run = checkout(H._text(host=_host(row_item_bytes=1,
                                               operand="bfloat16")))
    assert _read("row_item_bytes", reduced, run) == 1.0
    reduced, run = checkout(H._text(host=_host(row_item_bytes=2)))
    assert _read("row_item_bytes", reduced, run) == 2.0
    # a program from before the attribute (the parent): nothing to read
    assert _read("row_item_bytes", *checkout(H._text())) is None


@pytest.mark.parametrize("metric,reads", [
    ("class_kernel_ms", 3.5), ("class_sums_ms", 3.65), ("class_rows", 16.0),
    ("row_tile", 2048.0), ("step_roofline", 100 * 15.0298 / 4.0)])
def test_the_accepted_readers_of_the_class_kernel_find_the_int8_one(
        checkout, metric, reads):
    """No reader of the cell's own repeats them: the int8 kernel is the
    class kernel under its scope and its name, and ``train.run`` says
    ``class_rows`` and ``row_tile`` of it as of the twin's; the cell is on
    those metrics' ``workloads`` since PR 58."""
    int8 = _host(row_item_bytes=1, operand="bfloat16", by_rows=1,
                 class_rows=16, row_tile=2048)
    reduced, run = checkout(H._text(host=int8, ops=OPS, tf_ops=ONE_READ))
    run = dict(run, work=WORK, peaks=PEAKS)
    # kernel (50 + 20) ms, cast 1, fold 2, update 3, over 2 fits of 10
    # iterations; the roofline holds the whole step (busy 80 ms: the
    # while's own 4 too) against the bytes' floor at ONE byte a feature,
    # 15.03 ms
    assert _read(metric, reduced, run) == pytest.approx(reads, rel=1e-4)


# -- the entry ---------------------------------------------------------------------

@pytest.mark.parametrize("metric,unit,better,source", [
    ("row_item_bytes", "count", "lower", "program_span")])
def test_the_cells_own_metric_is_the_cells_alone_and_has_its_reader(
        metric, unit, better, source):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": source, "layer": "step",
                     "moves": "rows_per_s", "workloads": [NAME]}
    assert metric in cells.Cell(NAME).readers
    for other in (w["name"] for w in BENCH["workloads"] if w["name"] != NAME):
        assert metric not in cells.Cell(other).readers
    assert cells.load_module("layers", metric).__doc__.startswith("Step")
