"""What ``imagenet1k-r50-multinomial`` brings to the benchmark (PR 48): the
first configuration whose step the two products bound and not the read of X,
a thousand classes (1,008 padded class rows) over ImageNet-1k's frozen
ResNet-50 features UNCUT; its entries, appended; its work at the cell's
shape; the program through the cell's entry at the tiny sizes (more than 128
class rows, by rows); and the two readers, ``class_rows`` and
``class_kernel_ms``, on traces written by hand."""

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

CONFIG = "imagenet1k-r50-multinomial"
NAME = CONFIG + ".resident-classes"
BENCH = cells.benchmark()


def _tiny_cell():
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides=tiny)


# -- the entries ---------------------------------------------------------------

def _index(kind, name):
    return [e["name"] for e in BENCH[kind]].index(name)


def test_the_entries_are_appended_behind_what_the_benchmark_had():
    assert _index("configs", CONFIG) > _index("configs",
                                              "dense1000-lsq-dp4-run")
    assert _index("workloads", NAME) > _index(
        "workloads", "dense1000-lsq-dp4-run.from-host-sharded")
    for metric in ("class_rows", "class_kernel_ms"):
        assert _index("per_layer", metric) > _index("per_layer",
                                                    "h2d_runtime_overlap")
    entry = BENCH["configs"][_index("configs", CONFIG)]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    for word in ("ILSVRC-2012", "train split", "2,048", "ResNet-50",
                 "LogisticGradient(numClasses=1000)"):
        assert word in entry["source"], word
    cell = BENCH["workloads"][_index("workloads", NAME)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "resident-classes", 1)
    # the cell took none of the four-chip quota: up to it the two cells
    # that had it (by index: a later PR's four-chip cell stands behind)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][
        :_index("workloads", NAME) + 1]) == 2


@pytest.mark.parametrize("metric,source,better", [
    ("class_rows", "program_span", "higher"),
    ("class_kernel_ms", "device_trace", "lower")])
def test_the_two_metrics_are_the_new_cells_and_move_rows_per_s(
        metric, source, better):
    entry = BENCH["per_layer"][_index("per_layer", metric)]
    # the cell the metric came with stands first on its list; a later cell
    # whose step is the class kernel is appended behind it
    assert {**entry, "workloads": entry["workloads"][:1]} == {
        "name": metric, "unit": entry["unit"], "better": better,
        "source": source, "layer": "step",
        "moves": "rows_per_s", "workloads": [NAME]}
    assert metric in cells.Cell(NAME).readers
    assert metric not in cells.Cell(
        "cifar5m-multinomial.resident-classes").readers


# -- the configuration and the cell ------------------------------------------------

def test_the_configuration_is_on_cifar5ms_keys_and_cuts_nothing():
    config = cells.Cell(NAME).config
    with open(os.path.join(cells.BENCH, "configs",
                           "cifar5m-multinomial.json")) as f:
        cifar = json.load(f)
    assert set(config) - set(cifar) == {"limits_from"}  # the limits' reasons
    assert set(cifar) <= set(config)
    assert set(config["limits_from"]) == {"what", *correct.NUMBERS}
    assert config["model"] == cifar["model"].replace("= 10)", "= 1000)")
    same = ("gradient", "updater", "storage", "x_dtype",
            "matmul_operands", "accumulation", "weights_dtype",
            "mini_batch_fraction", "sampling", "sampling_seed", "step_size",
            "reg_param", "num_iterations", "convergence_tol", "generator",
            "reference", "control_operands", "work")
    for key in same:
        assert config[key] == cifar[key], key
    assert config["reduced"] == [] and config["classes"] == 1000
    assert config["published"]["rows"] == config["rows"] == 1_281_167
    assert config["published"]["features"] == config["features"] == 2048
    assert config["published"]["classes"] == config["classes"]
    assert "ALL 1,000 classes" in config["guarantees"]
    assert {"x", "true_weights", "labels", "source_sizes"} <= set(
        config["assumed"])
    tiny = config["tiny"]
    # more than 128 class rows, by rows, a cut last block
    from tpu_sgd.ops import pallas_kernels as PK

    assert PK.class_rows_of(tiny["classes"] - 1, "bfloat16") \
        > PK.FM_CLASS_ROWS
    assert PK.by_rows_form(tiny["rows"], tiny["features"])
    assert tiny["rows"] % 128


def test_the_cell_fills_a_quarter_of_the_chip_uncut():
    cell = cells.Cell(NAME)
    assert cell.rows == 1_281_167
    size = cell.work.dataset_bytes(cell.config, cell.rows)
    assert size == 1_281_167 * 2048 * 2 == 5_247_660_032
    assert size >= 0.25 * 2**34  # the driver's floor: 4.00 GiB of 16
    assert size <= cell.job["dataset_bytes_cap"]
    assert cell.config["as_run"]["rows"]["resident-classes"] == cell.rows
    # the weights a fit brings back: 8.18 MB where cifar5m's are 111 KB
    assert 999 * 2048 * 4 == 8_183_808


def test_the_work_modules_least_names_operations_at_the_cells_shape():
    """The first cell whose ``step_roofline`` stands on the operations: two
    products of the ``(999, 2048)`` weights' shape over every row are 53.2
    ms at the chip's peak, the one read of X 6.4 ms; the other two class
    cells stand on the bytes."""
    from bench.layers import step_roofline

    with open(os.path.join(cells.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]

    def least(name, which="least"):
        cell = cells.Cell(name)
        run = {"work": cell.work.step_work(cell.config, cell.rows),
               "peaks": peaks}
        return step_roofline.least_ms(run, which), run["work"]

    (ms, bound), work = least(NAME)
    assert bound == "operations" and ms == pytest.approx(53.22, abs=0.01)
    assert work["least"] == {
        "bytes": 1_281_167 * 2048 * 2 + 1_281_167 * 4,
        "flops": 4 * 1_281_167 * 2048 * 999}
    assert work["least"]["bytes"] / peaks["hbm_bytes_per_s"] \
        == pytest.approx(6.414e-3, rel=1e-3)
    # as laid out: 999 class rows padded to 1,008, the matrix read and the
    # sums written once a step
    assert work["as_laid_out"] == {
        "bytes": 1_281_167 * 2048 * 2 + 1_281_167 * 4 + 2 * 1008 * 2048 * 4,
        "flops": 4 * 1_281_167 * 2048 * 1008}
    (ms_laid, bound_laid), _ = least(NAME, "as_laid_out")
    assert bound_laid == "operations" and ms_laid == pytest.approx(53.70,
                                                                   abs=0.01)
    for other in ("mnist8m-multinomial.resident-classes",
                  "cifar5m-multinomial.resident-classes"):
        assert least(other)[0][1] == "bytes"


# -- the program, through the cell's own entry, at the tiny sizes -----------------

@pytest.mark.parametrize("seed", [1, 2])
def test_the_program_follows_the_reference_at_200_classes(seed):
    cell = _tiny_cell()
    config = cell.config
    assert (cell.rows, config["features"], config["classes"]) == (
        4000, 128, 200)
    X, y = cell.generator.make(config, cell.rows, seed)
    w, losses = cell.entry.prepare(config, X, y, config["sampling_seed"])()
    assert isinstance(w, np.ndarray) and w.shape == (199, 128)
    assert losses.shape == (config["num_iterations"],)
    w0 = np.zeros((config["features"],), np.float32)  # as the harness does
    ref = cell.reference.fit(config, X, y, w0, config["sampling_seed"])
    got = correct.readings(w, losses, *ref, w0)
    for name in correct.NUMBERS:
        assert got[name] <= config["limits"][name], (name, got)
    assert losses[0] == pytest.approx(np.log(200), rel=1e-5)
    assert losses[-1] < 0.9 * losses[0]


def test_the_generator_follows_the_seed_at_200_classes():
    cell = _tiny_cell()
    X, y = cell.generator.make(cell.config, cell.rows, 11)
    assert X.shape == (4000, 128) and str(X.dtype) == "bfloat16"
    labels = np.asarray(y).astype(int)
    assert labels.min() >= 0 and labels.max() == 199
    # the pivot's zero logit stands against 199 whose exponentials average
    # exp(2.67): it is the rarest class, and most others are drawn
    counts = np.bincount(labels, minlength=200)
    assert (counts > 0).sum() > 190 and counts[0] < np.median(counts)
    again = cell.generator.make(cell.config, cell.rows, 11)
    other = cell.generator.make(cell.config, cell.rows, 12)
    np.testing.assert_array_equal(labels, np.asarray(again[1]).astype(int))
    assert (labels != np.asarray(other[1]).astype(int)).any()


# -- the two readers, on traces written by hand ---------------------------------------

KERNEL = "%_fused_rows_class_sums.10 = custom-call(X, y, W)"
CAST, FOLD = "%pad.13 = pad(reshape(W))", "%reduce_sum.32 = reduce(call.24)"
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
    """The hand-written host events with ``stats`` on every ``train.run``
    (``train.select`` is no event of this trace)."""
    return [(n, s, d, {**st, **stats} if n == "train.run" else st)
            for n, s, d, st in H.HOST]


def test_class_kernel_ms_reads_the_kernels_call_alone(checkout):
    reduced, run = checkout(H._text(ops=OPS, tf_ops=ONE_READ))
    # (50 + 20) ms over 2 fits of 10 iterations: not the cast, not the fold
    assert H._read("class_kernel_ms", reduced, run) == pytest.approx(3.5)
    # class_sums_ms holds the scope's whole: 1 + 50 + 2 + 20
    assert H._read("class_sums_ms", reduced, run) == pytest.approx(3.65)
    from bench.layers import class_kernel_ms

    assert class_kernel_ms.is_call(ONE_READ[KERNEL])
    assert not class_kernel_ms.is_call(ONE_READ[CAST])
    assert not class_kernel_ms.is_call(
        "jit(sgd_run)/while/body/sgd.fused_sums/jit(_fused_scan_sums)/"
        "pallas_call:")  # a vector's kernel is another scope's
    assert not class_kernel_ms.is_call(None)


def test_class_kernel_ms_is_silent_where_no_such_call_ran(checkout):
    """The parent of PR 48 in the cell: two matmuls under ``sgd.margins`` /
    ``sgd.gradient`` inside ``sgd.class_sums``, no ``pallas_call``."""
    two = dict(ONE_READ)
    two[KERNEL] = "jit(sgd_run)/while/body/sgd.class_sums/sgd.margins/" \
        "dot_general:"
    two[FOLD] = "jit(sgd_run)/while/body/sgd.class_sums/transpose:"
    assert H._read("class_kernel_ms",
                   *checkout(H._text(ops=OPS, tf_ops=two))) is None
    # a vector of weights; no device in the trace (the CPU rehearsal)
    assert H._read("class_kernel_ms", *checkout(H._text())) is None
    from bench.layers import class_kernel_ms

    assert class_kernel_ms.read({"fits": [], "devices": 0},
                                {"workload": NAME, "iterations": 100}) is None


def test_class_rows_reads_train_runs_attribute(checkout):
    reduced, run = checkout(H._text(host=_host(class_rows=1008, classes=1000,
                                               row_tile=2048, by_rows=1)))
    assert H._read("class_rows", reduced, run) == 1008.0
    # two reads, or a vector of weights: the attribute is there and reads 0
    reduced, run = checkout(H._text(host=_host(class_rows=0)))
    assert H._read("class_rows", reduced, run) == 0.0
    # a program from before the attribute (the parent): nothing to read
    assert H._read("class_rows", *checkout(H._text())) is None
