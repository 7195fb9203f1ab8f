"""What ``mnist8m-multinomial`` brings to the benchmark (PR 32): the first
reference for a MATRIX of weights against steps by hand and against itself in
row blocks, the first work module whose operations are not ``4 x rows x d``,
the program through the cell's own entry against that reference, and
``class_sums_ms``' reader on traces written by hand."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, correct
from bench.reference import glm_dense_classes, rules

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NAME = "mnist8m-multinomial.resident-classes"


def _tiny_cell():
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides=tiny)


# -- the work module -----------------------------------------------------------

def test_work_from_shapes_by_hand():
    cell = cells.Cell(NAME)
    assert cell.rows == 8_100_000 and cell.config["reduced"] == []
    assert cell.work.dataset_bytes(cell.config, cell.rows) \
        == 8_100_000 * 784 * 2 == 12_700_800_000
    work = cell.work.step_work(cell.config, cell.rows)
    # every row once, in bf16, with its label; two products of the (9, 784)
    # weights' shape over all rows
    assert work["least"] == {"bytes": 8_100_000 * 784 * 2 + 8_100_000 * 4,
                             "flops": 4 * 8_100_000 * 784 * 9}
    # the kernel: X once, the labels as f32, the 16 padded class rows of the
    # weights read and of the gradient written; products issued at 16 rows
    assert work["as_laid_out"] == {
        "bytes": 8_100_000 * 784 * 2 + 8_100_000 * 4 + 2 * 16 * 784 * 4,
        "flops": 4 * 8_100_000 * 784 * 16}
    assert work["flops_peak"] == "bf16_flops_per_s"
    for key in ("bytes", "flops"):
        assert work["least"][key] <= work["as_laid_out"][key]
    # bound by bytes at the chip's peaks: 15.5 ms against 1.2 ms
    assert work["least"]["bytes"] / 819e9 > 10 * work["least"]["flops"] / 197e12


def test_the_cell_fills_the_chip_uncut():
    cell = cells.Cell(NAME)
    size = cell.work.dataset_bytes(cell.config, cell.rows)
    assert 0.75 * 16e9 < size + 4 * cell.rows < 16e9  # 79% of the chip
    assert size <= cell.job["dataset_bytes_cap"]
    assert cell.config["published"]["rows"] == cell.config["rows"] == cell.rows
    assert cell.config["published"]["features"] == cell.config["features"]
    assert cell.config["published"]["classes"] == cell.config["classes"] == 10


# -- the reference ---------------------------------------------------------------

def _config(**kw):
    return {"updater": "SquaredL2Updater", "classes": 3, "step_size": 0.5,
            "reg_param": 0.01, "num_iterations": 2,
            "mini_batch_fraction": 1.0, **kw}


def _softmax_step_by_hand(X, y, W, K):
    """float64: ``(mean gradient (K-1, d), mean loss)`` with the pivot's zero
    logit written out row by row."""
    grad, loss = np.zeros_like(W), 0.0
    for x, label in zip(X, y):
        logits = np.concatenate([[0.0], W @ x])
        p = np.exp(logits) / np.exp(logits).sum()
        loss -= np.log(p[int(label)])
        for c in range(1, K):
            grad[c - 1] += (p[c] - (label == c)) * x
    return grad / len(X), loss / len(X)


def test_classes_reference_follows_two_full_batch_steps_by_hand():
    X = np.array([[1.0, 2.0], [0.5, -1.0], [-1.5, 0.25], [0.75, 0.5]])
    y = np.array([1.0, 0.0, 2.0, 2.0])
    cfg = _config()
    W = np.zeros((2, 2))
    losses, reg_val = [], 0.0
    for t in (1, 2):
        g, loss = _softmax_step_by_hand(X, y, W, 3)
        losses.append(loss + reg_val)
        eta = cfg["step_size"] / np.sqrt(t)
        W = W * (1 - eta * cfg["reg_param"]) - eta * g
        reg_val = 0.5 * cfg["reg_param"] * np.sum(W * W)
    got_w, got_l = glm_dense_classes.fit(
        cfg, X.astype(np.float32), y.astype(np.float32),
        np.zeros(2, np.float32), seed=42)
    assert got_w.shape == (2, 2)  # the matrix; its flattening is MLlib's
    np.testing.assert_allclose(got_w, W, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_l, losses, rtol=1e-5)
    assert losses[0] == pytest.approx(np.log(3))  # zero weights: uniform
    # w0 as the (K-1, d) matrix is w0 as one class's row, broadcast
    same_w, same_l = glm_dense_classes.fit(
        cfg, X.astype(np.float32), y.astype(np.float32),
        np.zeros((2, 2), np.float32), seed=7)
    np.testing.assert_array_equal(same_w, got_w)
    np.testing.assert_array_equal(same_l, got_l)


@pytest.mark.parametrize("block_rows", [1000, 4096, 16384 - 1])
def test_classes_reference_in_row_blocks_is_the_reference_whole(block_rows):
    cell = _tiny_cell()
    config = dict(cell.config, num_iterations=5)
    X, y = cell.generator.make(config, cell.rows, 3)
    w0 = np.zeros((config["features"],), np.float32)
    whole = glm_dense_classes.fit(config, X, y, w0, 42, block_rows=cell.rows)
    blocks = glm_dense_classes.fit(config, X, y, w0, 42,
                                   block_rows=block_rows)
    np.testing.assert_allclose(blocks[0], whole[0], rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(blocks[1], whole[1], rtol=2e-6)


def test_classes_reference_is_the_full_batch_fit_only():
    with pytest.raises(ValueError, match="mini_batch_fraction must be 1.0"):
        glm_dense_classes.fit(_config(mini_batch_fraction=0.1),
                              np.zeros((4, 2), np.float32),
                              np.zeros(4, np.float32),
                              np.zeros(2, np.float32), 42)


@pytest.mark.parametrize("operands,moves", [("bfloat16", True),
                                            ("float8_e4m3fn", True),
                                            ("float32", False)])
def test_lower_operand_precision_moves_the_classes_reference(operands,
                                                             moves):
    cell = _tiny_cell()
    config = dict(cell.config, num_iterations=10)
    X, y = cell.generator.make(config, 4096, 5)
    w0 = np.zeros((config["features"],), np.float32)
    ref = glm_dense_classes.fit(config, X, y, w0, 42)
    low = glm_dense_classes.fit(config, jnp.array(X), y, w0, 42,
                                operands=operands)
    gap = correct.readings(*low, *ref, w0)["w_rel_gap"]
    assert (gap > 1e-6) == moves
    if operands == "float8_e4m3fn":
        assert gap > 10 * correct.readings(
            *glm_dense_classes.fit(config, jnp.array(X), y, w0, 42,
                                   operands="bfloat16"), *ref, w0)["w_rel_gap"]


def test_the_generator_draws_every_class_and_follows_the_seed():
    cell = _tiny_cell()
    X, y = cell.generator.make(cell.config, cell.rows, 11)
    assert X.shape == (16384, 64) and X.dtype == jnp.bfloat16
    counts = np.bincount(np.asarray(y).astype(int), minlength=10)
    assert counts.shape == (10,) and counts.min() > 100
    again = cell.generator.make(cell.config, cell.rows, 11)
    other = cell.generator.make(cell.config, cell.rows, 12)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again[1]))
    assert (np.asarray(y) != np.asarray(other[1])).any()
    # in row blocks, the last one overlapping: one shape, every row written
    from bench.data import dense_synthetic_classes as data
    Xb, yb = data.generator(1000, 64, 10, jnp.dtype("bfloat16"), 384)(
        jax.random.PRNGKey(0))
    assert float(jnp.min(jnp.sum(jnp.abs(Xb.astype(jnp.float32)), axis=1))) > 0


# -- the program, through the cell's own entry --------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_the_program_follows_the_classes_reference_at_the_tiny_sizes(seed):
    cell = _tiny_cell()
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, seed)
    w, losses = cell.entry.prepare(config, X, y, config["sampling_seed"])()
    assert isinstance(w, np.ndarray) and w.shape == (9, 64)
    assert losses.shape == (config["num_iterations"],)
    w0 = np.zeros((config["features"],), np.float32)  # as the harness does
    ref = cell.reference.fit(config, X, y, w0, config["sampling_seed"])
    got = correct.readings(w, losses, *ref, w0)
    for name in correct.NUMBERS:
        assert got[name] <= config["limits"][name], (name, got)
    assert losses[-1] < 0.7 * losses[0] == pytest.approx(0.7 * np.log(10))
    # the matrix's norm is its flat vector's, as the comparison takes it
    assert np.linalg.norm(w) == pytest.approx(np.linalg.norm(w.reshape(-1)))


# -- class_sums_ms ---------------------------------------------------------------------

KERNEL = "%_fused_class_sums.5 = custom-call(X, y, W)"
CAST, FOLD = "%pad.7 = pad(convert(W))", "%reduce.9 = reduce(pallas_call.2)"
SCOPE = "jit(sgd_run)/while/body/sgd.class_sums/cond/branch_0_fun/"
ONE_READ = {KERNEL: SCOPE + "jit(_fused_class_sums)/pallas_call:",
            CAST: SCOPE + "jit(_fused_class_sums)/pad:",
            FOLD: SCOPE + "jit(_fused_class_sums)/reduce_sum:",
            H.WHILE: "jit(sgd_run)/while:"}
#: fit 0: a while of 60 ms holding the cast's 1, the kernel's 50, the fold's
#: 2; fit 1: the kernel's 20 bare
OPS = [(H.WHILE, 30, 60), (CAST, 30.5, 1), (KERNEL, 32, 50), (FOLD, 87, 2),
       (KERNEL, 110, 20)]


def test_class_sums_ms_reads_the_kernels_scope(checkout):
    reduced, run = checkout(H._text(ops=OPS, tf_ops=ONE_READ))
    # (1 + 50 + 2 + 20) ms over 2 fits of 10 iterations
    assert H._read("class_sums_ms", reduced, run) == pytest.approx(3.65)
    assert H._read("fused_sums_ms", reduced, run) is None


def test_class_sums_ms_reads_what_the_two_matmuls_scopes_leave(checkout):
    """On the two-read path the products and the softmax keep their own
    scopes INSIDE ``sgd.class_sums``; an operation goes by its innermost."""
    two = dict(ONE_READ)
    two[KERNEL] = SCOPE.replace("cond/branch_0_fun/", "") \
        + "sgd.margins/dot_general:"
    two[FOLD] = SCOPE.replace("cond/branch_0_fun/", "") + "transpose:"
    reduced, run = checkout(H._text(ops=OPS, tf_ops=two))
    assert H._read("class_sums_ms", reduced, run) == pytest.approx(3 / 20)
    assert H._read("margins_ms", reduced, run) == pytest.approx(70 / 20)


def test_class_sums_ms_leaves_itself_out_where_there_is_nothing_to_read(
        checkout):
    # a vector of weights, and the parent of the PR that added the scope
    assert H._read("class_sums_ms", *checkout(H._text())) is None
    # no device in the trace: the CPU rehearsal
    from bench.layers import class_sums_ms

    assert class_sums_ms.read({"fits": [], "devices": 0},
                              {"workload": NAME, "iterations": 100}) is None
