"""What ``rcv1-dense-hinge-l1`` brings to the benchmark (PR 34): BASELINE
config 3 as it is written (RCV1 densified), 47,236 features wide.  The job and
the work module from shapes, the generator against the sparse recipe it
densifies, the program through the cell's own entry against ``glm_dense`` at
the tiny sizes with the fp8 control outside the limits, and the two readers
(``wide_sums_ms``, ``row_tile``) on traces written by hand."""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, correct

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NAME = "rcv1-dense-hinge-l1.resident-wide"
ROWS, D = 131_072, 47_236


def _tiny_cell(**more):
    """The cell at its tiny sizes (float32 rows: the configuration says
    why); ``x_dtype="bfloat16"`` for the rows the chip holds."""
    tiny = dict(cells.Cell(NAME).config["tiny"])
    tiny.pop("what")
    return cells.Cell(NAME, overrides={**tiny, **more})


BF16 = {"x_dtype": "bfloat16", "matmul_operands": "bfloat16"}


# -- the job, the cut and the work module ----------------------------------------

def test_the_job_cuts_the_rows_to_sixteen_steps_that_fill_the_chip():
    cell = cells.Cell(NAME)
    config = cell.config
    assert cell.rows == ROWS == 16 * cell.job["rows_step"]
    size = cell.work.dataset_bytes(config, cell.rows)
    assert size == ROWS * D * 2 == 12_382_633_984
    assert size <= cell.job["dataset_bytes_cap"] \
        < cell.work.dataset_bytes(config, ROWS + cell.job["rows_step"])
    assert 0.75 * 16e9 < size + 4 * ROWS < 16e9  # 77% of the chip
    # the rows are cut and nothing else: the width is the source's
    assert config["reduced"] == ["rows"]
    assert config["published"] == {**config["published"], "rows": 677_399,
                                   "features": D}
    assert config["rows"] == 677_399 and config["features"] == D
    assert config["as_run"]["rows"] == {"resident-wide": ROWS}
    assert (config["gradient"], config["updater"]) == ("HingeGradient",
                                                       "L1Updater")
    assert config["mini_batch_fraction"] == 1.0
    assert config["num_iterations"] == 100
    assert cell.job["entry"] == {"dense": "optimizer_resident"}


def test_the_sparse_configurations_assumptions_are_kept():
    """Step size, regulariser, entries a row and the recipe are the prepared
    sparse configuration's; that file and its prepared cell stay as they
    are."""
    dense = cells.Cell(NAME).config
    with open(os.path.join(cells.BENCH, "configs", "rcv1-hinge-l1.json")) as f:
        import json
        sparse = json.load(f)
    for key in ("step_size", "reg_param", "nnz_per_row", "features", "rows",
                "sampling_seed"):
        assert dense[key] == sparse[key], key
    for key in ("rows", "features", "nnz_per_row"):
        assert dense["tiny"][key] == sparse["tiny"][key], key
    assert sparse["storage"] == "bcoo" and sparse["num_iterations"] == 10
    assert os.path.isfile(os.path.join(cells.BENCH, "prepared",
                                       "rcv1-hinge-l1.json"))


def test_work_from_shapes_by_hand():
    cell = cells.Cell(NAME)
    work = cell.work.step_work(cell.config, cell.rows)
    # every row once, in bf16, with its label; two matvecs over all rows
    assert work["least"] == {"bytes": ROWS * D * 2 + ROWS * 4,
                             "flops": 4 * ROWS * D}
    # the wide kernel: X once, the labels as f32, 16 rows of weights in
    # bf16 read and 16 rows of gradient in f32 written; products issued at
    # 16 rows
    assert work["as_laid_out"] == {
        "bytes": ROWS * D * 2 + ROWS * 4 + 16 * D * (2 + 4),
        "flops": 4 * ROWS * D * 16}
    assert work["flops_peak"] == "bf16_flops_per_s"
    for key in ("bytes", "flops"):
        assert work["least"][key] <= work["as_laid_out"][key]
    # bound by bytes at the chip's peaks: 15.12 ms against 0.13 ms (2.0 ms
    # as issued), and no program that reads X twice can pass 50%
    assert work["least"]["bytes"] / 819e9 == pytest.approx(15.12e-3, rel=1e-3)
    assert work["least"]["bytes"] / 819e9 \
        > 7 * work["as_laid_out"]["flops"] / 197e12


def test_the_kernels_rows_are_the_work_modules():
    from bench.work import dense_wide_step
    from tpu_sgd.ops import pallas_kernels as PK

    assert PK.wide_rows_of(jnp.bfloat16)[1] == dense_wide_step.WEIGHT_ROWS
    assert PK.fm_blocks(ROWS, D, 2, masked=False) == (256, 8)


# -- the generator ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 2_147_483_000])
def test_the_generator_is_the_sparse_recipe_densified_and_rounded(seed):
    """``rcv1_like_dense`` at the tiny size equals ``rcv1_like``'s BCOO
    densified and rounded to bf16, labels equal, for the same seed."""
    from bench.data import rcv1_like

    cell = _tiny_cell(**BF16)
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, seed)
    assert X.shape == (2048, 512) and X.dtype == jnp.bfloat16
    assert y.shape == (2048,) and y.dtype == jnp.float32
    sparse, y_sparse = rcv1_like.make(config, cell.rows, seed)
    want = sparse.todense().astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(X.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(y), y_sparse)
    stored = np.asarray(X != 0).sum(axis=1)
    assert (stored == config["nnz_per_row"]).all()  # 12 entries a row
    norms = np.linalg.norm(np.asarray(X.astype(jnp.float32)), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=4e-3)  # unit rows, rounded


def test_the_generator_in_row_blocks_writes_every_row():
    """Blocks that do not divide the rows: the last one overlaps, one
    shape, every row written as the whole array is."""
    from bench.data import rcv1_like, rcv1_like_dense

    cell = _tiny_cell()
    sparse, _ = rcv1_like.make(cell.config, 1000, 5)
    vals = sparse.data.reshape(1000, 12)
    cols = sparse.indices[:, 1].reshape(1000, 12)
    blocks = rcv1_like_dense.densifier(1000, 512, 12, jnp.dtype("bfloat16"),
                                       384)(vals, cols)
    np.testing.assert_array_equal(
        np.asarray(blocks.astype(jnp.float32)),
        np.asarray(sparse.todense().astype(jnp.bfloat16).astype(jnp.float32)))


# -- the program, through the cell's own entry ------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_the_program_follows_glm_dense_at_the_tiny_sizes(seed):
    """A 100-iteration ``HingeGradient`` + ``L1Updater`` fit within the
    configuration's limits, and the fp8 control outside them."""
    cell = _tiny_cell()
    config = cell.config
    X, y = cell.generator.make(config, cell.rows, seed)
    w, losses = cell.entry.prepare(config, X, y, config["sampling_seed"])()
    w = np.asarray(w)
    assert w.shape == (512,) and losses.shape == (100,)
    w0 = np.zeros((config["features"],), np.float32)  # as the harness does
    ref = cell.reference.fit(config, X, y, w0, config["sampling_seed"])
    got = correct.readings(w, losses, *ref, w0)
    for name in correct.NUMBERS:
        assert got[name] <= config["limits"][name], (name, got)
    assert losses[0] == 1.0 and losses[-1] < 0.6  # zero weights: slack 1
    control = correct.readings(*cell.reference.fit(
        config, jnp.array(X), y, w0, 42,
        operands=config["control_operands"]), *ref, w0)
    assert any(control[n] > config["limits"][n] for n in correct.NUMBERS)
    assert control["w_rel_gap"] > config["limits"]["w_rel_gap"]


def test_the_wide_kernel_follows_glm_dense_through_a_whole_fit():
    """The fit the chip runs, step by step on the CPU: the wide kernel in
    interpret mode (the width in feature blocks, rows no multiple of the
    tile) inside ``L1Updater``'s loop for 20 iterations, against
    ``glm_dense``: the weights ride as three bf16 parts, so the gaps are a
    float32 reordering's, far under the bf16-operand reference's."""
    from tpu_sgd.ops.gradients import HingeGradient
    from tpu_sgd.ops.pallas_kernels import fused_wide_sums
    from tpu_sgd.ops.updaters import L1Updater

    cell = _tiny_cell(**BF16)
    config = dict(cell.config, num_iterations=20)
    X, y = cell.generator.make(config, 1000, 4)
    assert X.dtype == jnp.bfloat16
    g, upd = HingeGradient(), L1Updater()
    w = jnp.zeros((512,), jnp.float32)
    losses, reg_val = [], 0.0
    for t in range(1, 21):
        gs, ls, c = fused_wide_sums(g.pointwise, X, y, w, vmem_limit=5 << 19,
                                    interpret=True)
        losses.append(float(ls / c) + float(reg_val))
        w, reg_val = upd.compute(w, gs / c, config["step_size"], t,
                                 config["reg_param"])
    w0 = np.zeros((512,), np.float32)
    ref = cell.reference.fit(config, X, y, w0, 42)
    got = correct.readings(np.asarray(w), np.asarray(losses), *ref, w0)
    assert got["w_rel_gap"] < 2e-5 and got["loss_max_gap"] < 2e-5, got
    sound = correct.readings(*cell.reference.fit(
        config, jnp.array(X), y, w0, 42, operands="bfloat16"), *ref, w0)
    assert sound["w_rel_gap"] > 20 * got["w_rel_gap"]


# -- wide_sums_ms and row_tile ---------------------------------------------------------

KERNEL = "%_fused_wide_sums.11 = custom-call(X, y, W)"
SPLIT, FOLD = "%fusion.10 = fusion(w)", "%reduce.9 = reduce(pallas_call.2)"
SCOPE = "jit(sgd_run)/while/body/cond/branch_0_fun/sgd.wide_sums/"
WIDE = {KERNEL: SCOPE + "jit(_fused_wide_sums)/pallas_call:",
        SPLIT: SCOPE + "jit(_fused_wide_sums)/sub:",
        FOLD: SCOPE + "jit(_fused_wide_sums)/reduce_sum:",
        H.WHILE: "jit(sgd_run)/while:"}
#: fit 0: a while of 60 ms holding the split's 1, the kernel's 50, the fold's
#: 2; fit 1: the kernel's 20 bare
OPS = [(H.WHILE, 30, 60), (SPLIT, 30.5, 1), (KERNEL, 32, 50), (FOLD, 87, 2),
       (KERNEL, 110, 20)]


def _host(stats_of):
    """``H.HOST`` with each fit's ``train.run`` stats from ``stats_of(i)``."""
    seen, out = 0, []
    for name, start, length, stats in H.HOST:
        if name == "train.run":
            stats, seen = {**stats, **stats_of(seen)}, seen + 1
        out.append((name, start, length, stats))
    return out


def test_wide_sums_ms_reads_the_kernels_scope(checkout):
    reduced, run = checkout(H._text(ops=OPS, tf_ops=WIDE))
    # (1 + 50 + 2 + 20) ms over 2 fits of 10 iterations
    assert H._read("wide_sums_ms", reduced, run) == pytest.approx(3.65)
    assert H._read("fused_sums_ms", reduced, run) is None
    assert H._read("class_sums_ms", reduced, run) is None


def test_wide_sums_ms_leaves_itself_out_where_no_operation_carries_the_scope(
        checkout):
    # the parent's two matvecs (sgd.margins / sgd.gradient), a narrow width
    assert H._read("wide_sums_ms", *checkout(H._text())) is None
    # no device in the trace: the CPU rehearsal
    from bench.layers import wide_sums_ms

    assert wide_sums_ms.read({"fits": [], "devices": 0},
                             {"workload": NAME, "iterations": 100}) is None


@pytest.mark.parametrize("stats_of,expected", [
    (lambda i: {"row_tile": 256, "feature_blocks": 8}, 256),
    (lambda i: {"row_tile": (256, 128)[i], "feature_blocks": 8}, 192),
    (lambda i: {"row_tile": 0, "feature_blocks": 1}, 0),  # two reads
    (lambda i: {}, None),  # the parent's span carries no such attribute
], ids=["wide", "mean_over_fits", "no_kernel", "parent"])
def test_row_tile_reads_the_spans_attribute(checkout, stats_of, expected):
    got = H._read("row_tile", *checkout(H._text(host=_host(stats_of))))
    assert got == (None if expected is None else pytest.approx(expected))


def test_row_tile_is_nothing_without_the_span(checkout):
    no_run = [e for e in H.HOST if e[0] != "train.run"]
    assert H._read("row_tile", *checkout(H._text(host=no_run))) is None


def test_the_two_metrics_are_the_wide_cells_and_move_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["wide_sums_ms"] == {
        "name": "wide_sums_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step", "moves": "rows_per_s",
        "workloads": [NAME]}
    # the cell the metric came with stands first; a later cell whose
    # ``train.run`` says its kernel's row tile is appended behind it
    assert {**entries["row_tile"],
            "workloads": entries["row_tile"]["workloads"][:1]} == {
        "name": "row_tile", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "step", "moves": "rows_per_s",
        "workloads": [NAME]}
    # their order by index: later PRs append behind them
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("row_tile") == names.index("wide_sums_ms") + 1
    assert names.index("wide_sums_ms") > names.index("class_sums_ms")
    cell = cells.Cell(NAME)
    reported = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"wide_sums_ms", "row_tile", "step_ms", "step_roofline"} <= reported
    assert not {"fused_sums_ms", "class_sums_ms", "h2d_ms"} & reported
