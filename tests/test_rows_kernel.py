"""The class body over ROW blocks (PR 39): where the chip stores X by rows, a
width that is a multiple of 128, the one-read kernel takes ``(tile, d)`` blocks
of X itself.  In interpret mode on the CPU against the two-read sums and
against the feature-major class kernel on the same values, for a matrix of
weights and for a vector as rows; the selection from the operands; and the
``by_rows`` attribute of ``train.select`` / ``train.run``."""

import re

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (HingeGradient, LeastSquaresGradient,
                                   LogisticGradient,
                                   MultinomialLogisticGradient, one_read_of)
from tpu_sgd.ops.pallas_kernels import (fused_class_sums, fused_rows_sums,
                                        fused_wide_sums)

TILE = 256
#: rows against a tile of 256: whole tiles, one row past them (a cut block
#: of one row: the interpreter fills the 255 past it with NaN), and a count
#: that is no multiple of a packed register's 16 rows
ROWS = {"whole_tiles": 512, "one_past": 513, "ragged": 500}


def _rows(n, d, dtype, seed):
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    return r, jnp.asarray(r.normal(size=(n, d)), dtype)


def _mask(r, n, masked):
    return (r.uniform(size=n) < 0.4) if masked else None


#: (K, dtype, masked, rows, d): every class count, type, mask and cut at one
#: lane group of features; the wider rows (1,024; CIFAR's 3,072) at the cell's
#: class count and type under the cut that bites
MATRIX = ([(K, dtype, masked, rows, 128)
           for K in (3, 10) for dtype in ("bfloat16", "float32")
           for masked in (False, True) for rows in sorted(ROWS)]
          + [(10, "bfloat16", masked, "one_past", d)
             for masked in (False, True) for d in (1024, 3072)])


@pytest.mark.parametrize("K,dtype,masked,rows,d", MATRIX)
def test_rows_class_kernel_matches_two_matmuls_and_the_feature_major_kernel(
        K, dtype, masked, rows, d):
    import jax.numpy as jnp

    n = ROWS[rows]
    r, X = _rows(n, d, dtype, seed=K * d + n)
    y = jnp.asarray(r.integers(0, K, n), jnp.float32)
    W = jnp.asarray(r.normal(size=(K - 1, d)) * 0.3 / np.sqrt(d), jnp.float32)
    mask = _mask(r, n, masked)
    g = MultinomialLogisticGradient(K)
    want = g._two_read_sums(X, y, W.reshape(-1), mask)
    got = fused_class_sums(g.class_rule, X, y, W, mask, tile_m=TILE,
                           interpret=True, by_rows=True)
    other = fused_class_sums(g.class_rule, X, y, W, mask, tile_m=TILE,
                             interpret=True, by_rows=False)
    assert got[0].shape == (K - 1, d)
    assert got[0].dtype == got[1].dtype == got[2].dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want[0])))
    # all three round W and the coefficients to X's type; the sums' order
    # differs (the two kernels': only inside a product)
    np.testing.assert_allclose(np.asarray(got[0]).reshape(-1),
                               np.asarray(want[0]), atol=2e-3 * scale)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(other[0]),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(float(got[1]), float(want[1]), rtol=2e-4)
    np.testing.assert_allclose(float(got[1]), float(other[1]), rtol=1e-6)
    assert float(got[2]) == float(want[2]) == float(other[2]) == (
        mask.sum() if masked else n)


GRADS = {"logistic": LogisticGradient(), "hinge": HingeGradient(),
         "least_squares": LeastSquaresGradient()}
#: (gradient, dtype, masked, rows, d)
VECTOR = ([(name, dtype, masked, "ragged", 128)
           for name in sorted(GRADS) for dtype in ("bfloat16", "float32")
           for masked in (False, True)]
          + [("logistic", "bfloat16", True, rows, 128)
             for rows in ("whole_tiles", "one_past")]
          + [(name, "bfloat16", False, "one_past", d)
             for name in sorted(GRADS) for d in (1024, 3072)])


@pytest.mark.parametrize("name,dtype,masked,rows,d", VECTOR)
def test_rows_kernel_takes_a_vector_as_rows_and_matches_two_matvecs(
        name, dtype, masked, rows, d):
    """The vector rides as three bf16 rows (one f32 row) under
    ``_vector_rule``, as in the wide form: against f32 operands it loses
    nothing, so it reads the float32 sums where the two matvecs round w and
    the coefficients to bf16."""
    import jax.numpy as jnp

    n, g = ROWS[rows], GRADS[name]
    r, X = _rows(n, d, dtype, seed=d + n)
    y = jnp.asarray(r.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(r.normal(size=(d,)) / np.sqrt(d), jnp.float32)
    mask = _mask(r, n, masked)
    got = fused_rows_sums(g.pointwise, X, y, w, mask, tile_m=TILE,
                          interpret=True)
    exact = g._two_read_sums(X.astype(jnp.float32), y, w, mask)
    # the same body over blocks of X.T (one feature block)
    other = fused_wide_sums(g.pointwise, X, y, w, mask, tile_m=TILE,
                            interpret=True)
    assert got[0].shape == (d,) and got[0].dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(exact[0])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(exact[0]),
                               atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(other[0]),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(float(got[1]), float(exact[1]), rtol=2e-5)
    np.testing.assert_allclose(float(got[1]), float(other[1]), rtol=1e-6)
    assert float(got[2]) == float(exact[2]) == (mask.sum() if masked else n)
    # and the two matvecs', to what their bf16 w and coefficients leave
    rough = g._two_read_sums(X, y, w, mask)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(rough[0]),
                               atol=5e-3 * scale)


def test_the_entry_takes_the_blocks_in_the_order_the_chip_stores_x():
    """``fused_class_sums(by_rows=None)``: row blocks of X where the chip
    stores it by rows, blocks of ``X.T`` where feature-major; the same sums
    either way."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops import pallas_kernels as PK

    K = 4
    g = MultinomialLogisticGradient(K)
    seen = []
    for d in (128, 120):
        r, X = _rows(2048, d, "float32", seed=d)
        y = jnp.asarray(r.integers(0, K, 2048), jnp.float32)
        W = jnp.asarray(r.normal(size=(K - 1, d)) * 0.1, jnp.float32)
        text = jax.jit(lambda X, y, W: fused_class_sums(
            g.class_rule, X, y, W, interpret=True)).lower(X, y, W).as_text()
        seen.append("_fused_rows_class_sums" in text)
        assert ("_fused_class_sums" in text) != seen[-1]
        assert PK.by_rows_form(2048, d) == seen[-1]
    assert seen == [True, False]


# -- the selection -------------------------------------------------------------

def _shapes(n, d, dtype="bfloat16", classes=None):
    import jax
    import jax.numpy as jnp

    X = jax.ShapeDtypeStruct((n, d), jnp.dtype(dtype))
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    w = jax.ShapeDtypeStruct((d * (classes - 1 if classes else 1),),
                             jnp.float32)
    return X, y, w, jax.ShapeDtypeStruct((n,), bool)


def _blocks(own):
    """``(by rows, row tile, feature blocks)`` of a record, or None."""
    return own and (own.by_rows, own.tile, own.feature_blocks)


#: every width the issue names (embeddings, hashed spaces, 32 x 32 x 3
#: pixels) at 2**20 rows: the row tile of a vector's / ten classes' by-rows
#: kernel, bf16 and f32
ADMITTED = {128: (2048, 2048), 768: (2048, 2048), 1024: (2048, 2048),
            1536: (2048, 2048), 2048: (2048, 1024), 3072: (1024, 512),
            4096: (1024, 256)}


@pytest.mark.parametrize("d", sorted(ADMITTED))
def test_one_read_of_admits_the_widths_the_chip_stores_by_rows(d):
    n = 2**20
    for dtype, tile in zip(("bfloat16", "float32"), ADMITTED[d]):
        X, y, w, mask = _shapes(n, d, dtype)
        assert _blocks(one_read_of(X, y, w)) == (True, tile, 1)
        Xc, yc, wc, _ = _shapes(n, d, dtype, classes=10)
        assert _blocks(one_read_of(Xc, yc, wc, classes=10)) == (
            True, tile, 1)
        assert _blocks(one_read_of(X, y, w, mask)) == (True, tile, 1)
        # no window grid, no draw in the kernel
        assert one_read_of(X, y, w, window=n // 10) is None
        g = LogisticGradient()
        assert g.one_read(X, y, w) is not None
        assert not g.one_read(X, y, w).draws
        assert g.one_read(X, y, w, window=n // 10) is None


#: what the six older cells' steps ask ``one_read_of`` and are answered,
#: on the parent and now, then the by-rows cell's and LIBSVM SVHN's shape (no
#: cell: its rows end in a cut block): (rows a shard, d, classes, masked,
#: window) -> blocks
CELLS = {
    "dense1000-logistic.resident": (4_194_304, 1000, None, True, False),
    "dense1000-logistic.from-host": (2_145_000, 1000, None, True, False),
    "dense1000-lsq-dp4.resident-sharded": (2_500_000, 1000, None, True,
                                           False),
    "dense1000-logistic-sliced.resident": (4_194_304, 1000, None, False,
                                           True),
    "mnist8m-multinomial.resident-classes": (8_100_000, 784, 10, False,
                                             False),
    "rcv1-dense-hinge-l1.resident-wide": (131_072, 47_236, None, False,
                                          False),
    "cifar5m-multinomial.resident-classes": (2_000_896, 3072, 10, False,
                                              False),
    "svhn-shape": (604_388, 3072, 10, False, False),
}
BY_ROWS_CELLS = ("cifar5m-multinomial.resident-classes", "svhn-shape")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_one_read_of_answers_the_cells_shapes(cell):
    n, d, classes, masked, window = CELLS[cell]
    X, y, w, mask = _shapes(n, d, classes=classes)
    own = one_read_of(X, y, w, mask if masked else None, classes=classes,
                      window=n // 10 if window else None)
    assert _blocks(own) == {
        "rcv1-dense-hinge-l1.resident-wide": (False, 256, 8),
        **dict.fromkeys(BY_ROWS_CELLS, (True, 1024, 1))}.get(
            cell, (False, 2048, 1))


#: by-rows shapes that stay two reads, and why
OFF = {
    "overflows_vmem": (2**14, 16_384, "bfloat16"),  # 128 rows: 8 MB a buffer
    "overflows_vmem_f32": (2**14, 8192, "float32"),
    "no_lane_multiple": (2**20, 1020, "bfloat16"),  # by rows, padded lanes
    "few_rows": (100, 1000, "bfloat16"),  # by rows for its few rows
}


@pytest.mark.parametrize("case", sorted(OFF))
def test_one_read_of_leaves_two_reads_where_no_row_block_fits(case):
    from tpu_sgd.ops.pallas_kernels import by_rows_form, feature_major

    n, d, dtype = OFF[case]
    X, y, w, mask = _shapes(n, d, dtype)
    assert not feature_major(n, d)
    assert by_rows_form(n, d) == case.startswith("overflows")
    assert one_read_of(X, y, w) is None
    assert one_read_of(X, y, w, mask) is None
    Xc, yc, wc, _ = _shapes(n, d, dtype, classes=10)
    assert one_read_of(Xc, yc, wc, classes=10) is None
    assert LogisticGradient().one_read(X, y, w) is None
    assert LogisticGradient().one_read(X, y, w, mask) is None


def _lowered_for(platform, fn, *args):
    import jax

    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text(debug_info=True)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("name", sorted(GRADS) + ["ten_classes"])
def test_batch_sums_lowers_the_rows_kernel_for_a_tpu_and_two_reads_here(
        name, masked):
    """At a by-rows width ``batch_sums`` lowered for a TPU is ONE Mosaic
    call in a jitted function of the by-rows form's own name, under the
    scope its feature-major sibling has, and no product outside it;
    lowered for the CPU the two ``dot_general`` it always was."""
    classes = 10 if name == "ten_classes" else None
    g = MultinomialLogisticGradient(10) if classes else GRADS[name]
    X, y, w, mask = _shapes(4096, 1024, classes=classes)
    mask = mask if masked else None
    tpu = _lowered_for("tpu", g.batch_sums, X, y, w, mask)
    assert tpu.count("tpu_custom_call") >= 1
    assert "stablehlo.dot_general" not in tpu
    assert re.search(
        r"sgd\.class_sums/[^\"]*jit\(_fused_rows_class_sums\)" if classes
        else r"sgd\.fused_sums/jit\(_fused_rows_sums\)", tpu)
    assert "sgd.wide_sums" not in tpu and "_fused_scan_sums" not in tpu
    cpu = _lowered_for("cpu", g.batch_sums, X, y, w, mask)
    assert cpu.count("stablehlo.dot_general") == 2
    assert "tpu_custom_call" not in cpu


def test_window_sums_of_by_rows_x_keeps_the_slice_and_two_matvecs_on_a_tpu():
    import jax.numpy as jnp

    X, y, w, _ = _shapes(4096, 1024)
    fn = lambda X, y, w, s: LogisticGradient().window_sums(  # noqa: E731
        X, y, w, s, 400)
    tpu = _lowered_for("tpu", fn, X, y, w, jnp.int32(7))
    assert "tpu_custom_call" not in tpu
    assert tpu.count("stablehlo.dot_general") == 2


def test_a_bernoulli_step_over_by_rows_x_hands_the_kernel_an_array():
    """The class body draws nothing, so the step's mask is the ``(n,)``
    array and the by-rows kernel reads it as a row operand."""
    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import step_sums

    X, y, w, _ = _shapes(2**20, 1024)
    cfg = SGDConfig(mini_batch_fraction=0.1)
    g = LogisticGradient()
    plan = step_sums(g, cfg, X, y, w)
    assert plan.drawn and not plan.mask_in_kernel
    assert _blocks(plan.kernel) == (True, 2048, 1)
    sliced = SGDConfig(mini_batch_fraction=0.1, sampling="sliced")
    assert step_sums(g, sliced, X, y, w).kernel is None


# -- the counter ---------------------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_train_select_and_train_run_say_whether_the_step_is_by_rows(
        backend, monkeypatch):
    """``by_rows``: 1 where this fit's step is the one-read kernel's by-rows
    form (a TPU, an X the chip stores by rows at a multiple of 128, a full
    batch or a drawn mask), 0 where it is the feature-major kernel, two reads
    (a window or a gathered batch of a by-rows X) or any fit on a CPU."""
    import jax

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, dict(payload)))

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    r = np.random.default_rng(7)
    y = r.integers(0, 2, 512).astype(np.float32)

    def fit(d, gradient=None, sampling=None, mesh=None):
        X = r.normal(size=(512, d)).astype(np.float32)
        gradient = gradient or LogisticGradient()
        opt = tpu_sgd.GradientDescent(
            gradient, tpu_sgd.SquaredL2Updater()
        ).set_num_iterations(2).set_mini_batch_fraction(0.5)
        if sampling:
            opt.set_sampling(sampling)
        if mesh is not None:
            opt.set_mesh(mesh)
        opt.optimize_with_history(
            (X, y), np.zeros(gradient.weight_dim(d), np.float32))

    sink = Sink()
    enable_tracing(sink)
    try:
        fit(128)
        fit(128, MultinomialLogisticGradient(3))
        fit(128, mesh=tpu_sgd.data_mesh(jax.devices()[:4]))
        fit(24)
        fit(128, sampling="sliced")
        fit(128, sampling="indexed")
    finally:
        disable_tracing()
    want = [1, 1, 1, 0, 0, 0] if backend == "tpu" else [0] * 6
    for name in ("train.run", "train.select"):
        spans = [p for k, p in sink.records
                 if k == "trace_span" and p["name"] == name]
        assert [s["by_rows"] for s in spans] == want, name
    runs = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.run"]
    assert [s["row_tile"] for s in runs] == (
        [512, 512, 128, 512, 0, 0] if backend == "tpu" else [0] * 6)
    assert [s["mask_in_kernel"] for s in runs] == (
        [0, 0, 0, 1, 0, 0] if backend == "tpu" else [0] * 6)
