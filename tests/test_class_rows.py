"""The class kernel PAST one pass of the matrix unit (PR 48): a ``(K-1, d)``
matrix of more than 128 padded class rows (ImageNet's 999 pad to 1,008) is
held whole in ONE read of X, in both orientations.  In interpret mode on the
CPU: against the plain reference (``bench/reference/glm_dense_classes.py``:
float32 at ``highest``, no program code) and against the two-read path, by
rows and feature-major, masked and not, rows that cut the last block; the
selection's table at 16, 128, 208 and 1,008 class rows; a whole
``GradientDescent`` fit through the kernel against the reference's fit; and
``class_rows`` on ``train.select`` and ``train.run``.  PR 50: past 128 class
rows a block's lane chunks run AHEAD (the next chunk's margins before this
chunk's rule), and the sums are the in-turn body's bit for bit; ``ahead``
in the table and on the two spans."""

import numpy as np
import pytest

from tpu_sgd.ops import pallas_kernels as PK
from tpu_sgd.ops.gradients import MultinomialLogisticGradient

K = 200  # 199 class rows pad to 208 in bf16: past FM_CLASS_ROWS


def _data(n, d, classes=K, seed=0):
    """bf16 rows and labels that follow a ``W_true`` (the benchmark's
    recipe, its spread of 4), and weights away from zero."""
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    X = jnp.asarray(r.normal(size=(n, d)), jnp.bfloat16)
    W_true = r.uniform(-4.0, 4.0, (classes - 1, d)) / np.sqrt(d)
    logits = np.concatenate(
        [np.zeros((n, 1)), np.asarray(X, np.float64) @ W_true.T], axis=1)
    y = np.argmax(logits + r.gumbel(size=logits.shape), axis=1)
    W = jnp.asarray(r.normal(size=(classes - 1, d)) * 0.5 / np.sqrt(d),
                    jnp.float32)
    return X, jnp.asarray(y, jnp.float32), W


#: orientation -> (width, the row tile of the test): 128 features are stored
#: by rows, 200 feature-major; 700 rows cut the third block of 256 at 188
SHAPES = {"by_rows": (128, True), "feature_major": (200, False)}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("form", sorted(SHAPES))
def test_past_128_class_rows_the_kernel_is_the_reference_and_the_two_reads(
        form, masked):
    from bench.reference import glm_dense_classes as reference

    n, (d, by_rows) = 700, SHAPES[form]
    X, y, W = _data(n, d, seed=d + masked)
    assert PK.class_rows_of(K - 1, X.dtype) == 208 > PK.FM_CLASS_ROWS
    assert PK.by_rows_form(2**20, d) == by_rows
    mask = (np.random.default_rng(7).uniform(size=n) < 0.4) if masked \
        else None
    g = MultinomialLogisticGradient(K)
    got = PK.fused_class_sums(g.class_rule, X, y, W, mask, tile_m=256,
                              interpret=True, by_rows=by_rows)
    assert got[0].shape == (K - 1, d) and float(got[2]) == (
        mask.sum() if masked else n)
    # the two-read path rounds W and the coefficients to bf16 as the
    # kernel does: the sums' order alone differs
    two = g._two_read_sums(X, y, W.reshape(-1), mask)
    scale = float(np.max(np.abs(np.asarray(two[0]))))
    np.testing.assert_allclose(np.asarray(got[0]).reshape(-1),
                               np.asarray(two[0]), atol=2e-3 * scale)
    assert float(got[1]) == pytest.approx(float(two[1]), rel=2e-4)
    # the plain reference: float32 operands at highest (a mask: its rows)
    keep = slice(None) if mask is None else np.flatnonzero(mask)
    ref_g, ref_l = reference.class_sums(W, X[keep], y[keep], K)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref_g),
                               atol=1e-2 * scale)
    assert float(got[1]) == pytest.approx(float(ref_l), rel=2e-3)


def test_every_class_row_is_in_every_rows_softmax():
    """No class left out, no approximate sum: one huge margin in the LAST
    real class row (row 198 of 208) takes a row's whole probability, and
    the padding rows behind it take none."""
    import jax.numpy as jnp

    n, d = 256, 128
    X, y, W = _data(n, d, seed=3)
    W = W.at[K - 2].set(W[K - 2] + 40.0 * X[0].astype(jnp.float32) / d)
    g = MultinomialLogisticGradient(K)
    got = PK.fused_class_sums(g.class_rule, X, y, W, tile_m=256,
                              interpret=True, by_rows=True)
    two = g._two_read_sums(X, y, W.reshape(-1), None)
    scale = float(np.max(np.abs(np.asarray(two[0]))))
    np.testing.assert_allclose(np.asarray(got[0]).reshape(-1),
                               np.asarray(two[0]), atol=2e-3 * scale)
    assert float(got[1]) == pytest.approx(float(two[1]), rel=2e-4)
    # row 0's margin of the last class is ~40: its loss is that margin
    assert float(got[1]) > 30.0


#: case -> (rows, row tile): at 208 class rows a pass of the body takes
#: 1,024 lanes, so a block of 2,048 is two chunks (the prologue, one trip
#: of the loop's body, the epilogue) and 404 rows cut a third block, which
#: stays in turn; a block of 1,024 is ONE chunk (prologue and epilogue
#: alone) and 300 rows cut a third; blocks of 3,072 are three chunks each
#: and none is cut
CHUNKS = {"two_chunks_and_a_cut_block": (4500, 2048),
          "one_chunk_and_a_cut_block": (2348, 1024),
          "three_chunks_no_cut": (6144, 3072)}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("form", sorted(SHAPES))
@pytest.mark.parametrize("case", sorted(CHUNKS))
def test_the_ahead_body_sums_what_the_in_turn_body_sums_bit_for_bit(
        case, form, masked):
    """The same chunks' operations, added into the sums in the same order:
    gradient, loss partials and count partials EQUAL, not close."""
    import jax.numpy as jnp

    (n, tile), (d, by_rows) = CHUNKS[case], SHAPES[form]
    X, y, W = _data(n, d, seed=n + d + masked)
    rows = PK.class_rows_of(K - 1, X.dtype)
    assert PK._fm_ahead(rows) and not PK._fm_ahead(PK.FM_CLASS_ROWS)
    assert tile // PK._fm_lane_chunk(tile, rows) == {
        2048: 2, 1024: 1, 3072: 3}[tile]
    mask = jnp.asarray(np.random.default_rng(n).uniform(size=n) < 0.4) \
        if masked else None
    Wp = jnp.pad(W.astype(X.dtype), ((0, rows - (K - 1)), (0, 0)))
    rule = MultinomialLogisticGradient(K).class_rule

    def sums(ahead):
        return PK._class_call(rule, X, y, Wp, mask, tile, d,
                              PK._fm_class_limit(rows), True, by_rows,
                              ahead=ahead)

    turn, ahead = sums(False), sums(True)
    assert float(jnp.sum(jnp.abs(turn[0]))) > 0.0
    for a, b in zip(ahead, turn):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


#: (rows, width, class rows) -> (body, by_rows, tile, VMEM limit in MiB,
#: scope, lanes a pass of the body takes, chunks ahead): up to 128 class
#: rows the parent's
#: record under the parent's limit (the K = 10 cells' programs are pinned in
#: tests/test_chip_compile.py); past it the wide form's limit, and the lanes
#: that keep a (class rows, lanes) f32 array at a megabyte: 256 at 1,008,
#: and the next chunk's margins ahead of this chunk's rule (PR 50)
TABLE = {
    # by rows: cifar5m's and ImageNet's widths
    (2_000_896, 3072, 16): ("class", True, 1024, 32, "sgd.class_sums", 1024,
                            False),
    (2_000_896, 3072, 128): ("class", True, 1024, 32, "sgd.class_sums", 1024,
                             False),
    (2_000_896, 3072, 208): ("class", True, 2048, 100, "sgd.class_sums",
                             1024, True),
    (1_281_167, 2048, 16): ("class", True, 2048, 32, "sgd.class_sums", 1024,
                            False),
    (1_281_167, 2048, 128): ("class", True, 2048, 32, "sgd.class_sums", 1024,
                             False),
    (1_281_167, 2048, 208): ("class", True, 2048, 100, "sgd.class_sums",
                             1024, True),
    (1_281_167, 2048, 1008): ("class", True, 2048, 100, "sgd.class_sums",
                              256, True),
    # feature-major: mnist8m's width and the north star's
    (8_100_000, 784, 16): ("class", False, 2048, 32, "sgd.class_sums", 1024,
                           False),
    (8_100_000, 784, 128): ("class", False, 2048, 32, "sgd.class_sums",
                            1024, False),
    (8_100_000, 784, 208): ("class", False, 2048, 100, "sgd.class_sums",
                            1024, True),
    (8_100_000, 784, 1008): ("class", False, 2048, 100, "sgd.class_sums",
                             256, True),
    (4_194_304, 1000, 1008): ("class", False, 2048, 100, "sgd.class_sums",
                              256, True),
    # where not even one lane group of rows fits beside the matrix
    (1_281_167, 2048, 4096): None,
}


@pytest.mark.parametrize("case", sorted(TABLE), ids=lambda c: "x".join(
    map(str, c)))
def test_one_reads_table_by_class_rows(case):
    n, d, rows = case
    for masked in (False, True):
        own = PK.one_read(n, d, 2, masked, rows)
        if TABLE[case] is None:
            assert own is None
            continue
        assert own.class_rows == rows and own.fblock == d
        assert (own.body, own.by_rows, own.tile, own.vmem_limit >> 20,
                own.scope, PK._fm_lane_chunk(own.tile, rows),
                own.ahead) == TABLE[case]
        assert PK._fm_vmem_bytes(own.tile, d, 2, masked, rows,
                                 by_rows=own.by_rows) <= own.vmem_limit
    # a vector of weights carries no class rows, and no body of it runs
    # its chunks ahead (by rows it rides the class body as 16 rows)
    assert PK.one_read(4_194_304, 1000, 2, True).class_rows == 0
    assert not PK.one_read(4_194_304, 1000, 2, True).ahead
    assert not PK.one_read(2_097_152, 1024, 2, True).ahead


def _through_the_kernel(monkeypatch, tile=256):
    """Every ``batch_sums`` of a matrix of weights takes the kernel in
    interpret mode, as a fit lowered for a TPU would take it compiled."""
    from tpu_sgd.ops import gradients as G

    calls = []

    def fused(self, X, y, weights, mask=None, margin_axis_name=None,
              rows=None):
        kernel = G.one_read_of(X, y, weights, mask, margin_axis_name,
                               classes=self.num_classes)
        assert kernel is not None and kernel.class_rows > PK.FM_CLASS_ROWS
        calls.append(kernel)
        W = weights.reshape(self.num_classes - 1, X.shape[-1])
        grad, loss, count = PK.fused_class_sums(
            self.class_rule, X, y, W, mask, tile_m=tile, interpret=True,
            by_rows=kernel.by_rows)
        return grad.reshape(-1), loss, count

    monkeypatch.setattr(G.MultinomialLogisticGradient, "batch_sums", fused)
    return calls


@pytest.mark.parametrize("form", sorted(SHAPES))
def test_a_whole_fit_through_the_kernel_follows_the_references_fit(
        monkeypatch, form):
    """``GradientDescent(MultinomialLogisticGradient(200), SquaredL2Updater)``
    over 1,000 rows (the last block of 256 is cut at 232) with every step's
    sums the kernel's, against ``glm_dense_classes.fit`` from the same
    zeros: the benchmark's three numbers under cifar5m's limits."""
    import tpu_sgd
    from bench import correct
    from bench.reference import glm_dense_classes as reference

    d, by_rows = SHAPES[form]
    n = 1000
    X, y, _ = _data(n, d, seed=11)
    assert PK.by_rows_form(n, d) == by_rows
    config = {"classes": K, "updater": "SquaredL2Updater", "step_size": 1.0,
              "reg_param": 0.001, "num_iterations": 12,
              "mini_batch_fraction": 1.0}
    calls = _through_the_kernel(monkeypatch)
    opt = (tpu_sgd.GradientDescent(MultinomialLogisticGradient(K),
                                   tpu_sgd.SquaredL2Updater())
           .set_step_size(config["step_size"])
           .set_num_iterations(config["num_iterations"])
           .set_reg_param(config["reg_param"])
           .set_mini_batch_fraction(1.0).set_convergence_tol(0.0))
    w, losses = opt.optimize_with_history(
        (X, y), np.zeros(((K - 1) * d,), np.float32))
    assert calls and all(k.by_rows == by_rows for k in calls)
    w0 = np.zeros((d,), np.float32)
    ref = reference.fit(config, X, y, w0, 42)
    got = correct.readings(np.asarray(w).reshape(K - 1, d),
                           np.asarray(losses), *ref, w0)
    assert got["w_rel_gap"] < 0.004 and got["loss_max_gap"] < 0.005 \
        and got["dw_norm_gap"] < 0.0014, got
    assert losses[0] == pytest.approx(np.log(K), rel=1e-5)
    assert losses[-1] < losses[0]


def test_batch_sums_lowers_one_call_for_a_tpu_past_128_class_rows():
    """The selection, at lowering, from the operands: 1,008 class rows over
    a by-rows X are ONE Mosaic call under ``sgd.class_sums`` in the by-rows
    form's own jitted function, and no product outside it."""
    import re

    import jax
    import jax.numpy as jnp

    n, d, classes = 4096, 2048, 1000
    g = MultinomialLogisticGradient(classes)
    shape = jax.ShapeDtypeStruct
    text = jax.jit(g.batch_sums).trace(
        shape((n, d), jnp.bfloat16), shape((n,), jnp.float32),
        shape(((classes - 1) * d,), jnp.float32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 1
    assert "stablehlo.dot_general" not in text
    assert re.search(
        r"sgd\.class_sums/[^\"]*jit\(_fused_rows_class_sums\)", text)
    assert "bf16[1008,2048]" in text.replace("tensor<1008x2048xbf16>",
                                             "bf16[1008,2048]")


@pytest.mark.parametrize("classes,rows", [(10, 16), (200, 208),
                                          (1000, 1008)])
def test_train_select_and_train_run_carry_the_class_rows(monkeypatch,
                                                         classes, rows):
    """``class_rows``: the padded class rows the kernel's products are
    issued with, on both spans, where the backend is a TPU; 0 on the CPU
    (the step is two matmuls) and for a vector of weights.  ``ahead``
    beside it: 1 past 128 class rows (208, 1,008), 0 at 16."""
    import jax

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, dict(payload)))

    n, d = 1024, 128
    X, y, _ = _data(n, d, classes=classes, seed=classes)

    def fit(gradient, w_dim):
        sink = Sink()
        enable_tracing(sink)
        try:
            (tpu_sgd.GradientDescent(gradient, tpu_sgd.SquaredL2Updater())
             .set_num_iterations(2).set_mini_batch_fraction(1.0)
             .optimize_with_history((X, y), np.zeros((w_dim,), np.float32)))
        finally:
            disable_tracing()
        return {p["name"]: p for kind, p in sink.records
                if kind == "trace_span"
                and p["name"] in ("train.select", "train.run")}

    g = MultinomialLogisticGradient(classes)
    here = fit(g, (classes - 1) * d)
    assert here["train.run"]["class_rows"] == 0
    assert here["train.select"]["class_rows"] == 0
    assert here["train.run"]["ahead"] == here["train.select"]["ahead"] == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    there = fit(g, (classes - 1) * d)
    assert there["train.run"]["class_rows"] == rows
    assert there["train.select"]["class_rows"] == rows
    assert there["train.run"]["ahead"] == there["train.select"]["ahead"] \
        == int(rows > PK.FM_CLASS_ROWS)
    assert there["train.run"]["classes"] == classes
    assert there["train.run"]["by_rows"] == 1
    vector = fit(tpu_sgd.LogisticGradient(), d)
    assert vector["train.run"]["class_rows"] == 0
    assert vector["train.run"]["ahead"] == 0

