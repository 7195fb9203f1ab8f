"""The class kernel (a ``(K-1, d)`` matrix of weights through one read of X:
both products on the matrix unit, the pivot softmax between them) against
the two-matmul path, in interpret mode on the CPU; the rule between the
products; and the selection inside ``MultinomialLogisticGradient.batch_sums``."""

import re

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (LogisticGradient,
                                   MultinomialLogisticGradient,
                                   one_read_of)
from tpu_sgd.ops.pallas_kernels import class_rows_of, fused_class_sums


def _data(n, d, K, seed, dtype="bfloat16", scale=0.3):
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    X = jnp.asarray(r.normal(size=(n, d)), dtype)
    y = jnp.asarray(r.integers(0, K, n), jnp.float32)
    w = jnp.asarray(r.normal(size=((K - 1) * d,)) * scale / np.sqrt(d),
                    jnp.float32)
    return X, y, w


def _by_hand(X, y, w, K, mask=None):
    """float64: ``(gradient (K-1, d), loss sum)`` of the pivot softmax."""
    X = np.asarray(X, np.float64)
    W = np.asarray(w, np.float64).reshape(K - 1, X.shape[1])
    logits = np.concatenate([np.zeros((len(X), 1)), X @ W.T], axis=1)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    onehot = np.eye(K)[np.asarray(y, int)]
    keep = np.ones(len(X)) if mask is None else np.asarray(mask, float)
    loss = -(np.log(p) * onehot).sum(axis=1)
    return ((p - onehot)[:, 1:] * keep[:, None]).T @ X, (loss * keep).sum()


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("d", [64, 784])
@pytest.mark.parametrize("K", [3, 10])
def test_class_kernel_matches_two_matmuls(K, d, masked):
    """n = 700 at a tile of 256: the last block is cut at 188 lanes, and
    the interpreter fills what lies past the end with NaN (the poisoned
    tail of ``tests/test_pallas.py``)."""
    import jax.numpy as jnp

    n = 700
    X, y, w = _data(n, d, K, seed=K * d)
    mask = (np.random.default_rng(d).uniform(size=n) < 0.4) if masked \
        else None
    g = MultinomialLogisticGradient(K)
    gs_ref, ls_ref, c_ref = g._two_read_sums(X, y, w, mask)
    gs, ls, c = fused_class_sums(g.class_rule, X, y, w.reshape(K - 1, d),
                                 mask, tile_m=256, interpret=True)
    assert gs.shape == (K - 1, d)
    assert gs.dtype == ls.dtype == c.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(gs_ref)))
    # both paths round W and the coefficients to bf16; the sums' order differs
    np.testing.assert_allclose(np.asarray(gs).reshape(-1), np.asarray(gs_ref),
                               atol=2e-3 * scale)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == (mask.sum() if masked else n)
    # and both are the sums by hand, to what bf16 operands leave
    by_hand, loss = _by_hand(X, y, w, K, mask)
    np.testing.assert_allclose(np.asarray(gs), by_hand, atol=1e-2 * scale)
    assert float(ls) == pytest.approx(loss, rel=2e-3)


def test_class_kernel_on_float32_rows_and_whole_tiles():
    n, d, K = 512, 64, 5
    X, y, w = _data(n, d, K, seed=5, dtype="float32")
    g = MultinomialLogisticGradient(K)
    gs, ls, c = fused_class_sums(g.class_rule, X, y, w.reshape(K - 1, d),
                                 tile_m=256, interpret=True)
    by_hand, loss = _by_hand(X, y, w, K)
    np.testing.assert_allclose(np.asarray(gs), by_hand, rtol=1e-4, atol=1e-3)
    assert float(ls) == pytest.approx(loss, rel=1e-5) and float(c) == n
    assert class_rows_of(K - 1, X.dtype) == 8
    assert class_rows_of(9, "bfloat16") == 16


def test_the_flat_vector_is_the_matrixs_row_major_flattening():
    """``weight_dim`` counts ``(K-1) * d`` entries; entry ``c * d + j`` is
    class ``c + 1``'s weight of feature ``j``, in the weights and in the
    gradient both paths return."""
    import jax

    n, d, K = 300, 24, 4
    X, y, w = _data(n, d, K, seed=9, dtype="float32")
    g = MultinomialLogisticGradient(K)
    assert g.weight_dim(d) == (K - 1) * d == w.shape[0]
    by_hand, _ = _by_hand(X, y, w, K)
    flat = jax.jit(g.batch_sums)(X, y, w)[0]
    assert flat.shape == (g.weight_dim(d),)
    np.testing.assert_allclose(np.asarray(flat), by_hand.reshape(-1),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("path", ["two_matmuls", "kernel"])
def test_two_classes_are_the_binary_logistic_gradient(path):
    """K = 2: one row of weights, the pivot's zero logit against it, is
    ``LogisticGradient`` with labels in {0, 1}."""
    n, d = 400, 32
    X, y, w = _data(n, d, 2, seed=11, dtype="float32")
    mask = np.random.default_rng(1).uniform(size=n) < 0.5
    g = MultinomialLogisticGradient(2)
    want = LogisticGradient()._two_read_sums(X, y, w, mask)
    if path == "kernel":
        got = fused_class_sums(g.class_rule, X, y, w.reshape(1, d), mask,
                               tile_m=128, interpret=True)
        got = (got[0].reshape(-1),) + got[1:]
    else:
        got = g.batch_sums(X, y, w, mask)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == float(want[2])


def test_the_rule_gives_padded_class_rows_a_zero_coefficient():
    """The kernel holds the class rows at whole registers: rows past K - 1
    take no part in the softmax, whatever margin they hold."""
    import jax.numpy as jnp

    K, lanes = 10, 256
    r = np.random.default_rng(3)
    margins = jnp.asarray(r.normal(size=(K - 1, lanes)) * 3, jnp.float32)
    labels = jnp.asarray(r.integers(0, K, (1, lanes)), jnp.float32)
    g = MultinomialLogisticGradient(K)
    coeff, loss = g.class_rule(margins, labels)
    padded = jnp.concatenate(
        [margins, jnp.full((7, lanes), 50.0, jnp.float32)])
    coeff_p, loss_p = g.class_rule(padded, labels)
    np.testing.assert_array_equal(np.asarray(coeff_p[:K - 1]),
                                  np.asarray(coeff))
    np.testing.assert_array_equal(np.asarray(coeff_p[K - 1:]), 0.0)
    np.testing.assert_array_equal(np.asarray(loss_p), np.asarray(loss))
    # a column's coefficients and the pivot's sum to zero; the loss is the
    # label's negative log-probability
    logits = np.concatenate([np.zeros((1, lanes)), np.asarray(margins)])
    p = np.exp(logits) / np.exp(logits).sum(axis=0)
    picked = p[np.asarray(labels, int)[0], np.arange(lanes)]
    np.testing.assert_allclose(np.asarray(loss)[0], -np.log(picked),
                               rtol=1e-5, atol=1e-6)


def test_compute_is_one_rows_batch_sums():
    n, d, K = 8, 12, 4
    X, y, w = _data(n, d, K, seed=2, dtype="float32")
    g = MultinomialLogisticGradient(K)
    grad, loss = g.compute(X[3], y[3], w)
    want = g.batch_sums(X[3:4], y[3:4], w)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want[0]),
                               rtol=1e-6)
    assert float(loss) == pytest.approx(float(want[1]))


# -- the selection ---------------------------------------------------------

def _case(name):
    """``(X, y, w, mask, classes)`` of (1024, 784) bf16 rows — a shape the
    chip stores feature-major — with one observable changed."""
    import jax.numpy as jnp

    n, d, K = 1024, 784, 10
    X = jnp.zeros((n, d), jnp.bfloat16)
    y, mask = jnp.zeros((n,)), jnp.zeros((n,), bool)
    if name == "a_vector_of_d_weights":
        return X, y, jnp.zeros((d,)), mask, K
    if name == "more_rows_than_one_pass":
        K = 200  # 199 rows pad to 208, over the matrix unit's 128 (PR 48)
    elif name == "row_major_width":
        d = 1024  # stored by rows: the by-rows form's (PR 39)
        X = jnp.zeros((n, d), jnp.bfloat16)
    elif name == "row_major_odd_width":
        d = 1020  # by rows with padded lanes: no block of either form
        X = jnp.zeros((n, d), jnp.bfloat16)
    elif name == "two_classes":
        K = 2
    else:
        assert name == "ten_classes", name
    return X, y, jnp.zeros(((K - 1) * d,)), mask, K


ON = ["ten_classes", "two_classes", "row_major_width",
      "more_rows_than_one_pass"]
OFF = ["a_vector_of_d_weights", "row_major_odd_width"]


@pytest.mark.parametrize("case", ON + OFF)
def test_one_read_of_says_what_it_admits_of_a_class_count(case):
    X, y, w, mask, K = _case(case)
    for m in (mask, None):
        own = one_read_of(X, y, w, m, classes=K)
        assert (own is not None) == (case in ON)
        assert own is None or (own.body, own.scope, own.draws) == (
            "class", "sgd.class_sums", False)


def _lowered_for(platform, fn, *args):
    import jax

    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text(debug_info=True)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
def test_batch_sums_lowers_the_class_kernel_for_a_tpu_and_two_matmuls_here(
        masked):
    """Decided at LOWERING, from the operands, as ``Gradient.batch_sums``:
    lowered for a TPU the program holds ONE Mosaic call under
    ``sgd.class_sums`` and no product outside it; lowered for the CPU the
    two ``dot_general`` with ``sgd.margins`` / ``sgd.pointwise`` /
    ``sgd.gradient`` inside ``sgd.class_sums``."""
    X, y, w, mask, K = _case("ten_classes")
    g = MultinomialLogisticGradient(K)
    args = (X, y, w, mask) if masked else (X, y, w)
    tpu = _lowered_for("tpu", g.batch_sums, *args)
    assert tpu.count("tpu_custom_call") == 1
    assert "stablehlo.dot_general" not in tpu
    assert re.search(r"sgd\.class_sums/[^\"]*jit\(_fused_class_sums\)", tpu)
    assert "sgd.fused_sums" not in tpu
    cpu = _lowered_for("cpu", g.batch_sums, *args)
    assert cpu.count("stablehlo.dot_general") == 2
    assert "tpu_custom_call" not in cpu
    for scope in ("sgd.margins", "sgd.pointwise", "sgd.gradient"):
        assert re.search(rf"sgd\.class_sums/[^\"]*{scope}", cpu)


@pytest.mark.parametrize("case", OFF[1:])
def test_batch_sums_keeps_two_matmuls_on_a_tpu_where_the_kernel_is_off(case):
    X, y, w, mask, K = _case(case)
    tpu = _lowered_for("tpu", MultinomialLogisticGradient(K).batch_sums,
                       X, y, w, mask)
    assert "tpu_custom_call" not in tpu
    assert tpu.count("stablehlo.dot_general") == 2
    assert re.search(r"sgd\.class_sums/[^\"]*sgd\.margins", tpu)


def test_batch_sums_on_the_cpu_is_bitwise_the_two_matmuls():
    import jax

    n, d, K = 1024, 784, 10
    X, y, w = _data(n, d, K, seed=21)
    mask = np.random.default_rng(22).uniform(size=n) < 0.1
    g = MultinomialLogisticGradient(K)
    new = jax.jit(g.batch_sums)(X, y, w, mask)
    old = jax.jit(g._two_read_sums)(X, y, w, mask)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_sums_keeps_the_slice_and_two_matmuls_on_a_tpu():
    """The class kernel has no window grid: the windowed step reads its
    slice in place twice, on every platform."""
    import jax.numpy as jnp

    X, y, w, mask, K = _case("ten_classes")
    g = MultinomialLogisticGradient(K)
    tpu = _lowered_for("tpu", lambda X, y, w, s: g.window_sums(
        X, y, w, s, 100), X, y, w, jnp.int32(5))
    assert "tpu_custom_call" not in tpu
    assert tpu.count("stablehlo.dot_general") == 2
    assert re.search(r"sgd\.class_sums/[^\"]*sgd\.margins", tpu)


def test_the_class_kernel_refuses_a_matrix_no_vmem_holds_with_the_count():
    """More rows than one pass of the matrix unit are held whole under the
    wide form's limit (PR 48; ``tests/test_class_rows.py``); what is
    refused, before any compile, is a matrix whose weights and sums fit no
    VMEM beside one lane group of rows: 8,000 class rows of 784 features
    are 2 x 8,000 x 896 x 6 bytes."""
    import jax.numpy as jnp

    X, y, _, _, _ = _case("ten_classes")
    K = 8001
    g = MultinomialLogisticGradient(K)
    assert one_read_of(X, y, jnp.zeros(((K - 1) * 784,)), classes=K) is None
    with pytest.raises(ValueError, match="8000 class rows of d=784 weights and sums"):
        fused_class_sums(g.class_rule, X, y, jnp.zeros((K - 1, 784)))


# -- through the optimizer -------------------------------------------------

def test_train_run_carries_the_class_count():
    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, dict(payload)))

    n, d = 256, 8
    X, y, _ = _data(n, d, 3, seed=4, dtype="float32")
    fits = ((MultinomialLogisticGradient(3), y, np.zeros(2 * d, np.float32)),
            (LogisticGradient(), np.minimum(y, 1.0), np.zeros(d, np.float32)))
    sink = Sink()
    enable_tracing(sink)
    try:
        for grad, labels, w0 in fits:
            (tpu_sgd.GradientDescent(grad, tpu_sgd.SquaredL2Updater())
             .set_num_iterations(3).optimize_with_history((X, labels), w0))
    finally:
        disable_tracing()
    runs = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.run"]
    assert [r["classes"] for r in runs] == [3, 2]
