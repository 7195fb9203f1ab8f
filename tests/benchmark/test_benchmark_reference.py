"""The plain references against steps computed by hand, against each other,
and (tiny, on the CPU) against the program they judge."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.sparse import BCOO

from bench import cells, correct
from bench.reference import glm_dense, glm_dense_window, glm_sparse, rules

GRADIENTS = ("LeastSquaresGradient", "LogisticGradient", "HingeGradient")
UPDATERS = ("SimpleUpdater", "SquaredL2Updater", "L1Updater")


def _loss(name, m, y):
    if name == "LeastSquaresGradient":
        return 0.5 * (m - y) ** 2
    if name == "LogisticGradient":
        return np.log1p(np.exp(-m)) if y > 0 else np.log1p(np.exp(m))
    return max(0.0, 1.0 - (2 * y - 1) * m)


@pytest.mark.parametrize("name", GRADIENTS)
@pytest.mark.parametrize("y", [0.0, 1.0])
def test_pointwise_is_the_losss_derivative(name, y):
    margins = np.array([-3.0, -0.4, 0.3, 2.5])
    coeff, loss = rules.pointwise(np, name, margins, np.full(4, y))
    h = 1e-6
    for m, c, l in zip(margins, coeff, loss):
        assert l == pytest.approx(_loss(name, m, y), abs=1e-12)
        slope = (_loss(name, m + h, y) - _loss(name, m - h, y)) / (2 * h)
        assert c == pytest.approx(slope, abs=1e-6)


@pytest.mark.parametrize("name", GRADIENTS)
def test_pointwise_is_the_same_in_numpy_and_jax(name):
    m = np.linspace(-4, 4, 9).astype(np.float32)
    y = (np.arange(9) % 2).astype(np.float32)
    for a, b in zip(rules.pointwise(np, name, m, y),
                    rules.pointwise(jnp, name, jnp.asarray(m),
                                    jnp.asarray(y))):
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-6, atol=2e-7)


def test_updaters_by_hand():
    w, g = np.array([1.0, -2.0, 0.05]), np.array([0.5, 0.5, -0.5])
    eta = 2.0 / np.sqrt(4)  # step 2 at iteration 4
    new, reg = rules.update(np, "SimpleUpdater", w, g, 2.0, 4, 0.1)
    np.testing.assert_allclose(new, w - eta * g)
    assert reg == 0.0
    new, reg = rules.update(np, "SquaredL2Updater", w, g, 2.0, 4, 0.1)
    np.testing.assert_allclose(new, w * (1 - eta * 0.1) - eta * g)
    assert reg == pytest.approx(0.05 * np.sum(new ** 2))
    new, reg = rules.update(np, "L1Updater", w, g, 2.0, 4, 0.1)
    stepped = w - eta * g  # [0.5, -2.5, 0.55], shrunk by 0.1 towards 0
    np.testing.assert_allclose(new, [0.4, -2.4, 0.45])
    np.testing.assert_allclose(new, np.sign(stepped) * (np.abs(stepped) - .1))
    assert reg == pytest.approx(0.1 * np.abs(new).sum())


@pytest.mark.parametrize("kind,name", [("pointwise", "NoSuchGradient"),
                                       ("update", "NoSuchUpdater")])
def test_an_unknown_rule_is_an_error(kind, name):
    with pytest.raises(ValueError, match=name):
        if kind == "pointwise":
            rules.pointwise(np, name, np.zeros(2), np.zeros(2))
        else:
            rules.update(np, name, np.zeros(2), np.zeros(2), 1.0, 1, 0.0)


def _config(gradient, updater, **kw):
    return {"gradient": gradient, "updater": updater, "step_size": 0.5,
            "reg_param": 0.01, "num_iterations": 2,
            "mini_batch_fraction": 1.0, "nnz_per_row": 2, **kw}


def test_dense_reference_follows_two_full_batch_steps_by_hand():
    X = np.array([[1.0, 2.0], [0.5, -1.0], [-1.5, 0.25]], np.float32)
    y = np.array([1.0, 0.0, 1.0], np.float32)
    w = np.zeros(2)
    losses = []
    for t in (1, 2):
        m = X @ w
        sig = 1 / (1 + np.exp(-m))
        losses.append(np.mean(np.where(y > 0, np.log1p(np.exp(-m)),
                                       np.log1p(np.exp(m))))
                      + 0.005 * np.sum(w * w))
        g = X.T @ (sig - y) / 3
        eta = 0.5 / np.sqrt(t)
        w = w * (1 - eta * 0.01) - eta * g
    got_w, got_l = glm_dense.fit(
        _config("LogisticGradient", "SquaredL2Updater"), X, y, np.zeros(2),
        seed=42)
    np.testing.assert_allclose(got_w, w, rtol=1e-5)
    np.testing.assert_allclose(got_l, losses, rtol=1e-5)


def _bcoo(vals, cols, d):
    n, k = vals.shape
    idx = np.stack([np.repeat(np.arange(n), k), cols.reshape(-1)], 1)
    return BCOO((jnp.asarray(vals.reshape(-1)), jnp.asarray(idx, jnp.int32)),
                shape=(n, d))


def test_sparse_reference_follows_two_hinge_l1_steps_by_hand():
    vals = np.array([[1.0, 2.0], [0.5, -1.0], [-1.5, 0.25]], np.float32)
    cols = np.array([[0, 3], [1, 3], [0, 2]])
    y = np.array([1.0, 0.0, 1.0])
    dense = np.zeros((3, 4))
    for i in range(3):
        dense[i, cols[i]] = vals[i]
    w, reg_val, losses = np.zeros(4), 0.0, []
    for t in (1, 2):
        s = 2 * y - 1
        slack = 1 - s * (dense @ w)
        losses.append(np.mean(np.maximum(slack, 0)) + reg_val)
        g = dense.T @ np.where(slack > 0, -s, 0.0) / 3
        eta = 0.5 / np.sqrt(t)
        w = w - eta * g
        w = np.sign(w) * np.maximum(np.abs(w) - 0.01 * eta, 0)
        reg_val = 0.01 * np.abs(w).sum()
    got_w, got_l = glm_sparse.fit(_config("HingeGradient", "L1Updater"),
                                  _bcoo(vals, cols, 4), y, np.zeros(4),
                                  seed=42)
    np.testing.assert_allclose(got_w, w, rtol=1e-6)
    np.testing.assert_allclose(got_l, losses, rtol=1e-6)


@pytest.mark.parametrize("gradient", GRADIENTS)
@pytest.mark.parametrize("updater", UPDATERS)
def test_sparse_and_dense_references_agree_on_the_same_rows(gradient,
                                                           updater):
    rng = np.random.default_rng(3)
    n, d, k = 40, 12, 3
    cols = np.sort(np.stack([rng.choice(d, k, replace=False)
                             for _ in range(n)]), axis=1)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    dense = np.zeros((n, d), np.float32)
    for i in range(n):
        dense[i, cols[i]] = vals[i]
    config = _config(gradient, updater, nnz_per_row=k, num_iterations=5)
    a = glm_sparse.fit(config, _bcoo(vals, cols, d), y, np.zeros(d), seed=1)
    b = glm_dense.fit(config, dense, y, np.zeros(d), seed=1)
    np.testing.assert_allclose(a[0], b[0], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(a[1], b[1], rtol=2e-5)


def test_sparse_reference_refuses_rows_without_a_fixed_run():
    X = BCOO((jnp.ones(4), jnp.asarray([[0, 0], [0, 1], [0, 2], [1, 0]],
                                       jnp.int32)), shape=(2, 3))
    with pytest.raises(ValueError, match="fixed run"):
        glm_sparse.fit(_config("HingeGradient", "L1Updater"), X,
                       np.zeros(2), np.zeros(3), seed=1)


def test_dense_reference_draws_the_programs_bernoulli_batches():
    """A mini-batch fit of the program (Bernoulli, its seed) and the
    reference's agree step by step only if the draws are the same rows."""
    cell = cells.Cell("dense1000-logistic.resident",
                      overrides={"rows": 4096, "features": 32,
                                 "num_iterations": 6})
    X, y = cell.generator.make(cell.config, cell.rows, 5)
    w, losses = cell.entry.prepare(cell.config, X, y, 42)()
    w0 = np.zeros(32, np.float32)
    ref = cell.reference.fit(cell.config, X, y, w0, 42)
    got = correct.readings(np.asarray(w), losses, *ref, w0)
    assert max(got.values()) < 2e-3, got
    other = cell.reference.fit(cell.config, X, y, w0, 43)
    assert correct.readings(np.asarray(w), losses, *other, w0)[
        "loss_max_gap"] > 10 * got["loss_max_gap"]


@pytest.mark.parametrize("operands,worse_than", [("bfloat16", 1e-5),
                                                 ("float8_e4m3fn", 3e-3)])
def test_lower_operand_precision_moves_the_dense_reference(operands,
                                                          worse_than):
    cell = cells.Cell("dense1000-logistic.resident",
                      overrides={"rows": 8192, "features": 64})
    X, y = cell.generator.make(cell.config, cell.rows, 2)
    w0 = np.zeros(64, np.float32)
    ref = cell.reference.fit(cell.config, X, y, w0, 42)
    low = cell.reference.fit(cell.config, jnp.array(X), y, w0, 42,
                             operands=operands)
    assert correct.readings(*low, *ref, w0)["w_rel_gap"] > worse_than


# -- the windowed reference (sampling="sliced") -------------------------------

SLICED = "dense1000-logistic-sliced.resident"
ALL = cells.benchmark(with_prepared=True)  # the cell may wait prepared


def test_window_reference_follows_two_windowed_steps_by_hand():
    """Five rows, a window of two (fraction 0.4): each step trains the rows
    from the offset the seed gives, normalised by the window's two rows."""
    import jax

    X = np.array([[1.0, 2.0], [0.5, -1.0], [-1.5, 0.25], [2.0, 0.5],
                  [-0.5, -0.75]], np.float32)
    y = np.array([1.0, 0.0, 1.0, 0.0, 1.0], np.float32)
    key = jax.random.PRNGKey(42)
    starts = [int(jax.random.randint(jax.random.fold_in(key, t), (), 0, 4))
              for t in (1, 2)]
    assert starts == [int(glm_dense_window.offset(key, t, 5, 2))
                      for t in (1, 2)]
    assert all(0 <= o <= 3 for o in starts)
    assert glm_dense_window.window_rows(5, 0.4) == 2
    w, losses = np.zeros(2), []
    for t, o in zip((1, 2), starts):
        Xw, yw = X[o:o + 2], y[o:o + 2]
        m = Xw @ w
        losses.append(np.mean(np.where(yw > 0, np.log1p(np.exp(-m)),
                                       np.log1p(np.exp(m))))
                      + 0.005 * np.sum(w * w))
        g = Xw.T @ (1 / (1 + np.exp(-m)) - yw) / 2
        eta = 0.5 / np.sqrt(t)
        w = w * (1 - eta * 0.01) - eta * g
    got_w, got_l = glm_dense_window.fit(
        _config("LogisticGradient", "SquaredL2Updater",
                mini_batch_fraction=0.4), X, y, np.zeros(2), seed=42)
    np.testing.assert_allclose(got_w, w, rtol=1e-5)
    np.testing.assert_allclose(got_l, losses, rtol=1e-5)


def test_a_window_of_everything_is_the_full_batch_fit():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(24, 5)).astype(np.float32)
    y = (rng.random(24) < 0.5).astype(np.float32)
    config = _config("LogisticGradient", "SquaredL2Updater", num_iterations=4)
    a = glm_dense_window.fit(config, X, y, np.zeros(5), seed=3)
    b = glm_dense.fit(config, X, y, np.zeros(5), seed=3)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)
    np.testing.assert_allclose(a[1], b[1], rtol=1e-6)


def test_window_reference_draws_the_programs_offsets():
    """The program's sliced fit and the reference's agree step by step only
    if both train the same windows: the gaps are under the committed limits
    with the program's seed, and far over with another."""
    cell = cells.Cell(SLICED, ALL, overrides={"rows": 4096, "features": 32,
                                              "num_iterations": 6})
    assert cell.config["sampling"] == "sliced"
    assert cell.reference.__file__.endswith("glm_dense_window.py")
    X, y = cell.generator.make(cell.config, cell.rows, 5)
    w, losses = cell.entry.prepare(cell.config, X, y, 42)()
    w0 = np.zeros(32, np.float32)
    ref = cell.reference.fit(cell.config, X, y, w0, 42)
    got = correct.readings(np.asarray(w), losses, *ref, w0)
    limits = cell.config["limits"]
    assert all(got[n] <= limits[n] for n in correct.NUMBERS), got
    other = cell.reference.fit(cell.config, X, y, w0, 43)
    assert correct.readings(np.asarray(w), losses, *other, w0)[
        "loss_max_gap"] > 10 * max(got["loss_max_gap"], limits["loss_max_gap"])
    # and not the Bernoulli draws of the sibling's reference
    masked = glm_dense.fit(cell.config, X, y, w0, 42)
    assert correct.readings(np.asarray(w), losses, *masked, w0)[
        "loss_max_gap"] > limits["loss_max_gap"]


@pytest.mark.parametrize("operands,worse_than", [("bfloat16", 1e-5),
                                                 ("float8_e4m3fn", 3e-3)])
def test_lower_operand_precision_moves_the_window_reference(operands,
                                                           worse_than):
    cell = cells.Cell(SLICED, ALL, overrides={"rows": 8192, "features": 64})
    X, y = cell.generator.make(cell.config, cell.rows, 2)
    w0 = np.zeros(64, np.float32)
    ref = cell.reference.fit(cell.config, X, y, w0, 42)
    low = cell.reference.fit(cell.config, jnp.array(X), y, w0, 42,
                             operands=operands)
    assert correct.readings(*low, *ref, w0)["w_rel_gap"] > worse_than
