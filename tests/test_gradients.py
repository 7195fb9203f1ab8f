"""Unit tests of gradients against NumPy closed forms (SURVEY.md §4)."""

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
    MultinomialLogisticGradient,
)


def _rand(n=32, d=7, seed=1):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, d)).astype(np.float32)
    w = r.normal(size=(d,)).astype(np.float32)
    return X, w


class TestLeastSquares:
    def test_closed_form_single(self):
        X, w = _rand()
        y = np.random.default_rng(2).normal(size=(X.shape[0],)).astype(np.float32)
        g = LeastSquaresGradient()
        grad, loss = g.compute(X[0], y[0], w)
        diff = X[0] @ w - y[0]
        np.testing.assert_allclose(loss, 0.5 * diff**2, rtol=1e-5)
        np.testing.assert_allclose(grad, diff * X[0], rtol=1e-5)

    def test_batch_matches_sum_of_singles(self):
        X, w = _rand()
        y = np.random.default_rng(2).normal(size=(X.shape[0],)).astype(np.float32)
        g = LeastSquaresGradient()
        gs, ls, c = g.batch_sums(X, y, w)
        grad_ref = sum(np.asarray(g.compute(X[i], y[i], w)[0]) for i in range(len(y)))
        loss_ref = sum(float(g.compute(X[i], y[i], w)[1]) for i in range(len(y)))
        np.testing.assert_allclose(gs, grad_ref, rtol=1e-4)
        np.testing.assert_allclose(ls, loss_ref, rtol=1e-4)
        assert c == len(y)

    def test_mask(self):
        X, w = _rand()
        y = np.zeros((X.shape[0],), np.float32)
        mask = np.zeros((X.shape[0],), bool)
        mask[:5] = True
        g = LeastSquaresGradient()
        gs, ls, c = g.batch_sums(X, y, w, mask)
        gs2, ls2, c2 = g.batch_sums(X[:5], y[:5], w)
        np.testing.assert_allclose(gs, gs2, rtol=1e-5)
        np.testing.assert_allclose(ls, ls2, rtol=1e-5)
        assert c == 5


class TestLogistic:
    def test_closed_form(self):
        X, w = _rand()
        y = (np.random.default_rng(3).uniform(size=(X.shape[0],)) < 0.5).astype(
            np.float32
        )
        g = LogisticGradient()
        margins = X @ w
        coeff, loss = g.pointwise(margins, y)
        sig = 1.0 / (1.0 + np.exp(-margins))
        np.testing.assert_allclose(coeff, sig - y, rtol=1e-4, atol=1e-6)
        # reference form: loss = log1p(exp(-x.w)) [- (-x.w) if y == 0]
        neg = -margins
        ref = np.log1p(np.exp(neg))
        ref = np.where(y > 0, ref, ref - neg)
        np.testing.assert_allclose(loss, ref, rtol=1e-4, atol=1e-6)

    def test_numerical_stability_large_margin(self):
        g = LogisticGradient()
        coeff, loss = g.pointwise(np.asarray([1e4, -1e4], np.float32),
                                  np.asarray([1.0, 0.0], np.float32))
        assert np.all(np.isfinite(np.asarray(loss)))
        assert np.all(np.isfinite(np.asarray(coeff)))

    def test_gradient_is_autodiff_of_loss(self):
        import jax
        import jax.numpy as jnp

        X, w = _rand(8, 5)
        y = (np.random.default_rng(4).uniform(size=(8,)) < 0.5).astype(np.float32)
        g = LogisticGradient()

        def total_loss(w_):
            _, loss = g.pointwise(jnp.asarray(X) @ w_, jnp.asarray(y))
            return jnp.sum(loss)

        auto = jax.grad(total_loss)(np.asarray(w))
        gs, _, _ = g.batch_sums(X, y, w)
        np.testing.assert_allclose(auto, gs, rtol=1e-3, atol=1e-5)


class TestHinge:
    def test_closed_form(self):
        X, w = _rand()
        y = (np.random.default_rng(5).uniform(size=(X.shape[0],)) < 0.5).astype(
            np.float32
        )
        g = HingeGradient()
        margins = X @ w
        coeff, loss = g.pointwise(margins, y)
        s = 2 * y - 1
        slack = 1 - s * margins
        np.testing.assert_allclose(
            loss, np.where(slack > 0, slack, 0.0), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            coeff, np.where(slack > 0, -s, 0.0), rtol=1e-5, atol=1e-6
        )

    def test_inactive_examples_contribute_nothing(self):
        g = HingeGradient()
        # margin 5 with label +1 -> slack = -4 < 0
        grad, loss = g.compute(
            np.ones((3,), np.float32) * 2.0, np.float32(1.0),
            np.asarray([1.0, 0.5, 1.0], np.float32),
        )
        assert float(loss) == 0.0
        np.testing.assert_allclose(grad, np.zeros((3,)), atol=1e-7)


class TestMultinomial:
    def test_reduces_to_binary(self):
        X, w = _rand(64, 6, seed=7)
        y = (np.random.default_rng(8).uniform(size=(64,)) < 0.5).astype(np.float32)
        m = MultinomialLogisticGradient(2)
        b = LogisticGradient()
        gs_m, ls_m, c_m = m.batch_sums(X, y, w)
        gs_b, ls_b, c_b = b.batch_sums(X, y, w)
        np.testing.assert_allclose(gs_m, gs_b, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(ls_m, ls_b, rtol=1e-3, atol=1e-4)

    def test_gradient_is_autodiff_of_loss(self):
        import jax
        import jax.numpy as jnp

        K, d, n = 4, 5, 32
        r = np.random.default_rng(9)
        X = r.normal(size=(n, d)).astype(np.float32)
        y = r.integers(0, K, size=(n,)).astype(np.float32)
        w = r.normal(size=((K - 1) * d,)).astype(np.float32)
        m = MultinomialLogisticGradient(K)

        def total_loss(w_):
            W = w_.reshape(K - 1, d)
            logits = jnp.concatenate(
                [jnp.zeros((n, 1)), jnp.asarray(X) @ W.T], axis=-1
            )
            lp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(
                jnp.take_along_axis(lp, jnp.asarray(y, jnp.int32)[:, None], axis=-1)
            )

        auto = jax.grad(total_loss)(np.asarray(w))
        gs, ls, c = m.batch_sums(X, y, w)
        np.testing.assert_allclose(auto, gs, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(float(total_loss(np.asarray(w))), float(ls), rtol=1e-4)


#: ``Gradient.window_sums``' window of m = 100 of n = 333 rows (neither a
#: multiple of anything): where it starts, and where it has to end up
WINDOW_STARTS = {"first_row": 0, "unaligned_middle": 77, "last_window": 233,
                 "past_the_end": 300}


@pytest.mark.parametrize("with_valid", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("start", sorted(WINDOW_STARTS))
@pytest.mark.parametrize(
    "g", [LeastSquaresGradient(), LogisticGradient(), HingeGradient()],
    ids=lambda g: type(g).__name__)
def test_window_sums_equal_batch_sums_on_the_sliced_rows(g, start, with_valid):
    """The ``sampling="sliced"`` step: exactly the m rows from a traced
    ``start``, the window pushed back in bounds where it would pass the
    end (as ``lax.dynamic_slice`` clamps), ``valid`` cut to the same rows
    and counted.  The contract a window kernel is held to."""
    import jax
    import jax.numpy as jnp

    n, m = 333, 100
    X, w = _rand(n, 16, seed=11)
    r = np.random.default_rng(12)
    y = (r.uniform(size=n) < 0.5).astype(np.float32)
    valid = (r.uniform(size=n) < 0.7) if with_valid else None
    at = WINDOW_STARTS[start]
    gs, ls, c = jax.jit(g.window_sums, static_argnums=4)(
        X, y, w, jnp.int32(at), m, valid)
    lo = min(at, n - m)
    rows = slice(lo, lo + m)
    gs_ref, ls_ref, c_ref = g.batch_sums(
        X[rows], y[rows], w, None if valid is None else valid[rows])
    np.testing.assert_allclose(gs, gs_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ls, ls_ref, rtol=1e-5)
    assert float(c) == float(c_ref) == (valid[rows].sum() if with_valid
                                        else m)
