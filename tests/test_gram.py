"""GramLeastSquaresGradient (sufficient-statistics path) parity tests.

The bound gradient must reproduce the stock two-pass results exactly (up to
float summation order) for window sums at arbitrary offsets including
partial-block edges and non-block-multiple tails, full-batch sums, the
line-search sweep, and the whole GradientDescent / LBFGS trajectories —
and must fall back (warning once) whenever it is called with anything but
the bound dataset.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sgd import GradientDescent, LBFGS, SimpleUpdater, SquaredL2Updater
from tpu_sgd.ops.gradients import LeastSquaresGradient
from tpu_sgd.ops.gram import GramLeastSquaresGradient


def _data(rng, n=1000, d=16, noise=0.1):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, size=(d,)).astype(np.float32)
    y = (X @ w + noise * rng.normal(size=(n,))).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(w)


@pytest.mark.parametrize("block", [64, 100, 1000, 2048])
@pytest.mark.parametrize("start,m", [(0, 100), (37, 200), (123, 64),
                                     (900, 100), (999, 1), (0, 1000)])
def test_window_sums_parity(rng, block, start, m):
    # n=1000 is NOT a multiple of 64 or 2048 -> exercises the tail backoff
    X, y, w = _data(rng)
    base = LeastSquaresGradient()
    gram = GramLeastSquaresGradient.build(X, y, block_rows=block)
    g0, l0, c0 = base.window_sums(X, y, w, jnp.int32(start), m)
    g1, l1, c1 = gram.window_sums(X, y, w, jnp.int32(start), m)
    # Absolute tolerance scales with the f32 prefix cancellation: results
    # are differences of [0, r) accumulations, so tiny windows (m=1) carry
    # the full-prefix rounding noise while their own magnitude is O(1).
    atol = 2e-3 if m >= 64 else 2e-2
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-4, atol=atol)
    assert float(l1) == pytest.approx(float(l0), rel=1e-3, abs=atol)
    assert float(c1) == float(c0) == min(m, 1000)


def test_window_start_clamp_matches_stock(rng):
    X, y, w = _data(rng, n=500)
    base = LeastSquaresGradient()
    gram = GramLeastSquaresGradient.build(X, y, block_rows=128)
    # out-of-range start: stock dynamic_slice clamps to n - m
    g0, l0, _ = base.window_sums(X, y, w, jnp.int32(490), 100)
    g1, l1, _ = gram.window_sums(X, y, w, jnp.int32(490), 100)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-4, atol=2e-3)
    assert float(l1) == pytest.approx(float(l0), rel=1e-3, abs=2e-3)


def test_batch_sums_and_loss_sweep_parity(rng):
    X, y, w = _data(rng)
    base = LeastSquaresGradient()
    gram = GramLeastSquaresGradient.build(X, y, block_rows=100)
    g0, l0, c0 = base.batch_sums(X, y, w)
    g1, l1, c1 = gram.batch_sums(X, y, w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-4, atol=2e-3)
    assert float(l1) == pytest.approx(float(l0), rel=2e-4)
    assert float(c1) == float(c0)

    W = jnp.stack([w, 0.5 * w, jnp.zeros_like(w)])
    s0, n0 = base.loss_sweep(X, y, W)
    s1, n1 = gram.loss_sweep(X, y, W)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=2e-4, atol=2e-3)
    assert float(n1) == float(n0)


def test_masked_paths_delegate_exactly(rng):
    X, y, w = _data(rng, n=300)
    base = LeastSquaresGradient()
    gram = GramLeastSquaresGradient.build(X, y, block_rows=64)
    mask = jnp.asarray((np.arange(300) % 2 == 0).astype(np.float32))
    g0, l0, c0 = base.batch_sums(X, y, w, mask)
    g1, l1, c1 = gram.batch_sums(X, y, w, mask)
    # delegation is the SAME code path -> bitwise equal
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    assert float(l1) == float(l0) and float(c1) == float(c0)

    valid = jnp.asarray(np.ones((300,), np.float32))
    g0, l0, c0 = base.window_sums(X, y, w, jnp.int32(10), 50, valid=valid)
    g1, l1, c1 = gram.window_sums(X, y, w, jnp.int32(10), 50, valid=valid)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))


def test_unbound_matrix_falls_back_with_warning(rng):
    X, y, w = _data(rng, n=200)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=64)
    X2, y2, _ = _data(rng, n=150)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        g1, l1, c1 = gram.window_sums(X2, y2, w, jnp.int32(0), 50)
        gram.window_sums(X2, y2, w, jnp.int32(0), 50)  # warns only once
    assert sum(issubclass(r.category, RuntimeWarning) for r in rec) == 1
    g0, l0, c0 = LeastSquaresGradient().window_sums(
        X2, y2, w, jnp.int32(0), 50)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))


def test_gd_trajectory_parity_sliced(rng):
    X, y, _ = _data(rng, n=4096, d=24)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=512)

    def run(gradient):
        opt = (GradientDescent(gradient, SimpleUpdater())
               .set_step_size(0.2).set_num_iterations(30)
               .set_mini_batch_fraction(0.1).set_sampling("sliced")
               .set_seed(7).set_convergence_tol(0.0))
        return opt.optimize_with_history((X, y), jnp.zeros((24,)))

    w0, h0 = run(LeastSquaresGradient())
    w1, h1 = run(gram)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=5e-4, atol=5e-4)


def test_gd_trajectory_parity_full_batch(rng):
    X, y, _ = _data(rng, n=1500, d=12)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=256)

    def run(gradient):
        opt = (GradientDescent(gradient, SquaredL2Updater())
               .set_step_size(0.3).set_num_iterations(25)
               .set_reg_param(0.01).set_seed(3))
        return opt.optimize_with_history((X, y), jnp.zeros((12,)))

    w0, h0 = run(LeastSquaresGradient())
    w1, h1 = run(gram)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=5e-4, atol=5e-4)


def test_lbfgs_matches_stock_and_accelerated_cost(rng):
    X, y, _ = _data(rng, n=2000, d=20)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=256)

    def run(gradient):
        opt = LBFGS(gradient, SquaredL2Updater(), reg_param=0.01,
                    max_num_iterations=15)
        return opt.optimize_with_history((X, y), jnp.zeros((20,)))

    w0, h0 = run(LeastSquaresGradient())
    w1, h1 = run(gram)
    assert float(h1[-1]) == pytest.approx(float(h0[-1]), rel=1e-3)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=1e-2, atol=1e-3)


def test_bf16_data_close_to_f32_truth(rng):
    """With bf16 data the gram path computes at HIGHEST precision in f32
    internally (the matmul_dtype bandwidth contract would amplify bf16
    rounding by prefix/window magnitude — see the module docstring), so it
    must track the f32 truth OF THE bf16 DATA tightly — tighter than the
    stock bf16 two-pass tracks it."""
    X, y, w = _data(rng, n=2048, d=16)
    Xb = X.astype(jnp.bfloat16)
    Xf = np.asarray(Xb, np.float32)  # the bf16 data, exactly, in f32
    gram = GramLeastSquaresGradient.build(Xb, y, block_rows=256)
    g1, l1, c1 = gram.window_sums(Xb, y, w, jnp.int32(100), 512)
    win = slice(100, 612)
    resid = Xf[win] @ np.asarray(w) - np.asarray(y)[win]
    g_truth = Xf[win].T @ resid
    l_truth = 0.5 * float(resid @ resid)
    np.testing.assert_allclose(np.asarray(g1, np.float32), g_truth,
                               rtol=1e-3, atol=5e-2)
    assert float(l1) == pytest.approx(l_truth, rel=1e-3)


def test_build_rejects_narrow_stats_and_empty(rng):
    X, y, _ = _data(rng, n=64)
    with pytest.raises(ValueError, match="f32"):
        GramLeastSquaresGradient.build(X, y, stats_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="non-empty"):
        GramLeastSquaresGradient.build(jnp.zeros((0, 4)), jnp.zeros((0,)))


def test_int_features_build_and_match(rng):
    Xi = (rng.integers(0, 2, size=(500, 8))).astype(np.int32)
    y = rng.normal(size=(500,)).astype(np.float32)
    w = rng.normal(size=(8,)).astype(np.float32)
    gram = GramLeastSquaresGradient.build(Xi, y, block_rows=128)
    # build() coerces int features to f32 internally; the accelerated path
    # is reached through the GramData bundle (identity binding means a
    # caller-side re-cast can never silently alias)
    Xf = jnp.asarray(Xi).astype(jnp.float32)
    g1, l1, c1 = gram.window_sums(gram.data, jnp.asarray(y), w,
                                  jnp.int32(3), 200)
    g0, l0, c0 = LeastSquaresGradient().window_sums(
        Xf, jnp.asarray(y), w, jnp.int32(3), 200)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-4, atol=2e-3)


def test_gd_set_sufficient_stats_flag(rng):
    X, y, _ = _data(rng, n=2048, d=16)

    def make(flag):
        opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
               .set_step_size(0.2).set_num_iterations(20)
               .set_mini_batch_fraction(0.25).set_sampling("sliced")
               .set_seed(5).set_convergence_tol(0.0))
        return opt.set_sufficient_stats(flag)

    w0, h0 = make(False).optimize_with_history((X, y), jnp.zeros((16,)))
    opt = make(True)
    w1, h1 = opt.optimize_with_history((X, y), jnp.zeros((16,)))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)
    assert opt._gram_entry is not None
    # identity cache: same arrays -> same built gradient; gradient restored
    built = opt._gram_entry[2]
    opt.optimize_with_history((X, y), jnp.zeros((16,)))
    assert opt._gram_entry[2] is built
    assert type(opt.gradient) is LeastSquaresGradient


def test_gd_sufficient_stats_noop_cases(rng):
    from tpu_sgd.ops.gradients import LogisticGradient

    X = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=(256,)) > 0.5).astype(np.float32))
    # non-least-squares gradient: flag must be a no-op
    opt = (GradientDescent(LogisticGradient(), SimpleUpdater())
           .set_num_iterations(3).set_sufficient_stats(True))
    opt.optimize_with_history((X, y), jnp.zeros((8,)))
    assert opt._gram_entry is None
    # bernoulli sub-unit sampling: no gram either
    opt2 = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_num_iterations(3).set_mini_batch_fraction(0.5)
            .set_sufficient_stats(True))
    opt2.optimize_with_history((X, y), jnp.zeros((8,)))
    assert opt2._gram_entry is None


def test_lbfgs_and_owlqn_sufficient_stats_flag(rng):
    from tpu_sgd import OWLQN

    X, y, _ = _data(rng, n=1500, d=12)

    r0 = LBFGS(LeastSquaresGradient(), SquaredL2Updater(), reg_param=0.01,
               max_num_iterations=12).optimize_with_history(
                   (X, y), jnp.zeros((12,)))
    lb = LBFGS(LeastSquaresGradient(), SquaredL2Updater(), reg_param=0.01,
               max_num_iterations=12).set_sufficient_stats(True)
    r1 = lb.optimize_with_history((X, y), jnp.zeros((12,)))
    assert float(r1[1][-1]) == pytest.approx(float(r0[1][-1]), rel=1e-3)
    assert lb._gram_entry is not None

    o0 = OWLQN(LeastSquaresGradient(), reg_param=1e-3,
               max_num_iterations=12).optimize_with_history(
                   (X, y), jnp.zeros((12,)))
    ow = OWLQN(LeastSquaresGradient(), reg_param=1e-3,
               max_num_iterations=12).set_sufficient_stats(True)
    o1 = ow.optimize_with_history((X, y), jnp.zeros((12,)))
    assert float(o1[1][-1]) == pytest.approx(float(o0[1][-1]), rel=1e-3)
    assert ow._gram_entry is not None


def test_gramdata_argument_path_matches_plain(rng):
    """Stats passed as the X argument (GramData pytree — the big-slab
    plumbing) must give the same results as plain-array binding, and must
    flow through a jitted make_run unchanged."""
    from tpu_sgd.config import SGDConfig
    from tpu_sgd.optimize.gradient_descent import make_run

    X, y, w = _data(rng, n=2048, d=16)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=256)
    g0, l0, c0 = gram.window_sums(X, y, w, jnp.int32(100), 512)
    g1, l1, c1 = gram.window_sums(gram.data, y, w, jnp.int32(100), 512)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    assert float(l1) == float(l0)

    cfg = SGDConfig(step_size=0.2, num_iterations=10,
                    mini_batch_fraction=0.25, convergence_tol=0.0,
                    sampling="sliced")
    run = jax.jit(make_run(gram, SimpleUpdater(), cfg))
    w1, h1, nr1 = run(jnp.zeros((16,)), gram.data, y, cfg.hyper())
    run0 = jax.jit(make_run(LeastSquaresGradient(), SimpleUpdater(), cfg))
    w0, h0, nr0 = run0(jnp.zeros((16,)), X, y, cfg.hyper())
    np.testing.assert_allclose(np.asarray(h1)[:int(nr1)],
                               np.asarray(h0)[:int(nr0)],
                               rtol=5e-4, atol=5e-4)


def test_gramdata_rejects_indexing():
    import pytest as _pytest

    X = jnp.ones((64, 4))
    y = jnp.ones((64,))
    gram = GramLeastSquaresGradient.build(X, y, block_rows=16)
    with _pytest.raises(TypeError, match="sliced"):
        gram.data[0]


def test_model_level_sufficient_stats(rng):
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(1024, 10)).astype(np.float32)
    w = rng.uniform(-1, 1, size=(10,)).astype(np.float32)
    y = X @ w + 0.05 * rng.normal(size=(1024,)).astype(np.float32)
    m0 = LinearRegressionWithSGD.train((X, y), num_iterations=40,
                                       step_size=0.3, intercept=True)
    m1 = LinearRegressionWithSGD.train((X, y), num_iterations=40,
                                       step_size=0.3, intercept=True,
                                       sufficient_stats=True)
    np.testing.assert_allclose(np.asarray(m1.weights),
                               np.asarray(m0.weights),
                               rtol=1e-3, atol=1e-3)
    assert float(m1.intercept) == pytest.approx(float(m0.intercept),
                                                abs=1e-3)


def test_same_shape_different_matrix_never_binds(rng):
    """Review finding: a DIFFERENT matrix with the same shape/dtype must
    not silently train against stale statistics — identity binding."""
    X, y, w = _data(rng, n=400, d=8)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=128)
    X2 = jnp.asarray(np.asarray(X) + 1.0)  # same shape, same dtype
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        g1, l1, _ = gram.window_sums(X2, y, w, jnp.int32(0), 200)
    assert any(issubclass(r.category, RuntimeWarning) for r in rec)
    g0, l0, _ = LeastSquaresGradient().window_sums(
        X2, y, w, jnp.int32(0), 200)
    # fell back to the stock path ON X2 (not X's stats): bitwise equal
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    assert float(l1) == float(l0)


def test_prebuilt_gram_routes_gramdata_through_optimizer(rng):
    """Passing a user-built gram gradient with its bound matrix must
    accelerate (GramData routed into the traced program), not fall back."""
    X, y, _ = _data(rng, n=2048, d=16)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=256)
    opt = (GradientDescent(gram, SimpleUpdater())
           .set_step_size(0.2).set_num_iterations(10)
           .set_mini_batch_fraction(0.25).set_sampling("sliced")
           .set_convergence_tol(0.0))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        w1, h1 = opt.optimize_with_history((X, y), jnp.zeros((16,)))
    assert not any(issubclass(r.category, RuntimeWarning) for r in rec)
    opt0 = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_step_size(0.2).set_num_iterations(10)
            .set_mini_batch_fraction(0.25).set_sampling("sliced")
            .set_convergence_tol(0.0))
    w0, h0 = opt0.optimize_with_history((X, y), jnp.zeros((16,)))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)


def test_dp_mesh_sufficient_stats_trajectory_parity(rng):
    """Gram over the 1-D data mesh (config 4's 8-way DP shape) must match
    the stock mesh trajectory — per-shard prefix stats, same psums."""
    from tpu_sgd import data_mesh

    mesh = data_mesh()
    X, y, _ = _data(rng, n=4096, d=24)  # divides the 8-way axis

    def run(flag):
        opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
               .set_step_size(0.2).set_num_iterations(25)
               .set_mini_batch_fraction(0.2).set_sampling("sliced")
               .set_seed(11).set_convergence_tol(0.0)
               .set_mesh(mesh).set_sufficient_stats(flag))
        return opt, opt.optimize_with_history((X, y), jnp.zeros((24,)))

    _, (w0, h0) = run(False)
    opt1, (w1, h1) = run(True)
    assert opt1._gram_dp_entry is not None  # the dp path actually engaged
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=5e-4, atol=5e-4)
    # identity cache: re-optimize on the same arrays reuses the stats
    stats0 = opt1._gram_dp_entry[3]
    opt1.optimize_with_history((X, y), jnp.zeros((24,)))
    assert opt1._gram_dp_entry[3] is stats0


def test_dp_mesh_full_batch_and_padding_fallback(rng):
    from tpu_sgd import data_mesh

    mesh = data_mesh()
    # full batch, divisible
    X, y, _ = _data(rng, n=2048, d=12)
    o0 = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
          .set_step_size(0.3).set_num_iterations(15).set_reg_param(0.01)
          .set_mesh(mesh))
    w0, h0 = o0.optimize_with_history((X, y), jnp.zeros((12,)))
    o1 = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
          .set_step_size(0.3).set_num_iterations(15).set_reg_param(0.01)
          .set_mesh(mesh).set_sufficient_stats(True))
    w1, h1 = o1.optimize_with_history((X, y), jnp.zeros((12,)))
    assert o1._gram_dp_entry is not None
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)

    # NON-divisible row count: padded -> valid mask -> gram must fall back
    Xp, yp, _ = _data(rng, n=2049, d=12)
    o2 = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
          .set_step_size(0.3).set_num_iterations(8).set_reg_param(0.01)
          .set_mesh(mesh).set_sufficient_stats(True))
    w2, h2 = o2.optimize_with_history((Xp, yp), jnp.zeros((12,)))
    assert o2._gram_dp_entry is None  # fell back to the stock mesh path
    o3 = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
          .set_step_size(0.3).set_num_iterations(8).set_reg_param(0.01)
          .set_mesh(mesh))
    w3, h3 = o3.optimize_with_history((Xp, yp), jnp.zeros((12,)))
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(h3))


def test_unbound_executor_is_silent_on_plain_arrays(rng):
    """An unbound executor (data=None, the DP-mesh internal) must treat
    plain arrays as stock input with NO warning."""
    X, y, w = _data(rng, n=256, d=8)
    unbound = GramLeastSquaresGradient()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        g1, l1, c1 = unbound.window_sums(X, y, w, jnp.int32(0), 64)
    assert not any(issubclass(r.category, RuntimeWarning) for r in rec)
    g0, l0, c0 = LeastSquaresGradient().window_sums(
        X, y, w, jnp.int32(0), 64)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))


def test_meshed_listener_warns_sufficient_stats_not_applied(rng):
    from tpu_sgd import data_mesh
    from tpu_sgd.utils.events import CollectingListener

    mesh = data_mesh()
    X, y, _ = _data(rng, n=512, d=8)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_num_iterations(2).set_mesh(mesh)
           .set_sufficient_stats(True)
           .set_listener(CollectingListener()))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        opt.optimize_with_history((X, y), jnp.zeros((8,)))
    assert any("sufficient_stats is not applied" in str(r.message)
               for r in rec)


def test_dp_stats_builder_memoized(rng):
    from tpu_sgd import data_mesh
    from tpu_sgd.parallel.gram_parallel import _stats_builder

    mesh = data_mesh()
    before = _stats_builder.cache_info().currsize

    def run(Xr, yr):
        opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
               .set_num_iterations(2).set_mesh(mesh)
               .set_sufficient_stats(True))
        opt.optimize_with_history((Xr, yr), jnp.zeros((8,)))

    X1, y1, _ = _data(rng, n=512, d=8)
    X2, y2, _ = _data(rng, n=512, d=8)  # different data, same shape
    run(X1, y1)
    run(X2, y2)
    # one builder serves both datasets (jit caches per shape underneath)
    assert _stats_builder.cache_info().currsize <= before + 1


def test_odd_dimensions_and_blocks(rng):
    """Nothing in the math requires lane-friendly shapes: odd d, odd n,
    odd block size must all agree with the stock path."""
    n, d = 777, 37
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1, 1, size=(d,)).astype(np.float32))
    y = jnp.asarray(
        (np.asarray(X) @ np.asarray(w)
         + 0.1 * rng.normal(size=(n,))).astype(np.float32))
    gram = GramLeastSquaresGradient.build(X, y, block_rows=53)
    for start, m in [(0, 100), (51, 53), (700, 77), (123, 1)]:
        g0, l0, c0 = LeastSquaresGradient().window_sums(
            X, y, w, jnp.int32(start), m)
        g1, l1, c1 = gram.window_sums(X, y, w, jnp.int32(start), m)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                   rtol=2e-4, atol=2e-2)
        assert float(c1) == float(c0)


def test_f64_data_keeps_f64_stats():
    """f64 data (jax_enable_x64) must get f64 statistics by default, not a
    silent f32 downgrade relative to the stock f64 path.  x64 is a global
    switch, so this runs in a subprocess."""
    import os
    import subprocess
    import sys

    code = (
        "import os; os.environ['XLA_FLAGS']=''; "
        "import jax; jax.config.update('jax_platforms','cpu'); "
        "jax.config.update('jax_enable_x64', True); "
        "import jax.numpy as jnp, numpy as np; "
        "from tpu_sgd.ops.gram import GramLeastSquaresGradient; "
        "X = jnp.asarray(np.random.default_rng(0).normal(size=(64,4))); "
        "y = jnp.asarray(np.random.default_rng(1).normal(size=(64,))); "
        "assert X.dtype == jnp.float64, X.dtype; "
        "g = GramLeastSquaresGradient.build(X, y, block_rows=16); "
        "assert g.data.PG.dtype == jnp.float64, g.data.PG.dtype; "
        "gs = GramLeastSquaresGradient.build_streamed("
        "    np.asarray(X), np.asarray(y), block_rows=16); "
        "assert gs.data.Pb.dtype == jnp.float64, gs.data.Pb.dtype; "
        "np.testing.assert_allclose(np.asarray(gs.data.Pb), "
        "    np.asarray(g.data.Pb), rtol=1e-12); "
        "print('OK')"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_gram_composes_with_listener_and_checkpoint(rng, tmp_path):
    """Single-device observed path (listener / checkpoint) with the
    sufficient-stats flag: the stepwise driver receives GramData and must
    produce the same trajectory as the stock stepwise run."""
    from tpu_sgd.utils.checkpoint import CheckpointManager
    from tpu_sgd.utils.events import CollectingListener

    X, y, _ = _data(rng, n=1024, d=8)

    def run(flag, subdir):
        listener = CollectingListener()
        opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
               .set_step_size(0.2).set_num_iterations(6)
               .set_mini_batch_fraction(0.5).set_sampling("sliced")
               .set_convergence_tol(0.0)
               .set_listener(listener)
               .set_checkpoint(CheckpointManager(str(tmp_path / subdir)), 2)
               .set_sufficient_stats(flag))
        w, h = opt.optimize_with_history((X, y), jnp.zeros((8,)))
        return w, h, listener

    w0, h0, _ = run(False, "a")
    w1, h1, lis = run(True, "b")
    assert len(lis.iterations) == 6
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=5e-4, atol=5e-4)


# ---- streamed / virtual (beyond-HBM) mode --------------------------------

def test_build_streamed_matches_resident_build(rng):
    """Chunked host streaming must produce the SAME statistics as the
    resident build on the block-truncated dataset."""
    X = rng.normal(size=(1000, 12)).astype(np.float32)
    y = (X @ rng.uniform(-1, 1, 12).astype(np.float32)).astype(np.float32)
    gs = GramLeastSquaresGradient.build_streamed(X, y, block_rows=64,
                                                 batch_rows=200)
    n_use = (1000 // 64) * 64  # 960
    g0 = GramLeastSquaresGradient.build(X[:n_use], y[:n_use], block_rows=64)
    assert gs.data.X is None
    assert gs.data.shape == (n_use, 12)
    np.testing.assert_allclose(np.asarray(gs.data.PG),
                               np.asarray(g0.data.PG), rtol=1e-6, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gs.data.Pb),
                               np.asarray(g0.data.Pb), rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs.data.G_tot),
                               np.asarray(g0.data.G_tot),
                               rtol=1e-6, atol=1e-3)


def test_aligned_window_math_vs_numpy(rng):
    X = rng.normal(size=(512, 8)).astype(np.float32)
    w = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ w + 0.1 * rng.normal(size=512)).astype(np.float32)
    B = 64
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=B)
    m = 130  # rounds to 2 blocks = 128 rows
    start = 70  # floors to block 1 -> rows [64, 192)
    g1, l1, c1 = gram.window_sums(gram.data, jnp.asarray(y), jnp.asarray(w),
                                  jnp.int32(start), m)
    rows = slice(64, 192)
    r = X[rows] @ w - y[rows]
    np.testing.assert_allclose(np.asarray(g1), X[rows].T @ r,
                               rtol=1e-4, atol=1e-2)
    assert float(l1) == pytest.approx(0.5 * float(r @ r), rel=1e-4)
    assert float(c1) == 128


def test_virtual_full_batch_matches_stock_on_truncated(rng):
    X = rng.normal(size=(960, 10)).astype(np.float32)
    wt = rng.uniform(-1, 1, 10).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=960)).astype(np.float32)
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=64)

    opt_v = GradientDescent(gram, SquaredL2Updater()) \
        .set_step_size(0.3).set_num_iterations(20).set_reg_param(0.01)
    wv, hv = opt_v.optimize_with_history((gram.data, y), np.zeros(10))
    opt_s = GradientDescent(LeastSquaresGradient(), SquaredL2Updater()) \
        .set_step_size(0.3).set_num_iterations(20).set_reg_param(0.01)
    ws, hs = opt_s.optimize_with_history((X, y), np.zeros(10))
    np.testing.assert_allclose(np.asarray(hv), np.asarray(hs),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(wv), np.asarray(ws),
                               rtol=5e-4, atol=5e-4)


def test_virtual_sliced_gd_converges(rng):
    X = rng.normal(size=(8192, 16)).astype(np.float32)
    wt = rng.uniform(-1, 1, 16).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=8192)).astype(np.float32)
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=256)
    opt = (GradientDescent(gram, SimpleUpdater())
           .set_step_size(0.3).set_num_iterations(60)
           .set_mini_batch_fraction(0.125).set_sampling("sliced")
           .set_convergence_tol(0.0))
    w, hist = opt.optimize_with_history((gram.data, y), np.zeros(16))
    werr = float(np.linalg.norm(np.asarray(w) - wt) / np.linalg.norm(wt))
    assert werr < 0.05, werr
    assert hist[-1] < hist[0] * 0.1


def test_virtual_lbfgs_full_batch(rng):
    X = rng.normal(size=(2048, 12)).astype(np.float32)
    wt = rng.uniform(-1, 1, 12).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=2048)).astype(np.float32)
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=128)
    opt = LBFGS(gram, SquaredL2Updater(), reg_param=0.001,
                max_num_iterations=15)
    w, hist = opt.optimize_with_history((gram.data, y), np.zeros(12))
    werr = float(np.linalg.norm(np.asarray(w) - wt) / np.linalg.norm(wt))
    assert werr < 0.02, werr


def test_virtual_guards(rng):
    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = rng.normal(size=256).astype(np.float32)
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=64)
    # bernoulli sub-unit sampling: clear error
    opt = (GradientDescent(gram, SimpleUpdater())
           .set_num_iterations(2).set_mini_batch_fraction(0.5))
    with pytest.raises(NotImplementedError, match="sliced"):
        opt.optimize((gram.data, y), np.zeros(8))
    # mesh: clear error
    from tpu_sgd import data_mesh
    opt2 = GradientDescent(gram, SimpleUpdater()).set_mesh(data_mesh())
    with pytest.raises(NotImplementedError, match="single-device"):
        opt2.optimize((gram.data, y), np.zeros(8))
    # plain gradient with GramData input: clear error
    opt3 = GradientDescent(LeastSquaresGradient(), SimpleUpdater())
    with pytest.raises(ValueError, match="GramLeastSquaresGradient"):
        opt3.optimize((gram.data, y), np.zeros(8))
    # masked call on virtual data: clear error
    valid = jnp.ones((256,), jnp.float32)
    with pytest.raises(NotImplementedError, match="virtual"):
        gram.window_sums(gram.data, jnp.asarray(y), jnp.zeros(8),
                         jnp.int32(0), 64, valid=valid)
    # meshed LBFGS on GramData: clear error
    lb = LBFGS(gram, SquaredL2Updater()).set_mesh(data_mesh())
    with pytest.raises(NotImplementedError, match="unmeshed"):
        lb.optimize_with_history((gram.data, y), np.zeros(8))


def test_resident_aligned_mode(rng):
    """aligned=True on RESIDENT data: same prefix-only math as the
    virtual path — results match the exact sums over the quantized
    window, and converge like the exact mode on i.i.d. data."""
    X, y, w = _data(rng, n=2048, d=16)
    gram = GramLeastSquaresGradient.build(X, y, block_rows=128,
                                          aligned=True)
    g1, l1, c1 = gram.window_sums(X, y, w, jnp.int32(200), 300)
    # start 200 floors to block 1 (128); 300 rows round to 2 blocks (256)
    rows = slice(128, 384)
    Xn, yn = np.asarray(X), np.asarray(y)
    r = Xn[rows] @ np.asarray(w) - yn[rows]
    np.testing.assert_allclose(np.asarray(g1), Xn[rows].T @ r,
                               rtol=1e-4, atol=1e-2)
    assert float(c1) == 256

    opt = (GradientDescent(gram, SimpleUpdater())
           .set_step_size(0.3).set_num_iterations(40)
           .set_mini_batch_fraction(0.25).set_sampling("sliced")
           .set_convergence_tol(0.0))
    wv, hist = opt.optimize_with_history((X, y), jnp.zeros((16,)))
    assert hist[-1] < hist[0] * 0.1


def test_lbfgs_gramdata_with_stock_gradient_clear_error(rng):
    X = jnp.asarray(rng.normal(size=(128, 8)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=128).astype(np.float32))
    gram = GramLeastSquaresGradient.build(X, y, block_rows=32)
    lb = LBFGS(LeastSquaresGradient(), SquaredL2Updater())
    with pytest.raises(ValueError, match="GramLeastSquaresGradient"):
        lb.optimize_with_history((gram.data, y), np.zeros(8))


def test_virtual_gramdata_requires_logical_metadata():
    from tpu_sgd.ops.gram import GramData

    z = jnp.zeros((2, 4, 4))
    with pytest.raises(ValueError, match="logical_shape"):
        GramData(None, z, jnp.zeros((2, 4)), jnp.zeros((2,)),
                 jnp.zeros((4, 4)), jnp.zeros((4,)), jnp.zeros(()), 4)


def test_build_rejects_bad_rank_and_streamed_int_features(rng):
    with pytest.raises(ValueError, match="non-empty"):
        GramLeastSquaresGradient.build(jnp.zeros((8,)), jnp.zeros((8,)))
    # int features through the streamed builder coerce to f32 stats
    Xi = rng.integers(0, 3, size=(256, 6)).astype(np.int32)
    yi = rng.normal(size=256).astype(np.float32)
    g = GramLeastSquaresGradient.build_streamed(Xi, yi, block_rows=64)
    assert g.data.dtype == jnp.float32
    assert g.data.PG.dtype == jnp.float32


def test_gramdata_save_load_round_trip(rng, tmp_path):
    """Statistics persist (streamed builds are expensive) and load back
    VIRTUAL — training from the loaded bundle matches training from the
    original."""
    from tpu_sgd.ops.gram import GramData

    X = rng.normal(size=(512, 8)).astype(np.float32)
    wt = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=512)).astype(np.float32)
    g0 = GramLeastSquaresGradient.build_streamed(X, y, block_rows=64)
    p = str(tmp_path / "stats")
    g0.data.save(p)
    data = GramData.load(p)
    assert data.X is None and data.shape == g0.data.shape
    g1 = GramLeastSquaresGradient(data)

    def run(gg):
        opt = (GradientDescent(gg, SimpleUpdater())
               .set_step_size(0.3).set_num_iterations(20)
               .set_mini_batch_fraction(0.25).set_sampling("sliced"))
        return opt.optimize_with_history((gg.data, y), np.zeros(8))

    w0, h0 = run(g0)
    w1, h1 = run(g1)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=1e-6, atol=1e-6)

    # wrong-class / wrong-version guards
    import json
    meta = json.load(open(p + "/metadata.json"))
    meta["class"] = "SomethingElse"
    json.dump(meta, open(p + "/metadata.json", "w"))
    with pytest.raises(ValueError, match="expected GramData"):
        GramData.load(p)


def test_gram_random_shape_window_parity_sweep(rng):
    """Randomized breadth: arbitrary (n, d, B, start, m) combinations must
    reproduce the stock window sums — catches shape/edge interactions the
    parametrized grid doesn't enumerate."""
    for _ in range(12):
        n = int(rng.integers(40, 1500))
        d = int(rng.integers(2, 40))
        B = int(rng.integers(8, n + 8))
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray(rng.uniform(-1, 1, d).astype(np.float32))
        y = jnp.asarray(
            (np.asarray(X) @ np.asarray(w)
             + 0.1 * rng.normal(size=n)).astype(np.float32))
        gram = GramLeastSquaresGradient.build(X, y, block_rows=B)
        for _ in range(3):
            m = int(rng.integers(1, n + 1))
            start = int(rng.integers(0, n))
            g0, l0, c0 = LeastSquaresGradient().window_sums(
                X, y, w, jnp.int32(start), m)
            g1, l1, c1 = gram.window_sums(X, y, w, jnp.int32(start), m)
            scale = max(1.0, float(jnp.max(jnp.abs(g0))))
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g0), rtol=5e-4,
                atol=5e-3 * scale,
                err_msg=f"n={n} d={d} B={B} start={start} m={m}")
            assert float(c1) == float(c0)


def test_virtual_gramdata_with_listener_and_checkpoint(rng, tmp_path):
    """Beyond-HBM stats + the observed per-iteration path: the stepwise
    driver must accept a virtual GramData as X (listener events fire,
    checkpoints save/restore weights)."""
    from tpu_sgd.utils.checkpoint import CheckpointManager
    from tpu_sgd.utils.events import CollectingListener

    X = rng.normal(size=(512, 8)).astype(np.float32)
    wt = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=512)).astype(np.float32)
    gram = GramLeastSquaresGradient.build_streamed(X, y, block_rows=64)
    listener = CollectingListener()
    opt = (GradientDescent(gram, SimpleUpdater())
           .set_step_size(0.3).set_num_iterations(6)
           .set_mini_batch_fraction(0.25).set_sampling("sliced")
           .set_convergence_tol(0.0)
           .set_listener(listener)
           .set_checkpoint(CheckpointManager(str(tmp_path / "ck")), 2))
    w, hist = opt.optimize_with_history((gram.data, y), np.zeros(8))
    assert len(listener.iterations) == 6
    assert len(hist) == 6 and hist[-1] < hist[0]


def test_feature_scaling_composes_with_sufficient_stats(rng):
    """GLM feature scaling rescales the training matrix before the
    optimizer sees it; the gram substitution must build on the SCALED
    matrix and produce the same model as the unaccelerated scaled run."""
    from tpu_sgd import LinearRegressionWithLBFGS

    X = (rng.normal(size=(1024, 12)) * np.logspace(0, 3, 12)).astype(
        np.float32)
    wt = (rng.uniform(-1, 1, 12) / np.logspace(0, 3, 12)).astype(np.float32)
    y = (X @ wt + 0.01 * rng.normal(size=1024)).astype(np.float32)
    m0 = LinearRegressionWithLBFGS.train((X, y), feature_scaling=True,
                                         intercept=True)
    m1 = LinearRegressionWithLBFGS.train((X, y), feature_scaling=True,
                                         intercept=True,
                                         sufficient_stats=True)
    np.testing.assert_allclose(np.asarray(m1.weights),
                               np.asarray(m0.weights), rtol=1e-3,
                               atol=1e-6)


def test_unbound_gram_gradient_runs_stock_in_optimizers(rng):
    """ADVICE r3 (medium): an UNBOUND ``GramLeastSquaresGradient(data=None)``
    — the documented DP-mesh constructor mode — handed to GradientDescent,
    LBFGS, or OWLQN with a plain matrix must fall through to the stock
    path bitwise, not crash the gram-substitution identity check with an
    AttributeError on ``None.X``."""
    from tpu_sgd.optimize.owlqn import OWLQN

    X, y, _ = _data(rng, n=256, d=8)
    w0 = jnp.zeros((8,))

    def gd(gradient):
        opt = (GradientDescent(gradient, SimpleUpdater())
               .set_step_size(0.2).set_num_iterations(8)
               .set_convergence_tol(0.0))
        return opt.optimize_with_history((X, y), w0)

    ws, hs = gd(LeastSquaresGradient())
    wu, hu = gd(GramLeastSquaresGradient())
    np.testing.assert_array_equal(np.asarray(wu), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(hu), np.asarray(hs))

    ws, hs = LBFGS(LeastSquaresGradient()).set_max_num_iterations(
        5).optimize_with_history((X, y), w0)
    wu, hu = LBFGS(GramLeastSquaresGradient()).set_max_num_iterations(
        5).optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(wu), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(hu), np.asarray(hs))

    wu, hu = OWLQN(GramLeastSquaresGradient(), reg_param=0.01,
                   max_num_iterations=5).optimize_with_history((X, y), w0)
    assert np.all(np.isfinite(np.asarray(wu))) and len(hu) >= 1


@pytest.mark.parametrize("form", ["prefix", "totals"])
def test_release_sufficient_stats_frees_cache(rng, form):
    """``release_sufficient_stats`` drops the identity-cached bundles (and
    gram-keyed compiled runners); the next run rebuilds and reproduces the
    same trajectory.  Sliced windows cache their prefix form; a full batch
    (PR 41) builds its totals anew every fit and caches nothing but its one
    unbound executor's runner."""
    X, y, _ = _data(rng, n=512, d=8)

    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(0.2).set_num_iterations(6)
           .set_convergence_tol(0.0).set_sufficient_stats(True))
    if form == "prefix":
        opt.set_mini_batch_fraction(0.5).set_sampling("sliced")
    w1, h1 = opt.optimize_with_history((X, y), jnp.zeros((8,)))
    assert (opt._gram_entry is not None) == (form == "prefix")
    assert (opt._totals_gradient is not None) == (form == "totals")
    assert any(isinstance(part, GramLeastSquaresGradient)
               for k in opt._run_cache for part in k)
    opt.release_sufficient_stats()
    assert opt._gram_entry is None and opt._gram_dp_entry is None
    assert opt._totals_gradient is None
    assert not any(
        isinstance(part, GramLeastSquaresGradient)
        for k in opt._run_cache for part in k
    )
    w2, h2 = opt.optimize_with_history((X, y), jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w1))

    lb = (LBFGS(LeastSquaresGradient()).set_max_num_iterations(5)
          .set_sufficient_stats(True))
    lb.optimize_with_history((X, y), jnp.zeros((8,)))
    assert lb._gram_entry is not None
    lb.release_sufficient_stats()
    assert lb._gram_entry is None


# ---- streamed statistics composed with the data mesh (round 4) -----------

def test_build_streamed_sharded_stats_match_per_shard_resident(rng):
    """Each shard's streamed-from-host statistics must equal the resident
    build of that shard's (block-truncated) row slice — uneven row counts
    drop the n % k remainder plus per-shard tails, like the single-device
    build_streamed."""
    from tpu_sgd import data_mesh
    from tpu_sgd.parallel.gram_parallel import (
        build_streamed_sharded_gram_stats,
    )

    mesh = data_mesh()
    k = mesh.shape["data"]
    n, d, B = k * 300 + 5, 6, 64
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    stats, Bout, n_used = build_streamed_sharded_gram_stats(
        mesh, X, y, block_rows=B, batch_rows=128)
    n_local = n // k
    assert Bout == B and n_used == (n_local // B) * B
    PG, Pb, _, Gt, bt, yyt = (np.asarray(s) for s in stats)
    for i in range(k):
        s = i * n_local
        g = GramLeastSquaresGradient.build(
            X[s:s + n_used], y[s:s + n_used], block_rows=B)
        np.testing.assert_allclose(PG[i], np.asarray(g.data.PG),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(Pb[i], np.asarray(g.data.Pb),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(Gt[i], np.asarray(g.data.G_tot),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(yyt[i], float(g.data.yy_tot),
                                   rtol=1e-5)


def test_sharded_build_rejects_dataless_mesh(rng):
    """A mesh WITHOUT a 'data' axis must raise the intended
    NotImplementedError, not a bare KeyError from reading
    mesh.shape['data'] before the axes check (ADVICE r4)."""
    import jax
    from jax.sharding import Mesh

    from tpu_sgd.parallel.gram_parallel import (
        build_streamed_sharded_gram_stats,
    )
    from tpu_sgd.parallel.mesh import MODEL_AXIS

    mesh = Mesh(np.array(jax.devices()[:2]), (MODEL_AXIS,))
    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    with pytest.raises(NotImplementedError, match="1-D 'data' mesh"):
        build_streamed_sharded_gram_stats(mesh, X, y, block_rows=16)


def test_streamed_stats_mesh_matches_resident_aligned_dp(rng):
    """Meshed set_streamed_stats (per-shard VIRTUAL stats built from host
    row streams, zero rows on device) must reproduce the meshed RESIDENT
    aligned-gram trajectory: same per-shard block-floored windows, same
    statistics math (VERDICT r3 #2)."""
    from tpu_sgd import data_mesh

    mesh = data_mesh()
    k = mesh.shape["data"]
    n, d, B = k * 512, 8, 64  # divisible everywhere: no truncation
    X = rng.normal(size=(n, d)).astype(np.float32)
    wt = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ wt + 0.05 * rng.normal(size=n)).astype(np.float32)

    def mk():
        return (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
                .set_step_size(0.3).set_num_iterations(20)
                .set_mini_batch_fraction(0.25).set_sampling("sliced")
                .set_convergence_tol(0.0).set_seed(9).set_mesh(mesh)
                .set_gram_options(block_rows=B))

    opt_v = mk().set_streamed_stats(True)
    w_v, h_v = opt_v.optimize_with_history((X, y), jnp.zeros((d,)))
    assert opt_v._streamed_gram_dp_entry is not None

    opt_r = mk().set_sufficient_stats(True).set_gram_options(aligned=True)
    w_r, h_r = opt_r.optimize_with_history((X, y), jnp.zeros((d,)))
    assert opt_r._gram_dp_entry is not None

    np.testing.assert_allclose(np.asarray(h_v), np.asarray(h_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w_v), np.asarray(w_r),
                               rtol=1e-5, atol=1e-6)
    assert h_v[-1] < h_v[0]  # and it actually optimizes


def test_streamed_stats_mesh_build_is_identity_cached(rng):
    from tpu_sgd import data_mesh

    mesh = data_mesh()
    n, d = 8 * 128, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_num_iterations(3).set_convergence_tol(0.0)
           .set_mesh(mesh).set_streamed_stats(True, block_rows=32))
    opt.optimize((X, y), jnp.zeros((d,)))
    entry1 = opt._streamed_gram_dp_entry
    opt.optimize((X, y), jnp.zeros((d,)))
    assert opt._streamed_gram_dp_entry is entry1  # no rebuild
    opt.release_sufficient_stats()
    assert opt._streamed_gram_dp_entry is None


# ---- resumable streamed build (round 5: VERDICT r4 #4) ---------------------

def test_build_streamed_resumable_bitwise(rng, tmp_path):
    """A streamed build killed after chunk j must resume from its
    high-water block and produce BITWISE-identical statistics — RDD
    lineage replay semantics for the one expensive pass (a build that
    took 278 s on the round-5 hardware restarts from zero otherwise)."""
    from tpu_sgd.ops import gram as gram_mod

    n, d, B = 1000, 6, 32
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)

    ref = GramLeastSquaresGradient.build_streamed(
        X, y, block_rows=B, batch_rows=128)

    # kill the build partway: the 3rd per-chunk prefix computation dies
    resume_dir = str(tmp_path / "ckpt")
    calls = {"n": 0}
    real = gram_mod._chunk_prefix

    def dying(*args):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("simulated transfer failure")
        return real(*args)

    gram_mod._chunk_prefix = dying
    try:
        with pytest.raises(RuntimeError, match="transfer failure"):
            GramLeastSquaresGradient.build_streamed(
                X, y, block_rows=B, batch_rows=128,
                resume_dir=resume_dir)
    finally:
        gram_mod._chunk_prefix = real
    import json
    import os

    with open(os.path.join(resume_dir, "meta.json")) as f:
        meta = json.load(f)
    assert 0 < meta["high_water_rows"] < (n // B) * B  # mid-pass state

    resumed = GramLeastSquaresGradient.build_streamed(
        X, y, block_rows=B, batch_rows=128, resume_dir=resume_dir)
    for leaf in ("PG", "Pb", "Pyy", "G_tot", "b_tot", "yy_tot"):
        np.testing.assert_array_equal(
            np.asarray(getattr(resumed.data, leaf)),
            np.asarray(getattr(ref.data, leaf)), err_msg=leaf)
    assert not os.path.exists(resume_dir)  # finalized: parts cleaned up


def test_build_streamed_resume_rejects_mismatched_geometry(rng, tmp_path):
    X = rng.normal(size=(256, 4)).astype(np.float32)
    y = rng.normal(size=(256,)).astype(np.float32)
    resume_dir = str(tmp_path / "ckpt")
    from tpu_sgd.ops.gram import _PrefixBuildCheckpoint

    ck = _PrefixBuildCheckpoint(resume_dir, n_used=256, d=4, B=32,
                                sd_name="float32", chunk=64)
    ck.save_part(0, np.zeros((2, 4, 4), np.float32),
                 np.zeros((2, 4), np.float32),
                 np.zeros((2,), np.float32), high_water_rows=64)
    with pytest.raises(ValueError, match="different build"):
        GramLeastSquaresGradient.build_streamed(
            X, y, block_rows=16, resume_dir=resume_dir)


def test_sharded_streamed_build_resumable(rng, tmp_path):
    """The per-shard mesh builder checkpoints each shard independently
    (resume_dir/shard_i) and a full re-run from checkpoints matches the
    uninterrupted build."""
    from tpu_sgd import data_mesh
    from tpu_sgd.parallel.gram_parallel import (
        build_streamed_sharded_gram_stats,
    )

    mesh = data_mesh()
    k = mesh.shape["data"]
    n, d, B = k * 160, 5, 32
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    ref, Bout, n_used = build_streamed_sharded_gram_stats(
        mesh, X, y, block_rows=B, batch_rows=64)
    resume_dir = str(tmp_path / "shards")
    # first pass persists per-shard parts; second pass resumes (and since
    # the first completed+finalized, it rebuilds — both must agree with
    # the checkpoint-free build bitwise)
    got, _, _ = build_streamed_sharded_gram_stats(
        mesh, X, y, block_rows=B, batch_rows=64, resume_dir=resume_dir)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_streamed_resume_rejects_different_dataset(rng, tmp_path):
    """A stale resume_dir from a DIFFERENT same-shaped dataset must be
    rejected (dataset fingerprint in the meta) — replaying another
    dataset's chunks would silently corrupt the statistics
    (code-review r5)."""
    from tpu_sgd.ops import gram as gram_mod

    n, d, B = 512, 5, 32
    XA = rng.normal(size=(n, d)).astype(np.float32)
    XB = rng.normal(size=(n, d)).astype(np.float32)  # same shape/dtype
    y = rng.normal(size=(n,)).astype(np.float32)
    resume_dir = str(tmp_path / "ckpt")

    calls = {"n": 0}
    real = gram_mod._chunk_prefix

    def dying(*args):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated wedge")
        return real(*args)

    gram_mod._chunk_prefix = dying
    try:
        with pytest.raises(RuntimeError, match="wedge"):
            GramLeastSquaresGradient.build_streamed(
                XA, y, block_rows=B, batch_rows=64,
                resume_dir=resume_dir)
    finally:
        gram_mod._chunk_prefix = real
    with pytest.raises(ValueError, match="different build"):
        GramLeastSquaresGradient.build_streamed(
            XB, y, block_rows=B, batch_rows=64, resume_dir=resume_dir)


def test_the_chunked_gather_option_is_gone_not_ignored():
    """PR 30 took ``chunk_iters`` and its driver out (21x slower on the
    chip, GRAM_SCAN_EXPERIMENT.json): the keyword is Python's own
    ``TypeError`` and no plan carries such a field."""
    import dataclasses

    from tpu_sgd.plan import Plan

    with pytest.raises(TypeError, match="chunk_iters"):
        GradientDescent().set_gram_options(chunk_iters=4)
    assert not [f.name for f in dataclasses.fields(Plan)
                if "chunk_iters" in f.name]
    assert not hasattr(GradientDescent(), "gram_chunk_iters")


def test_statistics_evaluator_dots_run_highest_precision(rng):
    """EVERY matmul inside the statistics evaluators must carry
    Precision.HIGHEST: the TPU default runs f32 operands through bf16
    passes, and near convergence the quadratic loss is a near-zero
    difference of ~||y||^2-magnitude terms — a default-precision dot's
    relative error dwarfs it (module docstring contract).  CPU runs
    full-precision dots either way, so this asserts the lowered jaxpr's
    precision attributes instead of numerics."""
    X, y, w = _data(rng)
    g = GramLeastSquaresGradient.build(X, y, block_rows=128)
    W = jnp.stack([w, 0.5 * w])
    evaluators = {
        "batch_sums": lambda: g.batch_sums(g.data, y, w),
        "loss_sweep": lambda: g.loss_sweep(g.data, y, W),
        "window_sums_exact": lambda: g.window_sums(
            g.data, y, w, jnp.int32(17), 256),
        "total_stats": lambda: GramLeastSquaresGradient._total_stats(
            jnp.asarray(X), jnp.asarray(y), B=128,
            stats_dtype=jnp.float32),
    }
    for name, fn in evaluators.items():
        s = str(jax.make_jaxpr(fn)())
        assert "dot_general" in s, name
        assert "precision=None" not in s, (
            f"{name} lowers a default-precision matmul")


def test_stats_dtype_rejects_non_floating(rng):
    """An int stats_dtype would silently truncate every element in the
    upcast; the resolver must reject the whole non-float family, not
    just sub-f32 floats."""
    X, y, _ = _data(rng)
    for bad in (jnp.int32, jnp.int16, bool):
        with pytest.raises(ValueError, match="floating"):
            GramLeastSquaresGradient.build(X, y, stats_dtype=bad)
    with pytest.raises(ValueError, match="float32 or wider"):
        GramLeastSquaresGradient.build(X, y, stats_dtype=jnp.bfloat16)


def test_single_block_virtual_stats_warn_on_sliced(rng):
    """A totals-only/single-block virtual bundle cannot express
    sub-batch windows — feeding it to sliced mini-batch GD silently
    runs full-batch iterations, and the driver must say so."""
    import warnings as _w

    from tpu_sgd import GradientDescent, SimpleUpdater

    X, y, _ = _data(rng, n=512, d=8)
    g = GramLeastSquaresGradient.build_streamed(X, y, block_rows=512)
    assert g.data.PG.shape[0] == 2  # single block by construction
    opt = (GradientDescent(g, SimpleUpdater())
           .set_step_size(0.1).set_num_iterations(3)
           .set_mini_batch_fraction(0.25).set_sampling("sliced"))
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        opt.optimize_with_history((g.data, y), np.zeros(8, np.float32))
    assert any("degenerate to FULL-BATCH" in str(r.message) for r in rec)


# ---- the totals form: one read, the configuration's precision (PR 41) --------

def _bf16_rows(rng, n, d):
    X = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    w = rng.uniform(-1, 1, size=(d,)).astype(np.float32)
    y = jnp.asarray(np.asarray(X, np.float32) @ w
                    + 0.1 * rng.normal(size=(n,)).astype(np.float32))
    return X, y


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("n,d", [(1000, 16), (4096, 64), (777, 8)])
def test_the_bf16_build_is_the_f32_highest_totals(rng, n, d):
    """One bf16 pass with f32 sums over bf16 rows (products of two bf16
    numbers are exact in f32) against ``_total_stats`` at f32 ``HIGHEST``
    over the rows upcast, and both against the float64 values; ``y`` goes
    in unrounded (its three bf16 parts add up to it)."""
    from tpu_sgd.ops.gram import stats_build

    X, y = _bf16_rows(rng, n, d)
    st = stats_build(X, y)
    assert st.X is None and st.PG is None and st.Pb is None
    assert st.shape == (n, d) and st.dtype == jnp.bfloat16
    assert st.G_tot.dtype == st.b_tot.dtype == st.yy_tot.dtype == jnp.float32
    ref = GramLeastSquaresGradient._total_stats(
        X, y, B=256, stats_dtype=jnp.float32)
    X64, y64 = np.asarray(X, np.float64), np.asarray(y, np.float64)
    exact = (X64.T @ X64, X64.T @ y64, y64 @ y64)
    for got, want, true in zip((st.G_tot, st.b_tot, st.yy_tot), ref, exact):
        assert _rel(got, true) < 1e-6
        assert _rel(got, want) < 1e-6 + _rel(want, true)
    # a y rounded to bf16 ONCE would be 200 times further out
    rounded = X64.T @ np.asarray(y.astype(jnp.bfloat16), np.float64)
    assert _rel(rounded, exact[1]) > 100 * _rel(st.b_tot, exact[1])


def test_the_build_of_f32_rows_keeps_highest(rng):
    from tpu_sgd.ops.gram import stats_build

    X, y, _ = _data(rng, n=1000, d=16)
    st = stats_build(X, y)
    ref = GramLeastSquaresGradient._total_stats(
        X, y, B=256, stats_dtype=jnp.float32)
    for got, want in zip((st.G_tot, st.b_tot, st.yy_tot), ref):
        assert _rel(got, want) < 1e-6
    text = jax.jit(lambda X, y: stats_build(X, y).G_tot).lower(X, y).as_text()
    assert "HIGHEST" in text


def test_the_build_reads_x_where_it_lies():
    """The program of the build at the stream cell's micro-batch: X goes
    into two ``dot_general``s as it is, contracted along its rows; nothing
    of X's size is converted, transposed, sliced or reshaped on the way
    (the chip's compile of it is pinned in ``tests/test_chip_compile.py``)."""
    from tpu_sgd.ops import gram

    n, d = 2_097_152, 1000
    jaxpr = jax.make_jaxpr(gram._stats_build)(
        jax.ShapeDtypeStruct((n, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((n,), jnp.float32))
    inner, = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    eqns = inner.params["jaxpr"].eqns
    X = inner.params["jaxpr"].jaxpr.invars[0]
    uses = [e for e in eqns if any(v is X for v in e.invars)]
    assert [e.primitive.name for e in uses] == ["dot_general", "dot_general"]
    for e in uses:
        (lhs, rhs), _ = e.params["dimension_numbers"]
        rows = (lhs, rhs) if e.invars[0] is X else (rhs, lhs)
        assert rows[0] == (0,) and e.params["precision"] is None
        assert e.params["preferred_element_type"] == jnp.float32
    made = [v.aval.size for e in eqns for v in e.outvars]
    assert max(made) == 3 * n  # y's three bf16 parts: 12.6 MB


def test_the_totals_form_serves_a_full_batch_and_no_window(rng):
    from tpu_sgd.ops.gram import stats_build

    X, y = _bf16_rows(rng, 512, 8)
    w = jnp.asarray(rng.uniform(-1, 1, 8), jnp.float32)
    st, unbound = stats_build(X, y), GramLeastSquaresGradient()
    g, l, c = unbound.batch_sums(st, y, w)
    g0, l0, c0 = GramLeastSquaresGradient.build(X, y, block_rows=64) \
        .batch_sums(X, y, w)
    np.testing.assert_allclose(g, g0, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(l, l0, rtol=1e-5)
    assert float(c) == float(c0) == 512.0
    with pytest.raises(NotImplementedError, match="full-batch sums only"):
        unbound.window_sums(st, y, w, jnp.int32(0), 64)
    with pytest.raises(ValueError, match="no prefix stack"):
        st.save("/nonexistent")
    opt = (GradientDescent(unbound, SimpleUpdater()).set_num_iterations(3)
           .set_mini_batch_fraction(0.5).set_sampling("sliced"))
    with pytest.raises(NotImplementedError, match="full-batch fits"):
        opt.optimize_with_history((st, y), np.zeros(8, np.float32))
    # and it is a pytree whose aux says nothing of the data's values
    leaves, tree = jax.tree_util.tree_flatten(st)
    assert len(leaves) == 3
    assert tree == jax.tree_util.tree_structure(stats_build(X[::-1], y))


# ---- the totals folded from row blocks (PR 44) --------------------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_blocks_fold_is_the_whole_build_within_f32_sums(rng, dtype):
    """``stats_fold`` over a micro-batch's row blocks in order (four of 1,024
    rows and a remainder) against ``stats_build`` of the whole: the same
    products at the same precision, summed in another order of f32
    additions, so the two agree to f32 rounding of the sums and the fold is
    no further from the float64 totals than the whole build; ``G``, ``b``,
    ``yy`` are given up (donated) at every call."""
    from tpu_sgd.ops.gram import stats_build, stats_fold

    n, d, rows = 4 * 1024 + 100, 16, 1024
    X = jnp.asarray(rng.normal(size=(n, d)), dtype)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    whole = stats_build(X, y)
    totals, held = None, []
    for a in range(0, n, rows):
        held.append(totals)
        totals, done = stats_fold(totals, y, a, X[a:a + rows])
        assert done.shape == () and float(done) == float(totals[0][0, 0])
    assert all(t[0].is_deleted() for t in held[1:])  # added to in place
    assert [t.dtype for t in totals] == [jnp.float32] * 3
    X64, y64 = np.asarray(X, np.float64), np.asarray(y, np.float64)
    exact = (X64.T @ X64, X64.T @ y64, y64 @ y64)
    eps = float(np.finfo(np.float32).eps)
    for got, want, true in zip(
            totals, (whole.G_tot, whole.b_tot, whole.yy_tot), exact):
        assert got.shape == want.shape
        assert _rel(got, want) < 16 * eps
        assert _rel(got, true) <= 1.5 * _rel(want, true) + eps


def test_the_blocks_fold_carries_the_builds_scope_under_its_own_name():
    """What ``stats_build_ms`` reads (the scope) and what the compile cache
    keys on (the jitted function's name: PERF.md, PR 25)."""
    from tpu_sgd.ops import gram

    S = jax.ShapeDtypeStruct
    text = gram._stats_fold.lower(
        S((8, 8), jnp.float32), S((8,), jnp.float32), S((), jnp.float32),
        S((512,), jnp.float32), S((), jnp.int32),
        S((256, 8), jnp.bfloat16)).as_text(debug_info=True)
    assert "sgd.stats_build" in text and "jit(_stats_fold)" in text
    assert "jit(_stats_build)" not in text
