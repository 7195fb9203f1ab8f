"""Sparse (BCOO) training path tests.

VERDICT r1 missing #2 / SURVEY.md §2 #10: the reference trains directly on
``SparseVector`` features ([U] mllib/linalg/Vectors.scala); these tests prove
the BCOO path gives the SAME results as the dense path (same fused step, same
seeds) and that config-3-shaped data (~47k features, ~0.1% nnz) trains
without ever materializing dense X.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_sgd.models.classification import (
    LogisticRegressionWithSGD,
    SVMWithSGD,
)
from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
    MultinomialLogisticGradient,
)
from tpu_sgd.ops.sparse import (
    append_bias_bcoo,
    csr_to_bcoo,
    is_sparse,
    load_libsvm_file_bcoo,
    sparse_data,
)
from tpu_sgd.ops.updaters import L1Updater, SquaredL2Updater
from tpu_sgd.optimize.gradient_descent import GradientDescent
from tpu_sgd.optimize.lbfgs import LBFGS
from tpu_sgd.optimize.owlqn import OWLQN


def _dense(X):
    return np.asarray(X.todense())


@pytest.fixture
def small_sparse():
    X, y, w_true = sparse_data(400, 60, nnz_per_row=8, kind="linear", seed=3)
    return X, jnp.asarray(y), w_true


def test_is_sparse(small_sparse):
    X, y, _ = small_sparse
    assert is_sparse(X)
    assert not is_sparse(_dense(X))
    assert not is_sparse(y)


def test_csr_to_bcoo_matches_dense_load(tmp_path):
    from tpu_sgd.utils.mlutils import load_libsvm_file, save_as_libsvm_file

    rng = np.random.default_rng(0)
    Xd = rng.normal(size=(30, 12)).astype(np.float32)
    Xd[rng.uniform(size=Xd.shape) < 0.7] = 0.0
    Xd[:, 0] = 1.0  # keep max-index discovery exact
    Xd[0, -1] = 0.5
    y = rng.integers(0, 2, size=30).astype(np.float32)
    path = str(tmp_path / "part.libsvm")
    save_as_libsvm_file(path, Xd, y)

    Xs, ys = load_libsvm_file_bcoo(path)
    Xd2, yd2 = load_libsvm_file(path)
    np.testing.assert_allclose(_dense(Xs), Xd2, rtol=1e-5)
    np.testing.assert_allclose(ys, yd2)


def test_csr_to_bcoo_roundtrip():
    # hand-built CSR triple: [[0, 2, 0], [1, 0, 3]]
    data = np.asarray([2.0, 1.0, 3.0], np.float32)
    indices = np.asarray([1, 0, 2], np.int32)
    indptr = np.asarray([0, 1, 3])
    X = csr_to_bcoo((data, indices, indptr), 3)
    np.testing.assert_allclose(
        _dense(X), [[0.0, 2.0, 0.0], [1.0, 0.0, 3.0]]
    )


def test_append_bias_bcoo(small_sparse):
    X, _, _ = small_sparse
    Xb = append_bias_bcoo(X)
    assert Xb.shape == (X.shape[0], X.shape[1] + 1)
    d = _dense(Xb)
    np.testing.assert_allclose(d[:, -1], 1.0)
    np.testing.assert_allclose(d[:, :-1], _dense(X))


@pytest.mark.parametrize(
    "grad", [LeastSquaresGradient(), LogisticGradient(), HingeGradient()]
)
@pytest.mark.parametrize("with_mask", [False, True])
def test_batch_sums_matches_dense(grad, with_mask, small_sparse):
    X, y, _ = small_sparse
    if not isinstance(grad, LeastSquaresGradient):
        y = (y > 0).astype(jnp.float32)
    w = jnp.asarray(
        np.random.default_rng(1).normal(size=(X.shape[1],)).astype(np.float32)
    )
    mask = (
        jnp.asarray(np.random.default_rng(2).uniform(size=X.shape[0]) < 0.5)
        if with_mask
        else None
    )
    gs, ls, c = grad.batch_sums(X, y, w, mask)
    gd, ld, cd = grad.batch_sums(jnp.asarray(_dense(X)), y, w, mask)
    np.testing.assert_allclose(gs, gd, rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(ls, ld, rtol=2e-5)
    assert int(c) == int(cd)


def test_multinomial_batch_sums_matches_dense():
    X, y, _ = sparse_data(200, 30, nnz_per_row=6, kind="linear", seed=7)
    y3 = jnp.asarray((np.asarray(y) > 0).astype(np.float32) + (
        np.asarray(y) > 1.0
    ).astype(np.float32))
    g = MultinomialLogisticGradient(3)
    w = jnp.asarray(
        np.random.default_rng(4).normal(size=(2 * 30,)).astype(np.float32)
    )
    gs, ls, c = g.batch_sums(X, y3, w)
    gd, ld, cd = g.batch_sums(jnp.asarray(_dense(X)), y3, w)
    np.testing.assert_allclose(gs, gd, rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(ls, ld, rtol=2e-5)


def test_gd_sparse_identical_to_dense(small_sparse):
    """Same seed + same fused step => the sparse run IS the dense run."""
    X, y, _ = small_sparse

    def run(Xin):
        opt = (
            GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
            .set_step_size(0.1)
            .set_num_iterations(15)
            .set_reg_param(0.01)
            .set_mini_batch_fraction(0.5)
            .set_seed(9)
        )
        w, hist = opt.optimize_with_history((Xin, y), jnp.zeros((X.shape[1],)))
        return np.asarray(w), np.asarray(hist)

    w_s, h_s = run(X)
    w_d, h_d = run(jnp.asarray(_dense(X)))
    np.testing.assert_allclose(h_s, h_d, rtol=1e-4)
    np.testing.assert_allclose(w_s, w_d, rtol=1e-4, atol=1e-5)
    assert h_s[-1] < h_s[0]


def test_lbfgs_sparse_matches_dense(small_sparse):
    X, y, w_true = small_sparse
    opt = LBFGS(LeastSquaresGradient(), max_num_iterations=30)
    w_s, h_s = opt.optimize_with_history((X, y), jnp.zeros((X.shape[1],)))
    opt_d = LBFGS(LeastSquaresGradient(), max_num_iterations=30)
    w_d, h_d = opt_d.optimize_with_history(
        (jnp.asarray(_dense(X)), y), jnp.zeros((X.shape[1],))
    )
    np.testing.assert_allclose(h_s[-1], h_d[-1], rtol=1e-3)
    # least-squares on well-conditioned data: recovers the truth
    assert float(jnp.linalg.norm(w_s - jnp.asarray(w_true))) < 0.5


def test_owlqn_sparse_sparsifies():
    X, y, _ = sparse_data(500, 40, nnz_per_row=10, kind="logistic", seed=11)
    # reg small enough that w=0 is NOT already optimal (|grad_i(0)| > reg
    # for informative coordinates), large enough to zero the weak ones
    opt = OWLQN(LogisticGradient(), reg_param=0.01, max_num_iterations=40)
    w, hist = opt.optimize_with_history(
        (X, jnp.asarray(y)), jnp.zeros((40,))
    )
    assert hist[-1] < hist[0]
    assert int(jnp.sum(w == 0.0)) > 0  # L1 actually zeroed coordinates


def test_svm_train_bcoo_with_intercept():
    X, y, _ = sparse_data(800, 50, nnz_per_row=10, kind="svm", seed=13)
    model = SVMWithSGD.train(
        (X, y), num_iterations=40, step_size=1.0, reg_param=0.01,
        intercept=True,
    )
    preds = np.asarray(model.predict(X))  # sparse batch predict
    acc = float(np.mean(preds == np.asarray(y)))
    assert acc > 0.85
    # dense rows predict identically
    preds_d = np.asarray(model.predict(_dense(X)))
    np.testing.assert_allclose(preds, preds_d)


def test_logistic_train_bcoo():
    X, y, _ = sparse_data(800, 50, nnz_per_row=10, kind="logistic", seed=17)
    model = LogisticRegressionWithSGD.train(
        (X, y), num_iterations=40, step_size=1.0, reg_param=0.01
    )
    acc = float(np.mean(np.asarray(model.predict(X)) == np.asarray(y)))
    assert acc > 0.75


def test_sparse_guards(small_sparse):
    X, y, _ = small_sparse
    w0 = jnp.zeros((X.shape[1],))
    opt = GradientDescent().set_sampling("sliced").set_mini_batch_fraction(0.5)
    with pytest.raises(NotImplementedError, match="bernoulli"):
        opt.optimize((X, y), w0)
    # host streaming on sparse features TRAINS since the compressed-wire
    # round (optimize/streamed_sparse.py; tests/test_sparse_wire.py) —
    # the remaining guard is the meshed variant (single-device only)
    from tpu_sgd.parallel import data_mesh as _dm

    opt2 = GradientDescent().set_host_streaming(True).set_mesh(_dm())
    with pytest.raises(NotImplementedError, match="single-device"):
        opt2.optimize((X, y), w0)
    # ...and the sliced-sampling guard holds on the streamed path too
    opt3 = (GradientDescent().set_host_streaming(True)
            .set_sampling("sliced").set_mini_batch_fraction(0.5))
    with pytest.raises(NotImplementedError, match="bernoulli"):
        opt3.optimize((X, y), w0)
    from tpu_sgd.optimize.normal import NormalEquations

    with pytest.raises(NotImplementedError, match="dense features"):
        NormalEquations().optimize((X, y), w0)
    from tpu_sgd.config import MeshConfig

    mesh_2d = MeshConfig(data=4, model=2).build()
    with pytest.raises(NotImplementedError, match="model"):
        GradientDescent().set_mesh(mesh_2d).optimize((X, y), w0)


def _uneven_sparse():
    """Uneven row count (1003 % 8 != 0) exercises the padded-shard path."""
    from tpu_sgd.ops.sparse import sparse_data

    X, y, w_true = sparse_data(1003, 80, nnz_per_row=9, kind="linear", seed=3)
    return X, jnp.asarray(y), w_true


def test_sparse_dp_matches_dense_dp():
    """Distributed sparse == distributed dense, bit-for-bit trajectory:
    same contiguous row blocks, same per-shard sample streams, same psum."""
    from tpu_sgd.parallel import data_mesh

    X, y, _ = _uneven_sparse()
    mesh = data_mesh()

    def mk():
        return (
            GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
            .set_step_size(0.2).set_num_iterations(12).set_reg_param(0.01)
            .set_mini_batch_fraction(0.5).set_seed(7).set_mesh(mesh)
        )

    w_s, h_s = mk().optimize_with_history((X, y), jnp.zeros((X.shape[1],)))
    Xd = jnp.asarray(_dense(X))
    w_d, h_d = mk().optimize_with_history((Xd, y), jnp.zeros((X.shape[1],)))
    np.testing.assert_allclose(h_s, h_d, rtol=1e-4)
    np.testing.assert_allclose(w_s, w_d, rtol=1e-4, atol=1e-5)


def test_sparse_lbfgs_dp_matches_single_device():
    from tpu_sgd.parallel import data_mesh

    X, y, _ = _uneven_sparse()
    w0 = jnp.zeros((X.shape[1],))
    w_m, h_m = (LBFGS(LeastSquaresGradient(), max_num_iterations=25)
                .set_mesh(data_mesh()).optimize_with_history((X, y), w0))
    w_1, h_1 = LBFGS(
        LeastSquaresGradient(), max_num_iterations=25
    ).optimize_with_history((X, y), w0)
    np.testing.assert_allclose(h_m[-1], h_1[-1], rtol=1e-4)
    np.testing.assert_allclose(w_m, w_1, rtol=1e-3, atol=1e-4)


def test_sparse_owlqn_dp_trains():
    from tpu_sgd.parallel import data_mesh

    X, y, _ = sparse_data(960, 40, nnz_per_row=10, kind="logistic", seed=11)
    opt = (OWLQN(LogisticGradient(), reg_param=0.01, max_num_iterations=30)
           .set_mesh(data_mesh()))
    w, hist = opt.optimize_with_history(
        (X, jnp.asarray(y)), jnp.zeros((40,))
    )
    assert hist[-1] < hist[0]
    # parity with the single-device orthant-wise run
    w1, h1 = OWLQN(
        LogisticGradient(), reg_param=0.01, max_num_iterations=30
    ).optimize_with_history((X, jnp.asarray(y)), jnp.zeros((40,)))
    np.testing.assert_allclose(hist[-1], h1[-1], rtol=1e-3)


def test_sparse_dp_handles_nse_sentinel_padding():
    """jax pads BCOO nse with out-of-bounds sentinel indices (== shape);
    BCOO ops drop them, and the mesh shard layout must too."""
    from jax.experimental.sparse import BCOO
    from tpu_sgd.parallel import data_mesh

    Xd = np.zeros((16, 5), np.float32)
    Xd[np.arange(16), np.arange(16) % 5] = 1.0
    X = BCOO.fromdense(jnp.asarray(Xd), nse=24)  # 8 sentinel entries
    y = jnp.asarray(np.arange(16, dtype=np.float32) % 5)

    def run(Xin, mesh):
        opt = GradientDescent().set_num_iterations(5).set_step_size(0.1)
        if mesh is not None:
            opt.set_mesh(mesh)
        return opt.optimize_with_history((Xin, y), jnp.zeros((5,)))

    w_m, h_m = run(X, data_mesh())
    w_d, h_d = run(jnp.asarray(Xd), data_mesh())
    np.testing.assert_allclose(h_m, h_d, rtol=1e-5)
    np.testing.assert_allclose(w_m, w_d, rtol=1e-5, atol=1e-6)


def test_sparse_multihost_assembly_degenerate_single_process():
    """The multi-host BCOO assembly path, run in its single-process
    degenerate form (process_allgather over one process), must produce the
    same global layout as the single-host path."""
    from tpu_sgd.parallel import data_mesh
    from tpu_sgd.parallel.sparse_parallel import (
        _shard_bcoo_multihost,
        shard_bcoo,
    )

    X, y, _ = _uneven_sparse()
    mesh = data_mesh()
    d1, i1, y1, v1, rl1, dd1 = shard_bcoo(mesh, X, np.asarray(y))
    d2, i2, y2, v2, rl2, dd2 = _shard_bcoo_multihost(mesh, X, np.asarray(y))
    assert (rl1, dd1) == (rl2, dd2)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2))
    np.testing.assert_allclose(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    # single-host fast path may drop the mask; multihost always keeps it
    assert v2 is not None
    if v1 is not None:
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))


def test_sparse_model_train_with_mesh():
    """SVMWithSGD.train(..., mesh=...) end-to-end on BCOO features."""
    from tpu_sgd.parallel import data_mesh

    X, y, _ = sparse_data(800, 50, nnz_per_row=10, kind="svm", seed=13)
    model = SVMWithSGD.train(
        (X, y), num_iterations=40, reg_param=0.01, intercept=True,
        mesh=data_mesh(),
    )
    acc = float(np.mean(np.asarray(model.predict(X)) == np.asarray(y)))
    assert acc > 0.85


def test_multinomial_lbfgs_sparse_train_and_predict():
    """Multiclass + intercept on BCOO: train via the bias-column override and
    predict on sparse batches (both code paths were sparse-blind before)."""
    from tpu_sgd.models.classification import LogisticRegressionWithLBFGS

    X, y, _ = sparse_data(600, 30, nnz_per_row=8, kind="linear", seed=23)
    y3 = ((np.asarray(y) > -0.5).astype(np.float32)
          + (np.asarray(y) > 0.5).astype(np.float32))
    model = LogisticRegressionWithLBFGS.train(
        (X, y3), max_num_iterations=30, num_classes=3, intercept=True
    )
    preds = np.asarray(model.predict(X))
    acc = float(np.mean(preds == y3))
    assert acc > 0.6
    # dense rows agree
    np.testing.assert_allclose(preds, np.asarray(model.predict(_dense(X))))
    # single sparse row == single dense row
    from jax.experimental.sparse import BCOO

    row = _dense(X)[0]
    p_sparse = model.predict(BCOO.fromdense(jnp.asarray(row)))
    assert float(p_sparse) == float(model.predict(row))


def test_streaming_sparse_batches():
    from tpu_sgd.models.streaming import StreamingLogisticRegressionWithSGD

    X, y, _ = sparse_data(900, 40, nnz_per_row=8, kind="logistic", seed=29)
    alg = StreamingLogisticRegressionWithSGD(
        step_size=1.0, num_iterations=10
    ).set_initial_weights(np.zeros(40))
    n = X.shape[0]
    for lo in range(0, n, 300):  # three sparse micro-batches
        idx = np.arange(lo, min(lo + 300, n))
        from jax.experimental.sparse import BCOO

        batch = BCOO.fromdense(jnp.asarray(_dense(X)[idx]))
        alg.train_on_batch(batch, np.asarray(y)[idx])
    acc = float(np.mean(np.asarray(alg.latest_model().predict(X))
                        == np.asarray(y)))
    assert acc > 0.7


def test_predict_margin_single_vector_shape(small_sparse):
    """Sparse and dense single-vector margins agree in value AND shape."""
    from jax.experimental.sparse import BCOO
    from tpu_sgd.models.regression import LinearRegressionModel

    X, _, _ = small_sparse
    model = LinearRegressionModel(np.ones(X.shape[1], np.float32), 0.5)
    row = _dense(X)[3]
    md = model.predict_margin(row)
    ms = model.predict_margin(BCOO.fromdense(jnp.asarray(row)))
    assert md.shape == ms.shape == (1,)
    np.testing.assert_allclose(md, ms, rtol=1e-6)


def test_sparse_int_features_promote():
    """Integer one-hot BCOO data must not truncate f32 weights (compute
    promotes to >= f32)."""
    from jax.experimental.sparse import BCOO

    onehot = np.zeros((6, 4), np.int32)
    onehot[np.arange(6), np.arange(6) % 4] = 1
    X = BCOO.fromdense(jnp.asarray(onehot))
    y = jnp.zeros((6,), jnp.float32)
    w = jnp.full((4,), 0.5, jnp.float32)
    gs, ls, c = LeastSquaresGradient().batch_sums(X, y, w)
    assert jnp.issubdtype(ls.dtype, jnp.floating)
    assert float(ls) > 0.0  # margins were 0.5, not int-truncated 0


def test_sparse_stepwise_listener_and_checkpoint(tmp_path, small_sparse):
    """The observed path (listener + checkpoint manager) accepts BCOO
    features single-device: per-iteration events fire and a mid-run
    checkpoint resumes to the same trajectory."""
    from tpu_sgd.utils.checkpoint import CheckpointManager
    from tpu_sgd.utils.events import CollectingListener

    X, y, _ = small_sparse
    w0 = jnp.zeros((X.shape[1],))

    listener = CollectingListener()
    opt = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
           .set_step_size(0.1).set_num_iterations(8).set_reg_param(0.01)
           .set_seed(5).set_listener(listener))
    w_full, h_full = opt.optimize_with_history((X, y), w0)
    assert len(listener.iterations) == 8
    assert listener.iterations[0].mini_batch_size == X.shape[0]

    # interrupted run saves at iteration 4; a fresh optimizer resumes
    mgr = CheckpointManager(str(tmp_path), keep=5)
    opt_a = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
             .set_step_size(0.1).set_num_iterations(4).set_reg_param(0.01)
             .set_seed(5).set_checkpoint(mgr, every=4))
    opt_a.optimize_with_history((X, y), w0)
    opt_b = (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
             .set_step_size(0.1).set_num_iterations(8).set_reg_param(0.01)
             .set_seed(5).set_checkpoint(mgr, every=4))
    w_res, h_res = opt_b.optimize_with_history((X, y), w0)
    np.testing.assert_allclose(np.asarray(w_res), np.asarray(w_full),
                               rtol=1e-5, atol=1e-6)


def test_config3_shape_trains_undensified():
    """Config-3 scale check (VERDICT r1 #4 'done' criterion): RCV1-shaped
    (d=47,236, ~0.1% nnz) hinge + L1 training in BCOO form.  Dense X here
    would be 100k x 47k f32 = 18.8 GB — far beyond this runner's memory —
    so completing at all proves nothing densified.  Row count is scaled to
    keep CI fast; the FEATURE dimension (what densification chokes on) is
    the real RCV1's."""
    n, d = 20_000, 47_236
    X, y, _ = sparse_data(n, d, nnz_per_row=47, kind="svm", seed=19)
    opt = (
        GradientDescent(HingeGradient(), L1Updater())
        .set_step_size(1.0)
        .set_num_iterations(5)
        .set_reg_param(1e-4)
        .set_mini_batch_fraction(0.3)
    )
    w, hist = opt.optimize_with_history((X, y), jnp.zeros((d,)))
    assert hist.shape[0] == 5
    assert np.isfinite(hist).all()
    assert hist[-1] < hist[0]


def test_rcv1_like_full_width_trains_undensified():
    """The realistic RCV1 stand-in at the REAL 47,236-feature width (Zipf
    feature frequencies, unit-norm tfidf-like rows) trains undensified."""
    from tpu_sgd.utils.mlutils import rcv1_like_data

    X, y, _ = rcv1_like_data(4000, d=47_236, seed=3)
    opt = (
        GradientDescent(HingeGradient(), L1Updater())
        .set_step_size(100.0)
        .set_num_iterations(30)
        .set_reg_param(1e-5)
    )
    w, hist = opt.optimize_with_history(
        (X, jnp.asarray(y)), jnp.zeros((47_236,))
    )
    assert np.isfinite(hist).all()
    assert hist[-1] < hist[0]


def test_take_rows_bcoo_matches_dense_gather(small_sparse):
    from tpu_sgd.ops.sparse import take_rows_bcoo

    X, _, _ = small_sparse
    idx = np.asarray([5, 0, 37, 12, 399])
    got = _dense(take_rows_bcoo(X, idx))
    np.testing.assert_allclose(got, _dense(X)[idx], rtol=1e-6)
    with pytest.raises(ValueError, match="unique"):
        take_rows_bcoo(X, np.asarray([1, 1, 2]))


def test_k_fold_and_split_on_sparse(small_sparse):
    """MLUtils fold utilities serve sparse features like the reference's
    kFold serves sparse RDDs: splits reassemble to the full dataset."""
    from tpu_sgd.utils.mlutils import k_fold, train_test_split

    X, y, _ = small_sparse
    y = np.asarray(y)
    n = X.shape[0]
    folds = list(k_fold(X, y, 4, seed=3))
    assert len(folds) == 4
    total_val = 0
    for (Xtr, ytr), (Xva, yva) in folds:
        assert is_sparse(Xtr) and is_sparse(Xva)
        assert Xtr.shape[0] + Xva.shape[0] == n
        assert Xtr.shape[0] == ytr.shape[0]
        total_val += Xva.shape[0]
        # a fold trains through the ordinary sparse path
    assert total_val == n
    (Xtr, ytr), (Xte, yte) = train_test_split(X, y, 0.25, seed=4)
    assert Xte.shape[0] == round(0.25 * n)
    # gathered rows carry the right contents
    np.testing.assert_allclose(
        _dense(Xtr).sum() + _dense(Xte).sum(), _dense(X).sum(), rtol=1e-4
    )


def test_sparse_stepwise_mesh_listener_matches_fused():
    """Listener/checkpoint (observed) mode now runs sparse over the data
    mesh; its trajectory matches the fused while_loop path exactly."""
    from tpu_sgd.parallel import data_mesh
    from tpu_sgd.utils.events import CollectingListener

    X, y, _ = _uneven_sparse()
    mesh = data_mesh()
    w0 = jnp.zeros((X.shape[1],))

    def mk():
        return (GradientDescent(LeastSquaresGradient(), SquaredL2Updater())
                .set_step_size(0.2).set_num_iterations(10)
                .set_reg_param(0.01).set_mini_batch_fraction(0.5)
                .set_seed(7).set_mesh(mesh))

    listener = CollectingListener()
    w_obs, h_obs = mk().set_listener(listener).optimize_with_history(
        (X, y), w0
    )
    assert len(listener.iterations) == 10
    w_fused, h_fused = mk().optimize_with_history((X, y), w0)
    np.testing.assert_allclose(h_obs, h_fused, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_obs), np.asarray(w_fused),
                               rtol=1e-5, atol=1e-6)


def test_multinomial_lbfgs_sparse_over_mesh():
    """Matrix-weight (multinomial) gradient + BCOO + data mesh: the
    quasi-Newton scalar line search path over sharded sparse components
    matches the single-device result."""
    from tpu_sgd.parallel import data_mesh

    X, y, _ = sparse_data(640, 24, nnz_per_row=6, kind="linear", seed=37)
    y3 = jnp.asarray(((np.asarray(y) > -0.5).astype(np.float32)
                      + (np.asarray(y) > 0.5).astype(np.float32)))
    g = MultinomialLogisticGradient(3)  # stateless: shared by both runs
    w0 = jnp.zeros((2 * 24,))
    _, h_m = (LBFGS(g, max_num_iterations=20)
              .set_mesh(data_mesh())
              .optimize_with_history((X, y3), w0))
    _, h_1 = LBFGS(
        g, max_num_iterations=20
    ).optimize_with_history((X, y3), w0)
    assert h_m[-1] < h_m[0]
    np.testing.assert_allclose(h_m[-1], h_1[-1], rtol=1e-3)


def test_labeled_points_with_sparse_vectors_train_undensified():
    """The reference's primary sparse form — LabeledPoint records holding
    SparseVector features — converts to one BCOO matrix and trains through
    the sparse path (previously crashed in to_arrays)."""
    from tpu_sgd.linalg import DenseVector, SparseVector
    from tpu_sgd.models.labeled_point import LabeledPoint, to_arrays

    rng = np.random.default_rng(41)
    d = 30
    pts = []
    dense_rows = np.zeros((300, d), np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    for i in range(300):
        idx = np.sort(rng.choice(d, size=5, replace=False))
        vals = rng.normal(size=5).astype(np.float32)
        dense_rows[i, idx] = vals
        label = float(dense_rows[i] @ w_true > 0)
        pts.append(LabeledPoint(label, SparseVector(d, idx, vals)))
    X, y = to_arrays(pts)
    assert is_sparse(X) and X.shape == (300, d)
    np.testing.assert_allclose(_dense(X), dense_rows, rtol=1e-6)
    model = SVMWithSGD.train(pts, num_iterations=40, reg_param=1e-4)
    acc = float(np.mean(np.asarray(model.predict(X)) == y))
    assert acc > 0.85
    # DenseVector records still take the dense path
    dpts = [LabeledPoint(float(l), DenseVector(r))
            for l, r in zip(y, dense_rows)]
    Xd, yd = to_arrays(dpts)
    assert isinstance(Xd, np.ndarray)
    np.testing.assert_allclose(Xd, dense_rows)
    # a MIXED collection (reference RDDs mix freely) goes sparse, dense
    # rows contributing their nonzeros
    mixed = pts[:150] + dpts[150:]
    Xm, ym = to_arrays(mixed)
    assert is_sparse(Xm)
    np.testing.assert_allclose(_dense(Xm), dense_rows, rtol=1e-6)


def test_streaming_predict_on_sparse_batches():
    """predict_on / predict_on_values consume BCOO feature batches."""
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    X, y, _ = _uneven_sparse()
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=10)
    alg.set_initial_weights(np.zeros(X.shape[1]))
    alg.train_on_batch(X, np.asarray(y))
    from tpu_sgd.ops.sparse import take_rows_bcoo

    batches = [take_rows_bcoo(X, np.arange(0, 100)),
               take_rows_bcoo(X, np.arange(100, 250))]
    preds = list(alg.predict_on(iter(batches)))
    assert [p.shape[0] for p in preds] == [100, 150]
    keyed = list(alg.predict_on_values([("a", batches[0])]))
    assert keyed[0][0] == "a" and keyed[0][1].shape == (100,)
    # sparse and dense batch predictions agree
    np.testing.assert_allclose(
        preds[0], np.asarray(alg.latest_model().predict(_dense(batches[0]))),
        rtol=1e-5,
    )


def test_save_libsvm_from_bcoo_round_trips(tmp_path, small_sparse):
    """saveAsLibSVMFile parity on sparse input: a BCOO saves without
    densifying and round-trips through the sparse loader."""
    from tpu_sgd.utils.mlutils import save_as_libsvm_file

    X, y, _ = small_sparse
    path = str(tmp_path / "sp.libsvm")
    save_as_libsvm_file(path, X, np.asarray(y))
    X2, y2 = load_libsvm_file_bcoo(path, num_features=X.shape[1])
    np.testing.assert_allclose(_dense(X2), _dense(X), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5)


def test_save_libsvm_coalesces_duplicates_and_zeros(tmp_path):
    """Duplicate BCOO entries sum (BCOO semantics) and stored zeros drop
    in the LIBSVM writer, so the text round-trips losslessly."""
    from jax.experimental.sparse import BCOO
    from tpu_sgd.utils.mlutils import load_libsvm_file, save_as_libsvm_file

    idx = np.asarray([[0, 1], [0, 1], [0, 3], [1, 2]], np.int32)
    vals = jnp.asarray([1.5, 2.5, 0.0, -1.0], jnp.float32)
    X = BCOO((vals, jnp.asarray(idx)), shape=(2, 5))
    path = str(tmp_path / "dups.libsvm")
    save_as_libsvm_file(path, X, np.asarray([1.0, 0.0], np.float32))
    text = open(path).read()
    assert "2:4" in text  # 1.5 + 2.5 summed at column index 1 (1-based 2)
    assert "4:0" not in text  # stored zero dropped
    Xd, yd = load_libsvm_file(path, num_features=5)
    np.testing.assert_allclose(Xd, np.asarray(X.todense()), rtol=1e-5)


def test_sparse_vector_rejects_out_of_range_indices():
    from tpu_sgd.linalg import SparseVector

    with pytest.raises(ValueError, match="indices must be in"):
        SparseVector(3, [-1], [9.0])
    with pytest.raises(ValueError, match="indices must be in"):
        SparseVector(3, [5], [9.0])


def test_take_rows_bcoo_rejects_out_of_range_indices():
    """Negative indices would silently alias tail rows through the
    position scatter — a split training on the wrong rows."""
    from tpu_sgd.ops.sparse import take_rows_bcoo

    X, y, _ = sparse_data(32, 8, nnz_per_row=3, seed=3)
    with pytest.raises(IndexError, match="row indices"):
        take_rows_bcoo(X, np.array([-1, 0]))
    with pytest.raises(IndexError, match="row indices"):
        take_rows_bcoo(X, np.array([0, 32]))


def test_take_rows_bcoo_inherits_uniqueness_flag():
    """A duplicate-coordinate input keeps its duplicates in the selected
    subset; the output must not falsely promise unique indices (scatter
    in unique mode may drop one duplicate's value)."""
    from jax.experimental.sparse import BCOO

    from tpu_sgd.ops.sparse import take_rows_bcoo

    dup = BCOO(
        (jnp.asarray([1.0, 2.0]), jnp.asarray([[0, 1], [0, 1]])),
        shape=(2, 4), unique_indices=False,
    )
    out = take_rows_bcoo(dup, np.array([0]))
    assert out.unique_indices is False
    assert float(out.todense()[0, 1]) == 3.0  # duplicates still SUM
    # a genuinely-unique input keeps the flag
    X, _, _ = sparse_data(16, 8, nnz_per_row=2, seed=0)
    assert take_rows_bcoo(X, np.arange(4)).unique_indices is True


def test_csr_to_bcoo_rejects_out_of_range_feature(tmp_path):
    """The dense loader raises for a feature index beyond num_features;
    the sparse path must not silently drop the entry instead."""
    p = tmp_path / "oob.txt"
    p.write_text("1 1:0.5 7:1.5\n0 2:2.0\n")
    from tpu_sgd import load_libsvm_file_bcoo

    X, y = load_libsvm_file_bcoo(str(p))  # self-sized: fine
    assert X.shape == (2, 7)
    with pytest.raises(IndexError, match="feature index"):
        load_libsvm_file_bcoo(str(p), num_features=5)
