"""Oracle-loss verification for the workload configs (VERDICT r1 missing #5).

BASELINE.md pass criteria, demonstrably checked:
  config 1 (least squares)  — final objective within 1% of the EXACT
                              normal-equations minimizer;
  config 2 (logistic + L2)  — within 1% of a tight-tolerance LBFGS optimum;
  config 3 (hinge + L1)     — subgradient SGD is O(1/sqrt(t)) on the
                              nonsmooth hinge (reference-identical
                              limitation, see tpu_sgd/optimize/oracle.py),
                              so: objective within 20% of the tight OWL-QN
                              reference point AND accuracy within 1 point.
Shapes are scaled down from the config sizes to keep CI fast; the
full-scale checks run in examples/run_configs.py.
"""

import numpy as np
import pytest

from tpu_sgd.models.classification import LogisticRegressionWithSGD, SVMWithSGD
from tpu_sgd.models.regression import LinearRegressionWithSGD
from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
)
from tpu_sgd.ops.updaters import L1Updater
from tpu_sgd.optimize.oracle import (
    full_objective,
    hinge_l1_oracle,
    least_squares_oracle,
    logistic_l2_oracle,
    objective_gap,
)
from tpu_sgd.utils.mlutils import linear_data, logistic_data, svm_data


def test_config1_matches_normal_equations_oracle():
    X, y, _ = linear_data(20_000, 60, eps=0.1, seed=0)
    w_star = least_squares_oracle(X, y)
    model = LinearRegressionWithSGD.train(
        (X, y), num_iterations=100, step_size=1.0
    )
    gap, L, L_star = objective_gap(
        LeastSquaresGradient(), X, y, model.weights, w_star
    )
    assert gap < 0.01, f"gap {gap:.4f} (L={L:.6f} L*={L_star:.6f})"


def test_config2_matches_lbfgs_oracle():
    X, y, _ = logistic_data(10_000, 60, seed=1)
    y = np.where(y > 0, 1.0, 0.0).astype(np.float32)
    reg = 0.01
    w_star = logistic_l2_oracle(X, y, reg)
    alg = LogisticRegressionWithSGD(2.0, 500, reg, 1.0)
    alg.optimizer.set_convergence_tol(0.0)
    model = alg.run((X, y))
    gap, L, L_star = objective_gap(
        LogisticGradient(), X, y, model.weights, w_star, reg, "l2"
    )
    assert gap < 0.01, f"gap {gap:.4f} (L={L:.6f} L*={L_star:.6f})"


def test_config3_tracks_owlqn_oracle():
    X, y, _ = svm_data(10_000, 50, seed=2)
    reg = 1e-4
    w_star = hinge_l1_oracle(X, y, reg)
    alg = SVMWithSGD(10.0, 3000, reg, 1.0)
    alg.optimizer.set_updater(L1Updater()).set_convergence_tol(0.0)
    model = alg.run((X, y))
    gap, L, L_star = objective_gap(
        HingeGradient(), X, y, model.weights, w_star, reg, "l1"
    )
    # nonsmooth subgradient rate: documented looser objective bound ...
    assert gap < 0.20, f"gap {gap:.4f} (L={L:.6f} L*={L_star:.6f})"
    # ... plus accuracy parity with the oracle's decision rule
    from tpu_sgd.models.classification import SVMModel

    acc_sgd = float(np.mean(np.asarray(model.predict(X)) == y))
    acc_star = float(
        np.mean(np.asarray(SVMModel(w_star, 0.0).predict(X)) == y)
    )
    assert acc_sgd > acc_star - 0.01, (acc_sgd, acc_star)


def test_oracle_objective_helper_closed_form():
    """full_objective agrees with the hand-computed least-squares value."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(50, 4)).astype(np.float32)
    w = rng.normal(size=(4,)).astype(np.float32)
    y = rng.normal(size=(50,)).astype(np.float32)
    expect = float(np.mean(0.5 * (X @ w - y) ** 2)) + 0.5 * 0.1 * float(
        np.sum(w**2)
    )
    got = full_objective(LeastSquaresGradient(), X, y, w, 0.1, "l2")
    np.testing.assert_allclose(got, expect, rtol=1e-5)
    with pytest.raises(ValueError, match="unknown reg kind"):
        full_objective(LeastSquaresGradient(), X, y, w, 0.1, "elastic")


def test_host_streamed_costfun_reaches_logistic_oracle():
    """Round 5: the beyond-HBM chunked-CostFun schedule must reach the
    SAME optimum as a resident fit — the oracle gap is the end-to-end
    check that chunked accumulation loses nothing (the reference's
    CostFun converges identically however many partitions feed it)."""
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize.lbfgs import LBFGS

    X, y, _ = logistic_data(10_000, 40, seed=9)
    reg = 0.01
    w_star = logistic_l2_oracle(X, y, reg_param=reg)
    opt = (LBFGS(LogisticGradient(), SquaredL2Updater(), reg_param=reg,
                 max_num_iterations=60, convergence_tol=1e-9)
           .set_host_streaming(True, batch_rows=1024))
    w, hist = opt.optimize_with_history(
        (X, y), np.zeros(X.shape[1], np.float32))
    gap, L, L_star = objective_gap(
        LogisticGradient(), X, y, w, w_star, reg_param=reg, reg="l2"
    )
    assert gap < 0.01, f"gap {gap:.4f} (L={L:.6f} L*={L_star:.6f})"


def test_aligned_gram_fit_reaches_least_squares_oracle():
    """The per-iteration aligned gram driver (streamed statistics, sliced
    windows floored to whole blocks) converges to the normal-equations
    optimum: the aligned sampling deviation does not move the optimum on
    shuffled data."""
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.gradient_descent import GradientDescent

    X, y, _ = linear_data(20_000, 40, eps=0.1, seed=2)
    w_star = least_squares_oracle(X, y)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(1.0).set_num_iterations(200)
           .set_mini_batch_fraction(0.1).set_sampling("sliced")
           .set_convergence_tol(0.0)
           .set_streamed_stats(True, block_rows=512))
    w, hist = opt.optimize_with_history(
        (X, y), np.zeros(X.shape[1], np.float32))
    assert len(hist) == 200
    gap, L, L_star = objective_gap(
        LeastSquaresGradient(), X, y, w, w_star
    )
    assert gap < 0.02, f"gap {gap:.4f} (L={L:.6f} L*={L_star:.6f})"
