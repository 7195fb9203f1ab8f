#!/usr/bin/env python
"""Run the five reference workload configs (BASELINE.json:6-12) end-to-end.

    python examples/run_configs.py [1|2|3|4|5|all] [--scale small|full]

Config 1: LinearRegressionWithSGD, least squares, dense synthetic.
Config 2: LogisticRegressionWithSGD, log loss + L2, LIBSVM file (a real a9a
          when present at data/a9a, else the synthetic stand-in
          data/a9a_synthetic written on first run — see data/README.md).
Config 3: SVMWithSGD, hinge + L1 updater, sparse->densified LIBSVM.
Config 4: Mini-batch SGD frac=0.1, 8-way data-parallel all-reduce.
Config 5: Streaming SGD over micro-batches, online weight updates.

On a machine without the TPU attached, run with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from tpu_sgd import (  # noqa: E402
    L1Updater,
    LinearRegressionWithSGD,
    LogisticRegressionWithSGD,
    StreamingLinearRegressionWithSGD,
    SVMWithSGD,
    data_mesh,
)
from tpu_sgd.optimize.oracle import (  # noqa: E402
    hinge_l1_oracle,
    least_squares_oracle,
    logistic_l2_oracle,
    objective_gap,
)
from tpu_sgd.ops.gradients import (  # noqa: E402
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
)
from tpu_sgd.utils import (  # noqa: E402
    a9a_like_data,
    linear_data,
    load_libsvm_file,
    rcv1_like_data,
    save_as_libsvm_file,
)

def _parse_args(argv):
    which = "all"
    scale = os.environ.get("SCALE", "small")
    args = list(argv)
    while args:
        a = args.pop(0)
        if a == "--scale":
            if not args or args[0] not in ("small", "full"):
                raise SystemExit("--scale takes 'small' or 'full'")
            scale = args.pop(0)
        elif a in ("1", "2", "3", "4", "5", "all"):
            which = a
        else:
            raise SystemExit(
                f"unknown argument {a!r}; usage: run_configs.py "
                "[1|2|3|4|5|all] [--scale small|full]"
            )
    return which, scale


SMALL = True  # overwritten in __main__ from --scale / SCALE env


def config1():
    n, d = (100_000, 100)
    X, y, w_true = linear_data(n, d, eps=0.1, seed=0)
    t0 = time.perf_counter()
    model = LinearRegressionWithSGD.train((X, y), num_iterations=100,
                                          step_size=1.0)
    mse = float(np.mean((np.asarray(model.predict(X)) - y) ** 2))
    # BASELINE.md pass criterion: final loss matches the EXACT oracle
    # (normal equations) within 1%
    gap, L, L_star = objective_gap(
        LeastSquaresGradient(), X, y, model.weights,
        least_squares_oracle(X, y))
    verdict = "PASS" if gap < 0.01 else "FAIL"
    print(f"config1: n={n} d={d} mse={mse:.4f} "
          f"w_err={float(np.linalg.norm(np.asarray(model.weights) - w_true)):.4f} "
          f"oracle_gap={gap * 100:.2f}% [{verdict} <1%] "
          f"({time.perf_counter() - t0:.1f}s)")


def _libsvm_path(real_name, synthetic_name, maker):
    """Prefer a REAL dataset at ``data/<real_name>`` if the user vendored
    one; otherwise use (writing on first run) the locally generated
    synthetic stand-in at ``data/<synthetic_name>`` — this environment has
    no network, so the real LIBSVM files cannot be fetched (see
    data/README.md)."""
    data_dir = os.path.join(os.path.dirname(__file__), "..", "data")
    real = os.path.join(data_dir, real_name)
    if os.path.exists(real):
        # "vendored", not "real": we can only know the user placed a file
        # here, not that it is the genuine dataset.  (Workspaces that ran
        # the pre-rename script may have a STALE auto-generated file at
        # this path — delete it; the honest stand-in lives at
        # data/<synthetic_name> now.)
        return real, "vendored"
    path = os.path.join(data_dir, synthetic_name)
    if not os.path.exists(path):
        os.makedirs(data_dir, exist_ok=True)
        X, y = maker()
        save_as_libsvm_file(path, X, y)
    return path, "synthetic stand-in"


def config2():
    # Stand-in mirrors the REAL a9a structure: 123 binary one-hot
    # features, exactly 14 active per row (see a9a_like_data)
    path, kind = _libsvm_path(
        "a9a", "a9a_synthetic_v2",
        lambda: a9a_like_data(20_000, seed=1)[:2]
    )
    X, y = load_libsvm_file(path)
    y = np.where(y > 0, 1.0, 0.0).astype(np.float32)  # a9a labels are +/-1
    t0 = time.perf_counter()
    reg = 0.01
    alg = LogisticRegressionWithSGD(2.0, 500, reg, 1.0)
    alg.optimizer.set_convergence_tol(0.0)  # run the full budget
    model = alg.run((X, y))
    acc = float(np.mean(np.asarray(model.predict(X)) == y))
    # BASELINE.md pass criterion: matches a tight-tolerance LBFGS oracle
    # on the same (unbiased) objective within 1%
    gap, L, L_star = objective_gap(
        LogisticGradient(), X, y, model.weights,
        logistic_l2_oracle(X, y, reg), reg, "l2")
    verdict = "PASS" if gap < 0.01 else "FAIL"
    # The evaluation surface a reference user scores this model with
    # ([U] mllib/evaluation/BinaryClassificationMetrics)
    from tpu_sgd.evaluation import BinaryClassificationMetrics

    model.clear_threshold()
    auc = BinaryClassificationMetrics(
        np.asarray(model.predict(X)), y
    ).area_under_roc
    print(f"config2: libsvm={os.path.basename(path)} ({kind}) "
          f"n={X.shape[0]} d={X.shape[1]} acc={acc:.4f} auc={auc:.4f} "
          f"oracle_gap={gap * 100:.2f}% [{verdict} <1%] "
          f"({time.perf_counter() - t0:.1f}s)")


def config3():
    # Stand-in mirrors the REAL RCV1 structure (power-law feature
    # frequencies, positive unit-norm tfidf-like rows) at a densifiable
    # width — the real 47,236-feature width runs undensified below
    def _rcv1_standin():
        X, y, _ = rcv1_like_data(20_000, d=2000, nnz_per_row=75, seed=2)
        return np.asarray(X.todense()), y

    # _v2 filenames: the stand-in generators changed in round 2, and a
    # stale cached file from the old dense-Gaussian generators would
    # silently mismatch the step sizes calibrated for these
    # distributions
    path, kind = _libsvm_path("rcv1", "rcv1_synthetic_v2", _rcv1_standin)
    X, y = load_libsvm_file(path, dense=True)  # sparse -> densified
    y = np.where(y > 0, 1.0, 0.0).astype(np.float32)
    t0 = time.perf_counter()
    reg = 1e-4
    # unit-norm tfidf-like rows give small margins, so the eta/sqrt(t)
    # subgradient schedule needs a large base step (calibrated: gap 1.2%)
    alg = SVMWithSGD(300.0, 3000, reg, 1.0)
    alg.optimizer.set_updater(L1Updater()).set_convergence_tol(0.0)
    model = alg.run((X, y))
    acc = float(np.mean(np.asarray(model.predict(X)) == y))
    # Subgradient descent is O(1/sqrt(t)) on the nonsmooth hinge (the
    # reference's SVMWithSGD has the same rate), so the criterion is a
    # documented 20% objective bound vs the tight OWL-QN reference point
    # plus accuracy parity (see tpu_sgd/optimize/oracle.py)
    w_star = hinge_l1_oracle(X, y, reg)
    gap, L, L_star = objective_gap(
        HingeGradient(), X, y, model.weights, w_star, reg, "l1")
    from tpu_sgd.models.classification import SVMModel

    acc_star = float(np.mean(np.asarray(SVMModel(w_star, 0.0).predict(X)) == y))
    ok = gap < 0.20 and acc > acc_star - 0.01
    verdict = "PASS" if ok else "FAIL"
    print(f"config3: libsvm={os.path.basename(path)} ({kind}) "
          f"n={X.shape[0]} d={X.shape[1]} acc={acc:.4f} "
          f"(oracle acc={acc_star:.4f}) oracle_gap={gap * 100:.1f}% "
          f"[{verdict} <20%+acc] ({time.perf_counter() - t0:.1f}s)")

    # Same config UNDENSIFIED: BCOO features through the sparse path,
    # sharded over the data mesh (real RCV1 at ~47k features cannot be
    # densified at all — this is the path that handles it).
    from tpu_sgd.ops.sparse import load_libsvm_file_bcoo

    Xs, ys = load_libsvm_file_bcoo(path)
    ys = np.where(ys > 0, 1.0, 0.0).astype(np.float32)
    t0 = time.perf_counter()
    alg_s = SVMWithSGD(300.0, 500, reg, 1.0)
    alg_s.optimizer.set_updater(L1Updater()).set_convergence_tol(0.0)
    alg_s.optimizer.set_mesh(data_mesh())
    model_s = alg_s.run((Xs, ys))
    acc_s = float(np.mean(np.asarray(model_s.predict(Xs)) == ys))
    print(f"config3-sparse: BCOO undensified, {dict(data_mesh().shape)}-way "
          f"mesh, nse={Xs.nse} acc={acc_s:.4f} "
          f"({time.perf_counter() - t0:.1f}s)")


def config4():
    n, d = (400_000, 200) if SMALL else (10_000_000, 1000)
    X, y, w_true = linear_data(n, d, eps=0.1, seed=3)
    mesh = data_mesh()
    t0 = time.perf_counter()
    # Full scale is 10M x 1000 f32 = 40 GB — beyond any single chip's HBM
    # (SURVEY.md §7 hard parts).  The EXECUTION PLANNER (tpu_sgd/plan.py,
    # round 4) owns the residency decision now: train() probes free device
    # memory and picks resident / partial-residency / host-streamed
    # itself; CONFIG4_FREE_HBM overrides the probe for smoke tests.
    free_hbm = os.environ.get("CONFIG4_FREE_HBM")
    alg = LinearRegressionWithSGD(0.5, 200, None, 0.1)
    alg.optimizer.set_mesh(mesh)
    if free_hbm is not None:
        # pin the budget by planning explicitly, then run with the result
        import tpu_sgd.plan as _plan_mod

        p = _plan_mod.plan(
            n, d, itemsize=X.dtype.itemsize, gram_able=True,
            sampling=alg.optimizer.config.sampling,
            mini_batch_fraction=0.1, num_iterations=200,
            n_devices=mesh.shape["data"], free_hbm=float(free_hbm),
        )
        p.apply(alg.optimizer)
        alg.set_schedule("off")
    model = alg.run((X, y))
    last = alg.optimizer.last_plan
    mode = last.schedule if last is not None else "unplanned"
    print(f"config4: n={n} d={d} {dict(mesh.shape)}-way DP (plan: {mode}) "
          f"w_err={float(np.linalg.norm(np.asarray(model.weights) - w_true)):.4f} "
          f"({time.perf_counter() - t0:.1f}s)")
    if mode.startswith("resident"):
        # The same shape through the sufficient-statistics schedule
        # (round 3, ops/gram.py): per-shard prefix Grams + the same ICI
        # psum; weights must agree with the stock DP run above.
        t0 = time.perf_counter()
        model_ss = LinearRegressionWithSGD.train(
            (X, y), num_iterations=200, step_size=0.5,
            mini_batch_fraction=0.1, sampling="sliced", mesh=mesh,
            sufficient_stats=True,
        )
        drift = float(np.abs(np.asarray(model_ss.weights)
                             - np.asarray(model.weights)).max())
        w_err = float(np.linalg.norm(
            np.asarray(model_ss.weights) - w_true))
        print(f"config4-gram: sufficient_stats=True w_err={w_err:.4f} "
              f"(|w-w_stock|max={drift:.1e}, sliced windows) "
              f"({time.perf_counter() - t0:.1f}s)")
    # Meshed quasi-Newton variant (round 5, VERDICT r4 #5): the SAME
    # 8-way shape through LBFGS with zero schedule flags — the planner
    # decides the statistics substitution itself (per-shard totals +
    # psum; tpu_sgd/plan.py plan_quasi_newton).
    from tpu_sgd.models import LinearRegressionWithLBFGS

    t0 = time.perf_counter()
    alg_qn = LinearRegressionWithLBFGS(max_num_iterations=25)
    alg_qn.optimizer.set_mesh(mesh)
    model_qn = alg_qn.run((X, y))
    last_qn = alg_qn.optimizer.last_plan
    mode_qn = last_qn.schedule if last_qn is not None else "unplanned"
    w_err_qn = float(np.linalg.norm(
        np.asarray(model_qn.weights) - w_true))
    print(f"config4-lbfgs: {dict(mesh.shape)}-way (plan: {mode_qn}) "
          f"w_err={w_err_qn:.4f} ({time.perf_counter() - t0:.1f}s)")


def config5():
    d = 50
    w_true = np.linspace(-1, 1, d).astype(np.float32)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=25)
    alg.set_initial_weights(np.zeros(d, np.float32))
    t0 = time.perf_counter()
    errs = []
    for i in range(10):  # micro-batched DStream analogue
        Xb, yb, _ = linear_data(2_000, d, weights=w_true, eps=0.05, seed=10 + i)
        alg.train_on_batch(Xb, yb)
        errs.append(float(np.linalg.norm(
            np.asarray(alg.latest_model().weights) - w_true)))
    print(f"config5: 10 micro-batches w_err {errs[0]:.3f} -> {errs[-1]:.3f} "
          f"({time.perf_counter() - t0:.1f}s)")


if __name__ == "__main__":
    which, scale = _parse_args(sys.argv[1:])
    SMALL = scale == "small"
    fns = {"1": config1, "2": config2, "3": config3, "4": config4,
           "5": config5}
    for k, fn in fns.items():
        if which in (k, "all"):
            fn()
