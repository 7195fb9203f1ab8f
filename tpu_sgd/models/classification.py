"""Classification model families: logistic regression and linear SVM.

Reference parity: [U] mllib/classification/{LogisticRegression,SVM}.scala
(SURVEY.md §2 #7-#8).  Reference defaults mirrored: both use step=1.0,
iters=100, reg=0.01, frac=1.0 and the squared-L2 updater; config 3
(BASELINE.json:9) swaps the SVM's updater for L1 via
``svm.optimizer.set_updater(L1Updater())``.  Prediction thresholds are
mutable and clearable exactly like the reference (``clear_threshold`` makes
``predict`` return raw scores).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.models.glm import GeneralizedLinearAlgorithm, GeneralizedLinearModel
from tpu_sgd.ops.gradients import HingeGradient, LogisticGradient
from tpu_sgd.ops.updaters import SquaredL2Updater
from tpu_sgd.optimize.gradient_descent import GradientDescent


class _ThresholdedModel(GeneralizedLinearModel):
    _default_threshold = 0.5

    def __init__(self, weights, intercept: float = 0.0):
        super().__init__(weights, intercept)
        self.threshold = self._default_threshold

    def set_threshold(self, t: float):
        self.threshold = float(t)
        return self

    def clear_threshold(self):
        """After this, ``predict`` returns raw scores (reference parity)."""
        self.threshold = None
        return self

    def score(self, margin):
        raise NotImplementedError

    def predict_point(self, margin):
        s = self.score(margin)
        if self.threshold is None:
            return s
        return (s > self.threshold).astype(jnp.float32)


class LogisticRegressionModel(_ThresholdedModel):
    """Sigmoid score thresholded at 0.5 by default."""

    def score(self, margin):
        return jax.nn.sigmoid(margin)


class SVMModel(_ThresholdedModel):
    """Raw margin thresholded at 0.0 by default."""

    _default_threshold = 0.0

    def score(self, margin):
        return margin


def _save(model, path):
    from tpu_sgd.utils.persistence import save_glm_model

    save_glm_model(path, model)


def _load(cls, path):
    from tpu_sgd.utils.persistence import load_glm_model

    return load_glm_model(path, cls)


LogisticRegressionModel.save = _save
LogisticRegressionModel.load = classmethod(_load)
SVMModel.save = _save
SVMModel.load = classmethod(_load)


class _BinaryClassifierWithSGD(GeneralizedLinearAlgorithm):
    _gradient_cls = None
    _model_cls = None

    def __init__(
        self,
        step_size: float = 1.0,
        num_iterations: int = 100,
        reg_param: float = 0.01,
        mini_batch_fraction: float = 1.0,
    ):
        super().__init__()
        self.optimizer = (
            GradientDescent(self._gradient_cls(), SquaredL2Updater())
            .set_step_size(step_size)
            .set_num_iterations(num_iterations)
            .set_reg_param(reg_param)
            .set_mini_batch_fraction(mini_batch_fraction)
        )

    def validators(self, X, y):
        """Binary label validator ([U] DataValidators.binaryLabelValidator)."""
        bad = np.logical_and(y != 0.0, y != 1.0)
        if bad.any():
            raise ValueError(
                "Classification labels should be 0 or 1; found "
                f"{np.unique(np.asarray(y)[bad])[:5]}"
            )

    def create_model(self, weights, intercept):
        return self._model_cls(weights, intercept)

    @classmethod
    def train(
        cls,
        data,
        num_iterations: int = 100,
        step_size: float = 1.0,
        reg_param: float = 0.01,
        mini_batch_fraction: float = 1.0,
        initial_weights=None,
        intercept: bool = False,
        updater=None,
        mesh=None,
        sampling: str = None,
        host_streaming: bool = False,
        streaming_resident_rows: int = 0,
        schedule: str = None,
    ):
        alg = cls(step_size, num_iterations, reg_param, mini_batch_fraction)
        alg.set_intercept(intercept)
        if updater is not None:
            alg.optimizer.set_updater(updater)
        if mesh is not None:
            alg.optimizer.set_mesh(mesh)
        if sampling is not None:
            alg.optimizer.set_sampling(sampling)
        if host_streaming:
            alg.optimizer.set_host_streaming(
                True, resident_rows=streaming_resident_rows
            )
        if schedule is not None:
            # execution-schedule policy (tpu_sgd/plan.py): "auto" is the
            # default; a schedule name forces it, "off" disables planning
            alg.set_schedule(schedule)
        return alg.run(data, initial_weights)


class LogisticRegressionWithSGD(_BinaryClassifierWithSGD):
    """Binary logistic regression via SGD (config 2, BASELINE.json:8)."""

    _gradient_cls = LogisticGradient
    _model_cls = LogisticRegressionModel

    @classmethod
    def train(cls, data, num_iterations: int = 100, step_size: float = 1.0,
              mini_batch_fraction: float = 1.0, initial_weights=None,
              reg_param: float = 0.0, **kw):
        """Reference static parity ([U] object LogisticRegressionWithSGD):
        ``train(input, numIterations, stepSize, miniBatchFraction[,
        initialWeights])`` — ``miniBatchFraction`` is the FOURTH
        positional and the STATIC trains UNREGULARIZED (the reference's
        companion object hardcodes regParam 0.0; the class constructor
        keeps the 0.01 class default).  ``reg_param`` and the TPU-side
        extensions are keyword-only here.  (``SVMWithSGD.train`` keeps
        the base signature: the reference's SVM static takes regParam as
        its own fourth positional.)"""
        return super().train(
            data, num_iterations, step_size, reg_param=reg_param,
            mini_batch_fraction=mini_batch_fraction,
            initial_weights=initial_weights, **kw)


class SVMWithSGD(_BinaryClassifierWithSGD):
    """Linear SVM via hinge-loss SGD (config 3, BASELINE.json:9)."""

    _gradient_cls = HingeGradient
    _model_cls = SVMModel


class MultinomialLogisticRegressionModel(GeneralizedLinearModel):
    """K-class logistic model over a flat ``(K-1)*D`` weight vector with
    pivot class 0 (reference parity: ``LogisticRegressionModel`` with
    ``numClasses > 2``, [U] mllib/classification/LogisticRegression.scala).
    The intercept per class lives as the last per-class weight when trained
    with ``intercept=True`` (bias column convention)."""

    def __init__(self, weights, intercept: float = 0.0, num_classes: int = 2,
                 num_features: int = None, has_intercept_column: bool = False):
        super().__init__(weights, intercept)
        self.num_classes = int(num_classes)
        if num_features is None:
            num_features = self.weights.shape[-1] // (self.num_classes - 1)
        self.num_features = int(num_features)
        #: True when trained with a folded-in bias column; recorded
        #: explicitly so predict never guesses from input width.
        self.has_intercept_column = bool(has_intercept_column)

    def _check_width(self, width: int) -> None:
        expect = self.num_features - (1 if self.has_intercept_column else 0)
        if width != expect:
            raise ValueError(
                f"expected {expect}-feature input, got {width}"
            )

    def predict_dense_bucketed(self, X, buckets=None) -> np.ndarray:
        """The SINGLE home of the dense multinomial decision path —
        validation, bias column, per-class margins through the shared
        bucketed program (ops/bucketed.py), host-side pivot argmax
        (ops/gradients.py).  ``model.predict`` and the serving engine
        both route here, which is what makes serving results identical
        to ad-hoc prediction; the engine passes its own ``buckets``."""
        import jax.numpy as jnp

        from tpu_sgd.ops.gradients import pivot_class_host
        from tpu_sgd.ops.bucketed import DEFAULT_BUCKETS, bucketed_matvec

        X = np.atleast_2d(np.asarray(X))  # batch-shaped: (d,) scores as (1,)
        self._check_width(int(X.shape[-1]))
        if self.has_intercept_column:
            from tpu_sgd.utils.mlutils import append_bias

            X = append_bias(X)
        K = self.num_classes
        W = jnp.asarray(self.weights).reshape(K - 1, X.shape[-1])
        margins = bucketed_matvec(
            X, W.T, 0.0, DEFAULT_BUCKETS if buckets is None else buckets
        )
        return pivot_class_host(margins)

    def predict(self, X):
        import jax.core
        import jax.numpy as jnp

        from tpu_sgd.ops.gradients import MultinomialLogisticGradient
        from tpu_sgd.ops.sparse import (append_bias_auto, is_sparse,
                                        row_matrix_bcoo)

        sparse = is_sparse(X)
        tracer = (isinstance(X, jax.core.Tracer)
                  or isinstance(self.weights, jax.core.Tracer))
        if not sparse and tracer:
            X = jnp.asarray(X)
        single = (X.ndim if sparse or tracer else np.ndim(X)) == 1
        if sparse or tracer:
            # sparse batches and tracers (user jit/vmap/grad around
            # predict, over the input OR the weights) take the pure-jnp
            # rule; the bucketed host path below cannot trace
            Xb = row_matrix_bcoo(X) if sparse else jnp.atleast_2d(X)
            self._check_width(int(Xb.shape[-1]))
            if self.has_intercept_column:
                if sparse:
                    Xb = append_bias_auto(Xb)
                else:  # traced dense: append the bias column in-trace
                    # graftlint: disable=shape-trap -- tracer-only branch (guarded above): fuses into the user's jit, no eager compile
                    Xb = jnp.concatenate(
                        [Xb, jnp.ones((Xb.shape[0], 1), Xb.dtype)], axis=1
                    )
            g = MultinomialLogisticGradient(self.num_classes)
            out = g.predict_class(Xb, self.weights)
        else:
            # concrete dense input: stay host-side (the bucketed program
            # pads in numpy; a device round-trip here is pure waste)
            out = jnp.asarray(
                self.predict_dense_bucketed(np.atleast_2d(np.asarray(X)))
            )
        return out[0] if single else out


MultinomialLogisticRegressionModel.save = _save
MultinomialLogisticRegressionModel.load = classmethod(_load)


class LogisticRegressionWithLBFGS(GeneralizedLinearAlgorithm):
    """Logistic regression via L-BFGS, binary or multinomial.

    Reference parity: [U] mllib/classification/LogisticRegression.scala's
    ``LogisticRegressionWithLBFGS`` — same user API as the SGD variant, with
    the L-BFGS optimizer (SURVEY.md §2 #18) behind the same boundary and
    ``set_num_classes(K)`` switching to the multinomial gradient (pivot
    class 0, ``(K-1)*D`` weights), as the reference's does.
    """

    def __init__(
        self,
        num_corrections: int = 10,
        convergence_tol: float = 1e-6,
        max_num_iterations: int = 100,
        reg_param: float = 0.0,
    ):
        super().__init__()
        from tpu_sgd.optimize.lbfgs import LBFGS

        self.num_classes = 2
        self.optimizer = LBFGS(
            LogisticGradient(),
            SquaredL2Updater(),
            num_corrections=num_corrections,
            convergence_tol=convergence_tol,
            max_num_iterations=max_num_iterations,
            reg_param=reg_param,
        )

    def set_num_classes(self, k: int):
        from tpu_sgd.ops.gradients import MultinomialLogisticGradient

        if k < 2:
            raise ValueError("num_classes must be >= 2")
        self.num_classes = int(k)
        if k == 2:
            self.optimizer.set_gradient(LogisticGradient())
        else:
            self.optimizer.set_gradient(MultinomialLogisticGradient(k))
        return self

    def validators(self, X, y):
        yv = np.asarray(y)
        bad = (yv < 0) | (yv >= self.num_classes) | (yv != np.floor(yv))
        if bad.any():
            raise ValueError(
                f"Classification labels should be integers in [0, "
                f"{self.num_classes}); found {np.unique(yv[bad])[:5]}"
            )

    def _weight_dim(self) -> int:
        if self.num_classes == 2:
            return self.num_features
        return (self.num_classes - 1) * self.num_features

    def run(self, data, initial_weights=None, initial_intercept: float = 0.0):
        if self.num_classes > 2 and self.add_intercept:
            # The bias-column trick gives each class its own intercept as the
            # last per-class weight; the harness's scalar split doesn't apply.
            X, y = data if isinstance(data, tuple) else (None, None)
            if X is None:
                from tpu_sgd.models.labeled_point import to_arrays

                X, y = to_arrays(data)
            from tpu_sgd.ops.sparse import append_bias_auto, is_sparse

            if not is_sparse(X):
                X = np.asarray(X)
            if X.shape[0] == 0:
                raise ValueError("empty input")
            d = X.shape[1]
            scaler = None
            if self.use_feature_scaling:
                # Same scale->train->rescale pass as the harness ([U] GLA.run
                # useFeatureScaling), applied before the bias column so each
                # class's intercept slot stays unscaled.
                from tpu_sgd.feature import StandardScaler

                scaler = StandardScaler(with_mean=False, with_std=True).fit(X)
                X = scaler.transform(X)  # host input stays on host
            X = append_bias_auto(X)
            K = self.num_classes
            if initial_weights is None:
                w0 = np.zeros(((K - 1), d), np.float32)
                has_bias_slots = False
            else:
                # Accept BOTH layouts: (K-1)*d (fresh weights, bias slots
                # added here) and (K-1)*(d+1) (a trained intercept model's
                # own weights — the warm-start/continuation contract:
                # run_warm passes model.weights straight back in).
                w0 = np.asarray(initial_weights, np.float32)
                if w0.size == (K - 1) * (d + 1):
                    w0 = w0.reshape(K - 1, d + 1)
                    has_bias_slots = True
                elif w0.size == (K - 1) * d:
                    w0 = w0.reshape(K - 1, d)
                    has_bias_slots = False
                else:
                    raise ValueError(
                        f"initial_weights has size {w0.size} but expected "
                        f"{(K - 1) * d} ((num_classes-1) * num_features) "
                        f"or {(K - 1) * (d + 1)} (with per-class bias "
                        "slots, e.g. a trained intercept model's weights)"
                    )
            if scaler is not None:
                # User initial weights arrive in original space; the inverse
                # of the weight-rescale below moves them into scaled space
                # (feature slots only — bias slots are unscaled).
                std = np.asarray(scaler.std)
                if has_bias_slots:
                    w0 = w0.copy()
                    w0[:, :d] = w0[:, :d] * std[None, :]
                else:
                    w0 = np.asarray(w0 * std[None, :], np.float32)
            if not has_bias_slots:
                bias0 = np.full((K - 1, 1), float(initial_intercept),
                                np.float32)
                w0 = np.concatenate([w0, bias0], axis=1)
            w0 = np.asarray(w0, np.float32).reshape(-1)
            if self.validate_data:
                self.validators(X, y)
            # the schedule contract holds on this branch too: zero-flag
            # runs auto-plan, set_schedule forces or raises — exactly as
            # the harness path does
            self._auto_plan(X, np.asarray(y))
            weights = self._optimize(X, np.asarray(y), w0)
            if scaler is not None:
                W = np.array(weights, np.float32).reshape(K - 1, d + 1)
                W[:, :d] = W[:, :d] * np.asarray(scaler.factor)[None, :]
                weights = W.reshape(-1)
            return MultinomialLogisticRegressionModel(
                weights, 0.0, self.num_classes, X.shape[1],
                has_intercept_column=True,
            )
        return super().run(data, initial_weights, initial_intercept)

    def create_model(self, weights, intercept):
        if self.num_classes > 2:
            return MultinomialLogisticRegressionModel(
                weights, intercept, self.num_classes, self.num_features
            )
        return LogisticRegressionModel(weights, intercept)

    @classmethod
    def train(cls, data, max_num_iterations: int = 100, reg_param: float = 0.0,
              initial_weights=None, intercept: bool = False,
              num_classes: int = 2, mesh=None):
        alg = cls(max_num_iterations=max_num_iterations, reg_param=reg_param)
        alg.set_intercept(intercept)
        alg.set_num_classes(num_classes)
        if mesh is not None:
            alg.optimizer.set_mesh(mesh)
        return alg.run(data, initial_weights)
