"""Generalized linear model harness.

Reference parity: [U] mllib/regression/GeneralizedLinearAlgorithm.scala
(SURVEY.md §2 #5, §1 L5).  Owns exactly what the reference's harness owns:
input validation, feature-count discovery, intercept handling (bias appended
as the LAST column, parity with ``MLUtils.appendBias``), calling
``optimizer.optimize``, splitting the intercept back out, and
``create_model``.  Models own prediction; training always flows through
``run``.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.models.labeled_point import LabeledPoint, to_arrays
from tpu_sgd.obs.builds import root
from tpu_sgd.obs.spans import span
from tpu_sgd.ops.sparse import append_bias_auto, is_sparse, row_matrix_bcoo
from tpu_sgd.optimize.gradient_descent import GradientDescent, StagedAhead
from tpu_sgd.optimize.optimizer import Optimizer

DatasetLike = Union[Tuple, Iterable[LabeledPoint]]


def as_features(X):
    """Features as ``run`` takes them: BCOO passes through undensified, an
    array that is on the device stays there (``np.asarray`` would fetch all
    of it) and so does a stream's micro-batch that went ahead of its fit
    (``StagedAhead``: it answers ``shape`` and ``dtype`` as its rows would,
    and is planned as they would be); anything else is a numpy array."""
    if is_sparse(X) or isinstance(X, (jax.Array, StagedAhead)):
        return X
    return np.asarray(X)


def off_stock(optimizer) -> bool:
    """Whether a schedule flag other than the stock resident schedule's is
    set on ``optimizer``, by its user or by a plan."""
    return bool(getattr(optimizer, "host_streaming", False)
                or getattr(optimizer, "sufficient_stats", False)
                or getattr(optimizer, "streamed_stats", False))


def _as_arrays(data: DatasetLike) -> Tuple[np.ndarray, np.ndarray]:
    if isinstance(data, tuple) and len(data) == 2:
        X, y = data
        return as_features(X), y if isinstance(y, jax.Array) \
            else np.asarray(y)
    return to_arrays(data)


class GeneralizedLinearModel:
    """Weights + intercept + prediction rule (abstract ``predict_point``)."""

    def __init__(self, weights, intercept: float = 0.0):
        self.weights = jnp.asarray(weights)
        self.intercept = float(intercept)

    def _margin(self, X):
        if not is_sparse(X):
            import jax.core
            from tpu_sgd.ops.bucketed import DEFAULT_BUCKETS

            if (not isinstance(X, jax.core.Tracer)
                    and not isinstance(self.weights, jax.core.Tracer)
                    and np.ndim(X) == 2
                    and 0 < np.shape(X)[0] <= DEFAULT_BUCKETS[-1]):
                # Canonical shape-bucketed margin program (ops/bucketed.py):
                # pads the row count to a fixed bucket set and reuses one
                # compiled program per bucket, so ad-hoc predict and the
                # serving endpoint score the same batch through the SAME
                # executable — bitwise-identical dense predictions, and no
                # per-batch-size recompiles.  Tracers (a user's jit/vmap/
                # grad around predict, over the input OR the weights) stay
                # on the pure-jnp path below — the host-side pad cannot
                # trace.
                from tpu_sgd.ops.bucketed import bucketed_matvec

                return jnp.asarray(
                    bucketed_matvec(X, self.weights, self.intercept)
                )
            # tracers, empty input, and beyond-max-bucket batches (the
            # training-scale case) stay pure device: one eager matmul at
            # the natural shape, no host round-trip
            X = jnp.asarray(X)
        return X @ self.weights + self.intercept

    def predict_margin(self, X):
        """Raw margin(s) ``x.w + b`` for a single vector or a batch; always
        returns a batch-shaped result (a single vector yields shape (1,))."""
        import jax.core

        if is_sparse(X):
            return self._margin(row_matrix_bcoo(X))
        if isinstance(X, jax.core.Tracer):
            return self._margin(jnp.atleast_2d(X))
        if np.ndim(X) == 1:
            # a single row is tiny: shape it host-side for the bucketed
            # path (2-D inputs pass through untouched — _margin decides
            # device vs host by batch size without materializing)
            return self._margin(np.atleast_2d(np.asarray(X)))
        return self._margin(X)

    def predict_point(self, margin):
        raise NotImplementedError

    def predict(self, X):
        """Predict for one feature vector or a batch (parity with the
        reference's ``predict(Vector)`` / ``predict(RDD[Vector])``); accepts
        dense arrays or sparse (BCOO) features."""
        single = np.ndim(X) == 1  # attribute-based: no device transfer
        out = self.predict_point(self.predict_margin(X))
        return out[0] if single else out

    def predict_streamed(self, X, batch_rows: int = 1_000_000) -> np.ndarray:
        """Chunked prediction for host-resident matrices beyond device HBM
        — the analogue of the reference's ``predict(RDD[Vector])`` scoring
        partitions executor-side ([U] GeneralizedLinearModel, SURVEY.md §2
        #5): each chunk is transferred, scored on device, and materialized
        back to host memory before the next chunk moves, so peak device
        memory is one ``batch_rows`` block regardless of ``len(X)``."""
        if batch_rows <= 0:
            raise ValueError(f"batch_rows must be positive, got {batch_rows}")
        if not is_sparse(X):  # BCOO chunks by row slicing, undensified
            X = np.asarray(X)
        if X.ndim == 1:
            return np.asarray(self.predict(X))
        outs = [
            np.asarray(self.predict(X[s:s + batch_rows]))
            for s in range(0, X.shape[0], batch_rows)
        ]
        return (
            np.concatenate(outs) if outs
            else np.zeros((0,), np.float32)
        )

    def __repr__(self):
        return (
            f"{type(self).__name__}(numFeatures={self.weights.shape[-1]}, "
            f"intercept={self.intercept})"
        )


class GeneralizedLinearAlgorithm:
    """Shared training harness; subclasses provide optimizer + create_model."""

    #: subclasses set an Optimizer instance
    optimizer: Optimizer = None

    def __init__(self):
        self.add_intercept = False
        self.validate_data = True
        self.num_features = -1
        self.use_feature_scaling = False
        self.schedule = "auto"

    # -- fluent config, parity with the reference's setters ----------------
    def set_intercept(self, flag: bool):
        self.add_intercept = bool(flag)
        return self

    def set_validate_data(self, flag: bool):
        self.validate_data = bool(flag)
        return self

    def set_feature_scaling(self, flag: bool):
        """Scale features to unit column std before optimizing, then map the
        weights back to original space — the reference harness's hidden
        ``useFeatureScaling`` pass ([U] GeneralizedLinearAlgorithm.run, which
        its LBFGS-backed classifier switches on to condition the Hessian
        approximation).  Deliberate deviation: the reference hard-enables
        this for ``LogisticRegressionWithLBFGS``; here it is opt-in on every
        family so round-2 trajectories stay bit-identical, and because with
        ``reg_param > 0`` scaling changes the optimum (regularization is
        applied in scaled space, reference behavior)."""
        self.use_feature_scaling = bool(flag)
        return self

    def set_num_features(self, n: int):
        self.num_features = int(n)
        return self

    def set_schedule(self, mode: str):
        """Execution-schedule policy (``tpu_sgd/plan.py`` — the scheduler
        analogue of the reference's DAGScheduler + ``cache()``, SURVEY.md
        §2 #16).  ``"auto"`` (default): when no manual schedule flag is
        set on the optimizer, ``run`` probes (shape, dtype, gradient
        family, sampling, free device memory) and picks the measured-best
        schedule, logging one ``plan: ...`` line on the
        ``tpu_sgd.plan`` logger.  A schedule name
        (``resident_stock`` / ``resident_gram`` / ``partial_residency`` /
        ``host_streamed`` / ``streamed_virtual_gram``) forces that
        schedule (with a warning when the estimate says it loses).
        ``"off"``: never plan — the optimizer runs exactly as configured.
        Manual optimizer flags (``set_host_streaming``,
        ``set_sufficient_stats``, ``set_streamed_stats``) always win over
        ``"auto"``."""
        valid = ("auto", "off")
        from tpu_sgd.plan import SCHEDULES

        if mode not in valid + SCHEDULES:
            raise ValueError(
                f"schedule must be one of {valid + SCHEDULES}, got {mode!r}"
            )
        self.schedule = mode
        return self

    def _auto_plan(self, X, y) -> None:
        """Apply the execution planner per ``set_schedule``; called by
        ``run`` on the exact matrix the optimizer will see (post scaling
        and intercept append)."""
        if self.schedule == "off":
            return
        with span("fit.plan") as sp:
            cached = self._apply_plan(X, y)
            plan = getattr(self.optimizer, "last_plan", None)
            sp.set(cached=cached,
                   schedule="unplanned" if plan is None else plan.schedule)

    def _apply_plan(self, X, y) -> bool:
        """``_auto_plan``'s work; True when the repeat-run ``_plan_key``
        hit skipped the probe and the plan."""
        opt = self.optimizer
        manual = off_stock(opt)
        # Flags set by a PREVIOUS plan (last_plan is not None) are the
        # planner's own and must not block re-planning for a new dataset;
        # the manual setters clear last_plan, so user-set flags — whenever
        # set, including after an auto-planned run — always win.
        if (self.schedule == "auto" and manual
                and getattr(opt, "last_plan", None) is None):
            return False  # explicit optimizer flags win
        import numpy as np

        from tpu_sgd.plan import logger, plan_for, plan_quasi_newton
        from tpu_sgd.optimize.lbfgs import LBFGS as _LBFGS

        force = None if self.schedule == "auto" else self.schedule
        # Identically-shaped repeat runs (the streaming mode's thousands
        # of micro-batches) skip the probe + plan + log entirely.
        key = (np.shape(X), str(getattr(X, "dtype", "")), force,
               getattr(opt, "config", None), opt.mesh,
               getattr(opt, "max_num_iterations", None))
        if (getattr(opt, "last_plan", None) is not None
                and getattr(opt, "_plan_key", None) == key):
            return True
        if isinstance(opt, _LBFGS):
            # quasi-Newton optimizers plan a narrower menu: stock
            # full-batch passes, the sufficient-stats substitution, or
            # (beyond HBM) the streamed-virtual-statistics schedule
            p = plan_quasi_newton(opt, X, y, force=force)
            if p is not None:
                p.apply_quasi_newton(opt)
        else:
            p = plan_for(opt, X, y, force=force)
            if p is not None:
                p.apply(opt)
        if p is not None:
            opt._plan_key = key
            logger.info(p.describe())
        elif getattr(opt, "last_plan", None) is not None:
            # Un-plannable input (sparse/BCOO, GramData, model mesh) after
            # a planned run: the PREVIOUS plan's schedule flags are the
            # planner's own and must not leak onto this dataset (e.g. a
            # stale host_streaming=True would crash a zero-flag user on
            # BCOO input) — reset to stock via the optimizers' own
            # clearing hook (one flag list, not three hand-rolled
            # copies).
            opt._clear_planned_schedule()  # flags AND plan-owned knobs
            opt.last_plan = None
            opt._plan_key = None
        if p is None and force is not None:
            raise ValueError(
                f"schedule={force!r} cannot be applied here: this "
                "optimizer/input is not planned (sparse/BCOO or GramData "
                "input, a 2-D data x model mesh, or an optimizer without "
                "schedules) — configure it directly with the optimizer "
                "setters instead"
            )
        return False

    # -- hooks -------------------------------------------------------------
    def create_model(self, weights, intercept) -> GeneralizedLinearModel:
        raise NotImplementedError

    def validators(self, X: np.ndarray, y: np.ndarray) -> None:
        """Input validation hook; classifier subclasses check label sets."""

    # -- training ----------------------------------------------------------
    def _optimize(self, X, y, w0):
        """``run``'s call into its optimizer, the one place it is made.
        Under ``run`` integer input is trained in float32, cast after the
        copy.  ``GradientDescent``, which at its own boundary trains 8-bit
        integer rows as the integers they are, is told so with the call;
        ``LBFGS``, ``OWLQN`` and ``NormalEquations`` compute integer rows
        of every width in float32 themselves; any other optimizer is
        handed them as they came, as ever."""
        if (isinstance(self.optimizer, GradientDescent) and not is_sparse(X)
                and not jnp.issubdtype(X.dtype, jnp.inexact)):
            return self.optimizer._fit((X, y), w0, integers_f32=True)[0]
        return self.optimizer.optimize((X, y), w0)

    def run(
        self,
        data: DatasetLike,
        initial_weights=None,
        initial_intercept: float = 0.0,
    ) -> GeneralizedLinearModel:
        with span("fit.run") as run_span, root("fit.run", run_span):
            with span("fit.validate") as sp:
                X, y = _as_arrays(data)
                # a stream's micro-batch at a row capacity says its own rows
                rows = X.rows if isinstance(X, StagedAhead) else X.shape[0]
                sp.set(rows=rows)
                if X.shape[0] == 0:
                    raise ValueError("empty input")
                if self.num_features < 0:
                    self.num_features = X.shape[1]
                if self.validate_data:
                    self.validators(X, y)
            run_span.set(rows=rows, features=X.shape[1],
                         sparse=is_sparse(X))
            if initial_weights is None:
                initial_weights = np.zeros((self._weight_dim(),), np.float32)
            w0 = initial_weights
            if (self.use_feature_scaling or self.add_intercept
                    or not isinstance(w0, jax.Array)
                    or w0.dtype != np.float32):
                # float32 weights that lie on the device and are handed on
                # as they are (a stream's warm start: the last fit's) stay
                # there: fetched and sent again they would wait, a few KB,
                # behind whatever else is on the wire
                w0 = np.asarray(w0, np.float32)
            scaler = None
            if self.use_feature_scaling or self.add_intercept:
                with span("fit.prepare", rows=rows):
                    if self.use_feature_scaling:
                        X, w0, scaler = self._scale_features(X, w0)
                    if self.add_intercept:
                        # Bias appended as the LAST column ([U]
                        # MLUtils.appendBias; SURVEY.md §3.1 intercept
                        # prepend/split).
                        X = append_bias_auto(X)
                        w0 = np.concatenate(
                            [w0, np.asarray([initial_intercept], np.float32)])
            self._auto_plan(X, y)
            weights = self._optimize(X, y, w0)
            with span("fit.finish"):
                intercept = 0.0
                if self.add_intercept:
                    intercept = float(weights[-1])
                    weights = weights[:-1]
                if scaler is not None:
                    # Same trick as the reference: transform() maps trained
                    # weights back to original space (margin w'.(x/std) ==
                    # (w'/std).x); flat stacked (multinomial) weights go
                    # block-wise.
                    d = int(np.asarray(scaler.std).shape[0])
                    weights = scaler.transform(
                        jnp.asarray(weights).reshape(-1, d)
                    ).reshape(jnp.asarray(weights).shape)
                return self.create_model(weights, intercept)

    @staticmethod
    def _scale_features(X, w0):
        """``(scaled X, w0 in scaled space, the fitted scaler)``.

        Fit BEFORE the bias column exists (the reference scales raw
        features, then appends the bias to the scaled matrix); user
        initial weights arrive in ORIGINAL space, so they move into
        scaled space by the inverse map (w * std) — an improvement on
        the reference, whose warm starts silently stay unscaled.  Flat
        stacked weights (the multinomial (K-1)*d layout) rescale per
        d-sized block."""
        from tpu_sgd.feature import StandardScaler

        scaler = StandardScaler(with_mean=False, with_std=True).fit(X)
        # host numpy input stays on host inside transform (the device
        # round-trip would triple the transfer); device and sparse
        # inputs keep their layout
        X = scaler.transform(X)
        d = int(np.asarray(scaler.std).shape[0])
        w0 = np.asarray(
            (w0.reshape(-1, d) * np.asarray(scaler.std)[None, :])
            .reshape(w0.shape),
            np.float32,
        )
        return X, w0, scaler

    def _weight_dim(self) -> int:
        return self.num_features

    def run_warm(self, data: DatasetLike, model: Optional[GeneralizedLinearModel]):
        """Warm-started run used by the streaming mode (SURVEY.md §3.3):
        re-run the batch optimizer seeded with the latest weights AND
        intercept (improves on the reference, which re-seeds the intercept)."""
        if model is None:
            return self.run(data)
        return self.run(data, model.weights, model.intercept)
