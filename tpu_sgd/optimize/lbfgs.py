"""L-BFGS optimizer behind the same plugin boundary.

Reference parity: [U] mllib/optimization/LBFGS.scala (SURVEY.md §2 #18):
``LBFGS(gradient, updater)`` is the alternative ``Optimizer`` that proves the
boundary is real.  Semantics mirrored: full-batch cost function
``loss_sum / n + regVal(w)`` (reg term and its gradient derived from the
updater family exactly as the reference's ``CostFun`` does for
``SquaredL2Updater``), ``num_corrections`` two-loop recursion, convergence on
relative loss improvement, loss history returned alongside weights.

TPU-first shape: the cost function is one fused batched matvec pass (the same
``Gradient.batch_sums`` the SGD path uses, so the MXU kernel is shared); the
two-loop recursion runs on-device over the correction history; only the
line-search control flow is host-side (it is data-dependent and tiny).

Distribution: ``set_mesh`` shards the cost function's batch sums row-wise
over a 1-D data mesh with one ``lax.psum`` over ICI — the analogue of the
reference's ``CostFun`` running through ``treeAggregate`` ([U]
mllib/optimization/LBFGS.scala, distributed by construction).  The whole
backtracking ladder is evaluated as ONE batched multi-weight loss sweep
(X is read once for all trial points; the host syncs once per iteration
instead of once per trial — crucial over a high-latency device link).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS
from tpu_sgd.ops.sparse import is_sparse
from tpu_sgd.ops.updaters import (
    L1Updater,
    SimpleUpdater,
    SquaredL2Updater,
    Updater,
)
from tpu_sgd.optimize.optimizer import Dataset, Optimizer

Array = jax.Array


def _reg_terms(updater: Updater, reg_param: float):
    """(reg_value(w), reg_grad(w)) matching the reference's CostFun handling
    of each updater family."""
    if isinstance(updater, SquaredL2Updater):
        return (
            lambda w: 0.5 * reg_param * jnp.sum(w * w),
            lambda w: reg_param * w,
        )
    if isinstance(updater, L1Updater):
        # Subgradient; the reference steers L1 users to OWL-QN, but accepts
        # this for parity testing at small reg.
        return (
            lambda w: reg_param * jnp.sum(jnp.abs(w)),
            lambda w: reg_param * jnp.sign(w),
        )
    return (lambda w: jnp.zeros((), w.dtype), lambda w: jnp.zeros_like(w))


def _warn_sequential_line_search(gradient, n_trials):
    """Tell the user their gradient lacks the ``loss_sweep`` protocol, so
    the Armijo backtracking runs one device call + host sync PER TRIAL (up
    to ``n_trials`` per iteration) instead of one fused multi-weight pass
    with a single sync — ruinous over a high-latency device link.  Every
    shipped gradient implements the sweep; this fires only for
    user-supplied exotics (cf. [U] LBFGS.scala's one-treeAggregate-per-
    iteration CostFun economy, SURVEY.md §2 #18)."""
    import warnings

    warnings.warn(
        f"{type(gradient).__name__} has no loss_sweep(X, y, W, mask) "
        "method, so the line search falls back to SEQUENTIAL trials — up "
        f"to {n_trials} device calls + host syncs per iteration instead "
        "of one batched sweep.  Implement loss_sweep (losses of a (T, d) "
        "stack of trial weights in one pass — see "
        "tpu_sgd.ops.gradients.LeastSquaresGradient.loss_sweep) to fuse "
        "the ladder.",
        RuntimeWarning,
        stacklevel=3,
    )


def _coerce_inputs(X, y, w, defer_commit: bool = False):
    """Shared (X, y, w) -> inexact arrays coercion for the quasi-Newton
    optimizers.  BCOO feature matrices and GramData statistics bundles
    pass through untouched (the fused cost dispatches to the sparse
    lowering / the sufficient-stats totals respectively).

    ``defer_commit`` (meshed runs): leave dense host (X, y) as
    dtype-coerced NUMPY arrays — ``jnp.asarray`` would commit the whole
    matrix to the DEFAULT device first, which OOMs for data larger than
    one device's HBM, exactly the regime the mesh serves.  The sharded
    placement (``shard_dataset`` / the per-shard statistics builders)
    then transfers each shard straight to its own device.  Already-
    committed ``jax.Array`` inputs keep their placement either way."""
    import numpy as np

    from tpu_sgd.ops.gram import GramData

    def to_inexact(a):
        # ONE dtype policy for both namespaces: deferred host arrays
        # stay numpy, everything else commits via jnp
        xp = (np if defer_commit and not isinstance(a, jax.Array)
              else jnp)
        a = xp.asarray(a)
        if not jnp.issubdtype(a.dtype, jnp.inexact):
            a = a.astype(xp.float32)
        return a

    if not is_sparse(X) and not isinstance(X, GramData):
        X = to_inexact(X)
    y = to_inexact(y)
    w = jnp.asarray(w)
    if not jnp.issubdtype(w.dtype, jnp.inexact):
        w = w.astype(jnp.float32)
    return X, y, w


def _wrap_mesh(mesh, body, n_weight_args, with_valid, n_outs,
               sparse=False):
    """Jit ``body`` — plain, or shard_mapped over the 1-D data mesh with
    the first ``n_weight_args`` args replicated and (X, y[, valid]) row-
    sharded; outputs replicated (the psum inside ``body`` makes them so).
    ``sparse``: X arrives as sharded BCOO component arrays ``(data, idx)``
    (see parallel/sparse_parallel.py) instead of a dense row block."""
    if mesh is None:
        return jax.jit(body)
    from jax.sharding import PartitionSpec as P

    from tpu_sgd.parallel.mesh import DATA_AXIS, shard_map_fn

    x_spec = (
        (P(DATA_AXIS), P(DATA_AXIS, None)) if sparse else P(DATA_AXIS, None)
    )
    in_specs = (P(),) * n_weight_args + (x_spec, P(DATA_AXIS))
    if with_valid:
        in_specs = in_specs + (P(DATA_AXIS),)
    out_specs = P() if n_outs == 1 else (P(),) * n_outs
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def _maybe_bcoo(X, sparse_shape):
    """Reassemble a shard's ``(data, idx)`` components into its local BCOO
    block inside the shard_map body; dense X passes through."""
    if sparse_shape is None:
        return X
    from tpu_sgd.parallel.sparse_parallel import local_bcoo

    return local_bcoo(X[0], X[1], *sparse_shape)


def _build_cost(gradient, reg_value, reg_grad, mesh, with_valid,
                sparse_shape=None):
    """``cost(w, X, y[, valid]) -> (f, g)``: full objective and gradient,
    one fused pass, psum'd per shard under a mesh (the treeAggregate-CostFun
    analogue)."""

    def body(w, X, y, valid=None):
        X = _maybe_bcoo(X, sparse_shape)
        g_sum, l_sum, c = gradient.batch_sums(X, y, w, mask=valid)
        if mesh is not None:
            from tpu_sgd.parallel.mesh import DATA_AXIS

            g_sum, l_sum, c = jax.lax.psum((g_sum, l_sum, c), DATA_AXIS)
        return l_sum / c + reg_value(w), g_sum / c + reg_grad(w)

    if not with_valid:  # fixed arity for shard_map specs
        full = body
        body = lambda w, X, y: full(w, X, y)
    return _wrap_mesh(mesh, body, 1, with_valid, 2,
                      sparse=sparse_shape is not None)


def _build_loss_only(gradient, reg_value, mesh, with_valid,
                     sparse_shape=None):
    """``loss(w, X, y[, valid]) -> f``: objective WITHOUT the gradient as a
    compiled output, so XLA dead-code-eliminates the ``coeffᵀ @ X`` matmul —
    half the HBM traffic of the fused cost.  Used for line-search trials of
    matrix-weight gradients (``cost(...)[0]`` would keep the matmul live)."""

    def body(w, X, y, valid=None):
        X = _maybe_bcoo(X, sparse_shape)
        _, l_sum, c = gradient.batch_sums(X, y, w, mask=valid)
        if mesh is not None:
            from tpu_sgd.parallel.mesh import DATA_AXIS

            l_sum, c = jax.lax.psum((l_sum, c), DATA_AXIS)
        return l_sum / c + reg_value(w)

    if not with_valid:
        full = body
        body = lambda w, X, y: full(w, X, y)
    return _wrap_mesh(mesh, body, 1, with_valid, 1,
                      sparse=sparse_shape is not None)


def _build_loss_sweep(gradient, reg_value, mesh, with_valid,
                      sparse_shape=None):
    """``sweep(W, X, y[, valid]) -> (T,)`` objective values of T trial
    weight vectors in ONE fused pass: the gradient's ``loss_sweep`` rule
    reads X once for the entire backtracking ladder (a single MXU matmul)
    vs T separate matvecs (and T host syncs) for a scalar line search.
    Covers vector weights (derived from ``pointwise``) AND matrix weights
    (``MultinomialLogisticGradient.loss_sweep``'s stacked-class matmul)."""

    def body(W, X, y, valid=None):
        X = _maybe_bcoo(X, sparse_shape)
        l_sum, c = gradient.loss_sweep(X, y, W, mask=valid)
        if mesh is not None:
            from tpu_sgd.parallel.mesh import DATA_AXIS

            l_sum, c = jax.lax.psum((l_sum, c), DATA_AXIS)
        return l_sum / c + jax.vmap(reg_value)(W)

    if not with_valid:
        full = body
        body = lambda W, X, y: full(W, X, y)
    return _wrap_mesh(mesh, body, 1, with_valid, 1,
                      sparse=sparse_shape is not None)


def _shard_for_mesh(mesh, X, y):
    """Shard (X, y) over the data mesh: dense rows via ``shard_dataset``,
    BCOO via equal-nse component blocks (``shard_bcoo``) — the distributed-
    sparse CostFun analogue.  Returns ``(X, y, valid, sparse_shape)`` where
    dense X keeps ``sparse_shape=None`` and sparse X becomes the component
    tuple ``(data, idx)``."""
    from tpu_sgd.ops.gram import GramData

    if isinstance(X, GramData):
        raise NotImplementedError(
            "GramData input supports unmeshed quasi-Newton runs (the "
            "statistics already live on one device); drop set_mesh"
        )
    if is_sparse(X):
        from tpu_sgd.parallel.sparse_parallel import shard_bcoo

        data, idx, y, valid, rows_local, d = shard_bcoo(mesh, X, y)
        return (data, idx), y, valid, (rows_local, d)
    from tpu_sgd.parallel.data_parallel import shard_dataset

    X, y, valid = shard_dataset(mesh, X, y)
    return X, y, valid, None


def _reject_model_axis(mesh, who: str):
    from tpu_sgd.parallel.mesh import has_model_axis

    if has_model_axis(mesh):
        raise ValueError(
            f"{who} shards rows over a 1-D 'data' mesh; a 2-D (data, "
            "model) mesh would silently replicate X across the model "
            "axis — use a data-only mesh"
        )


def _push_correction(s_stack, y_stack, rho, k, m, s, yv, sy):
    """Append a curvature pair to the fixed-size history (shift when full);
    shared by LBFGS and OWLQN.  Returns updated (s_stack, y_stack, rho, k)."""
    if k < m:
        return (
            s_stack.at[k].set(s),
            y_stack.at[k].set(yv),
            rho.at[k].set(1.0 / sy),
            k + 1,
        )
    return (
        jnp.roll(s_stack, -1, axis=0).at[m - 1].set(s),
        jnp.roll(y_stack, -1, axis=0).at[m - 1].set(yv),
        jnp.roll(rho, -1).at[m - 1].set(1.0 / sy),
        k,
    )


@jax.jit
def _two_loop(g, s_stack, y_stack, rho, k):
    """Standard L-BFGS two-loop recursion over a fixed-size history buffer
    holding ``k`` valid corrections (rows [0, k)).  Module-level jit: one
    compile per history/weight shape across every optimize() call (the
    streaming mode re-enters per micro-batch)."""
    m = s_stack.shape[0]

    def bwd(carry, idx):
        q, alphas = carry
        valid = idx < k
        alpha = jnp.where(valid, rho[idx] * jnp.dot(s_stack[idx], q), 0.0)
        q = q - alpha * y_stack[idx]
        return (q, alphas.at[idx].set(alpha)), None

    (q, alphas), _ = jax.lax.scan(
        bwd, (g, jnp.zeros((m,), g.dtype)), jnp.arange(m - 1, -1, -1)
    )
    # initial Hessian scaling gamma = s.y / y.y of newest correction
    newest = jnp.maximum(k - 1, 0)
    gamma = jnp.where(
        k > 0,
        jnp.dot(s_stack[newest], y_stack[newest])
        / jnp.maximum(jnp.dot(y_stack[newest], y_stack[newest]), 1e-10),
        1.0,
    )
    r = gamma * q

    def fwd(r, idx):
        valid = idx < k
        beta = jnp.where(valid, rho[idx] * jnp.dot(y_stack[idx], r), 0.0)
        r = r + (alphas[idx] - beta) * s_stack[idx]
        return r, None

    r, _ = jax.lax.scan(fwd, r, jnp.arange(m))
    return r


class LBFGS(Optimizer):
    """Limited-memory BFGS with backtracking Armijo line search."""

    planned_by = "plan_quasi_newton"

    def __init__(
        self,
        gradient: Gradient = None,
        updater: Updater = None,
        num_corrections: int = 10,
        convergence_tol: float = 1e-6,
        max_num_iterations: int = 100,
        reg_param: float = 0.0,
    ):
        from tpu_sgd.ops.gradients import LeastSquaresGradient

        self.gradient = gradient if gradient is not None else LeastSquaresGradient()
        self.updater = updater if updater is not None else SimpleUpdater()
        self.num_corrections = num_corrections
        self.convergence_tol = convergence_tol
        self.max_num_iterations = max_num_iterations
        self.reg_param = reg_param
        self.mesh = None
        self.sufficient_stats = False
        self.streamed_stats = False
        self.host_streaming = False
        self.stream_batch_rows = None
        self.gram_block_rows = DEFAULT_BLOCK_ROWS
        self.gram_batch_rows = None
        #: ingest-pipeline knobs (tpu_sgd/io; set_ingest_options) — the
        #: streamed statistics builds feed through the shared prefetcher
        self.ingest_wire_dtype = None
        self.ingest_prefetch_depth = 2
        self.ingest_pipeline = True
        self.ingest_retry_policy = None
        #: compressed update wire (tpu_sgd/io/sparse_wire): the meshed
        #: streamed totals MERGE ships top-k + error-feedback segments
        #: with one dense residual flush (README "Compressed wire")
        self.ingest_wire_compress = None
        #: gram-knob fields the USER set (planner preserves these; see
        #: GradientDescent._user_gram_opts)
        self._user_gram_opts = frozenset()
        self.last_plan = None
        self._plan_key = None
        self._gram_entry = None
        self._streamed_gram_entry = None
        self._stream_costfun_entry = None
        self._eval_cache = {}
        self._loss_history = None

    # fluent setters, reference parity
    def set_gradient(self, g):
        if g is not self.gradient:
            # a swapped-out gradient (e.g. a user-built gram bundle in a
            # dataset sweep) must not stay pinned through cached
            # evaluators keyed on it
            self._evict_eval_entries(self.gradient)
        self.gradient = g
        return self

    def set_updater(self, u):
        self.updater = u
        return self

    def set_num_corrections(self, m: int):
        self.num_corrections = int(m)
        return self

    def set_convergence_tol(self, t: float):
        self.convergence_tol = float(t)
        return self

    def set_max_num_iterations(self, n: int):
        self.max_num_iterations = int(n)
        return self

    def set_reg_param(self, r: float):
        self.reg_param = float(r)
        return self

    def _clear_planned_schedule(self):
        """A manual schedule setter taking the wheel AFTER an auto-planned
        run: the previous plan's sibling flags are the PLANNER's, not the
        user's — reset them so the mutual-exclusion guards never blame
        the user for a flag a plan set (user-set flags are untouched:
        they always come with ``last_plan is None``)."""
        if self.last_plan is not None:
            self.host_streaming = False
            self.sufficient_stats = False
            self.streamed_stats = False
            # ...and the plan's sizing knobs (see GradientDescent's
            # _clear_planned_schedule): a manual schedule on a new
            # dataset must not inherit the planned dataset's block size
            # or chunk caps
            from tpu_sgd.plan import reset_plan_owned_gram_knobs

            reset_plan_owned_gram_knobs(self)

    def set_sufficient_stats(self, flag: bool = True):
        """Run the least-squares CostFun and line-search sweep from
        precomputed block-prefix Gram statistics (``ops/gram.py``): each
        full-batch objective/gradient becomes an O(d²) matvec instead of
        two passes over X.  Applies when the gradient is exactly
        ``LeastSquaresGradient`` on dense unmeshed data; otherwise a
        no-op.

        The last built ``(X, y, GramData)`` is retained by identity so
        repeated calls on the same arrays (the streaming mode) never
        rebuild; call :meth:`release_sufficient_stats` to free the
        dataset plus its prefix stack from HBM after a one-shot run."""
        self._clear_planned_schedule()
        self.sufficient_stats = bool(flag)
        # user-set flags invalidate any auto-plan (see glm._auto_plan)
        self.last_plan = None
        self._plan_key = None
        return self

    def release_sufficient_stats(self):
        """Drop the cached sufficient-statistics bundle so the bound
        dataset plus the GB-scale prefix stack can be freed from HBM
        (``set_sufficient_stats``/``set_streamed_stats`` retain the last
        build by design).  Also drops the host-streamed CostFun entry
        (its compiled kernels and host array references)."""
        self._gram_entry = None
        self._streamed_gram_entry = None
        self._stream_costfun_entry = None
        self._eval_cache = {}  # entries close over the dropped gradients
        return self

    def _evict_eval_entries(self, gradient) -> None:
        """Drop cached evaluators that close over ``gradient``.  Called
        when a gram identity-cache slot is REPLACED (new dataset): the
        old single-slot behavior freed the prior GramData automatically,
        and the evaluator cache must not keep the displaced gradient —
        and its rows + GB-scale prefix stacks — pinned in HBM across a
        dataset sweep."""
        if gradient is None:
            return
        for k in [k for k in self._eval_cache if gradient in k]:
            del self._eval_cache[k]

    def _cached_eval(self, key, builder):
        """Instance-level evaluator cache.  The cost/sweep/loss builders
        create FRESH ``jax.jit`` wrappers, so without this every
        ``optimize()`` call retraced and recompiled the full-batch
        programs — seconds of compile per call on the streaming mode's
        repeated re-entries, where ``GradientDescent``'s cached runner
        pays it once.  ``key`` must capture everything the built closure
        BAKES IN (gradient/updater identity, reg params, mesh, masking,
        sparse shape — and for OWL-QN the reg vector's shape/dtype and
        intercept exemption); jit itself handles new data shapes within
        a cached wrapper."""
        fn = self._eval_cache.get(key)
        if fn is None:
            fn = builder()
            self._eval_cache[key] = fn
        return fn

    def set_gram_options(self, block_rows: int = None,
                         batch_rows: int = None):
        """Sufficient-statistics build knobs (set by the execution
        planner): ``block_rows`` sizes the prefix stack (memory vs edge
        traffic — see ``ops/gram.py``); ``batch_rows`` caps the streamed
        build's host→device chunk, co-resident with the stack."""
        from tpu_sgd.plan import apply_user_gram_knobs

        apply_user_gram_knobs(self, block_rows=block_rows,
                              batch_rows=batch_rows)
        return self

    def set_ingest_options(self, wire_dtype=None, prefetch_depth=None,
                           pipeline=None, retry=None, wire_compress=None):
        """Host→device ingest-pipeline knobs for the streamed builds
        (``tpu_sgd/io``; README "Ingestion pipeline"): opt-in bf16 wire
        (half the bytes per chunk, f32+ accumulation unchanged),
        prefetch lookahead (2 = double buffer), and the pipelined-feed
        master switch — same contract as
        ``GradientDescent.set_ingest_options``, including the ``retry``
        reliability knob (a ``tpu_sgd.reliability.RetryPolicy``; heals
        transient host-feed faults on the host-streamed schedules).
        ``wire_compress="topk:<frac>"`` compresses the MESHED streamed
        totals merge — per-shard top-k + error-feedback segments with
        one dense residual flush (README "Compressed wire")."""
        from tpu_sgd.plan import apply_user_ingest_options

        apply_user_ingest_options(self, wire_dtype=wire_dtype,
                                  prefetch_depth=prefetch_depth,
                                  pipeline=pipeline, retry=retry,
                                  wire_compress=wire_compress)
        return self

    def set_streamed_stats(self, flag: bool = True, block_rows: int = None):
        """Beyond-HBM quasi-Newton least squares: ONE host-streaming pass
        builds the block-prefix statistics on device
        (``GramLeastSquaresGradient.build_streamed``), after which every
        full-batch cost/gradient/sweep evaluation is an O(d²) statistics
        read — the rows never live on the device at all.  Full-batch
        sums are EXACT from the totals; the only deviation is the
        dropped ``n % block_rows`` tail rows (<0.1% at scale).  Applies
        to exactly ``LeastSquaresGradient`` on dense single-device data;
        the build is identity-cached per ``(X, y)``.  The build pass
        feeds through the shared double-buffered ingest pipeline
        (``tpu_sgd/io``; knobs via ``set_ingest_options``, bf16-wire
        safety in README "Ingestion pipeline")."""
        self._clear_planned_schedule()
        self.streamed_stats = bool(flag)
        if block_rows is not None:
            self.gram_block_rows = int(block_rows)
            self._user_gram_opts = self._user_gram_opts | {"block_rows"}
        self.last_plan = None
        self._plan_key = None
        return self

    def set_host_streaming(self, flag: bool = True,
                           batch_rows: int = None):
        """Beyond-HBM quasi-Newton for ANY loss: keep the dataset in host
        RAM and evaluate every full-batch cost/gradient/line-search sweep
        by streaming the rows through the device in fixed-size chunks
        with a device-resident accumulator — the chunked treeAggregate
        CostFun (``optimize/streamed_costfun.py``; [U]
        mllib/optimization/LBFGS.scala CostFun, SURVEY.md §2 #18).

        Unlike ``set_streamed_stats`` (least squares only, one build
        pass then O(d²) evaluations), this works for logistic, hinge,
        and multinomial losses — at the cost of re-reading the dataset
        through the host feed per evaluation (~3 reads per iteration).
        Composes with ``set_mesh``: each chunk is row-sharded across the
        data mesh and per-chunk sums psum over ICI.

        ``batch_rows`` caps the chunk size (default ~256 MB of rows;
        the execution planner sets it from the probed HBM budget).
        Note: the chunked CostFun keeps its own feed — the
        ``set_ingest_options`` knobs apply to the streamed STATISTICS
        builds (``set_streamed_stats``), not to this mode."""
        self._clear_planned_schedule()
        self.host_streaming = bool(flag)
        if batch_rows is not None:
            if int(batch_rows) < 1:
                raise ValueError(
                    f"batch_rows must be positive, got {batch_rows}"
                )
            self.stream_batch_rows = int(batch_rows)
            self._user_gram_opts = (
                self._user_gram_opts | {"stream_batch_rows"})
        self.last_plan = None
        self._plan_key = None
        return self

    def set_mesh(self, mesh):
        """Shard the cost function (and line-search sweep) row-wise over a
        1-D data mesh — the treeAggregate-CostFun analogue (SURVEY.md §2
        #18)."""
        _reject_model_axis(mesh, type(self).__name__)
        self.mesh = mesh
        return self

    @property
    def loss_history(self):
        return self._loss_history

    def optimize(self, data: Dataset, initial_weights: Array) -> Array:
        w, _ = self.optimize_with_history(data, initial_weights)
        return w

    def _maybe_streamed_reentry(self, X, y, initial_weights):
        """``set_streamed_stats`` front door, shared by LBFGS and the
        OWLQN override: build the virtual statistics once from the host
        rows BEFORE any device coercion, swap the gradient, and re-enter
        ``optimize_with_history`` with the virtual GramData as X (the
        flow the manual build_streamed + GramData-input path takes).
        Returns None when the flag is off or X is already statistics."""
        import numpy as np

        from tpu_sgd.ops.gram import GramData

        if self.streamed_stats and self.host_streaming:
            raise ValueError(
                "set_streamed_stats and set_host_streaming are "
                "alternative beyond-HBM schedules; enable exactly one"
            )
        if not self.streamed_stats or isinstance(X, GramData):
            return None
        g = self._maybe_streamed_gram(X, y)
        orig, self.gradient = self.gradient, g
        # The statistics are replicated/device-local after the build, so
        # the re-entered run executes UNMESHED — full-batch sums are the
        # exact totals; the mesh's job (dividing the rows) is done.
        orig_mesh, self.mesh = self.mesh, None
        try:
            return self.optimize_with_history(
                (g.data, np.asarray(y)[:g.data.shape[0]]),
                initial_weights,
            )
        finally:
            self.gradient = orig
            self.mesh = orig_mesh

    def _maybe_streamed_gram(self, X, y):
        """Guards + identity-cached build for ``set_streamed_stats``."""
        import numpy as np

        from tpu_sgd.ops.gradients import LeastSquaresGradient as _LS
        from tpu_sgd.ops.gram import GramLeastSquaresGradient
        from tpu_sgd.ops.sparse import is_sparse as _is_sp

        if _is_sp(X):
            raise NotImplementedError(
                "streamed statistics need dense rows; BCOO features are "
                "~1000x smaller and stay device-resident instead"
            )
        if type(self.gradient) is not _LS:
            raise NotImplementedError(
                "streamed statistics exist for least squares only (the "
                f"quadratic loss); got {type(self.gradient).__name__}; "
                "use set_host_streaming for beyond-HBM non-LS losses"
            )
        entry = self._streamed_gram_entry
        ingest = (self.ingest_wire_dtype, self.ingest_prefetch_depth,
                  self.ingest_pipeline, self.ingest_wire_compress)
        opts = (self.gram_block_rows, self.gram_batch_rows, self.mesh,
                ingest)
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[3] == opts):
            return entry[2]
        if self.mesh is not None:
            # Per-shard streamed TOTALS on each device, combined once:
            # the quasi-Newton CostFun reads only totals, so the mesh
            # matters only for the BUILD (each device digests its own
            # host row slice in parallel); evaluations then run O(d²)
            # from the replicated statistics — EXACT totals, no dropped
            # tail (parallel/gram_parallel.py).
            from tpu_sgd.parallel.gram_parallel import (
                build_streamed_total_stats,
            )

            data = build_streamed_total_stats(
                self.mesh, np.asarray(X), np.asarray(y),
                block_rows=self.gram_block_rows,
                batch_rows=self.gram_batch_rows,
                wire_dtype=self.ingest_wire_dtype,
                prefetch_depth=self.ingest_prefetch_depth,
                pipeline=self.ingest_pipeline,
                wire_compress=(self.ingest_wire_compress
                               if self.ingest_pipeline else None),
            )
            g = GramLeastSquaresGradient(data)
        else:
            g = GramLeastSquaresGradient.build_streamed(
                np.asarray(X), np.asarray(y),
                block_rows=self.gram_block_rows,
                batch_rows=self.gram_batch_rows,
                wire_dtype=self.ingest_wire_dtype,
                prefetch_depth=self.ingest_prefetch_depth,
                pipeline=self.ingest_pipeline,
            )
        if self._streamed_gram_entry is not None:
            # new dataset displaces the old bundle: drop evaluators
            # that would pin its statistics in HBM
            self._evict_eval_entries(self._streamed_gram_entry[2])
        self._streamed_gram_entry = (X, y, g, opts)
        return g

    #: backtracking ladder length (t = 1, 1/2, ..., 2^-(N-1))
    _LS_TRIALS = 25

    def _substitute_gram(self, gradient, X, y):
        """Apply ``set_sufficient_stats`` when it fits (exactly
        ``LeastSquaresGradient``, dense, unmeshed), identity-cached per
        ``(X, y)``.  Shared with OWLQN (Lasso least squares).  Returns
        ``(gradient, X)`` — on substitution, X becomes the ``GramData``
        bundle so the stats enter jit programs as argument buffers."""
        from tpu_sgd.ops.gradients import LeastSquaresGradient as _LS
        from tpu_sgd.ops.gram import GramData, GramLeastSquaresGradient
        from tpu_sgd.ops.sparse import is_sparse as _is_sp

        if isinstance(X, GramData) and not isinstance(
                gradient, GramLeastSquaresGradient):
            raise ValueError(
                "GramData input needs a GramLeastSquaresGradient "
                "(use GramLeastSquaresGradient.build/build_streamed and "
                "pass it as the gradient)"
            )
        if (self.mesh is None
                and isinstance(gradient, GramLeastSquaresGradient)
                and gradient.data is not None and gradient.data.X is X):
            # user-built gram gradient on exactly this matrix: route its
            # GramData through so the traced cost/sweep accelerate
            return gradient, gradient.data
        if not (self.sufficient_stats and not _is_sp(X)
                and type(gradient) is _LS
                and not isinstance(X, GramData)):
            return gradient, X
        entry = self._gram_entry
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[3:] == (self.gram_block_rows, self.mesh)):
            g = entry[2]
            return g, g.data
        if self.mesh is not None:
            # Meshed substitution: per-shard blockwise TOTALS + one psum
            # (the quasi-Newton CostFun reads only totals — no prefix
            # stacks), replicated; the caller then runs the iteration
            # loop unmeshed from the tiny (d, d) statistics.  EXACT for
            # any row count (padded rows are masked in the build).
            from tpu_sgd.parallel.gram_parallel import (
                build_sharded_total_stats,
            )

            data = build_sharded_total_stats(
                self.mesh, X, y, block_rows=self.gram_block_rows)
            g = GramLeastSquaresGradient(data)
        else:
            g = GramLeastSquaresGradient.build(
                X, y, block_rows=self.gram_block_rows)
            data = g.data
        if self._gram_entry is not None:
            self._evict_eval_entries(self._gram_entry[2])
        self._gram_entry = (X, y, g, self.gram_block_rows, self.mesh)
        return g, data

    def _mesh_spans_processes(self) -> bool:
        if self.mesh is None:
            return False
        from tpu_sgd.optimize.streamed_costfun import mesh_spans_processes

        return mesh_spans_processes(self.mesh)

    def _host_streamed_costfun(self, X, y):
        """Guards + identity-cached :class:`StreamedCostFun` for
        ``set_host_streaming`` (shared with the OWLQN override)."""
        from tpu_sgd.ops.gram import GramData
        from tpu_sgd.optimize.streamed_costfun import StreamedCostFun

        if isinstance(X, GramData):
            raise ValueError(
                "GramData input already runs beyond-HBM from its "
                "statistics; drop set_host_streaming"
            )
        if is_sparse(X):
            raise NotImplementedError(
                "host streaming needs dense rows; BCOO features are "
                "~1000x smaller and stay device-resident instead"
            )
        if self.streamed_stats:
            raise ValueError(
                "set_streamed_stats and set_host_streaming are "
                "alternative beyond-HBM schedules; enable exactly one"
            )
        if self.sufficient_stats:
            raise ValueError(
                "set_sufficient_stats needs device-resident data; it "
                "cannot combine with set_host_streaming"
            )
        entry = self._stream_costfun_entry
        opts = (self.stream_batch_rows, self.mesh)
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[3] == opts and entry[2].gradient is self.gradient):
            return entry[2]
        scf = StreamedCostFun(
            self.gradient, X, y,
            batch_rows=self.stream_batch_rows, mesh=self.mesh,
        )
        self._stream_costfun_entry = (X, y, scf, opts)
        return scf

    def _host_streamed_evaluators(self, X, y, initial_weights):
        """``(w0, cost1, sweep1, loss1)`` closures over the chunked
        streaming CostFun, in the exact shape :meth:`_qn_loop` consumes;
        None for empty input (the resident path's early return covers
        it)."""
        import numpy as np

        if int(np.shape(X)[0]) == 0 and not self._mesh_spans_processes():
            # single-host empty input: the resident path's early return
            # covers it.  A multihost process with ZERO local rows must
            # NOT bail here — it still joins every collective (allgather
            # + per-chunk psums), feeding all-invalid chunks; bailing
            # would deadlock its peers.
            return None
        scf = self._host_streamed_costfun(X, y)
        w = jnp.asarray(initial_weights)
        if not jnp.issubdtype(w.dtype, jnp.inexact):
            w = w.astype(jnp.float32)
        reg_value, reg_grad = _reg_terms(self.updater, self.reg_param)

        def _build_finishes():
            @jax.jit
            def _finish_cost(gs, ls, c, wv):
                return ls / c + reg_value(wv), gs / c + reg_grad(wv)

            @jax.jit
            def _finish_sweep(ls, c, W):
                return ls / c + jax.vmap(reg_value)(W)

            @jax.jit
            def _finish_loss(ls, c, wv):
                return ls / c + reg_value(wv)

            return _finish_cost, _finish_sweep, _finish_loss

        _finish_cost, _finish_sweep, _finish_loss = self._cached_eval(
            ("stream_finish", self.updater, float(self.reg_param)),
            _build_finishes)

        def cost1(wv):
            return _finish_cost(*scf.cost_sums(wv), wv)

        if hasattr(self.gradient, "loss_sweep"):
            def sweep1(W):
                return _finish_sweep(*scf.sweep_sums(W), W)

            return w, cost1, sweep1, None
        _warn_sequential_line_search(self.gradient, self._LS_TRIALS)

        def loss1(wv):
            return _finish_loss(*scf.loss_sums(wv), wv)

        return w, cost1, None, loss1

    def optimize_with_history(self, data: Dataset, initial_weights: Array):
        import numpy as np

        X, y = data
        streamed = self._maybe_streamed_reentry(X, y, initial_weights)
        if streamed is not None:
            return streamed
        if self.host_streaming:
            # BEFORE _coerce_inputs: jnp.asarray would commit the
            # beyond-HBM matrix to the device
            ev = self._host_streamed_evaluators(X, y, initial_weights)
            if ev is not None:
                return self._qn_loop(*ev)
        X, y, w = _coerce_inputs(X, y, initial_weights,
                                 defer_commit=self.mesh is not None)
        n = X.shape[0]
        if n == 0:
            self._loss_history = np.zeros((0,), np.float32)
            return w, self._loss_history
        from tpu_sgd.ops.gram import GramData as _GramData

        was_gram_input = isinstance(X, _GramData)
        gradient, X = self._substitute_gram(self.gradient, X, y)
        reg_value, reg_grad = _reg_terms(self.updater, self.reg_param)

        mesh = self.mesh
        if isinstance(X, _GramData) and not was_gram_input:
            # internally substituted statistics are replicated: the
            # iteration loop runs unmeshed from exact totals (user-passed
            # GramData with a mesh still raises in _shard_for_mesh)
            mesh = None
            if not isinstance(y, jnp.ndarray):
                # the statistics carry Xᵀy / yᵀy — the gram cost never
                # reads y, but the host numpy array defer_commit left
                # here would re-upload host→device on EVERY evaluation
                # (~3/iteration); swap in an empty device vector
                y = jnp.zeros((0,), jnp.float32)
        valid = None
        sparse_shape = None
        if mesh is not None:
            X, y, valid, sparse_shape = _shard_for_mesh(mesh, X, y)
        with_valid = valid is not None
        data_args = (X, y, valid) if with_valid else (X, y)

        eval_key = (gradient, self.updater, float(self.reg_param),
                    mesh, with_valid, sparse_shape)
        cost = self._cached_eval(
            ("cost",) + eval_key,
            lambda: _build_cost(gradient, reg_value, reg_grad, mesh,
                                with_valid, sparse_shape))

        def cost1(wv):
            return cost(wv, *data_args)

        if hasattr(gradient, "loss_sweep"):
            sweep = self._cached_eval(
                ("sweep",) + eval_key,
                lambda: _build_loss_sweep(gradient, reg_value, mesh,
                                          with_valid, sparse_shape))

            def sweep1(W):
                return sweep(W, *data_args)

            return self._qn_loop(w, cost1, sweep1, None)
        # exotic gradients without a sweep rule: sequential trials
        _warn_sequential_line_search(gradient, self._LS_TRIALS)
        loss_only = self._cached_eval(
            ("loss",) + eval_key,
            lambda: _build_loss_only(gradient, reg_value, mesh,
                                     with_valid, sparse_shape))

        def loss1(wv):
            return loss_only(wv, *data_args)

        return self._qn_loop(w, cost1, None, loss1)

    def _qn_loop(self, w, cost1, sweep1, loss1):
        """The L-BFGS iteration loop over abstract FULL-BATCH evaluators:
        ``cost1(w) -> (f, g)``, ``sweep1(W_trials) -> (T,)`` trial
        objectives (None for gradients without a sweep rule), ``loss1(w)
        -> f`` (the sequential fallback).  Both the device-resident and
        the host-streamed CostFun paths drive this same loop — the
        evaluators are the only thing that differs."""
        import numpy as np

        n_ls = self._LS_TRIALS
        ladder = jnp.asarray(
            0.5 ** np.arange(n_ls), jnp.float32
        )  # trial step sizes, largest first
        swept = sweep1 is not None
        if swept:
            @jax.jit
            def make_trials(w, direction):
                return w[None, :] + ladder[:, None] * direction[None, :]

        m = self.num_corrections
        d = w.shape[0]
        s_stack = jnp.zeros((m, d), w.dtype)
        y_stack = jnp.zeros((m, d), w.dtype)
        rho = jnp.zeros((m,), w.dtype)
        k = 0  # valid corrections

        f, g = cost1(w)
        losses: List[float] = [float(f)]
        for _ in range(self.max_num_iterations):
            direction = -_two_loop(g, s_stack, y_stack, rho, jnp.asarray(k))
            # Armijo backtracking; only the accept decision is host-side
            g_dot_d = float(jnp.dot(g, direction))
            if g_dot_d >= 0:  # not a descent direction: reset to -g
                direction = -g
                g_dot_d = float(jnp.dot(g, direction))
            f0 = float(f)
            if swept:
                # whole ladder in one device pass + ONE host sync
                f_trials = np.asarray(sweep1(make_trials(w, direction)))
                ok = f_trials <= f0 + 1e-4 * np.asarray(ladder) * g_dot_d
                j = int(np.argmax(ok)) if ok.any() else -1
                accepted = j >= 0
                if accepted:
                    t = float(ladder[j])
                    w_new = w + t * direction
            else:
                t = 1.0
                accepted = False
                for _ls in range(n_ls):
                    w_new = w + t * direction
                    f_new = loss1(w_new)
                    if float(f_new) <= f0 + 1e-4 * t * g_dot_d:
                        accepted = True
                        break
                    t *= 0.5
            if not accepted:
                break  # cannot make progress
            f_new, g_new = cost1(w_new)  # gradient at accepted pt
            s = w_new - w
            yv = g_new - g
            sy = float(jnp.dot(s, yv))
            if sy > 1e-10:  # curvature condition: keep correction
                s_stack, y_stack, rho, k = _push_correction(
                    s_stack, y_stack, rho, k, m, s, yv, sy
                )
            w, f, g = w_new, f_new, g_new
            losses.append(float(f))
            rel = abs(losses[-2] - losses[-1]) / max(
                abs(losses[-2]), abs(losses[-1]), 1.0
            )
            if rel < self.convergence_tol:
                break

        self._loss_history = np.asarray(losses, np.float32)
        return w, self._loss_history


def run_lbfgs(
    data: Dataset,
    gradient: Gradient,
    updater: Updater,
    num_corrections: int,
    convergence_tol: float,
    max_num_iterations: int,
    reg_param: float,
    initial_weights: Array,
    mesh=None,
):
    """Functional entry point, signature-parity with the reference's
    ``object LBFGS.runLBFGS`` ([U] mllib/optimization/LBFGS.scala,
    SURVEY.md §2 #18): same argument order, returns
    ``(weights, loss_history)``.
    """
    opt = LBFGS(
        gradient,
        updater,
        num_corrections=num_corrections,
        convergence_tol=convergence_tol,
        max_num_iterations=max_num_iterations,
        reg_param=reg_param,
    )
    if mesh is not None:
        opt.set_mesh(mesh)
    return opt.optimize_with_history(data, initial_weights)
