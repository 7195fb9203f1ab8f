"""Exact least-squares via normal equations — the one-pass TPU solver.

Reference parity note: the reference solves config 1/4's least-squares
problems iteratively through ``GradientDescent.runMiniBatchSGD`` ([U]
mllib/optimization/GradientDescent.scala, SURVEY.md §2 #2) because on a
Spark cluster each pass over the RDD costs a full job.  On TPU a *single*
pass is one Gram-matrix matmul on the MXU, so the exact solution

    (XᵀX / n + reg·I) w = Xᵀy / n

is cheaper than a handful of SGD iterations whenever ``d`` is modest
(d ≤ a few thousand: the Gram matmul reads X once and the (d, d) solve is
microseconds).  Upstream Spark ships the same idea one package over as
``spark.ml``'s WeightedLeastSquares "normal" solver; here it slots behind
the SAME ``Optimizer`` boundary (SURVEY.md §2 #1) so the GLM harness,
intercept handling, persistence, and streaming warm-starts all compose
with it unchanged.

Scaling: the Gram accumulation is data-parallel by construction — each
shard computes its local ``(XᵀX, Xᵀy, yᵀy, n)`` and one ``lax.psum``
combines them over ICI (the same collective pattern as the SGD path,
SURVEY.md §5.8); the tiny (d, d) solve then runs replicated on every core.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.ops.gradients import acc_dtype
from tpu_sgd.optimize.optimizer import Dataset, Optimizer

Array = jax.Array


def _gram_sums(X: Array, y: Array) -> Tuple[Array, Array, Array, Array]:
    """One pass: ``(XᵀX, Xᵀy, yᵀy, n)`` with f32 accumulation (bf16 data
    runs the Gram matmul on the MXU in bf16).  Integer rows of EVERY width
    in f32, not ``matmul_dtype``'s bf16 for 8-bit ones: it would round
    ``y`` to bf16 in ``Xᵀy``, three digits, in a solver that is exact."""
    mm_dtype = (X.dtype if jnp.issubdtype(X.dtype, jnp.inexact)
                else jnp.float32)
    acc = acc_dtype(mm_dtype)
    Xc = X.astype(mm_dtype)
    A = jnp.dot(Xc.T, Xc, preferred_element_type=acc)
    b = jnp.dot(Xc.T, y.astype(mm_dtype), preferred_element_type=acc)
    yty = jnp.dot(y, y, preferred_element_type=acc)
    return A, b, yty, jnp.float32(X.shape[0])


def _solve(A, b, yty, n, reg_param: float):
    """Solve the regularized normal equations and return (w, loss).

    Objective matched to the SGD path's SquaredL2Updater semantics:
    ``(1/n)·Σ ½(x.w − y)² + (reg/2)·‖w‖²``.
    """
    d = A.shape[0]
    An = A / n + reg_param * jnp.eye(d, dtype=A.dtype)
    bn = b / n
    # Cholesky: the regularized Gram is SPD for reg>0 and full-rank data;
    # rank deficiency surfaces as NaNs, which ``optimize`` checks and raises.
    L = jax.lax.linalg.cholesky(An)
    w = jax.lax.linalg.triangular_solve(
        L,
        jax.lax.linalg.triangular_solve(
            L, bn[:, None], left_side=True, lower=True
        ),
        left_side=True,
        lower=True,
        transpose_a=True,
    )[:, 0]
    # HIGHEST-precision loss dots (ops/gram.py contract): near the optimum
    # the loss is the near-zero difference of ~||y||^2-magnitude terms,
    # and TPU default-precision (bf16-pass) dots would report garbage —
    # the streamed path accumulates its totals at HIGHEST only to throw
    # that precision away here otherwise
    from tpu_sgd.ops.gram import _dot_hi

    sd = A.dtype
    loss = (
        0.5 * (_dot_hi(w, _dot_hi(A, w, sd), sd) - 2.0 * _dot_hi(w, b, sd)
               + yty) / n
        + 0.5 * reg_param * _dot_hi(w, w, sd)
    )
    return w, loss


#: memo-key contract (graftlint memo-key rule): the compiled-solver
#: cache keys on exactly these roots; reg is baked into the program, so
#: dropping reg_param from the key would serve one lambda's solver to
#: every other
GRAFTLINT_MEMO = {
    "NormalEquations._cache": ("reg_param", "mesh", "with_valid"),
}


class NormalEquations(Optimizer):
    """Exact least-squares solver behind the Optimizer boundary.

    Drop-in alternative to ``GradientDescent`` for the least-squares family
    (LeastSquaresGradient × Simple/SquaredL2 updater); raises nothing for
    other losses because it never sees them — model wrappers choose it
    explicitly.  ``reg_param`` is the L2 coefficient (0 = plain OLS).

    ``set_mesh`` shards the Gram accumulation row-wise over a 1-D data mesh
    with a single ICI all-reduce; the solve is replicated.
    """

    def __init__(self, reg_param: float = 0.0):
        self.reg_param = float(reg_param)
        self.mesh = None
        #: None = AUTO: stream when host data exceeds the probed device
        #: budget (the zero-flag placement contract); True/False force
        self.host_streaming = None
        self.stream_batch_rows = None
        self.stream_resume_dir = None
        self._loss = None
        self._cache = {}

    def set_reg_param(self, r: float):
        self.reg_param = float(r)
        return self

    def set_host_streaming(self, flag: bool = True,
                           batch_rows: int = None,
                           resume_dir: str = None):
        """Beyond-HBM EXACT least squares: accumulate the Gram totals by
        streaming host row chunks through the device with an O(d²) carry
        (``GramLeastSquaresGradient._streamed_totals``) — the literal
        analogue of the reference's spark.ml normal solver aggregating
        its Gram over an RDD of ANY size — then run the tiny (d, d)
        solve.  EXACT: every row contributes (no dropped tail).
        Composes with ``set_mesh``: each shard streams its own host
        slice to its own device and the totals combine once
        (``parallel/gram_parallel.py`` ``build_streamed_total_stats``).

        Precision note: the streamed totals accumulate at f32 HIGHEST
        (the statistics contract, ``ops/gram.py``), which is MORE
        precise than the resident bf16-data Gram matmul — trajectories
        agree to that rounding.  ``batch_rows`` caps the host→device
        chunk EXACTLY (default 64 blocks); ``resume_dir`` makes the
        accumulation resumable (one tiny carry checkpoint per chunk —
        see ``_streamed_totals``).

        The DEFAULT is AUTO: with no flag set, ``optimize`` streams
        whenever the host data exceeds the probed device budget (and
        runs resident otherwise) — ``set_host_streaming(False)`` forces
        the resident path.

        The chunk feed runs through the shared double-buffered ingest
        pipeline (``tpu_sgd/io``; README "Ingestion pipeline"): chunk
        ``k+1`` transfers while chunk ``k`` accumulates, and the
        ``batch_rows`` budget should allow for the two in-flight
        chunks."""
        self.host_streaming = bool(flag)
        if batch_rows is not None:
            if int(batch_rows) < 1:
                raise ValueError(
                    f"batch_rows must be positive, got {batch_rows}"
                )
            self.stream_batch_rows = int(batch_rows)
        if resume_dir is not None:
            # sticky like batch_rows: re-asserting the flag must not
            # silently drop crash protection (clear via the attribute)
            self.stream_resume_dir = resume_dir
        return self

    def set_mesh(self, mesh):
        from tpu_sgd.parallel.mesh import has_model_axis

        if has_model_axis(mesh):
            raise ValueError(
                "NormalEquations shards rows over a 1-D 'data' mesh; a "
                "2-D (data, model) mesh would silently replicate X across "
                "the model axis — use a data-only mesh"
            )
        self.mesh = mesh
        return self

    @property
    def loss_history(self):
        """Length-1 loss history (the final objective), matching the SGD
        optimizers' return contract shape (SURVEY.md §5.5)."""
        return self._loss

    def _solver(self, with_valid: bool):
        # Mesh is hashable and used directly (an id() key could alias a new
        # mesh to a stale compiled solver after GC id reuse).
        key = (self.reg_param, self.mesh, with_valid)
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        reg = self.reg_param
        if self.mesh is None:

            @jax.jit
            def fn(X, y):
                return _solve(*_gram_sums(X, y), reg)

        else:
            from jax.sharding import PartitionSpec as P

            from tpu_sgd.parallel.mesh import DATA_AXIS, shard_map_fn

            def local(X, y, valid=None):
                if valid is not None:
                    vf = valid.astype(jnp.float32)
                    X = X * vf[:, None].astype(X.dtype)
                    y = y * vf
                    n_local = jnp.sum(vf)
                else:
                    n_local = jnp.float32(X.shape[0])
                A, b, yty, _ = _gram_sums(X, y)
                A, b, yty, n = jax.lax.psum(
                    (A, b, yty, n_local), DATA_AXIS
                )
                return _solve(A, b, yty, n, reg)

            if with_valid:
                body = local
                in_specs = (P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS))
            else:
                body = lambda X, y: local(X, y)
                in_specs = (P(DATA_AXIS, None), P(DATA_AXIS))
            fn = jax.jit(shard_map_fn(self.mesh, body, in_specs, (P(), P())))
        self._cache[key] = fn
        return fn

    def optimize(self, data: Dataset, initial_weights: Array) -> Array:
        X, y = data
        from tpu_sgd.ops.sparse import is_sparse

        if is_sparse(X):
            raise NotImplementedError(
                "NormalEquations needs dense features: the d x d Gram "
                "matrix is dense regardless of input sparsity (47k "
                "features -> 8.8 GB), so wide sparse problems should use "
                "GradientDescent/LBFGS/OWLQN instead"
            )
        stream = self.host_streaming
        if stream is None and not isinstance(X, jax.Array):
            # AUTO placement (the user never picks it — the scheduler
            # contract, SURVEY.md §2 #16): a host dataset beyond the
            # probed per-device budget streams its Gram totals instead
            # of OOMing on the full commit; shards divide the budget.
            from tpu_sgd.plan import device_budget

            shape = np.shape(X)
            budget, _src = device_budget()
            multihost = False
            if self.mesh is not None:
                from tpu_sgd.optimize.streamed_costfun import (
                    mesh_spans_processes,
                )
                from tpu_sgd.parallel.mesh import DATA_AXIS

                multihost = mesh_spans_processes(self.mesh)
                if multihost:
                    # each process holds only ITS rows, spread over its
                    # LOCAL devices — scaling by the global shard count
                    # would over-commit HBM by process_count
                    budget *= max(1, len(self.mesh.local_devices))
                else:
                    budget *= dict(self.mesh.shape).get(DATA_AXIS, 1)
            itemsize = np.dtype(getattr(X, "dtype", np.float32)).itemsize
            data_bytes = shape[0] * shape[1] * itemsize + shape[0] * 4.0
            stream = data_bytes > budget
            if stream and multihost:
                # the streamed totals builder is single-host; AUTO must
                # not pick a path it cannot run — take the resident route
                # and SAY that it may not fit, rather than crash later
                # blaming a choice the user never made
                import warnings

                warnings.warn(
                    f"data ({data_bytes / 1e9:.2f} GB/process) exceeds "
                    f"the local-device budget ({budget / 1e9:.2f} GB) "
                    "but the streamed totals build is single-host; "
                    "committing resident and it may exhaust device "
                    "memory — shrink the per-process rows or stream on "
                    "a local mesh",
                    RuntimeWarning, stacklevel=3,
                )
                stream = False
            if stream:
                from tpu_sgd.plan import logger

                logger.info(
                    "plan: normal host_streamed — data "
                    f"({data_bytes / 1e9:.2f} GB) exceeds the device "
                    f"budget ({budget / 1e9:.2f} GB); Gram totals "
                    "accumulate from host-streamed chunks (exact)"
                )
        if stream:
            # BEFORE any device coercion: the whole point is that X never
            # lives on the device in full
            if np.shape(initial_weights)[-1] != np.shape(X)[1]:
                raise ValueError(
                    f"initial_weights has length "
                    f"{np.shape(initial_weights)[-1]} but the data has "
                    f"{np.shape(X)[1]} features"
                )
            return self._optimize_host_streamed(X, y)
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        if not jnp.issubdtype(y.dtype, jnp.inexact):
            y = y.astype(jnp.float32)
        w0 = jnp.asarray(initial_weights)
        if w0.shape[-1] != X.shape[1]:
            raise ValueError(
                f"initial_weights has length {w0.shape[-1]} but the data has "
                f"{X.shape[1]} features"
            )
        if self.mesh is None:
            w, loss = self._solver(with_valid=False)(X, y)
        else:
            from tpu_sgd.parallel.data_parallel import shard_dataset

            Xd, yd, valid = shard_dataset(self.mesh, X, y)
            if valid is not None:
                w, loss = self._solver(with_valid=True)(Xd, yd, valid)
            else:
                w, loss = self._solver(with_valid=False)(Xd, yd)
        return self._finish(w, loss)

    def _finish(self, w, loss):
        """Shared postlude: rank-deficiency surface + loss history."""
        if not bool(jnp.all(jnp.isfinite(w))):
            raise FloatingPointError(
                "normal-equations solve produced non-finite weights: the "
                "Gram matrix is rank-deficient (collinear or constant "
                "features) and reg_param="
                f"{self.reg_param} does not regularize it; set a positive "
                "reg_param or drop redundant features"
            )
        self._loss = np.asarray([float(loss)], np.float32)
        return w

    def _optimize_host_streamed(self, X, y):
        """Exact solve from host-streamed Gram totals (see
        ``set_host_streaming``)."""
        from tpu_sgd.ops.gram import (DEFAULT_BLOCK_ROWS,
                                      GramLeastSquaresGradient)

        Xh = np.asarray(X)
        yh = np.asarray(y)
        if not jnp.issubdtype(Xh.dtype, jnp.inexact):
            Xh = Xh.astype(np.float32)
        if not jnp.issubdtype(yh.dtype, jnp.inexact):
            yh = yh.astype(np.float32)
        n = Xh.shape[0]
        if self.mesh is not None:
            from tpu_sgd.optimize.streamed_costfun import (
                mesh_spans_processes,
            )
            from tpu_sgd.parallel.gram_parallel import (
                build_streamed_total_stats,
            )

            if mesh_spans_processes(self.mesh):
                # the per-device streamed builder device_puts to every
                # mesh device, which crashes on non-addressable remote
                # devices — fail with a real message instead
                raise NotImplementedError(
                    "streamed normal totals build single-host; on a "
                    "multi-host job run the resident meshed path, or "
                    "stream on a mesh of this process's devices"
                )

            data = build_streamed_total_stats(
                self.mesh, Xh, yh,
                batch_rows=self.stream_batch_rows,
                resume_dir=self.stream_resume_dir,
            )
            G, b, yty = data.G_tot, data.b_tot, data.yy_tot
        else:
            from tpu_sgd.ops.gram import streamed_totals_chunking

            B, chunk = streamed_totals_chunking(
                n, DEFAULT_BLOCK_ROWS, self.stream_batch_rows)
            sd = GramLeastSquaresGradient._resolve_stats_dtype(
                Xh.dtype, None)
            G, b, yty = GramLeastSquaresGradient._streamed_totals(
                Xh, yh, B, sd, chunk,
                resume_dir=self.stream_resume_dir)
        w, loss = jax.jit(_solve, static_argnums=(4,))(
            G, b, yty, jnp.asarray(float(n), G.dtype), self.reg_param
        )
        return self._finish(w, loss)
