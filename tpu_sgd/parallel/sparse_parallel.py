"""Data-parallel training on sparse (BCOO) features.

Reference parity: Spark trains sparse ``RDD[LabeledPoint]`` DISTRIBUTED —
each executor holds its partitions' sparse rows and ``treeAggregate``
combines per-partition gradient sums ([U] mllib/optimization/
GradientDescent.scala over sparse Vectors, SURVEY.md §2 #10/#13).  The
single-device BCOO path (tpu_sgd/ops/sparse.py) alone would cap the
framework below the reference's distributed-sparse capability.

The obstacle to sharding a BCOO directly is that a row range's nse varies
by shard, and ``shard_map`` needs one static local shape.  The layout here
makes nse uniform *by construction*:

  1. rows are split into ``n_shards`` contiguous equal blocks (row-padded
     like the dense path, with a ``valid`` mask);
  2. each block's entries are rebased to LOCAL row indices and padded to
     the max per-shard nse with null entries — value 0.0 at (row 0, col 0),
     which contribute exactly 0 to both matvecs;
  3. the per-shard blocks are concatenated into flat component arrays
     (``data``, ``indices``) sharded over the 'data' axis, and the
     shard_map body reassembles its LOCAL block into a BCOO of static shape
     ``(rows_local, d)``.

From there the body is *the same* ``make_run`` the dense mesh path uses —
the sparse gather/segment lowering per shard, one ``lax.psum`` of
``(grad_sum, loss_sum, count)`` over ICI per iteration.
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.sparse import host_entries
from tpu_sgd.ops.updaters import Updater
from tpu_sgd.parallel.mesh import DATA_AXIS, shard_map_fn

Array = jax.Array


def _layout_blocks(rows, cols, vals, n_shards: int, rows_local: int,
                   nse_local: int):
    """Scatter sorted entries into ``(n_shards, nse_local)`` equal-nse
    blocks with LOCAL row indices; unfilled slots stay null entries
    (0.0 at local (0, 0))."""
    shard_of = rows // rows_local
    local_row = (rows % rows_local).astype(np.int32)
    counts = np.bincount(shard_of, minlength=n_shards)
    data_h = np.zeros((n_shards, nse_local), vals.dtype)
    idx_h = np.zeros((n_shards, nse_local, 2), np.int32)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(rows.shape[0]) - offsets[shard_of]
    data_h[shard_of, slot] = vals
    idx_h[shard_of, slot, 0] = local_row
    idx_h[shard_of, slot, 1] = cols
    return data_h, idx_h


def shard_bcoo(mesh: Mesh, X, y) -> Tuple[Array, Array, Array, Array, int, int]:
    """Lay a BCOO matrix out for ``shard_map`` over the 'data' axis.

    Returns ``(data, indices, y, valid, rows_local, d)`` where the arrays
    are device-sharded so each core sees one equal-nse block with local row
    indices (see module docstring); ``valid`` is None when the row count
    divides evenly (the dense path's mask-free fast path).  This is the one
    host->device transfer of the run — the sparse analogue of
    ``shard_dataset``.

    Multi-host jobs: ``X``/``y`` are each process's LOCAL sparse rows (the
    analogue of each executor reading its own input splits); processes
    agree on a common per-shard ``(rows_local, nse_local)`` via allgather
    and assemble the global arrays without moving any row cross-host.
    """
    if jax.process_count() > 1:
        return _shard_bcoo_multihost(mesh, X, y)
    n_shards = mesh.shape[DATA_AXIS]
    n, d = X.shape
    rows_local = -(-n // n_shards)  # ceil: same contiguous blocks as the
    n_padded = rows_local * n_shards  # dense path's pad_to_multiple
    yh = np.zeros((n_padded,), np.asarray(y).dtype)
    yh[:n] = np.asarray(y)
    valid = np.zeros((n_padded,), bool)
    valid[:n] = True

    rows, cols, vals = host_entries(X)
    nse_local = max(
        1, int(np.bincount(rows // rows_local, minlength=n_shards).max())
    )
    data_h, idx_h = _layout_blocks(
        rows, cols, vals, n_shards, rows_local, nse_local
    )

    entry_sharding = NamedSharding(mesh, P(DATA_AXIS))
    data_d = jax.device_put(data_h.reshape(-1), entry_sharding)
    idx_d = jax.device_put(
        idx_h.reshape(-1, 2), NamedSharding(mesh, P(DATA_AXIS, None))
    )
    y_d = jax.device_put(yh, entry_sharding)
    valid_d = (
        None if n == n_padded else jax.device_put(valid, entry_sharding)
    )
    return data_d, idx_d, y_d, valid_d, rows_local, int(d)


def _shard_bcoo_multihost(mesh: Mesh, X, y):
    """Assemble globally-sharded BCOO component arrays from per-process
    local sparse rows (the sparse twin of ``_shard_dataset_multihost``).

    Processes allgather their ``(row count, per-shard max nse, d)`` so
    every process infers the SAME global shapes — common padded per-process
    row count, common per-shard nse — then contribute their local blocks
    via ``make_array_from_process_local_data``; no host ever holds another
    host's rows, and only gradient psums ride DCN at train time.  The
    validity mask is always on (per-process padding differs).
    """
    from jax.experimental import multihost_utils

    local_shards = dict(mesh.local_mesh.shape).get(DATA_AXIS, 1)
    n, d_local = X.shape
    rows, cols, vals = host_entries(X)

    # agree on (padded per-process rows, per-shard nse, d)
    counts0 = np.asarray(multihost_utils.process_allgather(np.asarray(n)))
    target = int(counts0.max())
    target += (-target) % local_shards
    rows_local = target // local_shards
    local_max_nse = int(
        np.bincount(rows // rows_local, minlength=local_shards).max()
    ) if rows.size else 0
    nse_all = np.asarray(
        multihost_utils.process_allgather(np.asarray(local_max_nse))
    )
    nse_local = max(1, int(nse_all.max()))
    d_all = np.asarray(
        multihost_utils.process_allgather(np.asarray(d_local))
    )
    if int(d_all.min()) != int(d_all.max()):
        # resolving by max would silently misalign everything built from
        # the LOCAL width (w0 length, the appended bias column) — each
        # process would trace a different program, which in multi-host
        # JAX is a distributed hang, not a clean error.  Make the user
        # pin num_features at load time instead.
        raise ValueError(
            "processes disagree on the feature count "
            f"({sorted(int(v) for v in set(d_all.tolist()))}); pass an "
            "explicit num_features to the loader so every process "
            "builds the same dimensionality"
        )
    d = int(d_all.max())

    data_h, idx_h = _layout_blocks(
        rows, cols, vals, local_shards, rows_local, nse_local
    )
    yh = np.zeros((target,), np.asarray(y).dtype)
    yh[:n] = np.asarray(y)
    valid = np.zeros((target,), bool)
    valid[:n] = True

    entry_sharding = NamedSharding(mesh, P(DATA_AXIS))
    data_d = jax.make_array_from_process_local_data(
        entry_sharding, data_h.reshape(-1)
    )
    idx_d = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(DATA_AXIS, None)), idx_h.reshape(-1, 2)
    )
    y_d = jax.make_array_from_process_local_data(entry_sharding, yh)
    valid_d = jax.make_array_from_process_local_data(entry_sharding, valid)
    return data_d, idx_d, y_d, valid_d, rows_local, d


def local_bcoo(data: Array, indices: Array, rows_local: int, d: int):
    """Reassemble one shard's component arrays into its local BCOO block
    (static shape; called inside the shard_map body)."""
    from jax.experimental.sparse import BCOO

    return BCOO((data, indices), shape=(rows_local, d))


def sparse_dp_step_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    rows_local: int,
    d: int,
    with_valid: bool,
):
    """Jitted shard_map'ed SINGLE-step function over sharded BCOO
    components — the sparse twin of ``dp_step_fn``, used by the observed
    (listener / checkpoint) path."""
    from tpu_sgd.optimize.gradient_descent import make_step

    step = make_step(gradient, updater, config, axis_name=DATA_AXIS)

    def local(w, X, y, i, reg_val, hyper, valid=None):
        return step(w, local_bcoo(X[0], X[1], rows_local, d), y, i, reg_val,
                    hyper, valid)

    # X arrives as the (data, idx) component tuple, matching the stepwise
    # caller's ``step(w, X, y, ...)`` signature for dense X
    # ``local`` defaults valid=None, so it serves both arities directly
    x_spec = (P(DATA_AXIS), P(DATA_AXIS, None))
    in_specs = (P(), x_spec, P(DATA_AXIS), P(), P(), P())
    if with_valid:
        in_specs = in_specs + (P(DATA_AXIS),)
    return jax.jit(
        shard_map_fn(mesh, local, in_specs, (P(), P(), P(), P()))
    )


def sparse_dp_run_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    rows_local: int,
    d: int,
    with_valid: bool,
):
    """Jitted shard_map'ed full-loop runner over sharded BCOO components —
    the sparse twin of ``dp_run_fn`` (same ``make_run``, same psum)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(gradient, updater, config, axis_name=DATA_AXIS)

    def local(w, data, idx, y, hyper, valid=None):
        return run(w, local_bcoo(data, idx, rows_local, d), y, hyper, valid)

    # ``local`` defaults valid=None, so it serves both arities directly
    in_specs = (P(), P(DATA_AXIS), P(DATA_AXIS, None), P(DATA_AXIS), P())
    if with_valid:
        in_specs = in_specs + (P(DATA_AXIS),)
    return jax.jit(shard_map_fn(mesh, local, in_specs, (P(), P(), P())))
