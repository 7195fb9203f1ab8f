"""Data-parallel SGD: shard the example axis, psum the gradient sums.

This is the TPU-native replacement for the reference's entire L1-L2 stack
(SURVEY.md §3.5): where Spark runs ``sample().treeAggregate(depth=2)`` through
shuffle files, task serialization and a driver hop every iteration, here the
batch lives sharded across cores, the weights live replicated, and
``lax.psum`` combines per-shard ``(grad_sum, loss_sum, count)`` in hardware
over ICI.  Broadcast of updated weights is free: the all-reduced update is
applied identically on every core (deterministic replication replaces
TorrentBroadcast, SURVEY.md §5.8).

Uneven example counts are handled by zero-padding each shard and carrying a
``valid`` row mask folded into the mini-batch mask — the analogue of Spark's
arbitrary-size partitions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sgd.config import SGDConfig
from tpu_sgd.obs.spans import NO_SPAN
from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.updaters import Updater
from tpu_sgd.parallel.mesh import DATA_AXIS, shard_map_fn, superchunk_specs

Array = jax.Array


def pad_to_multiple(
    X: np.ndarray, y: np.ndarray, n_shards: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Zero-pad rows so ``n`` divides evenly; returns (X, y, valid mask)."""
    n = X.shape[0]
    rem = (-n) % n_shards
    valid = np.ones((n + rem,), dtype=bool)
    if rem:
        X = np.concatenate([X, np.zeros((rem,) + X.shape[1:], X.dtype)], axis=0)
        y = np.concatenate([y, np.zeros((rem,), y.dtype)], axis=0)
        valid[n:] = False
    return X, y, valid


def shard_dataset(mesh: Mesh, X, y,
                  h2d=NO_SPAN) -> Tuple[Array, Array, Optional[Array]]:
    """Place ``(X, y)`` sharded over the 'data' axis of ``mesh``.

    Returns device arrays plus a ``valid`` mask (None when no padding was
    needed).  For host arrays this is the one host->device transfer of the
    whole run — the analogue of the reference's initial ``RDD.cache()``
    materialization — and it is the fit's own hand-off
    (``gradient_descent._stage_dense`` with a destination a device): the
    rows go in blocks, each to the device that owns it and in the form that
    leaves the host's runtime least to re-tile (a C-ordered array's rows
    flat, a Fortran-ordered array's 2-byte items as 32-bit words: the chip
    makes its own layout of them as it writes the block into the shard), so
    a dataset larger than one device's memory arrives and no device holds
    more than its shard and the blocks in flight to it.  ``h2d`` is the
    ``train.h2d`` span
    of a fit that hands its host arrays over here, told what the copy did.

    Place once, fit many: what this returns is laid out for ``mesh``, and
    handed such arrays (its own result, or any ``jax.Array`` whose sharding
    is equivalent to rows over 'data', whatever ``Mesh`` object spells it)
    it returns them AS THEY ARE — no fetch, no copy, ``valid`` None.  So a
    dataset is cached across the chips by calling this once and handing
    the result to every fit; each fit then trains it where it lies.  A
    ``jax.Array`` laid out otherwise (one device, another sharding) is
    re-laid on the devices, never through the host; rows that do not
    divide by the shards are zero-padded there and masked.

    Multi-host jobs (``jax.process_count() > 1`` after
    ``initialize_distributed``): ``X``/``y`` are each process's LOCAL rows —
    the analogue of each Spark executor reading its own input splits
    (SURVEY.md §3.4) — and the global sharded arrays are assembled without
    any cross-host data movement; only gradient psums ride DCN.
    """
    if jax.process_count() > 1:
        return _shard_dataset_multihost(mesh, np.asarray(X), np.asarray(y))
    n_shards = mesh.shape[DATA_AXIS]
    x_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    row_sharding = NamedSharding(mesh, P(DATA_AXIS))
    if isinstance(X, jax.Array):
        y = jnp.asarray(y)  # the labels follow the rows
        n = X.shape[0]
        rem = (-n) % n_shards
        if (not rem and X.sharding.is_equivalent_to(x_sharding, X.ndim)
                and y.sharding.is_equivalent_to(row_sharding, y.ndim)):
            return X, y, None
        valid = None
        if rem:
            # graftlint: disable=shape-trap -- once-per-dataset placement of rows that are on devices already: host numpy would fetch them
            X = jnp.concatenate([X, jnp.zeros((rem,) + X.shape[1:], X.dtype)])
            # graftlint: disable=shape-trap -- as above
            y = jnp.concatenate([y, jnp.zeros((rem,), y.dtype)])
            valid = jax.device_put(jnp.arange(n + rem) < n, row_sharding)
        return (jax.device_put(X, x_sharding),
                jax.device_put(y, row_sharding), valid)
    from tpu_sgd.optimize.gradient_descent import _stage_dense

    Xh = np.asarray(X)
    yh = np.asarray(y)
    n = Xh.shape[0]
    if mesh.devices.size != n_shards:
        # a model axis too: every shard lies on several devices, which the
        # plain placement by sharding lays out
        Xh, yh, validh = pad_to_multiple(Xh, yh, n_shards)
        return (jax.device_put(Xh, x_sharding),
                jax.device_put(yh, row_sharding),
                None if n == Xh.shape[0]
                else jax.device_put(validh, row_sharding))
    Xd, blocks, block_bytes = _stage_dense(Xh, h2d, mesh)
    h2d.set(blocks=blocks, block_bytes=block_bytes)
    rem = Xd.shape[0] - n  # the zero rows behind the last shard's
    if not rem:
        return Xd, jax.device_put(yh, row_sharding), None
    yh = np.concatenate([yh, np.zeros((rem,), yh.dtype)])
    return (Xd, jax.device_put(yh, row_sharding),
            jax.device_put(np.arange(n + rem) < n, row_sharding))


def _shard_dataset_multihost(mesh: Mesh, Xh, yh):
    """Assemble globally-sharded arrays from per-process local rows.

    Each process contributes its rows via
    ``make_array_from_process_local_data`` — no host ever holds (or sends)
    another host's shard.  Per-process row counts may be uneven (the
    analogue of Spark's arbitrary-size input splits): a process allgather
    agrees on one common padded per-process length, so every process infers
    the SAME global shape; padding rows are masked out via the ``valid``
    mask.  Equal, locally-aligned splits need no padding and return
    ``valid=None`` like the single-process path, keeping the no-mask fast
    paths (incl. gram DP) available.
    """
    from jax.experimental import multihost_utils

    local_shards = dict(mesh.local_mesh.shape).get(DATA_AXIS, 1)
    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray(Xh.shape[0]))
    )
    target = int(counts.max())
    target += (-target) % local_shards
    n = Xh.shape[0]
    pad = target - n
    valid = np.ones((target,), dtype=bool)
    if pad:
        Xh = np.concatenate(
            [Xh, np.zeros((pad,) + Xh.shape[1:], Xh.dtype)], axis=0
        )
        yh = np.concatenate([yh, np.zeros((pad,), yh.dtype)], axis=0)
        valid[n:] = False
    row_sharding = NamedSharding(mesh, P(DATA_AXIS))
    Xd = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(DATA_AXIS, None)), Xh
    )
    yd = jax.make_array_from_process_local_data(row_sharding, yh)
    if int(counts.min()) == target:
        # every process arrived equal AND locally aligned — no padding
        # anywhere, so return valid=None like the single-process path and
        # keep the no-mask fast paths (incl. gram DP) available; the
        # decision is identical on every process (counts is allgathered)
        return Xd, yd, None
    vd = jax.make_array_from_process_local_data(row_sharding, valid)
    return Xd, yd, vd


def dp_step_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    with_valid: bool,
):
    """Build the jitted shard_map'ed SINGLE-step function — the shared
    wiring for every observed/streamed mesh path (one source of truth for
    the step's in/out specs)."""
    from tpu_sgd.optimize.gradient_descent import make_step

    step = make_step(gradient, updater, config, axis_name=DATA_AXIS)
    # ``hyper`` (the step size and the regulariser: operands, replicated)
    # behind ``reg_val``, as in ``make_step``
    if with_valid:
        body = step
        in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS), P(), P(), P(),
                    P(DATA_AXIS))
    else:
        body = lambda w, X, y, i, r, hyper: step(w, X, y, i, r, hyper)
        in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS), P(), P(), P())
    return jax.jit(
        shard_map_fn(mesh, body, in_specs, (P(), P(), P(), P()))
    )


#: replicated per-step ys of one fused superstep — (weights, loss, reg,
#: count, delta_norm, weight_norm), each stacked (K, ...); the psums
#: inside make_step leave every leaf identical on all shards
_SUPERSTEP_YS_SPECS = (P(), P(), P(), P(), P(), P())


def dp_superstep_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
):
    """Build the jitted shard_map'ed K-fused superstep over PER-STEP
    batches — ``make_superstep`` with the ICI all-reduce, consuming a
    row-sharded ``(K, rows, d)`` superchunk (``superchunk_specs``).

    This is what lifts the meshed host-streamed feed's old
    per-iteration-driver restriction: one sharded superchunk transfer
    plus ONE sharded program dispatch advance K iterations on every
    core, with the same per-step math and psum combines as the meshed
    per-iteration ``dp_step_fn`` (same-program contracts bitwise; vs
    the per-iteration driver the usual cross-program reassociation
    tolerance — see ``make_superstep``)."""
    from tpu_sgd.optimize.gradient_descent import make_superstep

    sstep = make_superstep(gradient, updater, config, axis_name=DATA_AXIS)
    in_specs = (P(), P(), P(), P()) + superchunk_specs()
    return jax.jit(shard_map_fn(
        mesh, sstep, in_specs, (P(), _SUPERSTEP_YS_SPECS)))


def dp_shared_superstep_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    k: int,
    mesh: Mesh,
    with_valid: bool,
):
    """Build the jitted shard_map'ed K-fused superstep over ONE shared
    sharded batch — ``make_shared_batch_superstep`` with the ICI
    all-reduce: the meshed observed (listener/checkpoint) stepwise
    driver and the meshed streamed full-batch feed fuse K iterations
    per dispatch over data that moved once (``shard_dataset`` / the
    one-time streamed transfer)."""
    from tpu_sgd.optimize.gradient_descent import (
        make_shared_batch_superstep,
    )

    sstep = make_shared_batch_superstep(gradient, updater, config, k,
                                        axis_name=DATA_AXIS)
    if with_valid:
        body = sstep
        in_specs = (P(), P(), P(), P(), P(DATA_AXIS, None), P(DATA_AXIS),
                    P(DATA_AXIS))
    else:
        body = lambda w, rv, hyper, i0, X, y: sstep(w, rv, hyper, i0, X, y)
        in_specs = (P(), P(), P(), P(), P(DATA_AXIS, None), P(DATA_AXIS))
    return jax.jit(shard_map_fn(
        mesh, body, in_specs, (P(), _SUPERSTEP_YS_SPECS)))


#: per-shard error-feedback state of the compressed gradient wire: one
#: (dim,) accumulator per shard, globally a (n_shards, dim) array
#: sharded over 'data' — state, like the weights, but NOT replicated
#: (each shard's accumulator holds ITS dropped mass)
_EF_SPEC = P(DATA_AXIS, None)


def dp_compressed_step_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    mesh: Mesh,
    with_valid: bool,
):
    """Jitted shard_map'ed single step over the COMPRESSED gradient
    wire (``make_compressed_step`` with the 'data' axis): the gradient
    all-reduce ships top-k ``(values, indices)`` segments with per-shard
    error-feedback state instead of a dense ``(d,)`` psum — README
    "Compressed wire".  Signature: ``fn(w, ef, X, y, i, reg_val, hyper[,
    valid]) -> (new_w, new_ef, loss, new_reg, count)`` where ``ef`` is
    the ``(n_shards, dim)`` sharded accumulator."""
    from tpu_sgd.optimize.gradient_descent import make_compressed_step

    step = make_compressed_step(gradient, updater, config, topk_frac,
                                axis_name=DATA_AXIS)

    def body(w, ef, X, y, i, rv, hyper, valid=None):
        new_w, new_ef, loss, new_rv, c = step(w, ef[0], X, y, i, rv,
                                              hyper, valid)
        return new_w, new_ef[None], loss, new_rv, c

    in_specs = (P(), _EF_SPEC, P(DATA_AXIS, None), P(DATA_AXIS), P(),
                P(), P())
    if with_valid:
        in_specs = in_specs + (P(DATA_AXIS),)
    return jax.jit(shard_map_fn(
        mesh, body, in_specs, (P(), _EF_SPEC, P(), P(), P())))


def dp_compressed_superstep_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    mesh: Mesh,
):
    """:func:`dp_superstep_fn` over the compressed wire: K fused
    compressed steps per dispatch, the per-shard EF accumulator carried
    in the scan and the per-step post-update accumulators returned as a
    ``(K, n_shards, dim)`` ys leaf (iteration-exact EF for
    mid-superstep checkpoints).  ``fn(w, ef, reg_val, hyper, i0, Xs, ys,
    valids) -> (w, ef, (*step_ys, efs))``."""
    from tpu_sgd.optimize.gradient_descent import (
        make_compressed_superstep,
    )

    sstep = make_compressed_superstep(gradient, updater, config,
                                      topk_frac, axis_name=DATA_AXIS)

    def body(w, ef, rv, hyper, i0, Xs, ys, valids):
        new_w, new_ef, out = sstep(w, ef[0], rv, hyper, i0, Xs, ys, valids)
        return new_w, new_ef[None], out[:6] + (out[6][:, None, :],)

    in_specs = (P(), _EF_SPEC, P(), P(), P()) + superchunk_specs()
    out_specs = (P(), _EF_SPEC,
                 _SUPERSTEP_YS_SPECS + (P(None, DATA_AXIS, None),))
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def dp_compressed_shared_superstep_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    k: int,
    mesh: Mesh,
    with_valid: bool,
):
    """:func:`dp_shared_superstep_fn` over the compressed wire (one
    shared sharded batch, K fused compressed steps; same EF
    carry-and-ys contract as :func:`dp_compressed_superstep_fn`)."""
    from tpu_sgd.optimize.gradient_descent import (
        make_compressed_shared_superstep,
    )

    sstep = make_compressed_shared_superstep(
        gradient, updater, config, topk_frac, k, axis_name=DATA_AXIS)

    def body(w, ef, rv, hyper, i0, X, y, valid=None):
        new_w, new_ef, out = sstep(w, ef[0], rv, hyper, i0, X, y, valid)
        return new_w, new_ef[None], out[:6] + (out[6][:, None, :],)

    in_specs = (P(), _EF_SPEC, P(), P(), P(), P(DATA_AXIS, None),
                P(DATA_AXIS))
    if with_valid:
        in_specs = in_specs + (P(DATA_AXIS),)
    out_specs = (P(), _EF_SPEC,
                 _SUPERSTEP_YS_SPECS + (P(None, DATA_AXIS, None),))
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def dp_run_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    with_valid: bool,
):
    """Build the jitted shard_map'ed full-loop runner.

    The inner body is *the same* ``make_run`` used single-device, with
    ``axis_name='data'`` turning its combines into ICI all-reduces — one
    compiled XLA program for the entire optimization across all cores.
    ``fn(w0, X, y, hyper[, valid])``: ``hyper`` (``make_run``'s: the step
    size and the regulariser) is an operand here too, replicated, so a new
    value of either runs the program the mesh already holds.
    """
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(gradient, updater, config, axis_name=DATA_AXIS)
    if with_valid:
        body = lambda w, X, y, hyper, v: run(w, X, y, hyper, v)
        in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS), P(),
                    P(DATA_AXIS))
    else:
        body = lambda w, X, y, hyper: run(w, X, y, hyper)
        in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS), P())
    out_specs = (P(), P(), P())
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def dp_optimize(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    initial_weights,
    X,
    y,
):
    """Shard, run, return ``(weights, loss_history, n_recorded)``."""
    Xd, yd, valid = shard_dataset(mesh, X, y)
    w0 = jnp.asarray(initial_weights)
    fn = dp_run_fn(gradient, updater, config, mesh, valid is not None)
    hyper = config.hyper()
    if valid is not None:
        return fn(w0, Xd, yd, hyper, valid)
    return fn(w0, Xd, yd, hyper)
