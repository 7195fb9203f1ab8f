"""2-D (data x model) sharded SGD: example axis AND feature axis sharded.

The reference has no tensor parallelism — its model is one dense vector
(SURVEY.md §2 parallelism ledger) — but the ledger reserves a 2-D
``('data', 'model')`` hook for very wide feature spaces.  This module is that
hook: ``X`` is sharded over both axes, ``w`` is sharded over features, the
per-core partial margins ``X_block @ w_block`` are all-reduced over the
``model`` axis, gradients over ``data``, and the updater runs block-local
with its scalar reg value combined over ``model``.  Both all-reduces ride ICI.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.updaters import Updater
from tpu_sgd.parallel.mesh import DATA_AXIS, MODEL_AXIS, shard_map_fn


def pad_features_to_multiple(X: np.ndarray, w0: np.ndarray, n_shards: int):
    """Zero-pad the feature axis; zero columns stay exactly zero through all
    three updaters (grad is 0 and every update rule maps 0 -> 0), so padding
    is invisible in the result. Returns (X, w0, orig_dim)."""
    d = X.shape[1]
    rem = (-d) % n_shards
    if rem:
        X = np.concatenate([X, np.zeros((X.shape[0], rem), X.dtype)], axis=1)
        w0 = np.concatenate([w0, np.zeros((rem,), w0.dtype)])
    return X, w0, d


def dp_mp_run_fn(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    with_valid: bool,
):
    """Jitted shard_map'ed runner over a 2-D ('data', 'model') mesh."""
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(
        gradient, updater, config,
        axis_name=DATA_AXIS, model_axis_name=MODEL_AXIS,
    )
    if with_valid:
        body = lambda w, X, y, hyper, v: run(w, X, y, hyper, v)
        in_specs = (P(MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS),
                    P(), P(DATA_AXIS))
    else:
        body = lambda w, X, y, hyper: run(w, X, y, hyper)
        in_specs = (P(MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS),
                    P())
    out_specs = (P(MODEL_AXIS), P(), P())
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def dp_mp_optimize(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    mesh: Mesh,
    initial_weights,
    X,
    y,
):
    """Shard 2-D, run, return ``(weights[:orig_dim], loss_history, n_rec)``."""
    from tpu_sgd.parallel.data_parallel import pad_to_multiple

    n_data = mesh.shape[DATA_AXIS]
    n_model = mesh.shape[MODEL_AXIS]
    Xh = np.asarray(X)
    yh = np.asarray(y)
    w0h = np.asarray(initial_weights)
    n = Xh.shape[0]
    Xh, yh, validh = pad_to_multiple(Xh, yh, n_data)
    Xh, w0h, orig_dim = pad_features_to_multiple(Xh, w0h, n_model)
    need_valid = n != Xh.shape[0]

    Xd = jax.device_put(Xh, NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS)))
    yd = jax.device_put(yh, NamedSharding(mesh, P(DATA_AXIS)))
    wd = jax.device_put(w0h, NamedSharding(mesh, P(MODEL_AXIS)))
    fn = dp_mp_run_fn(gradient, updater, config, mesh, need_valid)
    hyper = config.hyper()
    if need_valid:
        vd = jax.device_put(validh, NamedSharding(mesh, P(DATA_AXIS)))
        w, losses, n_rec = fn(wd, Xd, yd, hyper, vd)
    else:
        w, losses, n_rec = fn(wd, Xd, yd, hyper)
    return w[:orig_dim], losses, n_rec
