"""Plain DATA-PARALLEL mini-batch SGD over dense rows: ``jax.numpy``, float32,
matmuls at ``highest`` precision, whole arrays.  No program code is imported;
what it shares with ``glm_dense.py`` (the rounding of the control's operands)
it takes from there.

The contract it follows is the program's for a dataset sharded by rows over
``as_run.data_parallel`` shards (``tpu_sgd/config.py``; Spark's per-partition
sampler): at iteration ``t`` (from 1) shard ``s`` holds rows ``[s * n/S, (s +
1) * n/S)`` and draws ``bernoulli(fold_in(fold_in(PRNGKey(seed), t), s),
fraction, (n/S,))``; the sums run over the drawn rows of ALL shards, the
normalisation is the realised count over all shards, and a draw that is empty
everywhere skips the update.  The loss recorded at ``t`` is the batch's mean
loss at the old weights plus the old weights' regularisation value.

It is written for the whole arrays: handed arrays that lie sharded by rows it
runs on them as they lie (the compiler partitions the two products and adds
the sum across chips), handed host arrays it runs on one device.

``operands`` names a lower precision for the CONTROL, as in ``glm_dense.py``:
every matmul operand is rounded to it first, the rows once and IN PLACE (the
caller's ``X`` is gone afterwards on the chip)."""

import functools

import jax
import jax.numpy as jnp

from bench.reference import rules
from bench.reference.glm_dense import HIGHEST, _round, _round_in_place


def draws(key, t, shards: int, n_local: int, fraction: float):
    """Iteration ``t``'s mask over all ``shards * n_local`` rows."""
    kt = jax.random.fold_in(key, t)
    return jax.vmap(lambda s: jax.random.bernoulli(
        jax.random.fold_in(kt, s), fraction, (n_local,)))(
            jnp.arange(shards)).reshape(shards * n_local)


@functools.lru_cache(maxsize=None)
def _fit_fn(n, d, shards, gradient, updater, fraction, step_size, reg,
            iterations, operands):
    def f32(a):
        return _round(a.astype(jnp.float32), operands)

    @jax.jit
    def fit(X, y, w0, key):
        _, reg0 = rules.update(jnp, updater, w0, jnp.zeros_like(w0), 0.0, 1,
                               reg)

        def step(t, carry):
            w, reg_val, losses = carry
            # in the step, so that the conversion fuses into both products
            # and no float32 copy of X is held; the control's X arrives
            # rounded
            Xf = X.astype(jnp.float32)
            if fraction < 1.0:
                mask = draws(key, t, shards, n // shards, fraction)
            else:
                mask = jnp.ones((n,), bool)
            margin = jnp.dot(Xf, f32(w), precision=HIGHEST)
            coeff, loss = rules.pointwise(jnp, gradient, margin, y)
            g = jnp.dot(f32(jnp.where(mask, coeff, 0.0)), Xf,
                        precision=HIGHEST)
            c = jnp.sum(mask)
            cf = jnp.maximum(c, 1).astype(jnp.float32)
            new_w, new_reg = rules.update(jnp, updater, w, g / cf, step_size,
                                          t, reg)
            losses = losses.at[t - 1].set(
                jnp.sum(jnp.where(mask, loss, 0.0)) / cf + reg_val)
            return (jnp.where(c > 0, new_w, w),
                    jnp.where(c > 0, new_reg, reg_val), losses)

        w, _, losses = jax.lax.fori_loop(
            1, iterations + 1, step,
            (w0, reg0, jnp.zeros((iterations,), jnp.float32)))
        return w, losses

    return fit


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``(weights, loss history)`` as numpy, after the configuration's
    iterations from ``w0``."""
    import numpy as np

    X, y = jnp.asarray(X), jnp.asarray(y, jnp.float32)
    shards = int(config["as_run"]["data_parallel"])
    if X.shape[0] % shards:
        raise ValueError(f"{X.shape[0]} rows do not divide over {shards} "
                         "shards: the contract pads, this reference does not")
    if operands is not None and (jnp.finfo(jnp.dtype(operands)).nmant
                                 < jnp.finfo(X.dtype).nmant):
        X = _round_in_place(X, operands)
    fn = _fit_fn(X.shape[0], X.shape[1], shards, config["gradient"],
                 config["updater"], float(config["mini_batch_fraction"]),
                 float(config["step_size"]), float(config["reg_param"]),
                 int(config["num_iterations"]), operands)
    w, losses = fn(X, y, jnp.asarray(w0, jnp.float32),
                   jax.random.PRNGKey(seed))
    return np.asarray(w), np.asarray(losses)
