"""Plain mini-batch SGD over dense rows: ``jax.numpy``, float32, matmuls at
``highest`` precision, whole arrays (the chip's compiler fuses the conversion
of X into both products, so no float32 copy of X is held).  No program code
is imported.

The mini-batch of iteration ``t`` (from 1) is the Bernoulli draw the
program's contract states (``tpu_sgd/config.py``: iteration ``t`` folds ``t``
into the key of the seed): ``bernoulli(fold_in(PRNGKey(seed), t), fraction,
(n,))``, normalised by the realised count; an empty draw skips the update.
The loss recorded at ``t`` is the batch's mean loss at the old weights plus
the old weights' regularisation value (MLlib's loss history).

``operands`` names a lower precision for the CONTROL: every matmul operand
(rows, weights, coefficients) is rounded to it first, the accumulation stays
float32.  The rows are rounded once, IN PLACE (the buffer of ``X`` is
donated: on the chip the caller's ``X`` is gone afterwards), since a rounded
copy beside an 8 GB ``X`` does not fit."""

import functools

import jax
import jax.numpy as jnp

from bench.reference import rules

HIGHEST = jax.lax.Precision.HIGHEST


def _round(a, operands):
    """``a`` rounded to the float type ``operands`` names, kept in its own
    type.  ``reduce_precision`` and not a pair of casts: the chip's compiler
    removes a cast down and up again as excess precision."""
    if operands is None:
        return a
    info = jnp.finfo(jnp.dtype(operands))
    return jax.lax.reduce_precision(a, info.nexp, info.nmant)


_round_in_place = jax.jit(_round, static_argnums=1, donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _fit_fn(n, d, gradient, updater, fraction, step_size, reg, iterations,
            operands):
    def f32(a):
        return _round(a.astype(jnp.float32), operands)

    def sums(w, X, y, mask):
        Xf = X.astype(jnp.float32)  # the control's X arrives rounded
        margin = jnp.dot(Xf, f32(w), precision=HIGHEST)
        coeff, loss = rules.pointwise(jnp, gradient, margin, y)
        coeff = jnp.where(mask, coeff, 0.0)
        return (jnp.dot(f32(coeff), Xf, precision=HIGHEST),
                jnp.sum(jnp.where(mask, loss, 0.0)), jnp.sum(mask))

    @jax.jit
    def fit(X, y, w0, key):
        _, reg0 = rules.update(jnp, updater, w0, jnp.zeros_like(w0), 0.0, 1,
                               reg)

        def step(t, carry):
            w, reg_val, losses = carry
            if fraction < 1.0:
                mask = jax.random.bernoulli(jax.random.fold_in(key, t),
                                            fraction, (n,))
            else:
                mask = jnp.ones((n,), bool)
            g, ls, c = sums(w, X, y, mask)
            cf = jnp.maximum(c, 1).astype(jnp.float32)
            new_w, new_reg = rules.update(jnp, updater, w, g / cf, step_size,
                                          t, reg)
            losses = losses.at[t - 1].set(ls / cf + reg_val)
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
    if operands is not None and (jnp.finfo(jnp.dtype(operands)).nmant
                                 < jnp.finfo(X.dtype).nmant):
        X = _round_in_place(X, operands)
    fn = _fit_fn(X.shape[0], X.shape[1], config["gradient"],
                 config["updater"], float(config["mini_batch_fraction"]),
                 float(config["step_size"]), float(config["reg_param"]),
                 int(config["num_iterations"]), operands)
    w, losses = fn(X, y, jnp.asarray(w0, jnp.float32),
                   jax.random.PRNGKey(seed))
    return np.asarray(w), np.asarray(losses)
