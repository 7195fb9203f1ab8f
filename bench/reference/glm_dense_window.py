"""Plain mini-batch SGD over one contiguous WINDOW of dense rows a step:
``jax.numpy``, float32, matmuls at ``highest`` precision.  No program code is
imported; what it shares with ``glm_dense.py`` (the rounding of the control's
operands) it takes from there.

The mini-batch of iteration ``t`` (from 1) is the window the program's
contract states for ``sampling="sliced"`` (``tpu_sgd/config.py``; one device,
so no shard index is folded in): the ``m = max(1, round(fraction * n))`` rows
from offset ``randint(fold_in(PRNGKey(seed), t), (), 0, n - m + 1)``, each
once, normalised by ``m``.  A window is never empty, so ``glm_dense.py``'s
rule for an empty draw has nothing to skip here.  The loss recorded at ``t``
is the window's mean loss at the old weights plus the old weights'
regularisation value (MLlib's loss history).

``operands`` names a lower precision for the CONTROL, as in ``glm_dense.py``:
every matmul operand is rounded to it first, the rows once and IN PLACE (the
caller's ``X`` is gone afterwards on the chip)."""

import functools

import jax
import jax.numpy as jnp

from bench.reference import rules
from bench.reference.glm_dense import HIGHEST, _round, _round_in_place


def window_rows(n: int, fraction: float) -> int:
    return max(1, round(fraction * n))


def offset(key, t, n: int, m: int):
    """Where iteration ``t``'s window starts."""
    return jax.random.randint(jax.random.fold_in(key, t), (), 0,
                              max(1, n - m + 1))


@functools.lru_cache(maxsize=None)
def _fit_fn(n, d, gradient, updater, fraction, step_size, reg, iterations,
            operands):
    m = window_rows(n, fraction)

    def f32(a):
        return _round(a.astype(jnp.float32), operands)

    @jax.jit
    def fit(X, y, w0, key):
        _, reg0 = rules.update(jnp, updater, w0, jnp.zeros_like(w0), 0.0, 1,
                               reg)

        def step(t, carry):
            w, reg_val, losses = carry
            start = offset(key, t, n, m)
            # the control's X arrives rounded
            Xw = jax.lax.dynamic_slice_in_dim(X, start, m).astype(jnp.float32)
            yw = jax.lax.dynamic_slice_in_dim(y, start, m)
            margin = jnp.dot(Xw, f32(w), precision=HIGHEST)
            coeff, loss = rules.pointwise(jnp, gradient, margin, yw)
            g = jnp.dot(f32(coeff), Xw, precision=HIGHEST)
            new_w, new_reg = rules.update(jnp, updater, w, g / m, step_size,
                                          t, reg)
            losses = losses.at[t - 1].set(jnp.sum(loss) / m + reg_val)
            return new_w, new_reg, losses

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
