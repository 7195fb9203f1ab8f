"""Plain full-batch gradient descent for K-class (multinomial) logistic
regression over dense rows: ``jax.numpy``, float32, matmuls at ``highest``
precision.  No program code is imported.

The model is MLlib's ``LogisticGradient(numClasses = K)``
(``mllib/optimization/Gradient.scala``): class 0 is the pivot with the zero
logit, the weights are a ``(K-1, d)`` matrix ``W`` (its row-major flattening
is MLlib's flat vector), a row's logits are ``[0, x . W[0], ..., x . W[K-2]]``,
its loss ``-log softmax(logits)[y]`` and its gradient ``(softmax(logits)[1:] -
onehot(y)[1:]) x``.  Every iteration takes ALL rows (``miniBatchFraction``
1.0: nothing is drawn), normalised by their count; the update and the loss
history are ``glm_dense``'s (``rules.update``; the mean loss at the old
weights plus the old weights' regularisation value).

The sums run over ROW BLOCKS: a block's ``(rows, K)`` logits, probabilities
and coefficients are small, where those of 8,100,000 rows at once do not fit
beside 12.7 GB of X.  The blocks are static slices (the last is what is
left), which the chip's compiler reads where they lie.

``w0`` may be one class's ``(d,)`` row (the harness's zeros), broadcast to all
rows, or the ``(K-1, d)`` matrix; the weights come back as the matrix.

``operands`` names a lower precision for the CONTROL, as in ``glm_dense``:
every matmul operand (rows, weights, coefficients) is rounded to it first, the
accumulation stays float32, and the rows are rounded once IN PLACE (the
caller's ``X`` is gone afterwards on the chip)."""

import functools

import jax
import jax.numpy as jnp

from bench.reference import rules
from bench.reference.glm_dense import HIGHEST, _round, _round_in_place

#: rows a block of the sums takes
BLOCK_ROWS = 1 << 18


def class_sums(W, X, y, classes, f32=lambda a: a.astype(jnp.float32)):
    """``(gradient sum (K-1, d), loss sum)`` over the rows of ``X``, the
    softmax written out; ``f32`` rounds a matmul's small operand (the
    control's)."""
    Xf = X.astype(jnp.float32)
    margins = jnp.dot(Xf, f32(W).T, precision=HIGHEST)  # (rows, K-1)
    logits = jnp.concatenate(
        [jnp.zeros((X.shape[0], 1), jnp.float32), margins], axis=1)
    top = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - top)
    total = jnp.sum(e, axis=1, keepdims=True)
    onehot = y[:, None] == jnp.arange(classes, dtype=jnp.float32)[None, :]
    log_p = logits - top - jnp.log(total)
    loss = -jnp.sum(jnp.where(onehot, log_p, 0.0), axis=1)
    coeff = (e / total - onehot)[:, 1:]  # the pivot has no row of W
    return jnp.dot(f32(coeff).T, Xf, precision=HIGHEST), jnp.sum(loss)


@functools.lru_cache(maxsize=None)
def _fit_fn(n, d, classes, updater, step_size, reg, iterations, operands,
            block):
    def f32(a):
        return _round(a.astype(jnp.float32), operands)

    def sums(W, X, y):
        # static slices: the chip's compiler reads each block of X where it
        # lies (a slice at a traced offset had all of X copied to row-major)
        parts = [class_sums(W, X[lo:lo + block], y[lo:lo + block], classes,
                            f32) for lo in range(0, n, block)]
        return sum(g for g, _ in parts), sum(ls for _, ls in parts)

    @jax.jit
    def fit(X, y, W0):
        _, reg0 = rules.update(jnp, updater, W0, jnp.zeros_like(W0), 0.0, 1,
                               reg)

        def step(t, carry):
            W, reg_val, losses = carry
            g, ls = sums(W, X, y)
            new_W, new_reg = rules.update(jnp, updater, W, g / n, step_size,
                                          t, reg)
            return new_W, new_reg, losses.at[t - 1].set(ls / n + reg_val)

        W, _, losses = jax.lax.fori_loop(
            1, iterations + 1, step,
            (W0, reg0, jnp.zeros((iterations,), jnp.float32)))
        return W, losses

    return fit


def fit(config: dict, X, y, w0, seed: int, operands=None,
        block_rows: int = BLOCK_ROWS):
    """``(weights (K-1, d), loss history)`` as numpy, after the
    configuration's iterations from ``w0``.  ``seed`` draws nothing: the
    batch is every row."""
    import numpy as np

    if float(config["mini_batch_fraction"]) != 1.0:
        raise ValueError("glm_dense_classes is the full-batch fit: "
                         "mini_batch_fraction must be 1.0, got "
                         f"{config['mini_batch_fraction']}")
    classes = int(config["classes"])
    X, y = jnp.asarray(X), jnp.asarray(y, jnp.float32)
    if operands is not None and (jnp.finfo(jnp.dtype(operands)).nmant
                                 < jnp.finfo(X.dtype).nmant):
        X = _round_in_place(X, operands)
    fn = _fit_fn(X.shape[0], X.shape[1], classes, config["updater"],
                 float(config["step_size"]), float(config["reg_param"]),
                 int(config["num_iterations"]), operands, int(block_rows))
    W0 = jnp.broadcast_to(jnp.asarray(w0, jnp.float32),
                          (classes - 1, X.shape[1]))
    W, losses = fn(X, y, W0)
    return np.asarray(W), np.asarray(losses)
