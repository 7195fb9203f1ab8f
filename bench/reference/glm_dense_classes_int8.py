"""Plain full-batch gradient descent for K-class (multinomial) logistic
regression over dense 8-bit INTEGER rows, trained as the integers they are:
``jax.numpy``, float32, matmuls at ``highest`` precision.  No program code is
imported.  ``cifar5m-int8-multinomial``'s copy of ``glm_dense_classes``: the
model, the update, the loss history and the sums over static ROW BLOCKS are
that file's, to the line.  The departures:

* the rows arrive as int8 and every block is widened to float32 where it is
  read (exact: an int8 is a float32), so the reference computes on the values
  the rows hold and no float copy of X is kept;
* a block is 131,072 rows, half of ``glm_dense_classes``': its float32 form
  is 1.6 GB, which fits beside 12.29 GB of int8 rows on a 16 GB chip where
  3.2 GB leaves no room for the products' own temporaries;
* the CONTROL's rows cannot be rounded in place (127 rounds to 128 under
  three bits of mantissa, which no int8 holds), so where ``operands`` names a
  type that does not hold every int8 exactly the block's float32 form is
  rounded where it is read, every iteration; bfloat16 (8 bits) holds them
  all and rounds nothing of X;
* the control rounds every operand to the MANTISSA of the type ``operands``
  names and keeps float32's exponent (``_round_mantissa``, not
  ``glm_dense._round``): the fit on the integers holds the weights at the
  rows' scale, ``w / 64``, 1e-4 to 1e-3, under ``float8_e4m3fn``'s smallest
  subnormal (2^-9), and a control that rounds them all to zero fails for its
  RANGE and says nothing of a precision.  The scale is a power of two, which
  a mantissa's rounding commutes with, so this is what ``float8_e4m3fn``
  operands do to the equivalent fit on ``x = q / 64`` (whose rows and weights
  lie inside that type's range) less its flushes of small coefficients to
  zero: the mildest form of that precision, which the limits still have to
  refuse.  bfloat16 has float32's exponent: nothing changes for it.

The model is MLlib's ``LogisticGradient(numClasses = K)``
(``mllib/optimization/Gradient.scala``): class 0 is the pivot with the zero
logit, the weights are a ``(K-1, d)`` matrix ``W`` (its row-major flattening
is MLlib's flat vector), a row's logits are ``[0, x . W[0], ..., x . W[K-2]]``,
its loss ``-log softmax(logits)[y]`` and its gradient ``(softmax(logits)[1:] -
onehot(y)[1:]) x``.  Every iteration takes ALL rows (``miniBatchFraction``
1.0: nothing is drawn), normalised by their count; the update and the loss
history are ``glm_dense``'s (``rules.update``; the mean loss at the old
weights plus the old weights' regularisation value).

``w0`` may be one class's ``(d,)`` row (the harness's zeros), broadcast to all
rows, or the ``(K-1, d)`` matrix; the weights come back as the matrix.

``operands`` names a lower precision for the CONTROL, as in ``glm_dense``:
every matmul operand (rows, weights, coefficients) is rounded to its mantissa
first, the accumulation stays float32."""

import functools

import jax
import jax.numpy as jnp

from bench.reference import rules
from bench.reference.glm_dense import HIGHEST

#: rows a block of the sums takes
BLOCK_ROWS = 1 << 17
#: mantissa bits (the implicit one left out) that hold every int8 exactly
INT8_MANTISSA = 7


def _round_mantissa(a, operands):
    """``a`` rounded to the mantissa of the float type ``operands`` names, at
    float32's range, kept in its own type.  ``reduce_precision`` and not a
    pair of casts: the chip's compiler removes a cast down and up again as
    excess precision."""
    if operands is None:
        return a
    return jax.lax.reduce_precision(
        a, jnp.finfo(jnp.float32).nexp, jnp.finfo(jnp.dtype(operands)).nmant)


def class_sums(W, X, y, classes, f32=lambda a: a.astype(jnp.float32),
               rows_f32=lambda a: a.astype(jnp.float32)):
    """``(gradient sum (K-1, d), loss sum)`` over the int8 rows of ``X``,
    the softmax written out; ``f32`` rounds a matmul's small operand and
    ``rows_f32`` the rows (the control's)."""
    Xf = rows_f32(X)
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
        return _round_mantissa(a.astype(jnp.float32), operands)

    rows_f32 = f32 if operands is not None and (
        jnp.finfo(jnp.dtype(operands)).nmant < INT8_MANTISSA) else (
            lambda a: a.astype(jnp.float32))

    def sums(W, X, y):
        # static slices: the chip's compiler reads each block of X where it
        # lies (a slice at a traced offset had all of X copied to row-major)
        parts = [class_sums(W, X[lo:lo + block], y[lo:lo + block], classes,
                            f32, rows_f32) for lo in range(0, n, block)]
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
        raise ValueError("glm_dense_classes_int8 is the full-batch fit: "
                         "mini_batch_fraction must be 1.0, got "
                         f"{config['mini_batch_fraction']}")
    classes = int(config["classes"])
    X, y = jnp.asarray(X), jnp.asarray(y, jnp.float32)
    if X.dtype != jnp.int8:
        raise ValueError(f"glm_dense_classes_int8 takes int8 rows, not "
                         f"{X.dtype}")
    fn = _fit_fn(X.shape[0], X.shape[1], classes, config["updater"],
                 float(config["step_size"]), float(config["reg_param"]),
                 int(config["num_iterations"]), operands, int(block_rows))
    W0 = jnp.broadcast_to(jnp.asarray(w0, jnp.float32),
                          (classes - 1, X.shape[1]))
    W, losses = fn(X, y, W0)
    return np.asarray(W), np.asarray(losses)
