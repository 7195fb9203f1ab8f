"""Plain mini-batch SGD over one contiguous window a step, ONCE A GRID POINT:
``glm_dense_window.py``'s fit (``jax.numpy``, float32, matmuls at ``highest``
precision, the window the program's contract states for
``sampling="sliced"``) at each ``(step_size, reg_param)`` of the
configuration's grid, every model from ``w0`` and ``iterations_a_model``
steps long, nothing shared between points but the rows.  No program code is
imported.

The answer is stacked as the sweep's entry stacks it: the weights ``(points,
d)`` in the grid's order (the step size outer), the loss histories one after
another ``(points * iterations_a_model,)``.

``operands`` names a lower precision for the CONTROL, as in
``glm_dense.py``: the rows are rounded once, IN PLACE, before the first
point (the caller's ``X`` is gone afterwards on the chip), every other
operand where it is used."""

import jax
import jax.numpy as jnp

from bench.reference.glm_dense import _round_in_place
from bench.reference.glm_dense_window import _fit_fn


def grid(config: dict):
    """``[(step_size, reg_param)]``, the step size the outer loop."""
    return [(float(s), float(r)) for s in config["grid"]["step_size"]
            for r in config["grid"]["reg_param"]]


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``(stacked weights, joined loss history)`` as numpy."""
    import numpy as np

    X, y = jnp.asarray(X), jnp.asarray(y, jnp.float32)
    if operands is not None and (jnp.finfo(jnp.dtype(operands)).nmant
                                 < jnp.finfo(X.dtype).nmant):
        X = _round_in_place(X, operands)
    w0, key = jnp.asarray(w0, jnp.float32), jax.random.PRNGKey(seed)
    weights, histories = [], []
    for step_size, reg in grid(config):
        fn = _fit_fn(X.shape[0], X.shape[1], config["gradient"],
                     config["updater"], float(config["mini_batch_fraction"]),
                     step_size, reg, int(config["iterations_a_model"]),
                     operands)
        w, losses = fn(X, y, w0, key)
        weights.append(np.asarray(w))
        histories.append(np.asarray(losses))
    return np.stack(weights), np.concatenate(histories)
