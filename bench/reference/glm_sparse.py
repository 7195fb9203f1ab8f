"""Plain mini-batch SGD over sparse rows with a fixed number of stored
entries a row: numpy on the host, float64, a gather for the margins and a
``bincount`` (a plain segment sum) for the gradient.  It holds nothing on the
device, so the device's peak memory stays the program's.  No program code is
imported.  Departure from "jax.numpy, float32": the same gather and segment
sum on the chip take as long as the program under test; float64 on the host
is the more exact and the shorter.

Sampling and the loss history are as in ``glm_dense``; at fraction 1.0 (the
only fraction a cell runs today) no key is drawn.

``operands`` names a lower precision for the CONTROL: stored values, weights
and coefficients are rounded to it before every product."""

import numpy as np

from bench.reference import rules


def _round(a, operands):
    if operands is None:
        return a
    import ml_dtypes  # noqa: F401  (registers bfloat16 and float8 with numpy)

    return a.astype(np.dtype(operands)).astype(np.float64)


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``X`` is a BCOO whose row ``i`` owns entries ``[i*k, (i+1)*k)``."""
    n, d = X.shape
    k = int(config["nnz_per_row"])
    fraction = float(config["mini_batch_fraction"])
    vals = _round(np.asarray(X.data, np.float64).reshape(n, k), operands)
    idx = np.asarray(X.indices)
    if not np.array_equal(idx[::k, 0], np.arange(n)):
        raise ValueError("rows do not own a fixed run of stored entries")
    cols = idx[:, 1].reshape(n, k)
    y = np.asarray(y, np.float64)
    w = np.asarray(w0, np.float64)
    step, reg = float(config["step_size"]), float(config["reg_param"])
    _, reg_val = rules.update(np, config["updater"], w, np.zeros_like(w),
                              0.0, 1, reg)
    losses = []
    for t in range(1, int(config["num_iterations"]) + 1):
        if fraction < 1.0:
            import jax

            mask = np.asarray(jax.random.bernoulli(
                jax.random.fold_in(jax.random.PRNGKey(seed), t), fraction,
                (n,)))
        else:
            mask = np.ones((n,), bool)
        c = int(mask.sum())
        if c == 0:
            continue
        margin = np.einsum("ij,ij->i", vals, _round(w, operands)[cols])
        coeff, loss = rules.pointwise(np, config["gradient"], margin, y)
        coeff = _round(np.where(mask, coeff, 0.0), operands)
        g = np.bincount(cols.reshape(-1),
                        weights=(coeff[:, None] * vals).reshape(-1),
                        minlength=d)
        losses.append(float(loss[mask].sum() / c + reg_val))
        w, reg_val = rules.update(np, config["updater"], w, g / c, step, t,
                                  reg)
    return w.astype(np.float32), np.asarray(losses, np.float32)
