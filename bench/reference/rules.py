"""The loss and update rules of MLlib's mini-batch SGD, written once for any
array module ``xp`` (``numpy`` or ``jax.numpy``); nothing of ``tpu_sgd`` is
imported.  Labels are in {0, 1}.

``pointwise(xp, name, margin, y) -> (dloss/dmargin, loss)`` per example;
``update(xp, name, w, g, step_size, t, reg) -> (w', reg_val(w'))`` with the
step ``step_size / sqrt(t)`` (Updater.scala)."""


def pointwise(xp, name, margin, y):
    if name == "LeastSquaresGradient":
        diff = margin - y
        return diff, 0.5 * diff * diff
    if name == "LogisticGradient":
        # log(1 + exp(-m)) for y = 1, log(1 + exp(m)) for y = 0, stable
        softplus = xp.maximum(-margin, 0.0) + xp.log1p(xp.exp(-xp.abs(margin)))
        loss = xp.where(y > 0, softplus, softplus + margin)
        return 1.0 / (1.0 + xp.exp(-margin)) - y, loss
    if name == "HingeGradient":
        s = 2.0 * y - 1.0
        slack = 1.0 - s * margin
        return xp.where(slack > 0, -s, 0.0), xp.maximum(slack, 0.0)
    raise ValueError(f"no pointwise rule for gradient {name!r}")


def update(xp, name, w, g, step_size, t, reg):
    eta = step_size / xp.sqrt(xp.asarray(t, w.dtype))
    if name == "SimpleUpdater":
        return w - eta * g, xp.zeros((), w.dtype)
    if name == "SquaredL2Updater":
        w = w * (1.0 - eta * reg) - eta * g
        return w, 0.5 * reg * xp.sum(w * w)
    if name == "L1Updater":
        w = w - eta * g
        w = xp.sign(w) * xp.maximum(xp.abs(w) - reg * eta, 0.0)
        return w, reg * xp.sum(xp.abs(w))
    raise ValueError(f"no update rule for updater {name!r}")
