"""A fit at the ``Optimizer`` plugin boundary: ``GradientDescent(...)
.optimize_with_history((X, y), w0)`` on arrays that already live on the
device (Spark's ``runMiniBatchSGD`` on a cached RDD).  No copy, no planner."""

import jax
import numpy as np

import tpu_sgd


def prepare(config: dict, X, y, seed: int):
    """Build the optimizer ONCE; ``fit() -> (weights, loss history)`` runs
    it again on the same data, done when both are in hand."""
    opt = (tpu_sgd.GradientDescent(getattr(tpu_sgd, config["gradient"])(),
                                   getattr(tpu_sgd, config["updater"])())
           .set_step_size(float(config["step_size"]))
           .set_num_iterations(int(config["num_iterations"]))
           .set_reg_param(float(config["reg_param"]))
           .set_mini_batch_fraction(float(config["mini_batch_fraction"]))
           .set_sampling(config["sampling"])
           .set_convergence_tol(float(config["convergence_tol"]))
           .set_seed(seed))
    w0 = np.zeros((X.shape[1],), np.float32)

    def fit():
        w, losses = opt.optimize_with_history((X, y), w0)
        return jax.block_until_ready(w), np.asarray(losses)

    return fit
