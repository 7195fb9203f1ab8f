"""A K-class fit at the ``Optimizer`` plugin boundary:
``GradientDescent(MultinomialLogisticGradient(K), ...)
.optimize_with_history((X, y), w0)`` on arrays that already live on the device
(Spark's ``runMiniBatchSGD`` with ``LogisticGradient(numClasses = K)`` on a
cached RDD).  No copy, no planner.

The weights go in as MLlib's flat vector of ``(K-1) * d`` zeros and come back
as the ``(K-1, d)`` matrix whose row-major flattening that vector is (the
pivot class 0 has no row): the harness hands the reference and the comparison
a ``(d,)`` row of zeros for ``w0``, which broadcasts against the matrix, and
numpy's norm of a matrix is its flat vector's."""

import jax
import numpy as np

import tpu_sgd


def prepare(config: dict, X, y, seed: int):
    """Build the optimizer ONCE; ``fit() -> (weights, loss history)`` runs
    it again on the same data, done when both are in hand."""
    classes = int(config["classes"])
    opt = (tpu_sgd.GradientDescent(
        getattr(tpu_sgd, config["gradient"])(classes),
        getattr(tpu_sgd, config["updater"])())
           .set_step_size(float(config["step_size"]))
           .set_num_iterations(int(config["num_iterations"]))
           .set_reg_param(float(config["reg_param"]))
           .set_mini_batch_fraction(float(config["mini_batch_fraction"]))
           .set_sampling(config["sampling"])
           .set_convergence_tol(float(config["convergence_tol"]))
           .set_seed(seed))
    w0 = np.zeros(((classes - 1) * X.shape[1],), np.float32)

    def fit():
        w, losses = opt.optimize_with_history((X, y), w0)
        # the fit started w's copy to the host behind the program: the
        # matrix is a view of what has landed, no program on the device
        w = np.asarray(jax.block_until_ready(w))
        return w.reshape(classes - 1, -1), np.asarray(losses)

    return fit
