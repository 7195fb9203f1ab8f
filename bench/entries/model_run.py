"""A fit through the model-level entry point most users call:
``<Model>WithSGD(...).run((X, y))`` — validation, the planner, and for dense
host arrays a host-to-device copy of the whole dataset, on every fit.  BCOO
features pass through ``run`` without a copy."""

import jax
import numpy as np

import tpu_sgd


def prepare(config: dict, X, y, seed: int):
    """Build the algorithm ONCE; ``fit() -> (weights, loss history)``."""
    alg = getattr(tpu_sgd, config["model"])(
        float(config["step_size"]), int(config["num_iterations"]),
        reg_param=float(config["reg_param"]),
        mini_batch_fraction=float(config["mini_batch_fraction"]))
    if type(alg.optimizer.gradient).__name__ != config["gradient"]:
        raise ValueError(
            f"{config['model']} trains {type(alg.optimizer.gradient).__name__}"
            f", the configuration states {config['gradient']}")
    (alg.optimizer.set_updater(getattr(tpu_sgd, config["updater"])())
     .set_sampling(config["sampling"])
     .set_convergence_tol(float(config["convergence_tol"]))
     .set_seed(seed))

    def fit():
        model = alg.run((X, y))
        return (jax.block_until_ready(model.weights),
                np.asarray(alg.optimizer.loss_history))

    return fit
