"""A pass of a stream through the streaming model's entry point:
``StreamingLinearRegressionWithSGD(...).train_on(micro-batches)`` — for each
micro-batch in order ``run()`` from the latest weights (validation, the
planner, the host-to-device copy of the micro-batch), the model published
and the listeners called.  One fit of the harness is one pass of a fresh
stream: the initial weights set anew, then the iterator over the
micro-batches' row ranges of the host array, in order."""

import jax
import numpy as np

import tpu_sgd


def prepare(config: dict, X, y, seed: int):
    """Build the streaming algorithm ONCE; ``fit() -> (the last model's
    weights, every micro-batch's loss history in order)``."""
    alg = getattr(tpu_sgd, config["model"])(
        float(config["step_size"]), int(config["num_iterations"]),
        float(config["mini_batch_fraction"]), float(config["reg_param"]))
    opt = alg.algorithm.optimizer
    for kind in ("gradient", "updater"):
        if type(getattr(opt, kind)).__name__ != config[kind]:
            raise ValueError(
                f"{config['model']} trains "
                f"{type(getattr(opt, kind)).__name__}, the configuration "
                f"states {config[kind]}")
    (opt.set_sampling(config["sampling"])
     .set_convergence_tol(float(config["convergence_tol"]))
     .set_seed(seed))
    alg.algorithm.set_schedule(config["schedule"])
    step = int(config["micro_batch_rows"])
    w0 = np.zeros((X.shape[1],), np.float32)
    losses, published = [], []

    def listener(model, batch_count):
        # what a predictOn beside the stream reads: the latest weights, on
        # the host; and this micro-batch's losses from the optimizer
        published[:] = [np.asarray(model.weights)]
        losses.append(np.asarray(opt.loss_history))

    alg.add_model_update_listener(listener)

    def fit():
        del losses[:]
        alg.set_initial_weights(w0)
        model = alg.train_on((X[a:a + step], y[a:a + step])
                             for a in range(0, X.shape[0], step))
        return jax.block_until_ready(model.weights), np.concatenate(losses)

    return fit
