"""A tuning job's grid at the static entry: ``tpu_sgd.run_mini_batch_sgd(
(X, y), gradient, updater, step_size, iterations, reg_param, fraction, w0,
...)`` once a grid point, in the grid's order, on arrays that already live on
the device (Spark's ``GradientDescent.runMiniBatchSGD`` on a cached RDD, a
``ParamGridBuilder``-style loop around it).  Every call builds a new
optimizer, as upstream's does; nothing is kept from one point to the next and
nothing is built here.  It uses nothing a program from before the step size
and the regulariser were operands lacks.

A fit of the harness is ONE SWEEP: the weights of the grid's models stacked
``(points, d)`` and their loss histories one after another.  Each sweep's
start and end on ``time.time()`` go to ``bench/layers/sweep_builds.py``,
whose readers find the ``build.*`` spans of the traced sweeps by them."""

import time

import jax
import numpy as np

import tpu_sgd
from bench.layers import sweep_builds


def grid(config: dict):
    """``[(step_size, reg_param)]`` in the loop's order: the step size
    outer."""
    return [(float(s), float(r)) for s in config["grid"]["step_size"]
            for r in config["grid"]["reg_param"]]


def prepare(config: dict, X, y, seed: int):
    """``fit() -> (stacked weights, joined loss history)`` of one sweep,
    done when every model's weights are in hand."""
    gradient = getattr(tpu_sgd, config["gradient"])
    updater = getattr(tpu_sgd, config["updater"])
    points = grid(config)
    iterations = int(config["iterations_a_model"])
    fraction = float(config["mini_batch_fraction"])
    tol = float(config["convergence_tol"])
    w0 = np.zeros((X.shape[1],), np.float32)

    def fit():
        start = time.time()
        weights, histories = [], []
        for step_size, reg_param in points:
            w, losses = tpu_sgd.run_mini_batch_sgd(
                (X, y), gradient(), updater(), step_size, iterations,
                reg_param, fraction, w0, convergence_tol=tol, seed=seed,
                sampling=config["sampling"])
            weights.append(w)
            histories.append(np.asarray(losses))
        stacked = np.stack([np.asarray(jax.block_until_ready(w))
                            for w in weights])
        sweep_builds.SWEEPS.append((start, time.time()))
        return stacked, np.concatenate(histories)

    return fit
