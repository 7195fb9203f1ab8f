from tpu_sgd.optimize.optimizer import Optimizer
from tpu_sgd.optimize.gradient_descent import (
    GradientDescent,
    make_run,
    make_step,
    row_capacity,
    run_mini_batch_sgd,
)
from tpu_sgd.optimize.lbfgs import LBFGS, run_lbfgs
from tpu_sgd.optimize.normal import NormalEquations
from tpu_sgd.optimize.owlqn import OWLQN

__all__ = [
    "Optimizer",
    "GradientDescent",
    "LBFGS",
    "NormalEquations",
    "OWLQN",
    "make_run",
    "make_step",
    "row_capacity",
    "run_mini_batch_sgd",
    "run_lbfgs",
]
