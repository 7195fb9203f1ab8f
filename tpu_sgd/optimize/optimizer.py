"""The Optimizer plugin boundary.

Reference parity: [U] mllib/optimization/Optimizer.scala (SURVEY.md §2 #1,
§1 L4): ``trait Optimizer { def optimize(data, initialWeights): Vector }`` is
the boundary the TPU backend slots behind (BASELINE.json:5).  Here ``data`` is
a ``(X, y)`` pair of arrays (the dense-resident analogue of
``RDD[(label, features)]``) and weights are 1-D jax arrays.
"""

from __future__ import annotations

from typing import Tuple

import jax

Array = jax.Array
Dataset = Tuple[Array, Array]  # (X: (n, d), y: (n,))


class Optimizer:
    """Anything that maps ``(data, initial_weights) -> weights``."""

    #: the function of ``tpu_sgd.plan`` that plans this optimizer's
    #: schedule, by name (the planner lies below ``optimize/`` and imports
    #: none of it); None: nothing plans it
    planned_by = None

    def optimize(self, data: Dataset, initial_weights: Array) -> Array:
        raise NotImplementedError
