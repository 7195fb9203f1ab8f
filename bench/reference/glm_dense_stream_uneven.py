"""Plain streaming SGD over dense micro-batches of UNEQUAL sizes (MLlib's
``StreamingLinearAlgorithm`` over a DStream whose RDDs hold whatever arrived
in the batch interval): ``glm_dense_stream``'s fold over the run's own row
ranges in place of a fixed stride.  For each micro-batch in order
``glm_dense``'s fit — float32, matmuls at ``highest``, the configuration's
iterations of full-batch steps of size ``step_size / sqrt(t)`` with ``t`` from
1 again, loss and gradient summed over the micro-batch's REAL rows and divided
by their count, the loss of each step at the old weights — from the weights
the previous micro-batch left.  It knows nothing of capacities, of padding or
of how the program hands a micro-batch over (it compiles a fit a size: it
runs once, outside set-up and window); no program code is imported.

The ranges are the generator's (``boundaries``: a pure function of the rows),
so the entry and this fold cut the pass alike.

``operands`` is ``glm_dense``'s control: every matmul operand rounded to a
lower precision first, a micro-batch's rows in place on the device."""

import numpy as np

from bench.data.dense_synthetic_stream_uneven import boundaries
from bench.reference import glm_dense


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``(the last micro-batch's weights, every micro-batch's loss history
    in order)`` as numpy."""
    w, losses = np.asarray(w0, np.float32), []
    for a, b in boundaries(config, X):
        w, history = glm_dense.fit(config, X[a:b], y[a:b], w, seed, operands)
        losses.append(history)
    return w, np.concatenate(losses)
