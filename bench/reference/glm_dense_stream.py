"""Plain streaming SGD over dense micro-batches (MLlib's
``StreamingLinearAlgorithm``): for each micro-batch in the stream's order,
``glm_dense``'s fit — float32, matmuls at ``highest``, the configuration's
iterations of full-batch steps of size ``step_size / sqrt(t)`` with ``t``
from 1 again, the loss of each step at the old weights — from the weights
the previous micro-batch left.  A micro-batch is ``micro_batch_rows`` rows of
``X`` (the last one what is left).  It knows nothing of schedules or of how
the program hands a micro-batch over; no program code is imported.

``operands`` is ``glm_dense``'s control: every matmul operand rounded to a
lower precision first, a micro-batch's rows in place on the device."""

import numpy as np

from bench.reference import glm_dense


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``(the last micro-batch's weights, every micro-batch's loss history
    in order)`` as numpy."""
    step = int(config["micro_batch_rows"])
    w, losses = np.asarray(w0, np.float32), []
    for a in range(0, X.shape[0], step):
        w, history = glm_dense.fit(config, X[a:a + step], y[a:a + step], w,
                                   seed, operands)
        losses.append(history)
    return w, np.concatenate(losses)
