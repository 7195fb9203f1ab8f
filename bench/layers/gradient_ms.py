"""Step: own time of the device operations traced under ``sgd.gradient``
(``grad_sum_of``: coeff . X, the second read of X) per iteration.  Mean over
the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.gradient")
