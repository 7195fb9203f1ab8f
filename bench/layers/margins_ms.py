"""Step: own time of the device operations traced under ``sgd.margins``
(``margins_of``: X . w, the first read of X) per iteration.  Mean over the
traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.margins")
