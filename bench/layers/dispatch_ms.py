"""Optimizer driver: host time inside ``train.dispatch`` (the call of the
compiled whole-run program; with ``built`` 1 it also traces, lowers and
compiles or reads the compile cache) per fit.  Mean over the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "train.dispatch")
