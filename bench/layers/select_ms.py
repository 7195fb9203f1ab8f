"""Optimizer driver: host time inside ``train.select`` per fit: between the
hand-off (``train.h2d``, ``train.place`` under a mesh) and the call, the
sufficient-statistics substitution, the route, the compiled runner's lookup
and what ``train.run`` says of the step's kernel.  Mean over the traced fits; None where no fit has the
span (a program from before it)."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "train.select")
