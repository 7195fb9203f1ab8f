"""Step: own time of the device operations traced under ``sgd.wide_sums``
(``Gradient._fused_sums`` at a width whose weights do not fit along the lanes
beside a block of rows: the one-read kernel's wide form) per iteration.  Mean
over the traced fits.  An operation goes by its INNERMOST ``sgd.*`` scope: the
kernel's call, the weights' split into rows in front of it and the fold of its
partials behind it.  None where no operation carries the scope (a width the
narrow kernel takes; two reads; a program from before the scope; no device in
the trace)."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.wide_sums")
