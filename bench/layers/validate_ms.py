"""Model harness: host time inside ``fit.validate`` (``_as_arrays`` and the
model's label check) per fit.  Mean over the traced fits; the program's span,
read from the profiler's trace by ``bench/spans.py``."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "fit.validate")
