"""Model harness: host time inside ``fit.plan`` (``_auto_plan``: the probe
of the device's free memory and ``plan_for``, or the repeat-run key's hit,
which the span's ``cached`` says) per fit.  Mean over the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "fit.plan")
