"""Step: own time of the device operations traced under ``sgd.class_sums``
(``MultinomialLogisticGradient.batch_sums``: the sums of a step whose weights
are a ``(K-1, d)`` matrix) per iteration.  Mean over the traced fits.  An
operation goes by its INNERMOST ``sgd.*`` scope: on the one-read path this is
the kernel's call, the weights' cast in front of it and the fold of its
partials behind it; on the two-read path the two products and the softmax keep
``sgd.margins`` / ``sgd.gradient`` / ``sgd.pointwise`` inside it, and this
metric reads what they leave.  None where no operation carries the scope (a
vector of weights; a program from before the scope; no device in the
trace)."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.class_sums")
