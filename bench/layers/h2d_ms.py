"""Model harness: host time inside ``train.h2d`` (the ``jnp.asarray`` of X
and y and the coercion of the initial weights) per fit: the time the host
spends in the calls, not the copy's — the copy drains after them, while the
host is already in ``train.dispatch`` and ``train.fetch``; the span's
``bytes`` are what it was asked to move.  Mean over the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "train.h2d")
