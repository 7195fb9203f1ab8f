"""Optimizer driver: host time inside ``train.place`` (the meshed fit's call
of ``shard_dataset``) per fit: what a fit spends laying its dataset out over
the mesh.  About nothing where the dataset already lies there (the span's
``in_place`` 1, ``bytes`` 0); a fetch and a copy of all of it where it does
not.  Mean over the traced fits; None where the fit has no such span."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.span_ms(trace, run, "train.place")
