"""Model harness: the pieces a fit's dense host array went to the device in:
the ``blocks`` attribute of the fit's ``train.h2d`` spans, mean over the
traced fits.  1 for a ``train.h2d`` span that carries no ``blocks`` (a
program that knows one way only, ``jnp.asarray`` of the whole array: that is
one piece).  None where no fit has a ``train.h2d`` span."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    per_fit = [[int(s["stats"].get("blocks", 1)) for s in f["spans"]
                if s["name"] == "train.h2d"] for f in reduced["fits"]]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit)
