"""Step: whether the one-read kernel draws the step's Bernoulli mask itself:
the ``mask_in_kernel`` attribute of the fits' ``train.run`` spans, mean over
the traced fits.  1 where every step's draw is in the kernel (no array of
the mask is made), 0 where the step is handed an array or draws nothing.
None where no fit has a ``train.run`` span that carries it (a program from
before the attribute; no trace of the run's own)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    drawn = [int(s["stats"]["mask_in_kernel"]) for f in reduced["fits"]
             for s in f["spans"]
             if s["name"] == "train.run" and "mask_in_kernel" in s["stats"]]
    if not drawn:
        return None
    return sum(drawn) / len(drawn)
