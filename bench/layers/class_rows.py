"""Step: the padded class rows both products of the step's class kernel are
issued with: the ``class_rows`` attribute of the fits' ``train.run`` spans,
mean over the traced fits.  Over 0 where the step of a fit with a ``(K-1,
d)`` matrix of weights is the one-read kernel (1,008 for 999 class rows in
bf16: whether the mechanism engaged), 0 where it takes two reads or the
weights are a vector.  None where no fit has a ``train.run`` span that
carries it (a program from before the attribute: the parent of the PR that
added it; no trace of the run's own)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    rows = [int(s["stats"]["class_rows"]) for f in reduced["fits"]
            for s in f["spans"]
            if s["name"] == "train.run" and "class_rows" in s["stats"]]
    if not rows:
        return None
    return sum(rows) / len(rows)
