"""Step: the bytes of one feature as the step reads it from HBM: the
``row_item_bytes`` attribute of the fits' ``train.run`` spans, mean over the
traced fits.  1 where the rows are int8 and the step's products widen them
on the chip (the one-read kernel in VMEM: whether the mechanism engaged; the
two matmuls' fusions likewise), 2 for bfloat16 rows, 4 for float32 ones.
None where no fit has a ``train.run`` span that carries it (a program from
before the attribute: the parent of the PR that added it; no trace of the
run's own)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    items = [int(s["stats"]["row_item_bytes"]) for f in reduced["fits"]
             for s in f["spans"]
             if s["name"] == "train.run" and "row_item_bytes" in s["stats"]]
    if not items:
        return None
    return sum(items) / len(items)
