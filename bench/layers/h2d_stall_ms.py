"""Model harness: the time a fit's host stood in the hand-off's flow-control
wait (``_stage_dense``: the oldest block's write, before a block beyond
those in flight is issued): the ``stall_ms`` attribute of the fit's
``train.h2d`` spans, a duration the program took on the host's clock.  Near
``h2d_ms``: the wire or the device bounds the copy and the host waits on
it; near 0: the host's issue of the blocks does.  Mean over the traced
fits; None where no fit has a ``train.h2d`` span that carries it (a program
from before the counter)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    per_fit = [[float(s["stats"]["stall_ms"]) for s in f["spans"]
                if s["name"] == "train.h2d" and "stall_ms" in s["stats"]]
               for f in reduced["fits"]]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit)
