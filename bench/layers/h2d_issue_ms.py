"""Model harness: what a thread of the host spent issuing the hand-off's
blocks: the host's time inside a fit's ``train.h2d`` spans LESS the time it
stood in the flow-control wait there, both on the host's clock, mean over the
traced fits.  The wait is the spans' ``stall_ms`` over their ``shards``: under
a mesh a thread a device issues that device's blocks, all of them side by
side for the span's length, and ``stall_ms`` is summed over them (one
destination, or a program that does not say: over 1).  Near ``h2d``'s whole
time: the host's issue bounds the copy; small beside it: the wires do.  None
where no fit has a ``train.h2d`` span that carries ``stall_ms`` (a program
from before the counter)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    per_fit = [[(s["end_ns"] - s["start_ns"]) / 1e6
                - float(s["stats"]["stall_ms"])
                / max(1, int(s["stats"].get("shards", 1)))
                for s in f["spans"]
                if s["name"] == "train.h2d" and "stall_ms" in s["stats"]]
               for f in reduced["fits"]]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit)
