"""Stream fold: host time inside ``stream.publish`` a micro-batch (the
stream position, the history's tail, the checkpoint where one is set, the
model-update listeners: in this cell one, which reads the weights and the
optimizer's losses to the host), mean over the traced micro-batches.  None
where no pass has the span (the parent)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    took = [s["end_ns"] - s["start_ns"] for f in reduced["fits"]
            for s in f["spans"] if s["name"] == "stream.publish"]
    if not took:
        return None
    return sum(took) / len(took) / 1e6
