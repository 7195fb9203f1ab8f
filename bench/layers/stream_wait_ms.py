"""Stream fold: the time the fold stood waiting for ITS micro-batch to be
whole on the device, the hand-off that did not hide under the previous
micro-batch's fit: host time inside ``stream.wait`` (the worker's answer,
then the blocks' writes into the one array) over the micro-batches trained,
mean over the traced passes.  None where no pass has the span (a program
whose ``train_on`` copies each micro-batch inside its fit: the parent)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    found = [s for f in reduced["fits"] for s in f["spans"]
             if s["name"] in ("stream.wait", "stream.batch")]
    waits = [s["end_ns"] - s["start_ns"] for s in found
             if s["name"] == "stream.wait"]
    batches = len(found) - len(waits)
    if not waits or not batches:
        return None
    return sum(waits) / batches / 1e6
