"""Stream fold: whether a micro-batch's hand-off began under its
predecessor's fit: the ``ahead`` attribute of the passes' ``stream.batch``
spans (1 where the worker had the micro-batch in hand and was issuing its
copy before the previous micro-batch's fit returned, else 0), mean over the
traced micro-batches.  A pass's first micro-batch has no predecessor, so a
pass of three reads 2/3 at best.  None where no span carries it (the
parent)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    ahead = [int(s["stats"]["ahead"]) for f in reduced["fits"]
             for s in f["spans"]
             if s["name"] == "stream.batch" and "ahead" in s["stats"]]
    if not ahead:
        return None
    return sum(ahead) / len(ahead)
