"""Model harness: the devices a fit's dense host array was written to: the
``shards`` attribute of the fits' ``train.h2d`` spans, mean over the traced
fits.  The mesh's size where every row block went to the device that owns
its rows (4 in the four-chip cell), 1 for one destination on one device, 0
for a dataset that was on the devices already.  Whether the mechanism
engaged.  None where no fit has a ``train.h2d`` span that carries it (a
program from before the attribute)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    shards = [int(s["stats"]["shards"]) for f in reduced["fits"]
              for s in f["spans"]
              if s["name"] == "train.h2d" and "shards" in s["stats"]]
    if not shards:
        return None
    return sum(shards) / len(shards)
