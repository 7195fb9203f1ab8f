"""Stream fold: the share of a stream's row blocks whose part of the totals
``X^T X``, ``X^T y``, ``y^T y`` was folded in UNDER the copy, while the next
blocks were on the wire, so that no micro-batch was ever made one array on
the device: ``folded`` over ``blocks`` of the traced passes' ``stream.stage``
spans (the worker's; ``folded`` is 0 where the rows were staged for a join).
1.0 where every block of every micro-batch that went ahead was folded.  None
where no ``stream.stage`` span carries the attribute (a program from before
it: the parent) or none staged a block."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    staged = [s["stats"] for f in reduced["fits"] for s in f["spans"]
              if s["name"] == "stream.stage" and "folded" in s["stats"]]
    blocks = sum(int(s.get("blocks", 0)) for s in staged)
    if not blocks:
        return None
    return sum(int(s["folded"]) for s in staged) / blocks
