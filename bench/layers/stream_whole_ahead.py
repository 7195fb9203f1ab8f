"""Stream fold: whether a micro-batch was made whole BEHIND the fit of the
micro-batch before it: the ``ahead`` attribute of the passes'
``stream.whole`` spans (1 where the join was dispatched between that fit's
``train.dispatch`` and its ``train.fetch``, so that the chip runs it the
moment the fit ends and the host's turn-around passes under it; 0 where it
was dispatched in turn, after the fit's fetch and its publish, the chip
idle meanwhile), mean over the traced passes' joins.  A take that was not
done before the fit had ended goes in turn, so a join may read 0 where the
chip waited for the wire.
None where no ``stream.whole`` span carries the attribute (a program whose
fold joins in turn alone: the parent) or the passes joined nothing."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    ahead = [int(s["stats"]["ahead"]) for f in reduced["fits"]
             for s in f["spans"]
             if s["name"] == "stream.whole" and "ahead" in s["stats"]]
    if not ahead:
        return None
    return sum(ahead) / len(ahead)
