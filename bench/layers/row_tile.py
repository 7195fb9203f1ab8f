"""Step: the rows one grid step of the step's kernel takes: the ``row_tile``
attribute of the fits' ``train.run`` spans, mean over the traced fits.  Over
0 where the step is the one-read kernel (and how wide its blocks of rows
are), 0 where it takes two reads.  None where no fit has a ``train.run`` span
that carries it (a program from before the attribute; no trace of the run's
own)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    tiles = [int(s["stats"]["row_tile"]) for f in reduced["fits"]
             for s in f["spans"]
             if s["name"] == "train.run" and "row_tile" in s["stats"]]
    if not tiles:
        return None
    return sum(tiles) / len(tiles)
