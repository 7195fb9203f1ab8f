"""Step: own time of the device operations traced under ``sgd.stats_build``
(``ops/gram.py``'s ``_stats_build``: ``X^T X``, ``X^T y``, ``y^T y`` of all of
a full batch's rows in one read, from which a least-squares fit then runs) a
micro-batch trained: the scope's time between the first traced fit's start
and the last one's end, over the fits (``train.run`` spans) inside them.  An
operation goes by its INNERMOST ``sgd.*`` scope: the two products, the split
of the labels into bf16 parts in front of them and the folds behind.  None
where no operation carries the scope (a fit that runs from its rows; a
program from before the scope: the parent; no device in the trace) or no
``train.run`` span lies in the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or "sgd.stats_build" not in reduced["scopes"]:
        return None
    trained = sum(s["name"] == "train.run"
                  for f in reduced["fits"] for s in f["spans"])
    if not trained:
        return None
    return reduced["scopes"]["sgd.stats_build"] / trained / 1e6
