"""Model harness: whether a fit ran from the totals of its rows (the
planner's statistics schedule in its totals form: ``X^T X``, ``X^T y``,
``y^T y`` built in one read, then iterations that read no row): the ``stats``
attribute of the passes' ``train.run`` spans (1 where it did, 0 where the fit
read its rows every iteration), mean over the traced micro-batches.  Whether
the mechanism engaged; what its build costs is ``stats_build_ms``.  None
where no ``train.run`` span carries the attribute (a program from before it:
the parent; no trace of the run's own)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    ran = [int(s["stats"]["stats"]) for f in reduced["fits"]
           for s in f["spans"]
           if s["name"] == "train.run" and "stats" in s["stats"]]
    if not ran:
        return None
    return sum(ran) / len(ran)
