"""Stream fold: how many fit programs the micro-batches of the traced passes
were trained by: the distinct ``capacity`` of their ``stream.batch`` spans (a
micro-batch at a row capacity is trained by the program keyed by the capacity,
its own row count an operand), a span without one counted by its ``rows`` (its
fit is keyed by its own shape: the parent reads one a distinct size, 4 in a
pass of four sizes).  It counts what the program SAYS of itself (the
``capacity`` attribute), not compiled programs: ``compiles_in_window`` and
``cold_first_fit_s`` are what hold it to that.  None where no pass has the
span.  (The cell's passes are one
stream: a pass's last ``stream.batch`` ends in the entry's listener, behind
the harness's fit, and is not among the fit's spans: three of a pass's four
are read.)"""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    keys = {int(s["stats"].get("capacity", s["stats"]["rows"]))
            for f in reduced["fits"] for s in f["spans"]
            if s["name"] == "stream.batch" and "rows" in s["stats"]}
    return len(keys) or None
