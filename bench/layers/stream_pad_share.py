"""Stream fold: the share of the rows a step READS that are no rows of the
stream: ``1 - sum(rows) / sum(rows_read)`` over the traced passes'
``stream.batch`` spans, in per cent.  ``rows_read`` is what one step of that
micro-batch's program reads: the real rows rounded up to the kernel's row tile
where its grid is bounded by the row count, all of the capacity under a mask.
None where no span carries both (a program that trains every micro-batch in an
array of its own rows: the parent, which reads no padding and compiles a
program a size).  (The cell's passes are one
stream: a pass's last ``stream.batch`` ends in the entry's listener, behind
the harness's fit, and is not among the fit's spans: three of a pass's four
are read.)"""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    pairs = [(int(s["stats"]["rows"]), int(s["stats"]["rows_read"]))
             for f in reduced["fits"] for s in f["spans"]
             if s["name"] == "stream.batch" and "rows_read" in s["stats"]
             and "rows" in s["stats"]]
    read_rows = sum(r for _, r in pairs)
    if not read_rows:
        return None
    return 100.0 * (1.0 - sum(n for n, _ in pairs) / read_rows)
