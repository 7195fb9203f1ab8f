"""Stream fold: HOST time inside ``stream.whole`` a micro-batch trained: the
DISPATCH of the program that makes the landed blocks of a micro-batch that
went ahead the one array its fit reads, mean over the traced passes'
micro-batches.  Dispatch only: the chip runs the join in front of the fit
(12.9 ms a micro-batch at a capacity of 2,097,152 x 1000 bf16: PERF.md, PR
58), its operations read ``(unscoped)`` in a device trace (the chip's
compiler keeps ``sgd.whole`` on one write of 128 and gives the others no
``op_name`` at all: ``step_unscoped_share`` counts them), and that time is
``stream_join_ms``', which finds them by the jitted function of their
launch.  A micro-batch that is made whole inside its fit (a stream's
first, before any plan) adds nothing here; the micro-batches are the more of
the passes' ``stream.batch`` and ``stream.whole`` spans (the cell's passes
are one stream: a pass's last ``stream.batch`` ends in the entry's listener,
behind the harness's fit, and is not among the fit's spans; its
``stream.whole`` is).  None where no pass has the span (the parent, whose
join has no span of its own: its time is inside ``stream.wait``)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    whole = [s["end_ns"] - s["start_ns"] for f in reduced["fits"]
             for s in f["spans"] if s["name"] == "stream.whole"]
    if not whole:
        return None
    return sum(whole) / spans.micro_batches(reduced) / 1e6
