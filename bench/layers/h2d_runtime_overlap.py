"""Model harness: how many of the runtime's OWN host threads work behind the
hand-off's issuing threads: within a fit's ``train.h2d`` spans, the mean
number of host threads other than the issuing ones that are inside an event
of the runtime's, over the time any is (the re-tiling of the row blocks into
the chip's layout on ``pjrt-tpu-tasks`` threads and its chunks on the
transposition pool, the transfers' dispatch, the completions), mean over the
traced fits.

Its source is the profiler's own events on the host's plane of the run's
file, which the runtime writes and the program does not (``device_trace``
as this benchmark uses the word: the trace's lines beside the program's
spans); the span only gives the window.  Which lines are the issuing
threads' is told by the events' names (``bench/handoff_calls.py``,
``CALLER``: right for JAX 0.9.0 and the libtpu beside it; a runtime that
names them otherwise counts its callers in, and the reading rises by about
the number of issuing threads).

It is what the hand-off costs the host beside its issuing threads, so lower
is better: 5.9 on one chip, 25.3 on four, most of it the re-tiling (PR 46).
Identical code read 6.47, 5.89 and 6.31 on one chip in three runs of PR 46:
a difference of a tenth is no finding.  None where the runtime wrote no
event of its own inside the span (a CPU; a dataset that was on the
device)."""

from bench import handoff_calls, spans


def read(trace: dict, run: dict):
    return handoff_calls.runtime_overlap(spans.of(trace, run),
                                         spans.find(run))
