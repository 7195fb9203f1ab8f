"""Model harness: what ONE issuing thread of the hand-off spent in its loop
OUTSIDE the runtime a fit: the slice of the host array, the deque, the
loop's own statements: the interpreter alone.  The ``own_ms`` attribute of
the fits' ``train.h2d`` spans (a thread's time in the loop less its puts,
writes, deletes and flow-control waits; a sum over the issuing threads, on
their own clocks) over ``shards``, mean over the traced fits.  With
``h2d_free_ms`` the probe for the interpreter's lock: near 0, the threads do
not wait for it.  ``h2d_put_ms + h2d_write_ms + h2d_free_ms + h2d_own_ms`` is
``h2d_issue_ms`` but for what the span holds beside the loop.  None on a
program without the attribute."""

from bench import handoff_calls, spans


def read(trace: dict, run: dict):
    return handoff_calls.thread_ms(spans.of(trace, run), "own_ms")
