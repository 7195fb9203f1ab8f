"""Model harness: what ONE issuing thread of the hand-off spent in
``block.delete()`` a fit: the ``free_ms`` attribute of the fits'
``train.h2d`` spans (a sum over the issuing threads, on their own clocks)
over ``shards``, mean over the traced fits.  A call of microseconds, so it is
the probe for the interpreter's lock: near 0 (a few microseconds a block),
nobody waits to be let back in; a millisecond a block under four threads,
that wait is what the four stand in.  None on a program without the
attribute."""

from bench import handoff_calls, spans


def read(trace: dict, run: dict):
    return handoff_calls.thread_ms(spans.of(trace, run), "free_ms")
