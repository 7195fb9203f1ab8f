"""Model harness: what ONE issuing thread of the hand-off spent dispatching
device programs a fit: ``_stage_block``'s in-place write of each row block
and, once a device, the destination's fill.  The ``write_ms`` attribute of
the fits' ``train.h2d`` spans (a sum over the issuing threads, on their own
clocks) over ``shards``, mean over the traced fits.  A dispatch is the
interpreter and the runtime's launch queue: near 0 beside ``h2d_put_ms``,
the launches are not what holds the threads.  None on a program without the
attribute."""

from bench import handoff_calls, spans


def read(trace: dict, run: dict):
    return handoff_calls.thread_ms(spans.of(trace, run), "write_ms")
