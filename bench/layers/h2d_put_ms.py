"""Model harness: what ONE issuing thread of the hand-off spent inside its
``put`` calls a fit: ``jnp.asarray`` / ``jax.device_put`` of each row block,
which allocates the block's buffer on the device and hands the block to the
runtime (its own threads copy it into the chip's layout and send it, behind
the call: ``h2d_runtime_overlap``).  The ``put_ms`` attribute of the fits'
``train.h2d`` spans (a sum over the issuing threads, on their own clocks)
over ``shards``, mean over the traced fits.  0.37 ms a block where one
thread issues and the wire is the bound; a multiple of that under a mesh is
the runtime pushing back on its callers, to be read with ``h2d_write_ms``
(the other call into the runtime, where the same push shows) and
``h2d_free_ms`` + ``h2d_own_ms`` (whether the threads wait for the
interpreter instead).  None on a program without the attribute."""

from bench import handoff_calls, spans


def read(trace: dict, run: dict):
    return handoff_calls.thread_ms(spans.of(trace, run), "put_ms")
