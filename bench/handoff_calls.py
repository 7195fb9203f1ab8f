"""The hand-off's host side cut by call: what the readers ``h2d_put_ms``,
``h2d_write_ms``, ``h2d_free_ms``, ``h2d_own_ms`` and ``h2d_runtime_overlap``
under ``bench/layers/`` share.

Where its ``train.h2d`` span is live the program times each row block's four
parts on the issuing thread's own clock and tells the span their sums over
the issuing threads (``tpu_sgd/optimize/gradient_descent.py``,
``_stage_dense``): ``put_ms`` (``jnp.asarray`` / ``jax.device_put`` of the
block), ``write_ms`` (the dispatch of the in-place write, once a device of
the fill), ``free_ms`` (``block.delete()``), ``own_ms`` (the rest of the
thread's time in the loop: the interpreter alone) and, since PR 37,
``stall_ms`` (the flow-control wait); the five are the threads' time in the
loop, by construction.  Under a mesh a thread a device issues that device's
blocks, all of them side by side for the span's length, so a sum over
``shards`` is what ONE thread spent and is comparable with the span's own
length: ``h2d_issue_ms`` is that length less ``stall_ms`` over ``shards``,
and the four ``*_ms`` here add up to it but for what the span holds beside
the loop (``y``'s copy, the pool's start and join, the slowest thread's lead
over the mean).

Behind the issuing threads the runtime works on threads of its own, and says
so on the host's plane of the run's file (TPU v5 lite, JAX 0.9.0, looked at
by hand, PR 46): a block's ``device_put`` returns after ~0.4 ms and the copy
of the strided block into the chip's layout runs as ``XlaLinearize`` on a
``pjrt-tpu-tasks/<tid>`` thread (4.9 ms a 32.8 MB block for one device,
12.8 where four devices' blocks are in it at once), cut into
``Transpose::ExecuteChunk`` pieces on a pool of ``futex-default-SDomainT/
<tid>`` threads, then ``H2D Dispatch``; completions run on
``EventFDAsyncWorker`` and ``tfrt-non-blocking-queue`` threads.
``bench/spans.py``'s loader drops all of these (they are no span of the
program), so ``runtime_overlap`` reads the host's plane itself.  A thread that
CALLS the runtime shows twice there, once a tracer (jaxlib names the fit's
thread ``python3``, libtpu names it ``main/<tid>`` and gives a pool's thread
no name at all): an issuing thread's lines are known by what only a caller's
thread holds, a span of the program, a ``PjitFunction(...)`` or an entry point
of the runtime's C API (``PJRT_...``, every block's write is one), anywhere
in the file; every other line is the runtime's own.

Every reading is a duration on the host's clock; nothing here compares two
clocks.  ``reduced`` is ``bench.spans.of``'s."""

import functools
import re

from bench import spans
from bench.trace import FIT, _clip, _union

H2D = "train.h2d"
#: what only a thread that calls INTO the runtime holds
CALLER = re.compile(r"^(PjitFunction\(|PJRT_)")


def _carrying(reduced, attr):
    """Per traced fit its ``train.h2d`` spans' stats that hold ``attr``."""
    return [[s["stats"] for s in f["spans"]
             if s["name"] == H2D and attr in s["stats"]]
            for f in reduced["fits"]]


def thread_ms(reduced, attr: str):
    """Mean over the traced fits of the spans' ``attr`` (ms, summed over the
    issuing threads) over their ``shards``: what one thread spent.  None
    where the run's trace resolved nothing or no span carries ``attr`` (a
    program from before the counter; a dataset that went in one piece)."""
    if reduced is None:
        return None
    per_fit = [[float(stats[attr]) / max(1, int(stats.get("shards", 1)))
                for stats in fit] for fit in _carrying(reduced, attr)]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit)


@functools.lru_cache(maxsize=None)
def runtime_threads(path: str) -> list:
    """Per host thread of the run's file that never calls into the runtime
    (see the module's docstring) the merged ``[start_ns, end_ns]`` it is
    inside an event; read once a process."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            if events and not any(
                    name == FIT or spans.SPAN.match(name)
                    or CALLER.match(name) for name, _, _ in events):
                threads.append(_union((s, s + d) for _, s, d in events))
    return threads


def runtime_overlap(reduced, path):
    """Mean over the traced fits of the number of the runtime's own threads
    inside an event, over the time any is, within the fit's ``train.h2d``
    spans.  None where the run's trace resolved nothing, no fit has the
    span, or no thread of the runtime's wrote an event inside one."""
    if reduced is None or path is None:
        return None
    depths = []
    for fit in reduced["fits"]:
        inside = [_clip(thread, s["start_ns"], s["end_ns"])
                  for s in fit["spans"] if s["name"] == H2D
                  for thread in runtime_threads(path)]
        busy = sum(e - s for s, e in _union(
            iv for thread in inside for iv in thread))
        if busy:
            depths.append(sum(e - s for thread in inside
                              for s, e in thread) / busy)
    return sum(depths) / len(depths) if depths else None
