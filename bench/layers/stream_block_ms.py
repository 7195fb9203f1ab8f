"""Stream fold: a row block's time on the wire while a stream runs: over the
traced passes' ``stream.stage`` spans that say ``blocks`` (the worker's,
``models/streaming._take``: a micro-batch that went AHEAD of its fit, its
rows issued as blocks that land beside whatever the chip is doing), the
span's length from its first issue on, over its blocks; the MEDIAN over the
micro-batches.

Why that length is the blocks' time: the worker issues a block only once the
block ``in flight`` places in front of it has landed (``StagedAhead``'s flow
control: 4 at a row capacity, 16 alive across the micro-batches where they
are folded into totals), so from the first issue to the span's end ``blocks``
blocks have landed, give or take the few in flight at either end.  What the
span holds IN FRONT of its first issue is the iterator's slice and, at a row
capacity, the worker's wait for ITS TURN (``_take`` waits for the micro-batch
in training to be whole before its first block: a take that finds the worker
idle stands 12 to 17 ms there, the join's time on the chip; one that follows
another at once 0.2 ms: PERF.md, PR 58).  That stretch is cut off at the
thread's first call of a jitted function inside the span (jaxlib's own
``PjitFunction`` event on the worker's line, which ``bench/spans.py`` keeps
as ``calls``: the row count's cast in the capacity form, the first block's
fold in the totals form; both clocks the host's); a span without such a call
is read whole.

The spans are the traced window's, not a fit's: where a cell's passes are one
stream the worker's take lies across two passes as often as inside one.

The record (``run["stream_block"]``) holds beside the reading the spans read,
the least and the most of them, their ``blocks``, ``staged_ms`` (their
length, waits and all: what the wire was held for, to set beside the passes)
and ``wait_ms`` (what was cut off), and ``in_turn_ms``: the reading for a
micro-batch copied IN TURN, inside its fit's ``train.h2d`` (a stream's first,
before any plan), where a traced pass has one.

With ``step_ms`` (the chip's busy time a pass) and ``stream_join_ms`` this is
what says whether the chip or the wire bounds a pass (PERF.md section 5).
None where no traced pass has such a span (no stream; the parent of the PR
that brought the spans)."""

import statistics

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    staged = [s for s in spans.window_spans(reduced, "stream.stage")
              if int(s["stats"].get("blocks", 0))]
    if not staged:
        return None
    calls = [call for made in reduced["calls"].values() for call in made]
    waits = [min((at for at, thread in calls if thread == s["thread"]
                  and s["start_ns"] <= at < s["end_ns"]),
                 default=s["start_ns"]) - s["start_ns"] for s in staged]
    taken = [(s["end_ns"] - s["start_ns"] - wait)
             / int(s["stats"]["blocks"]) / 1e6
             for s, wait in zip(staged, waits)]
    in_turn = [(s["end_ns"] - s["start_ns"]) / int(s["stats"]["blocks"]) / 1e6
               for s in spans.window_spans(reduced, "train.h2d")
               if int(s["stats"].get("blocks", 0)) > 1]
    run["stream_block"] = {
        "spans": len(staged), "least_ms": min(taken), "most_ms": max(taken),
        "blocks": sum(int(s["stats"]["blocks"]) for s in staged),
        "staged_ms": sum(s["end_ns"] - s["start_ns"] for s in staged) / 1e6,
        "wait_ms": sum(waits) / 1e6,
        "in_turn_ms": statistics.median(in_turn) if in_turn else None}
    return statistics.median(taken)
