"""Reduce a run's profiler trace to what the program itself says about it:
per traced fit the program's spans as a tree, the device's idle time inside
the fit cut by the LEAF span that covers it, and the device operations' own
time keyed by the ``sgd.*`` scope of the step they were traced under.

The program's spans (``tpu_sgd/obs/spans.py``) are ``TraceAnnotation``s while
a profiler session is active, so they lie on the host's plane of the same
``.xplane.pb`` as the device's lines: one file, TWO clocks (the device's
lines sit a constant of 1 to 2 ms off the host's, and the constant follows
the order of the process's profiler sessions: ``bench/trace.py``).  No
reading of this module is a time on one less a time on the other;
``breakdown``, which names a gap on the device by the span on the host that
covers it, first shifts the device's lines by what causality allows
(``clock_bracket_ns``) and says the shift it used.  ``bench/trace.py``'s
``load`` drops every host event but ``bench.fit`` and a reader is handed
``(trace, run)`` with no path, so this module finds the run's file itself:
the one ``.xplane.pb`` under ``<checkout>/.bench_trace/<run["workload"]>/``,
where ``bench/run.py`` traces to.  It is kept only if its ``bench.fit`` events
start where ``trace["fits"]`` says they do (a run that traced elsewhere, as
the CPU rehearsal does, matches nothing); then ``of`` returns None and every
reader built on it returns None.  A program without the spans or the scopes
(the parent of the PR that added them) gives fits with no spans and
operations all ``(unscoped)``: the readers return None there too.

What the trace looks like (TPU v5 lite, JAX 0.9.0, looked at by hand): a span
is an event on the line of the thread that entered it (``python3``), its
attributes the event's stats.  An ``XLA Ops`` event's name is its whole HLO
instruction WITHOUT ``metadata={...}``, and its own stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``:
the ``op_name`` is in neither.  It is the stat ``tf_op`` of the event's
METADATA (``jit(sgd_run)/while/body/sgd.margins/dot_general:``), which
``jax.profiler.ProfileData`` does not expose.  So the file's bytes are read a
second time, by ``op_names``, with a decoder of the protobuf wire format for
the six fields that takes (``XSpace.planes``, ``XPlane.name`` /
``event_metadata`` / ``stat_metadata``, ``XEventMetadata.name`` / ``stats``,
``XStat.metadata_id`` / ``str_value`` / ``ref_value``).  The name comes from
the executable the chip ran: one read back from a compile cache written
before the scopes existed has none (JAX leaves metadata out of the cache's
key), which is why the program's whole-run function was renamed with them.

Times in nanoseconds, as in ``bench/trace.py``."""

import bisect
import functools
import glob
import os
import re

from bench import cells
from bench import trace as trace_mod
from bench.trace import (DEVICE_PLANE, FIT, MODULES_LINE, NAME_CHARS,
                         OPS_LINE, _clip, _events, _self_times)

UNSPANNED, UNSCOPED, NO_FUNCTION = "(unspanned)", "(unscoped)", "(no jit)"
#: a span of the program: dotted lower-case (``fit.run``, ``train.h2d``);
#: the runtime's own events have capitals, colons, spaces or brackets
SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
SCOPE = re.compile(r"sgd\.[a-z0-9_]+")
#: the jitted function a launched program was made from: the OUTERMOST
#: ``jit(...)`` of an ``op_name`` (``jit(sgd_run)/while/body/.../
#: jit(_fused_scan_sums)/pallas_call`` is ``sgd_run``'s)
FUNCTION = re.compile(r"jit\(([^()]+)\)")
#: the host's call of a jitted function (jaxlib's own event on the calling
#: thread's line, a restored runner's nested in its wrapper's), and its
#: launch on the ``XLA Modules`` line: ``jit__stage_join(7145507630)``
CALL = re.compile(r"^PjitFunction\((.+)\)$")
MODULE = re.compile(r"^jit_(.+)\(\d+\)$")
#: ``of`` matches the ``bench.fit`` events of two readings of ONE file, both
#: on the host's clock: two starts further apart than this are two fits
MATCH_NS = 1e3


# -- the file ----------------------------------------------------------------

def find(run: dict):
    """The run's one ``.xplane.pb``, or None."""
    files = glob.glob(os.path.join(
        cells.REPO, ".bench_trace", str(run.get("workload")), "plugins",
        "profile", "*", "*.xplane.pb"))
    return files[0] if len(files) == 1 else None


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field, value)`` of one protobuf message: a varint as an int, a
    length-delimited field as bytes, fixed-width ones skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(data: bytes) -> dict:
    """``{device plane: {event name: op_name}}`` from the bytes of an
    ``.xplane.pb``: the ``tf_op`` stat of each event's metadata."""
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stats[meta.get(1, 0)] = meta.get(2, b"").decode()
        if not DEVICE_PLANE.match(name):
            continue
        names = out[name] = {}
        for event in events:
            event_name = ""
            for f, v in _fields(event):
                if f == 2:
                    event_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stats.get(stat.get(1)) == "tf_op":
                        names[event_name] = stat[5].decode() if 5 in stat \
                            else stats.get(stat.get(7), "")
    return out


def load(path: str) -> list:
    """As ``bench.trace.load`` (a device plane keeps its ``XLA Ops`` and
    ``XLA Modules`` lines), and: the host's plane keeps the program's spans
    ``(name, start_ns, duration_ns, stats)`` and the calls of jitted
    functions (``PjitFunction(<name>)``; ``reduce`` keeps them apart, as
    ``calls``) beside ``bench.fit``, and each device plane has ``"op_names":
    {event name: op_name}``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    names = op_names(data)
    planes = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events]
            else:
                events = [(e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats))
                          for e in line.events
                          if e.name == FIT or SPAN.match(e.name)
                          or CALL.match(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines,
                       "op_names": names.get(plane.name, {})})
    return planes


# -- the reduction -------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The innermost ``sgd.*`` segment of an operation's ``op_name``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def function_of(op_name: str) -> str:
    """The jitted function of the program an operation ran in: the
    outermost ``jit(...)`` of its ``op_name``."""
    found = FUNCTION.search(op_name or "")
    return found.group(1) if found else NO_FUNCTION


def _launched_by(plane: dict):
    """``f(ns) -> jitted function`` of the chip's launch that holds the time
    (its own clock): for the operations the chip's compiler gave no
    ``op_name`` at all (126 of the 128 writes of ``_stage_join``'s
    ``concatenate``, a ``while`` itself), which run inside a launch named
    ``jit_<function>(<fingerprint>)`` all the same."""
    launches = sorted((s, s + d, name)
                      for name, s, d in _events(plane, MODULES_LINE))
    starts = [launch[0] for launch in launches]

    def function(ns):
        i = bisect.bisect_right(starts, ns) - 1
        found = MODULE.match(launches[i][2]) \
            if i >= 0 and ns < launches[i][1] else None
        return found.group(1) if found else NO_FUNCTION

    return function


def _tree(events):
    """One thread's spans, each with its parent's index (containment)."""
    spans, open_ = [], []
    for name, start, dur, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and start >= spans[open_[-1]]["end_ns"]:
            open_.pop()
        spans.append({"name": name, "start_ns": start, "end_ns": start + dur,
                      "stats": stats, "parent": open_[-1] if open_ else None})
        open_.append(len(spans) - 1)
    return spans


def _cut(intervals, leaves):
    """``{leaf span's name: ns}`` of the intervals, ``(unspanned)`` for what
    no leaf covers.  Leaves of different threads may overlap: the part of
    an interval that one has taken is not given to the next."""
    out = {}
    for lo, hi in intervals:
        left = [(lo, hi)]
        for leaf in leaves:
            taken = _clip(left, leaf["start_ns"], leaf["end_ns"])
            if not taken:
                continue
            out[leaf["name"]] = out.get(leaf["name"], 0.0) \
                + sum(e - s for s, e in taken)
            left = [(s, e) for a, b in left
                    for s, e in ((a, min(b, leaf["start_ns"])),
                                 (max(a, leaf["end_ns"]), b)) if e > s]
        rest = sum(e - s for s, e in left)
        if rest:
            out[UNSPANNED] = out.get(UNSPANNED, 0.0) + rest
    return out


def reduce(planes: list) -> dict:
    """``{"fits": [...], "scopes": {scope: ns}, "op_scopes": {operation:
    scope}, "functions": {jitted function: ns}, "op_functions": {operation:
    jitted function}, "calls": ...}``.  A fit has ``start_ns``, ``end_ns``,
    ``spans`` (its threads' trees, one list; ``parent`` indexes it,
    ``thread`` numbers the host's lines) and ``leaves`` (the spans that hold
    no span): host times all.  ``scopes`` is the operations' own time
    between the first fit's start and the last one's end, over the chips,
    by the innermost ``sgd.*`` scope of each; ``functions`` the same time by
    the jitted function of the program each ran in (its ``op_name`` says,
    or the launch that holds it); ``calls`` is ``_host_calls``'.  ``fits``
    is empty without a ``bench.fit``; ``scopes`` and ``functions`` without
    a chip."""
    host = [line["events"] for p in planes if p["name"].startswith("/host:")
            for line in p["lines"]]
    windows = sorted((e[1], e[1] + e[2]) for events in host for e in events
                     if e[0] == FIT)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    fits = []
    for fs, fe in windows:
        spans = []
        for thread, events in enumerate(host):
            inside = [e for e in events
                      if e[0] != FIT and SPAN.match(e[0])
                      and fs <= e[1] and e[1] + e[2] <= fe]
            base = len(spans)
            for span in _tree(inside):
                if span["parent"] is not None:
                    span["parent"] += base
                span["thread"] = thread
                spans.append(span)
        parents = {s["parent"] for s in spans}
        fits.append({
            "start_ns": fs, "end_ns": fe, "spans": spans,
            "leaves": [s for i, s in enumerate(spans) if i not in parents]})
    scopes, op_scopes, functions, op_functions = {}, {}, {}, {}
    if windows and devices:
        lo, hi = windows[0][0], windows[-1][1]
        for plane in devices:
            ops = [e for e in _events(plane, OPS_LINE)
                   if e[1] + e[2] > lo and e[1] < hi]
            launched_by, first = _launched_by(plane), {}
            for name, start, _ in ops:
                first.setdefault(name, start)
            for name, ns in _self_times(ops).items():
                op_name = plane["op_names"].get(name)
                scope = op_scopes[name] = scope_of(op_name)
                scopes[scope] = scopes.get(scope, 0.0) + ns / len(devices)
                function = function_of(op_name)
                if function == NO_FUNCTION:
                    function = launched_by(first[name])
                op_functions[name] = function
                functions[function] = functions.get(function, 0.0) \
                    + ns / len(devices)
    return {"fits": fits, "scopes": scopes, "op_scopes": op_scopes,
            "functions": functions, "op_functions": op_functions,
            "calls": _host_calls(host)}


def _host_calls(host: list) -> dict:
    """``{jitted function: [(start_ns, thread) of each call of it]}`` over
    the host's threads, in time order; a call nested in a call of the same
    function on its thread (a restored runner's, in its wrapper's) is the
    outer one."""
    calls = {}
    for thread, events in enumerate(host):
        open_until = {}
        for name, start, dur, _ in sorted(events, key=lambda e: e[1]):
            found = CALL.match(name)
            if found and start >= open_until.get(name, float("-inf")):
                open_until[name] = start + dur
                calls.setdefault(found.group(1), []).append((start, thread))
    return {function: sorted(made) for function, made in calls.items()}


@functools.lru_cache(maxsize=None)
def _reduced(path: str) -> dict:
    """``reduce`` of the file, and ``planes``: what ``load`` made of it,
    for ``breakdown`` to take the gaps from anew."""
    planes = load(path)
    return {**reduce(planes), "planes": planes}


def of(trace: dict, run: dict):
    """The reduction of the run's own trace file, or None (see the module's
    docstring); the file is read once a process."""
    path = find(run)
    if path is None or not trace.get("fits") or not trace.get("devices"):
        return None
    reduced = _reduced(path)
    starts = [f["start_ns"] for f in reduced["fits"]]
    wanted = [f["start_ns"] for f in trace["fits"]]
    if len(starts) != len(wanted) or any(
            abs(a - b) > MATCH_NS for a, b in zip(starts, wanted)):
        return None
    return reduced


# -- the breakdown, across the two clocks ----------------------------------------

def _calls(fit: dict) -> list:
    """``(start of train.dispatch, end of train.fetch, thread)`` of each
    call of a fit's program, host times: a fused fit enters one of each, in
    order (a pass of a stream one pair a micro-batch); no pair where the two
    counts differ (a fit that took another path)."""
    called = sorted((s["start_ns"], s["thread"]) for s in fit["spans"]
                    if s["name"] == "train.dispatch")
    answered = sorted(s["end_ns"] for s in fit["spans"]
                      if s["name"] == "train.fetch")
    if len(called) != len(answered):
        return []
    return [(d, f, thread) for (d, thread), f in zip(called, answered)
            if f > d]


def clock_bracket_ns(fits: list, launches: list, calls: dict = None):
    """``(lo, hi, pairs)``: what has to be ADDED to one chip's times to put
    them on the host's clock lies in ``[lo, hi]``, by causality alone.
    ``launches`` are the chip's ``(name, start_ns, duration_ns)`` on its
    ``XLA Modules`` line, ``calls`` ``reduce``'s.

    No program starts before the host called it: the k-th launch of a jitted
    function is the k-th call of it (believed only where the trace holds as
    many of the one as of the other), and where the chip stood idle at a
    call the two lie a tenth of a millisecond apart: ``lo`` is the largest
    of ``call - launch``.  And no program ends after the
    ``block_until_ready`` that saw it: a fit's ``train.fetch`` ends behind
    every program its thread called since the start of its
    ``train.dispatch``: ``hi`` is the least of ``end of train.fetch - end of
    the launch``.  Where the file pairs no call with that stretch (no
    ``PjitFunction`` events: a trace written by hand, another jaxlib) the
    program is taken to be the longest launch whose midpoint lies inside the
    stretch as the clocks stand, believed only where it fills half of it or
    more (the whole-run program does; a fit of a few hundred microseconds
    from a micro-batch's totals, beside a worker's block folds of the same
    length, does not), and the start of ``train.dispatch`` bounds ``lo``.
    None where nothing bounds one of the two sides or they exclude each
    other."""
    lo, hi, pairs = float("-inf"), float("inf"), 0
    by_function = {}
    for name, start, dur in sorted(launches, key=lambda launch: launch[1]):
        found = MODULE.match(name)
        if found:
            by_function.setdefault(found.group(1), []).append((start, dur))
    paired = []  # (the call's start, its thread, the launch's start, its end)
    for function, made in (calls or {}).items():
        launched = by_function.get(function, [])
        if len(launched) == len(made):
            paired += [(called, thread, start, start + dur)
                       for (called, thread), (start, dur)
                       in zip(made, launched)]
    if paired:
        lo = max(called - start for called, _, start, _ in paired)
        pairs = len(paired)
    for fit in fits:
        for called, answered, thread in _calls(fit):
            seen = [end for at, by, _, end in paired
                    if by == thread and called <= at < answered]
            if seen:
                hi = min(hi, answered - max(seen))
                continue
            _, start, dur = max(
                (launch for launch in launches
                 if called <= launch[1] + launch[2] / 2 < answered),
                key=lambda launch: launch[2], default=("", 0.0, 0.0))
            if 2 * dur >= answered - called:
                lo = max(lo, called - start)
                hi = min(hi, answered - start - dur)
                pairs += 1
    return (lo, hi, pairs) if lo <= hi and hi - lo < float("inf") else None


def on_host_clock(planes: list, fits: list, calls: dict = None):
    """``(planes for bench.trace.reduce, {chip: record})``: ``planes`` (this
    module's ``load``'s) with each chip's lines shifted by the middle of its
    ``clock_bracket_ns``, a chip without a bracket as it stands; the record
    says ``shift_ms``, ``bracket_ms`` and ``pairs`` (None, None, 0)."""
    out, record = [], {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            out.append({"name": plane["name"], "lines": [
                {"name": line["name"], "events": [
                    e[:3] for e in line["events"] if e[0] == FIT]}
                for line in plane["lines"]]})
            continue
        bracket = clock_bracket_ns(
            fits, _events(plane, MODULES_LINE), calls)
        shift = 0.0
        record[plane["name"]] = {"shift_ms": None, "bracket_ms": None,
                                 "pairs": 0}
        if bracket is not None:
            lo, hi, pairs = bracket
            shift = (lo + hi) / 2
            record[plane["name"]] = {
                "shift_ms": shift / 1e6, "bracket_ms": [lo / 1e6, hi / 1e6],
                "pairs": pairs}
        out.append({"name": plane["name"], "lines": [
            {"name": line["name"], "events": [
                (n, s + shift, d) for n, s, d in line["events"]]}
            for line in plane["lines"]]})
    return out, record


def breakdown(trace: dict, run: dict) -> dict:
    """The last line's ``breakdown`` from ``bench/trace.py``'s two lists:
    each operation named ``<sgd.* scope>: <HLO text>`` (one under no scope
    ``(unscoped) <jitted function>: <HLO text>``) and each gap ``<leaf span
    that covers most of it>: <its place in the fit>``; the lists as they are
    where the run's file resolves neither (``of`` is None).  A gap lies on
    the device's clock and a span on the host's, so the gaps are taken anew
    from the device's lines SHIFTED onto the host's clock (``on_host_clock``)
    before a leaf is looked for; ``clock`` says the shift of each chip (None
    where nothing was resolved), for the run's record."""
    reduced = of(trace, run)
    ops, gaps, clock = trace["device_ops"], trace["idle_gaps"], None
    if reduced is not None:
        def named(name):
            scope = reduced["op_scopes"].get(name, UNSCOPED)
            function = reduced["op_functions"].get(name, NO_FUNCTION)
            if scope == UNSCOPED and function != NO_FUNCTION:
                scope = f"{UNSCOPED} {function}"
            return f"{scope}: {name}"

        ops = [[named(name), s] for name, s in ops]
        planes, clock = on_host_clock(reduced["planes"], reduced["fits"],
                                      reduced["calls"])
        shifted = trace_mod.reduce(planes)
        gaps = []
        for (name, s), (lo, hi) in zip(shifted["idle_gaps"],
                                       shifted["idle_gap_ns"]):
            fit = next((f for f in reduced["fits"]
                        if f["start_ns"] <= lo and hi <= f["end_ns"]), None)
            cut = _cut([(lo, hi)], fit["leaves"]) if fit else {}
            gaps.append([f"{max(cut, key=cut.get, default=UNSPANNED)}: "
                         f"{name}", s])
    return {"device_ops": [[name[:NAME_CHARS], s] for name, s in ops],
            "idle_gaps": gaps, "clock": clock}


# -- what the readers under bench/layers/ share ---------------------------------

def span_ms(trace: dict, run: dict, name: str):
    """Mean over the traced fits of the host time inside the spans called
    ``name``; None where no fit has one."""
    reduced = of(trace, run)
    if reduced is None:
        return None
    per_fit = [[s["end_ns"] - s["start_ns"] for s in f["spans"]
                if s["name"] == name] for f in reduced["fits"]]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit) / 1e6


def scope_ms(trace: dict, run: dict, scope: str):
    """The own time of the operations under ``scope`` per iteration, mean
    over the traced fits; None where no operation carries it."""
    reduced = of(trace, run)
    if reduced is None or scope not in reduced["scopes"]:
        return None
    return reduced["scopes"][scope] / len(reduced["fits"]) \
        / run["iterations"] / 1e6


def window_spans(reduced: dict, name: str) -> list:
    """The program's spans called ``name`` that lie between the first traced
    fit's start and the last one's end, whichever fit holds them or none (a
    worker's span across two passes of one stream is in no fit's ``spans``):
    ``{"start_ns", "end_ns", "stats", "thread"}`` each, ``thread`` as in the
    fits' spans and in ``calls``; in time order."""
    if not reduced["fits"]:
        return []
    lo, hi = reduced["fits"][0]["start_ns"], reduced["fits"][-1]["end_ns"]
    host = [line["events"] for p in reduced["planes"]
            if p["name"].startswith("/host:") for line in p["lines"]]
    return sorted(
        ({"start_ns": start, "end_ns": start + dur, "stats": stats,
          "thread": thread}
         for thread, events in enumerate(host)
         for found, start, dur, stats in events
         if found == name and lo <= start and start + dur <= hi),
        key=lambda span: span["start_ns"])


def micro_batches(reduced: dict) -> int:
    """The micro-batches the traced passes of a stream trained: the more of
    their ``stream.batch`` and ``stream.whole`` spans (where a cell's passes
    are ONE stream a pass's last ``stream.batch`` ends in the entry's
    listener, behind the harness's fit, and is not among the fit's spans;
    its ``stream.whole`` is).  0 for fits that are no passes."""
    names = [s["name"] for f in reduced["fits"] for s in f["spans"]]
    return max(names.count("stream.batch"), names.count("stream.whole"))
