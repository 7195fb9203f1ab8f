"""Reduce a run's profiler trace to what the program itself says about it:
per traced fit the program's spans as a tree, the device's idle time inside
the fit cut by the LEAF span that covers it, and the device operations' own
time keyed by the ``sgd.*`` scope of the step they were traced under.

The program's spans (``tpu_sgd/obs/spans.py``) are ``TraceAnnotation``s while
a profiler session is active, so they lie on the host's plane of the same
``.xplane.pb`` as the device's lines, on the same clock.  ``bench/trace.py``'s
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

import functools
import glob
import os
import re

from bench import cells
from bench.trace import (DEVICE_PLANE, FIT, NAME_CHARS, OPS_LINE, _clip,
                         _events, _self_times, _union)

UNSPANNED, UNSCOPED = "(unspanned)", "(unscoped)"
#: a span of the program: dotted lower-case (``fit.run``, ``train.h2d``);
#: the runtime's own events have capitals, colons, spaces or brackets
SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
SCOPE = re.compile(r"sgd\.[a-z0-9_]+")
#: the device's clock and the host's agree to some tens of microseconds
#: (bench/trace.py); two starts further apart than this are two fits
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
    """As ``bench.trace.load``, and: the host's plane keeps the program's
    spans ``(name, start_ns, duration_ns, stats)`` beside ``bench.fit``, and
    each device plane has ``"op_names": {event name: op_name}``."""
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
                if line.name != OPS_LINE:
                    continue
                events = [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events]
            else:
                events = [(e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats))
                          for e in line.events
                          if e.name == FIT or SPAN.match(e.name)]
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
    scope}}``.  A fit has ``start_ns``, ``end_ns``, ``spans`` (its threads'
    trees, one list; ``parent`` indexes it), ``leaves`` (the spans that hold
    no span), ``last_op_end_ns``, ``idle`` (``{leaf: ns}`` of the device's
    idle time inside the fit) and ``before_first_op`` (the same for the
    stretch up to the fit's first operation, what ``handoff_ms`` measures).
    ``scopes`` is the operations' own time between the first fit's start and
    the last one's end, over the chips.  ``fits`` is empty without a
    ``bench.fit``; ``scopes`` without a chip."""
    host = [line["events"] for p in planes if p["name"].startswith("/host:")
            for line in p["lines"]]
    windows = sorted((e[1], e[1] + e[2]) for events in host for e in events
                     if e[0] == FIT)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    merged = [_union((s, s + d) for _, s, d in _events(p, OPS_LINE))
              for p in devices]
    fits = []
    for fs, fe in windows:
        spans = []
        for events in host:
            inside = [e for e in events if e[0] != FIT
                      and fs <= e[1] and e[1] + e[2] <= fe]
            base = len(spans)
            for span in _tree(inside):
                if span["parent"] is not None:
                    span["parent"] += base
                spans.append(span)
        parents = {s["parent"] for s in spans}
        leaves = [s for i, s in enumerate(spans) if i not in parents]
        # the device is busy while any chip is: idle is what is left
        busy = _clip(_union(iv for m in merged for iv in m), fs, fe)
        idle = [(a, b) for a, b in zip([fs] + [e for _, e in busy],
                                       [s for s, _ in busy] + [fe]) if b > a]
        fits.append({
            "start_ns": fs, "end_ns": fe, "spans": spans, "leaves": leaves,
            "last_op_end_ns": busy[-1][1] if busy else None,
            "idle": _cut(idle, leaves),
            "before_first_op": _cut(
                idle[:1] if busy and busy[0][0] > fs else [], leaves)})
    scopes, op_scopes = {}, {}
    if windows and devices:
        lo, hi = windows[0][0], windows[-1][1]
        for plane in devices:
            ops = [e for e in _events(plane, OPS_LINE)
                   if e[1] + e[2] > lo and e[1] < hi]
            for name, ns in _self_times(ops).items():
                scope = op_scopes[name] = scope_of(
                    plane["op_names"].get(name))
                scopes[scope] = scopes.get(scope, 0.0) + ns / len(devices)
    return {"fits": fits, "scopes": scopes, "op_scopes": op_scopes}


@functools.lru_cache(maxsize=None)
def _reduced(path: str) -> dict:
    return reduce(load(path))


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


def breakdown(trace: dict, run: dict) -> dict:
    """The last line's ``breakdown`` from ``bench/trace.py``'s two lists:
    each operation named ``<sgd.* scope>: <HLO text>`` and each gap ``<leaf
    span that covers most of it>: <its place in the fit>``; the lists as they
    are where the run's file resolves neither (``of`` is None)."""
    reduced = of(trace, run)
    ops, gaps = trace["device_ops"], trace["idle_gaps"]
    if reduced is not None:
        ops = [[f"{reduced['op_scopes'].get(name, UNSCOPED)}: {name}", s]
               for name, s in ops]
        named = []
        for (name, s), (lo, hi) in zip(gaps, trace["idle_gap_ns"]):
            fit = next((f for f in reduced["fits"]
                        if f["start_ns"] <= lo and hi <= f["end_ns"]), None)
            cut = _cut([(lo, hi)], fit["leaves"]) if fit else {}
            named.append([f"{max(cut, key=cut.get, default=UNSPANNED)}: "
                          f"{name}", s])
        gaps = named
    return {"device_ops": [[name[:NAME_CHARS], s] for name, s in ops],
            "idle_gaps": gaps}


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
