"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per traced fit its span, the device's busy time inside it and the
programs launched; over all traced fits the busy time, the operations that
took most time and the longest idle gaps, each named by its place in the fit
(``bench/spans.py``'s ``breakdown`` puts the step's scope in front of an
operation and, the device's lines first shifted onto the host's clock, the
program's span in front of a gap).

``load`` turns the file into plain dicts with nothing but JAX
(``jax.profiler.ProfileData``); ``reduce`` works on those dicts, so a test
can hand it a trace built by hand.

What the trace looks like (TPU v5 lite, JAX 0.9.0): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
launched and whose line ``XLA Ops`` has one per operation, a ``while`` and
the operations of its body nested inside it on the same line; the host's
plane ``/host:CPU`` has one line per thread, and a ``TraceAnnotation`` is an
event on the line of the thread that entered it.  All in one file, on TWO
clocks: the device's lines sit a constant of 1 to 2 ms off the host's, and
the constant follows the order of the process's profiler sessions (the
ledger's PR 57 lines: the parent's side, traced first, against the change's
on one program).  So a fit's share of the device's lines is cut at the
host's bounds to within that constant, and nothing here reports a device
time less a host time."""

import re

FIT = "bench.fit"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
NAME_CHARS = 160  # of a breakdown's name: an operation's is its whole HLO


def load(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]`` for the host's plane and the devices'."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if device or e.name == FIT]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def _self_times(events):
    """``{name: ns}`` of each operation's own time: its duration less that
    of the operations nested inside it on the same line."""
    out, stack = {}, []  # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for name, _, own in stack:
        out[name] = out.get(name, 0.0) + own
    return out


def _events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def reduce(planes: list) -> dict:
    """See the module's docstring; times in nanoseconds except the two
    ``breakdown`` lists, which are in seconds (``idle_gap_ns`` holds each
    listed gap's ``[start, end]``, in the list's order).  ``fits`` is empty
    where the trace has no ``bench.fit``; ``devices`` is 0 where it has no
    chip."""
    fits = sorted((start, start + dur)
                  for p in planes if p["name"].startswith("/host:")
                  for line in p["lines"]
                  for name, start, dur in line["events"] if name == FIT)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    out = {"devices": len(devices), "fits": [], "window_ns": 0.0,
           "busy_ns": 0.0, "device_ops": [], "idle_gaps": [],
           "idle_gap_ns": []}
    if not devices:
        return out
    if fits:
        lo, hi = fits[0][0], fits[-1][1]
    else:
        spans = [(s, s + d) for p in devices for _, s, d in
                 _events(p, OPS_LINE)]
        lo, hi = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0.0, 0.0)
    out["window_ns"] = hi - lo
    own, gaps, busy_total = {}, [], 0.0
    per_fit = [{"start_ns": s, "end_ns": e, "busy_ns": 0.0, "programs": 0.0}
               for s, e in fits]
    for plane in devices:
        tag = "" if len(devices) == 1 else plane["name"] + " "
        ops = _events(plane, OPS_LINE)
        modules = _events(plane, MODULES_LINE)
        merged = _union((s, s + d) for _, s, d in ops)
        busy_total += sum(e - s for s, e in _clip(merged, lo, hi))
        for name, ns in _self_times(
                [e for e in ops if e[1] + e[2] > lo and e[1] < hi]).items():
            own[name] = own.get(name, 0.0) + ns / len(devices)
        launches = _union((s, s + d) for _, s, d in modules)
        for i, (fs, fe) in enumerate(fits):
            inside = _clip(merged, fs, fe)
            per_fit[i]["busy_ns"] += sum(e - s for s, e in inside) \
                / len(devices)
            # by midpoint: the device's clock is a millisecond or two off
            # the host's (the module's docstring), so a launch can start
            # "before" the fit that made it
            per_fit[i]["programs"] += sum(
                fs <= s + d / 2 < fe for _, s, d in modules) / len(devices)
            if not inside:
                gaps.append((f"{tag}fit {i}: no operation", fs, fe))
                continue
            gaps.append((f"{tag}fit {i}: before first operation", fs,
                         inside[0][0]))
            gaps.append((f"{tag}fit {i}: after last operation",
                         inside[-1][1], fe))
            for (_, e0), (s1, _) in zip(inside, inside[1:]):
                in_program = any(ls <= e0 and s1 <= le for ls, le in launches)
                gaps.append((f"{tag}fit {i}: " + ("inside a program"
                             if in_program else "between programs"), e0, s1))
    out["fits"] = per_fit
    out["busy_ns"] = busy_total / len(devices)
    out["device_ops"] = [[n, ns / 1e9] for n, ns in sorted(
        own.items(), key=lambda kv: -kv[1])[:TOP]]
    longest = [g for g in sorted(gaps, key=lambda g: g[1] - g[2])[:TOP]
               if g[2] > g[1]]
    out["idle_gaps"] = [[n, (e - s) / 1e9] for n, s, e in longest]
    out["idle_gap_ns"] = [[s, e] for _, s, e in longest]
    return out
