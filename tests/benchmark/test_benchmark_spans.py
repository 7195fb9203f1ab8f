"""``bench/spans.py`` and the readers built on it, on traces written by hand
as text and read back through the same path as a run's: two fits, a hand-off
split across ``train.h2d`` and ``train.fetch``, stretches no span covers, a
``while`` with scoped and unscoped bodies; then the same on four chips.

Which metrics are "span metrics" is read from ``bench/layers/``: every reader
that imports ``bench.spans``.  A PR that adds one adds its file and its entry,
and the tests below that take ``SPAN_METRICS`` hold it to the same rules."""

import os

import pytest

from bench import cells, spans, trace

MS = 1e6  # nanoseconds
WORKLOAD = "dense1000-logistic.from-host"
SPAN_METRICS = sorted(
    name for name in (f[:-3] for f in os.listdir(
        os.path.join(cells.BENCH, "layers")) if f.endswith(".py"))
    if getattr(cells.load_module("layers", name), "spans", None) is spans)
#: what the entries of PRs 25 to 27 state: host time inside a span alone, or
#: the device's lines too.  A later metric states either and is not listed
SOURCE = {**dict.fromkeys(("validate_ms", "plan_ms", "h2d_ms", "dispatch_ms"),
                          "program_span"),
          **dict.fromkeys(("margins_ms", "gradient_ms", "step_unscoped_share",
                           "fused_sums_ms"), "device_trace")}

#: fit 0 is [0, 100) ms, fit 1 [100, 200) ms; (name, start ms, length ms,
#: stats).  ``fit.run`` and ``train.run`` hold spans, so they are no leaves.
HOST = [
    ("bench.fit", 0, 100, {}),
    ("fit.run", 1, 98, {"rows": 64}),
    ("fit.validate", 1, 4, {"rows": 64}),
    ("fit.plan", 5, 1, {"cached": 1, "schedule": "resident_stock"}),
    ("train.run", 6, 92, {"path": "fused"}),
    ("train.h2d", 6, 10, {"bytes": 4096}),
    ("train.dispatch", 18, 2, {"built": 0}),
    ("train.fetch", 20, 77, {"recorded": 10}),
    ("shard_args", 7, 1, {}),  # the runtime's own: not a span
    ("bench.fit", 100, 100, {}),
    ("fit.run", 100, 100, {"rows": 64}),
    ("fit.validate", 100, 2, {"rows": 64}),
    ("fit.plan", 102, 3, {"cached": 0, "schedule": "resident_stock"}),
    ("train.run", 105, 95, {"path": "fused"}),
    ("train.h2d", 105, 2, {"bytes": 4096}),
    ("train.dispatch", 107, 1, {"built": 1}),
    ("train.fetch", 108, 90, {"recorded": 10}),
]
M, G = "%fusion.13 = fusion(X, w)", "%fusion.16 = fusion(c, X)"
WHILE, COPY = "%while.4 = while(...)", "%copy-done = copy-done(...)"
OPS = [  # fit 0: one while of 60 ms holding 40 + 15 ms; fit 1: bare ops
    (WHILE, 30, 60), (M, 31, 40), (G, 72, 15),
    (M, 110, 20), (COPY, 140, 20)]
TF_OPS = {M: "jit(sgd_run)/while/body/sgd.margins/dot_general:",
          G: "jit(sgd_run)/while/body/sgd.pointwise/sgd.gradient/dot:",
          WHILE: "jit(sgd_run)/while:"}


def _text(host=HOST, ops=OPS, tf_ops=TF_OPS, device="/device:TPU:0",
          modules=()):
    """An XSpace as text: one host thread and one chip, or with ``ops`` a
    ``{device plane's name: its operations}`` one chip for each; ``modules``
    are the launches on every chip's ``XLA Modules`` line."""
    stat_ids, event_ids = {"tf_op": 1}, {}

    def stat(key, value):
        sid = stat_ids.setdefault(key, len(stat_ids) + 1)
        kind = "str_value" if isinstance(value, str) else "int64_value"
        shown = f'"{value}"' if isinstance(value, str) else value
        return f"stats {{ metadata_id: {sid} {kind}: {shown} }}"

    def events(rows, ids):
        out = []
        for name, start, length, *rest in rows:
            mid = ids.setdefault(name, len(ids) + 1)
            stats = " ".join(stat(k, v) for k, v in (rest[0] if rest
                                                     else {}).items())
            out.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{int(start * 1e9)} duration_ps: "
                       f"{int(length * 1e9)} {stats} }}")
        return "\n".join(out)

    host_events = events(host, event_ids)
    host_meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in event_ids.items())

    def stat_meta():
        return "\n".join(
            f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
            for k, i in stat_ids.items())

    def chip(name, ops):
        op_ids = {}
        op_events = events(ops, op_ids)
        launches = events(modules, op_ids)
        # the last operation names its op_name by reference, as a string
        # that a plane holds once may be
        refs = list(tf_ops)[-1:] if tf_ops else []
        op_meta = "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
            + (f"stats {{ metadata_id: 1 ref_value: {100 + i} }}" if n in refs
               else stat("tf_op", tf_ops[n]) if n in tf_ops else "") + " } }"
            for n, i in op_ids.items())
        ref_meta = "\n".join(
            f'stat_metadata {{ key: {100 + i} value {{ id: {100 + i} '
            f'name: "{tf_ops[n]}" }} }}'
            for n, i in op_ids.items() if n in refs)
        return f"""
    planes {{ name: "{name}"
      lines {{ name: "XLA Modules" timestamp_ns: 0
        {launches} }}
      lines {{ name: "XLA Ops" timestamp_ns: 0
        {op_events} }}
      lines {{ name: "Async XLA Ops" timestamp_ns: 0
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 5 }} }}
      {op_meta}
      {stat_meta()}
      {ref_meta} }}"""

    planes = f"""
    planes {{ name: "/host:CPU"
      lines {{ name: "python3" timestamp_ns: 0
        {host_events} }}
      {host_meta}
      {stat_meta()} }}"""
    chips = ops if isinstance(ops, dict) else {device: ops} if device else {}
    return planes + "".join(chip(name, o) for name, o in chips.items())


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """``write(text) -> (trace, run)``: the text as the one ``.xplane.pb``
    of a run of ``WORKLOAD`` in a checkout of its own, reduced by
    ``bench/trace.py`` as ``bench/harness.py`` does."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(cells, "REPO", str(tmp_path))
    run = {"workload": WORKLOAD, "iterations": 10}

    def write(text):
        folder = tmp_path / ".bench_trace" / WORKLOAD / "plugins" \
            / "profile" / "2026_09_27"
        folder.mkdir(parents=True, exist_ok=True)
        path = folder / "host.xplane.pb"
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
        spans._reduced.cache_clear()
        return trace.reduce(trace.load(str(path))), run

    return write


def _read(metric, reduced, run):
    return cells.load_module("layers", metric).read(reduced, run)


# -- the file and the reduction ------------------------------------------------

def test_load_keeps_the_programs_spans_and_the_operations_op_names(checkout):
    checkout(_text())
    host, device = spans.load(spans.find({"workload": WORKLOAD}))
    names = [e[0] for e in host["lines"][0]["events"]]
    assert "shard_args" not in names and names.count("bench.fit") == 2
    assert ("train.h2d", 6 * MS, 10 * MS, {"bytes": 4096}) \
        in host["lines"][0]["events"]
    assert [line["name"] for line in device["lines"]] == ["XLA Ops"]
    # and the launches where the chip has any; the async line never (it
    # would count busy twice)
    checkout(_text(modules=LAUNCHES))
    device = spans.load(spans.find({"workload": WORKLOAD}))[1]
    assert [line["name"] for line in device["lines"]] \
        == ["XLA Modules", "XLA Ops"]
    assert device["op_names"] == TF_OPS  # by value and by reference
    assert spans.scope_of(TF_OPS[G]) == "sgd.gradient"  # the innermost
    assert spans.scope_of(TF_OPS[WHILE]) == spans.scope_of(None) \
        == spans.UNSCOPED


def test_spans_are_a_tree_by_containment(checkout):
    fit0, fit1 = spans.of(*checkout(_text()))["fits"]
    by_name = {s["name"]: i for i, s in enumerate(fit0["spans"])}
    parent = {s["name"]: s["parent"] for s in fit0["spans"]}
    assert parent["fit.run"] is None
    assert parent["fit.validate"] == parent["fit.plan"] \
        == parent["train.run"] == by_name["fit.run"]
    assert parent["train.h2d"] == parent["train.dispatch"] \
        == parent["train.fetch"] == by_name["train.run"]
    assert fit0["spans"][by_name["train.h2d"]]["stats"] == {"bytes": 4096}
    assert [s["stats"].get("built") for s in fit1["spans"]
            if s["name"] == "train.dispatch"] == [1]


def test_a_fit_holds_host_times_alone(checkout):
    """Spans, their tree and the leaves: nothing of a fit is a time on the
    device's clock, so no reader can take one from a time on the host's."""
    fit0, fit1 = spans.of(*checkout(_text()))["fits"]
    assert set(fit0) == {"start_ns", "end_ns", "spans", "leaves"}
    assert [s["name"] for s in fit0["leaves"]] == [
        "fit.validate", "fit.plan", "train.h2d", "train.dispatch",
        "train.fetch"]  # fit.run and train.run hold spans: no leaves
    assert (fit1["start_ns"], fit1["end_ns"]) == (100 * MS, 200 * MS)


def test_operations_own_time_is_keyed_by_scope(checkout):
    scopes = spans.of(*checkout(_text()))["scopes"]
    assert scopes == {"sgd.margins": 60 * MS, "sgd.gradient": 15 * MS,
                      spans.UNSCOPED: (5 + 20) * MS}  # the while's own, copy


def test_overlapping_leaves_of_two_threads_share_a_gap_once():
    leaves = [{"name": "a.x", "start_ns": 0.0, "end_ns": 6.0},
              {"name": "b.y", "start_ns": 4.0, "end_ns": 8.0}]
    assert spans._cut([(0.0, 10.0)], leaves) == {
        "a.x": 6.0, "b.y": 2.0, spans.UNSPANNED: 2.0}


# -- the readers -----------------------------------------------------------------

@pytest.mark.parametrize("metric,expected", [
    ("validate_ms", (4 + 2) / 2), ("plan_ms", (1 + 3) / 2),
    ("h2d_ms", (10 + 2) / 2), ("dispatch_ms", (2 + 1) / 2),
    ("margins_ms", 60 / 2 / 10), ("gradient_ms", 15 / 2 / 10),
    ("step_unscoped_share", 100 * 25 / 100)])
def test_reader(checkout, metric, expected):
    assert _read(metric, *checkout(_text())) == pytest.approx(expected)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(checkout, metric,
                                                       tmp_path):
    reduced, run = checkout(_text())
    # no file: the run traced elsewhere (the CPU rehearsal)
    assert _read(metric, reduced, dict(run, workload="some.other")) is None
    # the file's bench.fit events do not start where the trace's do
    moved = {**reduced, "fits": [dict(f, start_ns=f["start_ns"] + 5e3)
                                 for f in reduced["fits"]]}
    assert _read(metric, moved, run) is None
    assert _read(metric, {**reduced, "fits": reduced["fits"][:1]}, run) is None
    # no device plane
    assert _read(metric, *checkout(_text(device=None))) is None
    # two files: which one is the run's cannot be said
    extra = tmp_path / ".bench_trace" / WORKLOAD / "plugins" / "profile" \
        / "older"
    extra.mkdir()
    (extra / "host.xplane.pb").write_bytes(b"")
    assert _read(metric, reduced, run) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_program_without_spans_or_scopes_gives_nothing(checkout, metric):
    """The parent of the PR that added them: ``bench.fit`` and the device's
    lines are there, no span, no ``sgd.*`` in any ``op_name``."""
    bare = [e for e in HOST if e[0] == "bench.fit"]
    old = {name: "jit(run)/while/body/dot_general:" for name in TF_OPS}
    assert _read(metric, *checkout(_text(host=bare, tf_ops=old))) is None
    assert _read(metric, *checkout(_text(host=bare, tf_ops={}))) is None


def test_the_reduction_is_read_once_a_process(checkout, monkeypatch):
    reduced, run = checkout(_text())
    first = spans.of(reduced, run)
    monkeypatch.setattr(spans, "load", None)  # a second read would raise
    assert spans.of(reduced, run) is first


# -- the breakdown --------------------------------------------------------------

def test_the_breakdown_names_operations_by_scope_and_gaps_by_span(checkout):
    reduced, run = checkout(_text())
    got = spans.breakdown(reduced, run)
    assert [n for n, _ in got["device_ops"]] == [
        f"sgd.margins: {M}", f"(unscoped): {COPY}", f"sgd.gradient: {G}",
        f"(unscoped) sgd_run: {WHILE}"]
    # no launch in the file: nothing brackets the clocks, the device's lines
    # stand as they are and the record says so
    assert got["clock"] == {"/device:TPU:0": {
        "shift_ms": None, "bracket_ms": None, "pairs": 0}}
    # fetch covers [20, 97) of fit 0 and [108, 198) of fit 1; nobody [0, 1)
    gaps = dict((n, s) for n, s in got["idle_gaps"])
    assert gaps["train.fetch: fit 1: after last operation"] \
        == pytest.approx(0.040)
    assert gaps["train.fetch: fit 0: after last operation"] \
        == pytest.approx(0.010)
    # [0, 30): h2d 10, fetch 10, validate 4, ... - the first of the longest
    assert gaps["train.h2d: fit 0: before first operation"] \
        == pytest.approx(0.030)
    assert gaps["train.fetch: fit 1: between programs"] == pytest.approx(0.010)
    # only the names change: the seconds and the order are the trace's
    assert [s for _, s in got["device_ops"]] \
        == [s for _, s in reduced["device_ops"]]
    assert [[n.split(": ", 1)[1], s] for n, s in got["idle_gaps"]] \
        == reduced["idle_gaps"]


def test_a_breakdown_that_resolves_nothing_keeps_the_traces_names(checkout):
    reduced, run = checkout(_text())
    long = "%fusion.9 = " + "f32[4194304]{0:T(1024)} " * 20
    other = dict(reduced, device_ops=[[long, 1.0]])
    got = spans.breakdown(other, dict(run, workload="some.other"))
    assert got["idle_gaps"] == reduced["idle_gaps"] and got["clock"] is None
    assert got["device_ops"] == [[long[:trace.NAME_CHARS], 1.0]]
    # a gap that no leaf covers, and an operation the file does not name
    bare = [e for e in HOST if e[0] == "bench.fit"]
    reduced, run = checkout(_text(host=bare))
    got = spans.breakdown(dict(reduced, device_ops=[[long, 1.0]]), run)
    assert got["device_ops"][0][0] == f"(unscoped): {long}"[:trace.NAME_CHARS]
    assert all(n.startswith("(unspanned): fit ") for n, _ in got["idle_gaps"])


# -- four chips ------------------------------------------------------------------

CHIPS = [f"/device:TPU:{n}" for n in range(4)]
ALLREDUCE = "%all-reduce.3 = all-reduce(g)"
#: every chip runs the margins' fusion from 30 ms (40, 36, 32 and 28 ms long)
#: and then the all-reduce to 80 ms: the chip that is done first waits longest
FOUR = {chip: [(WHILE, 30, 50), (M, 30, 40 - 4 * n),
               (ALLREDUCE, 70 - 4 * n, 10 + 4 * n), (G, 110, 20 + n)]
        for n, chip in enumerate(CHIPS)}
FOUR_TF_OPS = {**TF_OPS,
               ALLREDUCE: "jit(sgd_run)/while/body/sgd.allreduce/psum:"}
LAUNCHES = [("jit_sgd_run(7)", 30, 50), ("jit_sgd_run(7)", 110, 25)]


def test_four_device_planes_reduce_to_per_device_means(checkout):
    reduced, run = checkout(_text(ops=FOUR, tf_ops=FOUR_TF_OPS,
                                  modules=LAUNCHES))
    assert reduced["devices"] == 4
    # busy: 50 ms of fit 0 on every chip, 20 + n of fit 1
    assert reduced["busy_ns"] == pytest.approx((50 + 21.5) * MS)
    f0, f1 = reduced["fits"]
    assert f0["busy_ns"] == pytest.approx(50 * MS) and f0["programs"] == 1
    assert f1["busy_ns"] == pytest.approx(21.5 * MS) and f1["programs"] == 1
    # own time, a chip: margins 34 + the mean of fit 1's 21.5 under
    # sgd.gradient, the all-reduce's mean 16, nothing left to the while
    resolved = spans.of(reduced, run)
    assert resolved["scopes"] == pytest.approx({
        "sgd.margins": 34 * MS, "sgd.allreduce": 16 * MS,
        "sgd.gradient": 21.5 * MS, spans.UNSCOPED: 0.0})
    assert resolved["op_scopes"][ALLREDUCE] == "sgd.allreduce"
    # and by the jitted function of the program each ran in
    assert resolved["functions"] == pytest.approx({
        "sgd_run": (34 + 16 + 21.5) * MS})
    assert _read("step_ms", reduced, run) == pytest.approx(71.5 / 2 / 10)
    assert _read("programs_per_fit", reduced, run) == 1
    assert _read("device_idle_share", reduced, run) \
        == pytest.approx(100 * (1 - 71.5 / 200))
    assert _read("margins_ms", reduced, run) == pytest.approx(34 / 2 / 10)
    assert _read("gradient_ms", reduced, run) == pytest.approx(21.5 / 2 / 10)
    assert _read("step_unscoped_share", reduced, run) == 0.0
    # each chip's gaps are its own, named by the chip, on a clock of its own:
    # the calls are [18, 97) and [107, 198), every chip's launches [30, 80)
    # and [110, 135).  Fit 0's launch fills its call and brackets the clocks,
    # [18 - 30, 97 - 80]; fit 1's is under half of its call and is not
    # believed.  The chip's lines go (-12 + 17) / 2 = 2.5 ms later
    got = spans.breakdown(reduced, run)
    assert got["clock"] == {chip: {
        "shift_ms": pytest.approx(2.5), "pairs": 1,
        "bracket_ms": pytest.approx([-12, 17])} for chip in CHIPS}
    gaps = dict((n, s) for n, s in got["idle_gaps"])
    # chip 0 is done at 130 + 2.5 ms; train.fetch covers [132.5, 198) of it
    assert gaps["train.fetch: /device:TPU:0 fit 1: after last operation"] \
        == pytest.approx(0.0675)


# -- BENCHMARK.json ------------------------------------------------------------

@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_span_metric_is_an_entry_with_a_reader(metric):
    """Every reader built on ``bench/spans.py`` has its entry; the entry
    moves an end-to-end metric, reads a span or the trace, and the cells it
    lists (all, where it lists none) exist and load its reader.  Nothing
    here names a cell or a position in the list: a cell or a metric is added
    with new files and new entries alone."""
    bench = cells.benchmark(with_prepared=True)
    assert metric in [m["name"] for m in bench["per_layer"]], \
        f"bench/layers/{metric}.py has no entry in BENCHMARK.json " \
        "or under bench/prepared/"
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["moves"] in [m["name"] for m in bench["end_to_end"]]
    assert entry["source"] in ("program_span", "device_trace")
    assert entry["source"] == SOURCE.get(metric, entry["source"])
    named = [w["name"] for w in bench["workloads"]]
    listed = entry.get("workloads", named)
    assert listed and set(listed) <= set(named)
    for cell in listed:
        assert metric in cells.Cell(cell, bench).readers
    for cell in set(named) - set(listed):
        assert metric not in cells.Cell(cell, bench).readers
