"""What PR 37 brings to the benchmark: six per-layer metrics that read the
host's share of a fit and stay on one clock (``fit_head_ms``, ``select_ms``,
``launch_wake_ms``, ``fit_tail_ms``, ``fit_unspanned_ms``, ``h2d_stall_ms``),
their readers on a trace written by hand (two fits, one chip and four, a
worker thread beside the fit's), and their entries, appended.

The point of them: every device event moved by +2 ms and by -2 ms, the
session's clock offset, leaves all six where they were, and since PR 58
nothing else of a traced run moves with it either: the three metrics that
read the offset are retired, and ``spans.breakdown`` names the same gaps,
of the same lengths, by the same leaves (it puts the device's lines on the
host's clock by what causality allows before it looks for a leaf)."""

import importlib.util
import os

import pytest

from bench import cells, host_share, spans

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NEW = ["fit_head_ms", "select_ms", "launch_wake_ms", "fit_tail_ms",
       "fit_unspanned_ms", "h2d_stall_ms"]
FROM_HOST = "dense1000-logistic.from-host"

#: fit 0 is [0, 100) ms, through ``run()`` from host blocks; fit 1 is
#: [100, 200) ms, at the Optimizer boundary.  (name, start ms, length ms,
#: stats).  Leaves of fit 0: 3 + 1 + 10 + 1 + 2 + 76 + 1 = 94 of 100 ms;
#: of fit 1: 1 + 0.5 + 1.5 + 94 = 97 of 100.
HOST = [
    ("bench.fit", 0, 100, {}),
    ("fit.run", 1, 97, {"rows": 64}),
    ("fit.validate", 1, 3, {"rows": 64}),
    ("fit.plan", 4, 1, {"cached": 1}),
    ("train.run", 5.5, 91.5, {"path": "fused"}),
    ("train.h2d", 6, 10, {"bytes": 4096, "blocks": 4, "stalls": 2,
                          "stall_ms": 7.5}),
    ("train.select", 16, 1, {}),
    ("train.dispatch", 17, 2, {"built": 0}),
    ("train.fetch", 19, 76, {"recorded": 10, "waits": 1}),
    ("fit.finish", 95, 1, {}),
    ("bench.fit", 100, 100, {}),
    ("train.run", 100.5, 99, {"path": "fused"}),
    ("train.h2d", 101, 1, {"bytes": 0, "blocks": 0, "stalls": 0,
                           "stall_ms": 0.0}),
    ("train.select", 102, 0.5, {}),
    ("train.dispatch", 102.5, 1.5, {"built": 1}),
    ("train.fetch", 104, 94, {"recorded": 10, "waits": 1}),
]
#: a worker's span over a stretch of fit 0 that no leaf of the fit's thread
#: covers: it is another thread's, and covers nothing of the fit's
WORKER = [("ingest.produce", 0.2, 0.6, {})]
WRITE, WHILE = "%dynamic-update-slice.1 = ...", "%while.4 = while(...)"
OPS = [(WRITE, 8, 3), (WRITE, 12, 3), (WHILE, 22, 70), (WHILE, 106, 90)]
#: fit 0 launches two block writes and then the whole-run program
LAUNCHES = [("jit__stage_block(3)", 8, 3), ("jit__stage_block(3)", 12, 3),
            ("jit_sgd_run(7)", 22, 70), ("jit_sgd_run(7)", 106, 90)]
ONE = {"/device:TPU:0": (OPS, LAUNCHES)}
#: four chips: each launches the program at the same time and is done at a
#: time of its own; the fit's launch is the longest (chip 2's and chip 0's)
FOUR = {f"/device:TPU:{n}": (
    OPS[:2] + [(WHILE, 22, first), (WHILE, 106, second)],
    LAUNCHES[:2] + [("jit_sgd_run(7)", 22, first),
                    ("jit_sgd_run(7)", 106, second)])
    for n, (first, second) in enumerate([(66, 90), (68, 88), (70, 88),
                                         (67, 89)])}
#: head, wake (call less launch), launch, tail of each fit, in ms
PARTS = [{"head": 17, "wake": 78 - 70, "launch": 70, "tail": 5},
         {"head": 2.5, "wake": 95.5 - 90, "launch": 90, "tail": 2}]
EXPECTED = {"fit_head_ms": (17 + 2.5) / 2, "select_ms": (1 + 0.5) / 2,
            "launch_wake_ms": (8 + 5.5) / 2, "fit_tail_ms": (5 + 2) / 2,
            "fit_unspanned_ms": (6 + 3) / 2, "h2d_stall_ms": (7.5 + 0) / 2}


def _text(host=HOST, worker=WORKER, chips=ONE, shift=0.0):
    """An XSpace as text: the fit's thread and a worker's on the host's
    plane, and per chip its ``XLA Ops`` and ``XLA Modules`` lines, every
    device event ``shift`` ms later."""
    stat_ids = {}

    def events(rows, ids, shift=0.0):
        out = []
        for name, start, length, *rest in rows:
            mid = ids.setdefault(name, len(ids) + 1)
            stats = " ".join(
                f"stats {{ metadata_id: "
                f"{stat_ids.setdefault(k, len(stat_ids) + 1)} "
                + (f'str_value: "{v}"' if isinstance(v, str)
                   else f"double_value: {v}" if isinstance(v, float)
                   else f"int64_value: {v}") + " }"
                for k, v in (rest[0] if rest else {}).items())
            out.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{int((start + shift) * 1e9)} duration_ps: "
                       f"{int(length * 1e9)} {stats} }}")
        return "\n".join(out)

    def meta(ids):
        return "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items())

    def stat_meta():
        return "\n".join(
            f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
            for k, i in stat_ids.items())

    ids = {}
    threads = "".join(
        f'lines {{ name: "{name}" timestamp_ns: 0\n{events(rows, ids)} }}\n'
        for name, rows in (("python3", host), ("worker", worker)) if rows)
    text = f'planes {{ name: "/host:CPU"\n{threads}{meta(ids)}\n' \
        f'{stat_meta()} }}'
    for name, (ops, launches) in chips.items():
        ids = {}
        text += f"""
    planes {{ name: "{name}"
      lines {{ name: "XLA Modules" timestamp_ns: 0
        {events(launches, ids, shift)} }}
      lines {{ name: "XLA Ops" timestamp_ns: 0
        {events(ops, ids, shift)} }}
      {meta(ids)} }}"""
    return text


@pytest.fixture
def traced(checkout):
    """``checkout``: the launches are read with the file, anew with every
    one (they are ``bench/spans.py``'s reading of it)."""
    return checkout


# -- the readers ---------------------------------------------------------------

@pytest.mark.parametrize("chips", [ONE, FOUR], ids=["one_chip", "four_chips"])
@pytest.mark.parametrize("metric", NEW)
def test_reader(traced, metric, chips):
    got = H._read(metric, *traced(_text(chips=chips)))
    assert got == pytest.approx(EXPECTED[metric], abs=1e-9)


SHIFTS = (0.0, 2.0, -2.0)


@pytest.mark.parametrize("chips", [ONE, FOUR], ids=["one_chip", "four_chips"])
def test_a_clock_offset_moves_none_of_the_six(traced, chips):
    """Every device event 2 ms later and 2 ms earlier: the six stand to
    1e-9."""
    at = {}
    for shift in SHIFTS:
        reduced, run = traced(_text(chips=chips, shift=shift))
        at[shift] = {m: H._read(m, reduced, run) for m in NEW}
    for shift in SHIFTS[1:]:
        for metric in NEW:
            assert at[shift][metric] == pytest.approx(
                at[0.0][metric], rel=0, abs=1e-9), (metric, shift)


@pytest.mark.parametrize("chips", [ONE, FOUR], ids=["one_chip", "four_chips"])
def test_a_clock_offset_renames_no_gap_of_the_breakdown(traced, chips):
    """The same gaps, as long, under the same leaves, whatever the session's
    offset: the shift ``breakdown`` finds takes the offset back out, and the
    record says which shift that was."""
    at = {}
    for shift in SHIFTS:
        got = spans.breakdown(*traced(_text(chips=chips, shift=shift)))
        at[shift] = got
        assert sorted(got["clock"]) == sorted(chips)
    for shift in SHIFTS[1:]:
        assert [n for n, _ in at[shift]["idle_gaps"]] \
            == [n for n, _ in at[0.0]["idle_gaps"]]
        assert [s for _, s in at[shift]["idle_gaps"]] == pytest.approx(
            [s for _, s in at[0.0]["idle_gaps"]], rel=0, abs=1e-9)
        for chip, clock in at[shift]["clock"].items():
            assert clock["pairs"] == 2
            assert clock["shift_ms"] == pytest.approx(
                at[0.0]["clock"][chip]["shift_ms"] - shift)
    # one chip: the calls are [17, 95) and [102.5, 198), the launches
    # [22, 92) and [106, 196): brackets [-5, 3] and [-3.5, 2], so the
    # chip's lines go (-3.5 + 2) / 2 = -0.75 ms.  Fit 0's first write then
    # starts at 7.25 (fit.validate has 3 ms of the wait, more than any) and
    # its program ends at 91.25: train.fetch has 3.75 ms of what is left
    # of the fit, fit.finish 1 and nobody 4
    if chips is ONE:
        assert at[0.0]["clock"]["/device:TPU:0"] == {
            "shift_ms": pytest.approx(-0.75), "pairs": 2,
            "bracket_ms": pytest.approx([-3.5, 2.0])}
        gaps = dict((n, s) for n, s in at[2.0]["idle_gaps"])
        assert gaps["fit.validate: fit 0: before first operation"] \
            == pytest.approx(7.25e-3)
        assert gaps["(unspanned): fit 0: after last operation"] \
            == pytest.approx(8.75e-3)
        assert gaps["train.fetch: fit 1: after last operation"] \
            == pytest.approx(4.75e-3)


def test_calls_that_exclude_each_other_bracket_nothing(traced):
    """Two calls whose brackets do not meet (a launch booked to the wrong
    call): no shift is believed, the lines stand and the record says so."""
    def clock(second):
        chips = {"/device:TPU:0": (OPS, LAUNCHES[:2] + [
            ("jit_sgd_run(7)", 18, 70), ("jit_sgd_run(7)", second, 90)])}
        return spans.breakdown(*traced(_text(chips=chips)))["clock"][
            "/device:TPU:0"]

    # [17 - 18, 95 - 88] = [-1, 7] and [102.5 - 107, 198 - 197] = [-4.5, 1]
    assert clock(107) == {"shift_ms": 0.0, "pairs": 2,
                          "bracket_ms": pytest.approx([-1.0, 1.0])}
    # [102.5 - 95, 198 - 185] = [7.5, 13] is past the first call's 7
    assert clock(95) == {"shift_ms": None, "bracket_ms": None, "pairs": 0}


@pytest.mark.parametrize("chips", [ONE, FOUR], ids=["one_chip", "four_chips"])
def test_the_four_parts_are_the_fit(traced, chips):
    reduced, run = traced(_text(chips=chips))
    path = spans.find(run)
    fits = spans.of(reduced, run)["fits"]
    for fit, expected in zip(fits, PARTS):
        parts = host_share.parts(fit, path)
        assert {k: v / H.MS for k, v in parts.items()} \
            == pytest.approx(expected)
        assert sum(parts.values()) == pytest.approx(
            fit["end_ns"] - fit["start_ns"], rel=0, abs=1e-3)
    # and so are the metrics' means, with the launches' mean
    launch = sum(p["launch"] for p in PARTS) / len(PARTS)
    total = sum(H._read(m, reduced, run) for m in
                ("fit_head_ms", "launch_wake_ms", "fit_tail_ms")) + launch
    assert total == pytest.approx(100.0, rel=0, abs=1e-9)


def test_a_launch_is_taken_whole_by_its_midpoint(traced):
    """A program that "starts" before its ``bench.fit`` on the device's
    clock is the fit's all the same, and is not cut at the fit's start."""
    early = {"/device:TPU:0": (
        OPS, LAUNCHES[:3] + [("jit_sgd_run(7)", 99, 97)])}
    reduced, run = traced(_text(chips=early))
    fit = spans.of(reduced, run)["fits"][1]
    assert host_share.longest_launch_ns(spans.find(run), fit) == 97 * H.MS
    # nor is it the LATEST launch: a block write of the next fit that the
    # clocks' offset books to this one (from host 133 launches a fit) is
    # shorter than the whole-run program, so the reading stands
    stray = {"/device:TPU:0": (
        OPS, early["/device:TPU:0"][1] + [("jit__stage_block(3)", 197, 2)])}
    reduced, run = traced(_text(chips=stray))
    fit = spans.of(reduced, run)["fits"][1]
    assert host_share.longest_launch_ns(spans.find(run), fit) == 97 * H.MS
    # a fit in which no program was launched has no wake-up to read
    none = {"/device:TPU:0": (OPS, LAUNCHES[:3])}
    reduced, run = traced(_text(chips=none))
    fit = spans.of(reduced, run)["fits"][1]
    assert host_share.parts(fit, spans.find(run))["wake"] is None
    assert H._read("launch_wake_ms", reduced, run) == pytest.approx(8.0)


def test_a_workers_span_covers_nothing_of_the_fits_thread(traced):
    """``fit_unspanned_ms`` is the fit's thread's: with the worker's span
    and without it the reading is the same, and a leaf more on the fit's
    own thread takes its length off."""
    with_worker = H._read("fit_unspanned_ms", *traced(_text()))
    assert with_worker == H._read("fit_unspanned_ms",
                                  *traced(_text(worker=[])))
    inside = HOST + [("fit.prepare", 5, 0.4, {})]
    assert H._read("fit_unspanned_ms", *traced(_text(host=inside))) \
        == pytest.approx(with_worker - 0.4 / 2)


def _without(*names, stats=()):
    return [(n, s, d, {k: v for k, v in st.items() if k not in stats})
            for n, s, d, st in HOST if n not in names]


@pytest.mark.parametrize("metric,host,expected", [
    # the parent: no train.select, no fit.finish, no stall counter
    ("select_ms", _without("train.select", "fit.finish",
                           stats=("stalls", "stall_ms")), None),
    ("h2d_stall_ms", _without("train.select", "fit.finish",
                              stats=("stalls", "stall_ms")), None),
    # its dispatch and fetch are there: the cut of the fit reads, and the
    # stretches the new leaves would cover are unspanned
    ("fit_head_ms", _without("train.select", "fit.finish"), (17 + 2.5) / 2),
    ("launch_wake_ms", _without("train.select", "fit.finish"),
     (8 + 5.5) / 2),
    ("fit_tail_ms", _without("train.select", "fit.finish"), (5 + 2) / 2),
    ("fit_unspanned_ms", _without("train.select", "fit.finish"),
     (6 + 2 + 3 + 0.5) / 2),
    # a fit that took another path: no dispatch, no fetch, no train.run
    ("fit_head_ms", _without("train.dispatch"), None),
    ("launch_wake_ms", _without("train.dispatch"), None),
    ("launch_wake_ms", _without("train.fetch"), None),
    ("fit_tail_ms", _without("train.fetch"), None),
    ("fit_unspanned_ms", _without("train.run"), None),
    ("h2d_stall_ms", _without("train.h2d"), None),
])
def test_a_reader_without_its_span_reads_nothing(traced, metric, host,
                                                 expected):
    got = H._read(metric, *traced(_text(host=host)))
    assert got == (None if expected is None else pytest.approx(expected))


# -- BENCHMARK.json ------------------------------------------------------------

def test_the_six_are_appended_after_mask_in_kernel_with_readers_on_disk():
    bench = cells.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("mask_in_kernel")
    assert names[at + 1:at + 7] == NEW
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = dict(zip(NEW, ["model harness", "optimizer driver",
                            "optimizer driver", "model harness",
                            "model harness", "model harness"]))
    for name in NEW:
        assert os.path.isfile(os.path.join(cells.BENCH, "layers",
                                           name + ".py"))
        wanted = {"name": name, "unit": "ms", "better": "lower",
                  "source": "device_trace" if name == "launch_wake_ms"
                  else "program_span",
                  "layer": layers[name], "moves": "rows_per_s"}
        if name == "h2d_stall_ms":
            wanted["workloads"] = [FROM_HOST]
        assert entries[name] == wanted
        assert name in H.SPAN_METRICS  # held to test_benchmark_spans' rules


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_each_cell_reports_the_five_and_from_host_the_stall(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert set(NEW[:5]) <= reported
    assert ("h2d_stall_ms" in reported) == (cell == FROM_HOST)


def test_no_reader_takes_a_device_time_from_a_host_time():
    """The rule, as far as a test can hold it: the new readers and what they
    share read no device timestamp (the clipped ``busy_ns`` of the
    reduction, a launch's start) and clip nothing."""
    files = [os.path.join(cells.BENCH, "host_share.py")] + [
        os.path.join(cells.BENCH, "layers", name + ".py") for name in NEW]
    for file in files:
        with open(file) as f:
            code = f.read().split('"""', 2)[2]  # past the docstring
        for banned in ("_clip", "busy_ns", "clock_bracket_ns",
                       "on_host_clock"):
            assert banned not in code, (file, banned)
