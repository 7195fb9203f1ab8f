"""What PR 58 brings to the benchmark: two readers that say whether the chip
or the wire bounds a stream's pass, ``stream_block_ms`` (a row block's time on
the wire, off the worker's ``stream.stage`` spans) and ``stream_join_ms`` (the
device's time in ``_stage_join``, whose operations no scope finds), on traces
written by hand: micro-batches taken ahead and copied in turn, a pass that
joins nothing, an operation known by its jitted function alone; the clocks
bracketed by the calls of jitted functions; and the entries, PR 56's and
PR 57's waiting ones with them, appended with no edit of a test: a dummy
entry appended to a COPY of ``BENCHMARK.json`` leaves the cases that read
the list green."""

import importlib.util
import os
import subprocess
import sys

import pytest

from bench import cells, spans

HERE = os.path.dirname(__file__)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{name}_helpers",
        os.path.join(HERE, f"test_benchmark_{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


H, S = _load("spans"), _load("host_share")
checkout, traced = H.checkout, S.traced  # the fixtures

LSQ = "dense1000-lsq-stream.stream-from-host"
UNEVEN = "dense1000-logistic-stream.stream-uneven-from-host"
INT8 = "cifar5m-int8-multinomial.resident-classes"
BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

#: one pass, [0, 400) ms, of four micro-batches on the fold's thread; (name,
#: start ms, length ms, stats)
FOLD = [("bench.fit", 0, 400, {})]
for k, at in enumerate((2, 100, 200, 300)):
    FOLD += [("stream.wait", at, 3, {}),
             ("stream.whole", at + 1, 2, {"blocks": 4}),
             ("stream.batch", at + 3, 95, {"index": k, "rows": 4096}),
             ("fit.run", at + 4, 90, {"rows": 4096}),
             ("train.run", at + 5, 88, {"path": "fused"}),
             ("train.dispatch", at + 6, 1, {"built": 0}),
             ("train.fetch", at + 7, 85, {"waits": 1})]
#: the worker's takes: the first finds the worker idle and waits 10 ms for its
#: turn (40 blocks in 110 ms), the next three follow each other at once, 2.5,
#: 2.4 and 3.0 ms a block, and the last finds the stream's end
WORKER = [("stream.stage", 4, 110, {"blocks": 40, "bytes": 1 << 20}),
          ("stream.stage", 114.5, 100, {"blocks": 40, "bytes": 1 << 20}),
          ("stream.stage", 214.6, 48, {"blocks": 20, "bytes": 1 << 19}),
          ("stream.stage", 262.7, 90, {"blocks": 30, "bytes": 1 << 20}),
          ("stream.stage", 352.8, 0.1, {})]
JOIN, WRITE, RUN = "%concatenate.1 = ...", "%dus_fusion.7 = ...", "%while.4"
#: in front of every fit the join (two operations, 4 + 2 ms: the chip's
#: compiler gives neither an ``op_name``), then the fit's program
OPS = [op for at in (2, 100, 200, 300)
       for op in ((JOIN, at + 1.5, 4), (WRITE, at + 5.5, 2),
                  (RUN, at + 9, 82))]
LAUNCHES = [launch for at in (2, 100, 200, 300)
            for launch in (("jit__stage_join(71455)", at + 1.5, 6),
                           ("jit_sgd_run(26265)", at + 9, 82))]
CHIP = {"/device:TPU:0": (OPS, LAUNCHES)}


#: the fold's thread calls the join 0.5 ms and the fit 0.8 ms before each
#: starts on the chip; a restored runner's call holds its wrapper's
CALLS = [call for at in (2, 100, 200, 300)
         for call in (("PjitFunction(_stage_join)", at + 1, 1.2, {}),
                      ("PjitFunction(sgd_run)", at + 6.2, 0.7, {}),
                      ("PjitFunction(sgd_run)", at + 6.3, 0.5, {}))]


def _read(metric, trace, run):
    return cells.load_module("layers", metric).read(trace, run)


# -- stream_block_ms -------------------------------------------------------------

#: the worker's first call of a jitted function inside each take: 10 ms into
#: the first (its wait for its turn), 0.2 ms into the others
ISSUES = [("PjitFunction(convert_element_type)", at, 0.3, {})
          for at in (14, 114.7, 214.8, 262.9)]


def test_stream_block_ms_is_the_median_of_the_takes_from_their_first_issue(
        traced):
    """A take is read from its thread's first jitted call on: 100 / 40,
    99.8 / 40, 47.8 / 20 and 89.8 / 30 ms a block; the stream's end (no
    blocks) is no take, and the FOLD's calls inside a take's time are
    another thread's."""
    reduced, run = traced(S._text(host=FOLD + CALLS, worker=WORKER + ISSUES,
                                  chips=CHIP))
    assert _read("stream_block_ms", reduced, run) \
        == pytest.approx((2.495 + 2.5) / 2)
    assert run["stream_block"] == {
        "spans": 4, "least_ms": pytest.approx(2.39),
        "most_ms": pytest.approx(89.8 / 30), "blocks": 130,
        "staged_ms": pytest.approx(348.0), "wait_ms": pytest.approx(10.6),
        "in_turn_ms": None}


def test_stream_block_ms_reads_a_take_whole_where_its_thread_called_nothing(
        traced):
    reduced, run = traced(S._text(host=FOLD + CALLS, worker=WORKER,
                                  chips=CHIP))
    assert _read("stream_block_ms", reduced, run) \
        == pytest.approx((2.5 + 2.75) / 2)  # of 2.75, 2.5, 2.4, 3.0
    assert run["stream_block"]["wait_ms"] == 0.0


def test_stream_block_ms_reads_a_take_that_lies_across_two_passes(traced):
    """The cell's passes are one stream: the worker takes the next pass's
    first micro-batch under this pass's last fit.  Such a take is in no
    fit's spans and is read all the same; one that ends behind the traced
    window is not."""
    passes = [e for e in FOLD if e[0] != "bench.fit"] + [
        ("bench.fit", 0, 250, {}), ("bench.fit", 250, 150, {})]
    across = [("stream.stage", 200, 100, {"blocks": 40}),
              ("stream.stage", 310, 100, {"blocks": 10})]
    reduced, run = traced(S._text(host=passes, worker=across, chips=CHIP))
    assert [s["spans"] for s in spans.of(reduced, run)["fits"]
            if any(x["name"] == "stream.stage" for x in s["spans"])] == []
    assert _read("stream_block_ms", reduced, run) == pytest.approx(2.5)
    assert run["stream_block"]["spans"] == 1


def test_stream_block_ms_says_a_copy_in_turn_in_the_record(traced):
    """A stream's first micro-batch, before any plan: copied inside its fit,
    ``train.h2d`` says its blocks; a scalar's ``train.h2d`` is no copy."""
    in_turn = FOLD + [("train.h2d", 5.1, 0.8, {"blocks": 1, "bytes": 4}),
                      ("train.h2d", 105.1, 0.8, {"blocks": 40, "bytes": 9})]
    reduced, run = traced(S._text(host=in_turn, worker=WORKER, chips=CHIP))
    assert _read("stream_block_ms", reduced, run) == pytest.approx(2.625)
    assert run["stream_block"]["in_turn_ms"] == pytest.approx(0.8 / 40)


@pytest.mark.parametrize("worker", [
    [], [("stream.stage", 4, 110, {})],
    [("ingest.produce", 4, 110, {"blocks": 40})]],
    ids=["no_worker", "no_blocks", "another_span"])
def test_stream_block_ms_is_nothing_without_a_staged_micro_batch(traced,
                                                                 worker):
    run = traced(S._text(host=FOLD, worker=worker, chips=CHIP))
    assert _read("stream_block_ms", *run) is None
    assert "stream_block" not in run[1]


# -- stream_join_ms and the jitted function of an operation ------------------------

def test_stream_join_ms_finds_the_joins_operations_by_their_launch(traced):
    """No ``op_name`` on any operation: each is its launch's function's."""
    reduced, run = traced(S._text(host=FOLD, worker=WORKER, chips=CHIP))
    resolved = spans.of(reduced, run)
    assert resolved["functions"] == pytest.approx({
        "_stage_join": 4 * 6 * H.MS, "sgd_run": 4 * 82 * H.MS})
    assert resolved["op_functions"] == {
        JOIN: "_stage_join", WRITE: "_stage_join", RUN: "sgd_run"}
    assert _read("stream_join_ms", reduced, run) == pytest.approx(6.0)
    # the micro-batches are the more of the batches and the wholes: a pass
    # whose last batch ends behind the fit still has its whole
    short = [e for e in FOLD if not (e[0] == "stream.batch"
                                     and e[3]["index"] == 3)]
    assert _read("stream_join_ms", *traced(S._text(
        host=short, worker=WORKER, chips=CHIP))) == pytest.approx(6.0)


def test_stream_join_ms_finds_them_by_their_op_name_too(checkout):
    """Where the operation says its function that is believed, whatever
    launch the clocks would book it to."""
    tf_ops = {JOIN: "jit(_stage_join)/sgd.whole/concatenate:",
              H.M: "jit(sgd_run)/while/body/sgd.margins/dot_general:"}
    ops = [(JOIN, 3, 5), (H.M, 10, 80)]
    reduced, run = checkout(H._text(host=FOLD, ops=ops, tf_ops=tf_ops,
                                    modules=[("jit_sgd_run(1)", 3, 90)]))
    assert spans.of(reduced, run)["functions"] == pytest.approx({
        "_stage_join": 5 * H.MS, "sgd_run": 80 * H.MS})
    assert _read("stream_join_ms", reduced, run) == pytest.approx(5 / 4)


def test_stream_join_ms_is_zero_where_the_passes_join_nothing(traced):
    """The least-squares stream folds its blocks into totals as they land:
    micro-batches trained, no ``_stage_join``: 0, the bypass, not None."""
    no_join = {"/device:TPU:0": (
        [op for op in OPS if op[0] == RUN],
        [launch for launch in LAUNCHES if "sgd_run" in launch[0]])}
    folded = [e for e in FOLD if e[0] != "stream.whole"]
    assert _read("stream_join_ms", *traced(S._text(
        host=folded, worker=WORKER, chips=no_join))) == 0.0


@pytest.mark.parametrize("host,chips", [
    (S.HOST, S.ONE),  # fits that are no passes of a stream
    (FOLD, {"/device:TPU:0": (OPS, [])}),  # no launch, no op_name: no name
], ids=["no_stream", "no_names"])
def test_stream_join_ms_is_nothing_where_nothing_can_say(traced, host, chips):
    assert _read("stream_join_ms",
                 *traced(S._text(host=host, worker=[], chips=chips))) is None


def test_the_breakdown_names_an_unscoped_operation_by_its_function(traced):
    got = spans.breakdown(*traced(S._text(host=FOLD, worker=WORKER,
                                          chips=CHIP)))
    assert [n for n, _ in got["device_ops"]] == [
        f"(unscoped) sgd_run: {RUN}", f"(unscoped) _stage_join: {JOIN}",
        f"(unscoped) _stage_join: {WRITE}"]
    assert [s for _, s in got["device_ops"]] == pytest.approx(
        [4 * 0.082, 4 * 0.004, 4 * 0.002])


# -- the clocks, bracketed by the calls of jitted functions -------------------------

@pytest.mark.parametrize("shift", [0.0, 2.0, -2.0])
def test_calls_and_fetches_bracket_the_clock_whatever_the_offset(traced,
                                                                 shift):
    """``lo`` by the call that its launch follows closest (the join's, 0.5
    ms), ``hi`` by the fetch that ends closest behind its program (at + 92
    against at + 91); the device's lines go to the middle, wherever the
    session put them."""
    got = spans.breakdown(*traced(S._text(
        host=FOLD + CALLS, worker=WORKER, chips=CHIP, shift=shift)))
    clock = got["clock"]["/device:TPU:0"]
    assert clock["pairs"] == 8  # four joins, four fits: the nested call is one
    assert clock["bracket_ms"] == pytest.approx([-0.5 - shift, 1.0 - shift])
    assert clock["shift_ms"] == pytest.approx(0.25 - shift)
    gaps = dict((n, s) for n, s in got["idle_gaps"])
    # the join is done at at + 7.75 and the fit starts at at + 9.25, under
    # train.fetch (from at + 7)
    assert gaps["train.fetch: fit 0: between programs"] \
        == pytest.approx(1.5e-3)


def test_a_function_called_more_often_than_launched_brackets_nothing(traced):
    """A call from before the trace began, a launch behind its end: the
    k-th of the one is not the k-th of the other, and neither is believed;
    the fetches then stand on the longest launch inside each call."""
    more = FOLD + CALLS + [("PjitFunction(_stage_join)", 399, 0.5, {})]
    clock = spans.breakdown(*traced(S._text(
        host=more, worker=WORKER, chips=CHIP)))["clock"]["/device:TPU:0"]
    # sgd_run's pairs alone: lo = 0.8 - 3.6 ... the fit called at + 6.2
    # starts at + 9: -2.8; hi as before
    assert clock["bracket_ms"] == pytest.approx([-2.8, 1.0])
    assert clock["pairs"] == 4


# -- the entries -----------------------------------------------------------------

ENTRIES = {
    "stream_block_ms": {"unit": "ms", "better": "lower",
                        "source": "program_span", "layer": "stream fold",
                        "moves": "rows_per_s", "workloads": [LSQ, UNEVEN]},
    "stream_join_ms": {"unit": "ms", "better": "lower",
                       "source": "device_trace", "layer": "stream fold",
                       "moves": "rows_per_s", "workloads": [LSQ, UNEVEN]},
    "first_fit_restored": {"unit": "count", "better": "higher",
                           "source": "program_span",
                           "layer": "model harness", "moves": "setup_s"},
    "row_item_bytes": {"unit": "count", "better": "lower",
                       "source": "program_span", "layer": "step",
                       "moves": "rows_per_s", "workloads": [INT8]},
}
RETIRED = ("handoff_" "ms", "fetch_" "ms", "idle_unspanned_" "share")


@pytest.mark.parametrize("metric", ENTRIES)
def test_the_entry_loads_its_reader_in_every_cell_its_list_names(metric):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    assert entry == {"name": metric, **ENTRIES[metric]}
    listed = entry.get("workloads", CELLS)
    for cell in CELLS:
        loaded = cells.Cell(cell)
        assert (metric in loaded.readers) == (cell in listed), cell
        if cell in listed:
            assert callable(loaded.readers[metric].read)


def test_the_four_stand_behind_the_first_fits_five_in_this_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = [names.index(m) for m in ("first_fit_rest_ms", "first_fit_restored",
                                   "row_item_bytes", "stream_block_ms",
                                   "stream_join_ms")]
    assert at == sorted(at)


def test_the_three_that_read_the_clocks_offset_are_gone():
    names = {m["name"] for m in BENCH["per_layer"]}
    assert not names & set(RETIRED)
    for metric in RETIRED:
        assert not os.path.exists(
            os.path.join(cells.BENCH, "layers", metric + ".py"))
    # and no file of the benchmark's names them any more
    for folder in (cells.BENCH, HERE):
        for root, _, files in os.walk(folder):
            for name in (f for f in files if f.endswith((".py", ".json"))):
                with open(os.path.join(root, name)) as f:
                    text = f.read()
                assert not [m for m in RETIRED if m in text], name


def test_an_entry_appended_to_a_copy_turns_no_case_of_the_list_red(tmp_path):
    """The pin does not come back: in ``test_benchmark_rehearsal.py``'s copy
    of the benchmark (its files and these tests; a cell, a metric and the
    prepared entries appended as a later PR appends them), every case of
    ``tests/benchmark/`` that reads the lists and runs no cell.  That file's
    own case runs the contract's and the spans' cases there; this one the
    cases that say WHERE an entry stands, which is where the pin was.  (All
    of ``tests/benchmark/`` on such a copy: by hand, PERF.md, PR 58.)"""
    R = _load("rehearsal")
    before = R.copy_benchmark(str(tmp_path))
    after = R.paste(str(tmp_path))
    assert len(after["per_layer"]) > len(before["per_layer"])
    assert after["per_layer"][-1]["name"] not in ENTRIES
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{tmp_path}{os.pathsep}{R.REPO}")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "tests/benchmark",
         "--ignore=tests/benchmark/test_benchmark_rehearsal.py",
         "--ignore=tests/benchmark/test_benchmark_rehearsal_one_chip.py",
         "-k", "(entry or entries or appended or stand or metrics_are or "
         "metric_is or reports or loads or quota) and not to_a_copy"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    assert " passed" in done.stdout and "failed" not in done.stdout
