"""What PR 46 brings to the benchmark: the hand-off's host side cut by call.
Five per-layer metrics of the two cells that train from a host array
(``h2d_put_ms``, ``h2d_write_ms``, ``h2d_free_ms``, ``h2d_own_ms``: what ONE
issuing thread spent in each part of a block's issue;
``h2d_runtime_overlap``: how many of the runtime's own threads worked behind
them), their readers on traces written by hand, their entries, appended, and
the attributes the program sets."""

import importlib.util
import os

import numpy as np
import pytest

from bench import cells, handoff_calls

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

_checkout = H.checkout  # the fixture: a run's trace in a checkout of its own


@pytest.fixture
def checkout(_checkout):
    """``H.checkout``, and the host's plane is read anew for every text."""
    def write(text):
        handoff_calls.runtime_threads.cache_clear()
        return _checkout(text)

    return write

ONE = "dense1000-logistic.from-host"
FOUR = "dense1000-lsq-dp4-run.from-host-sharded"
METRICS = ["h2d_put_ms", "h2d_write_ms", "h2d_free_ms", "h2d_own_ms"]
RUNTIME = "h2d_runtime_overlap"
#: one fit's sums over four threads (ms), whole numbers so that the trace's
#: integer stats hold them
CALLS = {"put_ms": 240, "write_ms": 24, "free_ms": 4, "own_ms": 12,
         "stall_ms": 40}


def _host(h2d_stats, h2d_ms=(80, 80)):
    """Two fits, a ``train.h2d`` of ``h2d_ms[i]`` ms with ``h2d_stats[i]``
    in each: (name, start ms, length ms, stats)."""
    out = []
    for base, ms, stats in zip((0, 200), h2d_ms, h2d_stats):
        out += [("bench.fit", base, 200, {}),
                ("fit.run", base + 1, 198, {"rows": 64}),
                ("train.run", base + 2, 196, {"path": "fused"}),
                ("train.h2d", base + 2, ms, stats),
                ("train.dispatch", base + 2 + ms, 1, {"built": 0}),
                ("train.fetch", base + 3 + ms, 60, {"recorded": 10})]
    return out


def _stats(scale=1, shards=4, **more):
    return {"bytes": 4096, "blocks": 8, "shards": shards, "stalls": 8,
            **{k: v * scale for k, v in CALLS.items()}, **more}


# -- the readers ----------------------------------------------------------------

@pytest.mark.parametrize("metric,attr", zip(METRICS, CALLS))
def test_a_part_is_one_threads_share_of_the_spans_sum(checkout, metric, attr):
    """The span's sum over its ``shards``, mean over the traced fits."""
    four = _host([_stats(), _stats(scale=3)])
    assert H._read(metric, *checkout(H._text(host=four))) \
        == pytest.approx((1 + 3) * CALLS[attr] / 4 / 2)
    # one destination: the thread's own time; no ``shards``: over one
    one = _host([_stats(shards=1), _stats(shards=1)])
    assert H._read(metric, *checkout(H._text(host=one))) \
        == pytest.approx(CALLS[attr])
    unsaid = _host([{k: v for k, v in _stats().items() if k != "shards"}] * 2)
    assert H._read(metric, *checkout(H._text(host=unsaid))) \
        == pytest.approx(CALLS[attr])
    # a fit whose span carries none counts as a fit that spent none
    half = _host([_stats(), {"bytes": 4096}])
    assert H._read(metric, *checkout(H._text(host=half))) \
        == pytest.approx(CALLS[attr] / 4 / 2)


def test_the_four_parts_are_the_issue_where_the_span_holds_nothing_else(
        checkout):
    """A span as long as one thread's loop: put + write + free + own is the
    span less the wait, which is ``h2d_issue_ms``."""
    in_send = sum(CALLS.values()) // 4  # 80 ms a thread
    reduced, run = checkout(H._text(
        host=_host([_stats(), _stats()], h2d_ms=(in_send, in_send))))
    parts = [H._read(m, reduced, run) for m in METRICS]
    assert sum(parts) == pytest.approx(H._read("h2d_issue_ms", reduced, run))
    assert sum(parts) == pytest.approx(in_send - CALLS["stall_ms"] / 4)


def _runtime(lines):
    """A second host plane: ``{thread's name: [(event, start ms, length
    ms)]}``, the lines the runtime's tracer writes."""
    ids, out = {}, []
    for thread, events in lines.items():
        rows = " ".join(
            f"events {{ metadata_id: {ids.setdefault(n, len(ids) + 1)} "
            f"offset_ps: {int(s * 1e9)} duration_ps: {int(d * 1e9)} }}"
            for n, s, d in events)
        out.append(f'lines {{ name: "{thread}" timestamp_ns: 0 {rows} }}')
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in ids.items())
    return f'planes {{ name: "/host:CPU" {" ".join(out)} {meta} }}'


#: the spans' windows are [2, 82) and [202, 282) ms
WORKERS = {
    "pjrt-tpu-tasks/1": [("XlaLinearize", 10, 20), ("Linearize", 11, 18),
                         ("XlaLinearize", 210, 40)],
    "futex-default-SDomainT/2": [("Transpose::ExecuteChunk", 20, 20),
                                 ("Transpose::ExecuteChunk", 75, 20),  # cut
                                 ("Transpose::ExecuteChunk", 120, 30),  # out
                                 ("Transpose::ExecuteChunk", 230, 20)],
    # the fit's thread as the runtime's tracer names it: a caller's line
    "main/9": [("PJRT_LoadedExecutable_Execute", 5, 1),
               ("Transpose::Execute", 12, 60)],
    # a pool's thread that issues blocks: it dispatches
    "python3/7": [("PjitFunction(_stage_block)", 3, 70)]}


def test_runtime_overlap_counts_the_runtimes_own_threads(checkout):
    """Fit 0: [10, 30) and [20, 40) + [75, 82) are 47 ms of threads over
    37 ms that any is inside an event; fit 1: 40 + 20 over 40."""
    host = _host([_stats(), _stats()])
    reduced, run = checkout(H._text(host=host) + _runtime(WORKERS))
    assert H._read(RUNTIME, reduced, run) == pytest.approx(
        (47 / 37 + 60 / 40) / 2)
    # what the accepted readers see has not moved
    assert H._read("h2d_ms", reduced, run) == pytest.approx(80)
    # one worker alone is never more than itself
    alone = {"pjrt-tpu-tasks/1": WORKERS["pjrt-tpu-tasks/1"],
             "main/9": WORKERS["main/9"]}
    assert H._read(RUNTIME, *checkout(
        H._text(host=host) + _runtime(alone))) == pytest.approx(1.0)


def test_runtime_overlap_is_nothing_where_the_runtime_wrote_nothing(checkout):
    """A CPU's trace (the runtime's events lie on the calling threads), a
    parent's spans (the window is there: the reading does not need the
    attributes), no ``train.h2d``, events outside the windows alone."""
    host = _host([_stats(), _stats()])
    assert H._read(RUNTIME, *checkout(H._text(host=host))) is None
    callers = {k: v for k, v in WORKERS.items() if k in ("main/9",
                                                         "python3/7")}
    assert H._read(RUNTIME, *checkout(
        H._text(host=host) + _runtime(callers))) is None
    outside = {"futex-default-SDomainT/2": [("Transpose", 120, 30)]}
    assert H._read(RUNTIME, *checkout(
        H._text(host=host) + _runtime(outside))) is None
    no_h2d = [e for e in host if e[0] != "train.h2d"]
    assert H._read(RUNTIME, *checkout(
        H._text(host=no_h2d) + _runtime(WORKERS))) is None
    parent = [{"bytes": 4096, "blocks": 8}] * 2
    assert H._read(RUNTIME, *checkout(
        H._text(host=_host(parent)) + _runtime(WORKERS))) is not None
    assert handoff_calls.runtime_overlap(None, "nowhere") is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_attribute_gives_nothing(checkout, metric):
    """The parent: ``train.h2d`` carries ``stall_ms`` and ``shards`` and
    none of the parts.  None, and no exception."""
    parent = {"bytes": 4096, "blocks": 8, "shards": 4, "stalls": 8,
              "stall_ms": 40}
    assert H._read(metric, *checkout(H._text(host=_host([parent] * 2)))) \
        is None
    # a trace with no span at all, or none of the run's own
    bare = [e for e in _host([_stats()] * 2) if e[0] == "bench.fit"]
    assert H._read(metric, *checkout(H._text(host=bare, tf_ops={}))) is None
    assert H._read(metric, *checkout(H._text())) is None
    assert cells.load_module("layers", metric).read(
        {"fits": [], "devices": 0}, {"workload": FOUR}) is None
    assert handoff_calls.thread_ms(None, "put_ms") is None


# -- the entries ------------------------------------------------------------------

def test_the_entries_name_both_cells_and_move_rows_per_s():
    bench = cells.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in METRICS + [RUNTIME]:
        # the runtime's events are the profiler's, not the program's, and
        # fewer threads behind the hand-off is a cheaper hand-off
        assert entries[metric] == {
            "name": metric, "better": "lower",
            "unit": "count" if metric == RUNTIME else "ms",
            "source": "device_trace" if metric == RUNTIME
            else "program_span",
            "layer": "model harness", "moves": "rows_per_s",
            "workloads": [ONE, FOUR]}
        assert metric in H.SPAN_METRICS  # held to test_benchmark_spans' rules
        reader = cells.load_module("layers", metric)
        assert reader.__doc__.startswith("Model harness")
    names = [m["name"] for m in bench["per_layer"]]
    # in their own order, behind what PR 44 left (a later PR's go behind)
    at = [names.index(m) for m in METRICS + [RUNTIME]]
    assert at == sorted(at) and at[0] > names.index("stream_folded")


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_the_two_cells_that_train_from_a_host_array_report_them(cell):
    reported = {m["name"] for m in cells.Cell(cell).metrics["per_layer"]}
    assert (set(METRICS + [RUNTIME]) <= reported) == (cell in (ONE, FOUR))
    assert bool(set(METRICS + [RUNTIME]) & reported) == (cell in (ONE, FOUR))


# -- the program --------------------------------------------------------------------

@pytest.mark.parametrize("meshed", [False, True], ids=["one", "mesh"])
def test_the_program_sets_the_attributes_the_readers_read(monkeypatch,
                                                          meshed):
    """A fit from a host array in blocks, tracing on: ``train.h2d`` says
    every attribute a reader here asks for, beside ``stall_ms`` and
    ``shards``."""
    import jax

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing
    from tpu_sgd.optimize import gradient_descent as gd

    shards = 4 if meshed else 1
    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES", gd._STAGE_ROWS * 32)
    monkeypatch.setattr(gd, "_STAGE_IN_FLIGHT", 2)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(shards * 3 * gd._STAGE_ROWS, 8)).astype(np.float32)
    y = X @ np.arange(8, dtype=np.float32)
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_num_iterations(3).set_mini_batch_fraction(0.5))
    if meshed:
        opt.set_mesh(tpu_sgd.data_mesh(jax.devices()[:shards]))
    records = []

    class Sink:
        @staticmethod
        def emit(kind, payload):
            records.append(dict(payload))

    enable_tracing(Sink)
    try:
        opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    finally:
        disable_tracing()
    h2d, = [r for r in records if r["name"] == "train.h2d"]
    assert (h2d["shards"], h2d["blocks"]) == (shards, shards * 3)
    assert set(CALLS) <= set(h2d)
    # and nothing that no reader reads
    assert not [k for k in h2d if k.startswith("put_") and k != "put_ms"]
