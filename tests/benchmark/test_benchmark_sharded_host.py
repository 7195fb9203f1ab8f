"""What ``dense1000-lsq-dp4-run`` brings to the benchmark (PR 42): BASELINE
config 4 as a user calls it, ``LinearRegressionWithSGD.run()`` with a data
mesh on ONE host array that no one chip holds.  The cell and its files, the
contract with two four-chip cells, the generator against
``dense_synthetic_sharded``'s rows, the reference's copy against
``glm_dense_dp``, the tiny rehearsal through the cell's own entry, the
entry's refusal of a program whose hand-off lands on one device, and the four
readers (``h2d_shards``, ``h2d_gb_s``, ``h2d_issue_ms``, ``stage_ms``) on
traces written by hand."""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import tpu_sgd
from bench import cells, correct, harness

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

NAME = "dense1000-lsq-dp4-run.from-host-sharded"
RESIDENT = "dense1000-lsq-dp4.resident-sharded"
ROWS, D, SHARDS = 10_000_000, 1000, 4
METRICS = ["h2d_shards", "h2d_gb_s", "h2d_issue_ms", "stage_ms"]


def _tiny_cell(name=NAME, **more):
    tiny = dict(cells.Cell(name).config["tiny"])
    tiny.pop("what")
    return cells.Cell(name, overrides={**tiny, **more})


# -- the cell, its files and the contract -------------------------------------------

def test_the_cell_resolves_to_its_files_and_is_uncut():
    cell = cells.Cell(NAME)
    config = cell.config
    assert cell.rows == config["as_run"]["rows"]["from-host-sharded"] == ROWS
    assert cell.chips == SHARDS == config["as_run"]["data_parallel"]
    assert cell.job["placement"] == "host"
    assert cell.job["entry"] == {"dense": "model_run_mesh"}
    assert cell.job["rows_step"] == 1_000_000 and cell.job["traced_fits"] == 3
    assert cell.work.dataset_bytes(config, ROWS) == 20_000_000_000 \
        <= cell.job["dataset_bytes_cap"] == 20_500_000_000
    for module, file in ((cell.entry, "model_run_mesh.py"),
                         (cell.generator, "dense_synthetic_sharded_host.py"),
                         (cell.reference, "glm_dense_dp_host.py"),
                         (cell.work, "dense_masked_step_dp.py")):
        assert module.__file__.endswith(file)
    # a shard, its labels and the blocks in flight clear a quarter of a chip
    from tpu_sgd.optimize import gradient_descent as gd

    local = ROWS // SHARDS
    block = gd._block_rows(np.zeros((1, D), jnp.bfloat16), local)
    assert block == 16_384 and -(-local // block) == 153
    in_flight = gd._STAGE_IN_FLIGHT * block * D * 2
    assert 2**34 / 4 < local * D * 2 + local * 4 + in_flight < 6.0e9
    # under 8,000,000 rows the fullest device falls below the floor
    assert 7_000_000 // SHARDS * (D * 2 + 4) + in_flight < 2**34 / 4 \
        < 8_000_000 // SHARDS * (D * 2 + 4) + in_flight


def test_the_configuration_is_dp4s_value_for_value_but_for_the_path():
    mine = cells.Cell(NAME).config
    dp4 = cells.Cell(RESIDENT).config
    own = {"name", "source", "schedule", "generator", "reference", "as_run",
           "guarantees", "assumed", "tiny"}
    assert set(mine) - set(dp4) == {"schedule"}
    assert set(dp4) <= set(mine)
    for key in set(dp4) - own:
        assert mine[key] == dp4[key], key
    assert mine["limits"] == dp4["limits"] == {
        "w_rel_gap": 0.005, "loss_max_gap": 0.4, "dw_norm_gap": 0.0005}
    assert mine["schedule"] == "auto" and mine["reduced"] == ["data_parallel"]
    assert mine["guarantees"].startswith(dp4["guarantees"])
    assert "exactly one shard" in mine["guarantees"]
    assert "outlives" in mine["guarantees"]
    for key, value in dp4["assumed"].items():
        assert mine["assumed"][key] == value
    assert "Fortran-ordered" in mine["assumed"]["host_array"]
    assert mine["tiny"]["rows"] == dp4["tiny"]["rows"]
    assert 1 <= len(mine["source"]) <= 200


def test_the_benchmark_grew_by_appended_entries_alone():
    """The ORDER of the cell's own entries, by ``index``: later PRs append
    behind them, so neither the end nor the length of a list is pinned."""
    bench = cells.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    at = configs.index("dense1000-lsq-dp4-run")
    assert at > configs.index("dense1000-lsq-dp4")
    assert bench["configs"][at]["reduced"] == ["data_parallel"]
    workloads = [w["name"] for w in bench["workloads"]]
    at = workloads.index(NAME)
    assert at > workloads.index(RESIDENT)
    assert bench["workloads"][at] == {
        "name": NAME, "config": "dense1000-lsq-dp4-run",
        "traffic": "from-host-sharded", "chips": 4,
        "why": bench["workloads"][at]["why"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(METRICS[0])
    assert names[first:first + 4] == METRICS
    for entry, unit, better, source, layer in zip(
            bench["per_layer"][first:first + 4], ("count", "GB/s", "ms", "ms"),
            ("higher", "higher", "lower", "lower"),
            ("program_span",) * 3 + ("device_trace",),
            ("model harness",) * 3 + ("step",)):
        assert entry == {"name": entry["name"], "unit": unit,
                         "better": better, "source": source, "layer": layer,
                         "moves": "rows_per_s", "workloads": [NAME]}
    # of the lists of the metrics accepted before the cell, it is on the
    # masked kernel's alone (PR 58: its traced runs read ``fused_sums_ms``)
    assert [entry["name"] for entry in bench["per_layer"][:first]
            if NAME in entry.get("workloads", [])] == ["fused_sums_ms"]
    reported = {m["name"] for m in cells.Cell(NAME).metrics["per_layer"]}
    assert set(METRICS) <= reported and "step_roofline" in reported
    assert not reported & {"h2d_ms", "h2d_blocks", "psum_ms", "place_ms"}


def test_two_of_the_cells_take_four_chips_and_the_quota_holds():
    """The quota as a share of however many cells there are: one in four,
    rounded down, and one always, or the two the accepted benchmark has."""
    workloads = cells.benchmark()["workloads"]
    four = [w["name"] for w in workloads if w["chips"] == 4]
    assert four[:2] == [RESIDENT, NAME]
    assert len(four) <= max(2, len(workloads) // 4)


# -- the generator ------------------------------------------------------------------

def test_the_generator_makes_the_sharded_generators_rows_on_the_host():
    cell, resident = _tiny_cell(), _tiny_cell(RESIDENT)
    config, seed = cell.config, 2_147_483_000
    live = {id(a) for a in jax.live_arrays()}
    X, y = cell.generator.make(config, cell.rows, seed)
    assert X.shape == (16384, 64) and y.shape == (16384,)
    assert isinstance(X, np.ndarray) and X.flags.f_contiguous
    assert jnp.asarray(X[:8]).dtype == jnp.bfloat16 and y.dtype == np.float32
    assert type(np.asarray(X)) is np.ndarray  # what ``place`` keeps
    X.delete(), y.delete()  # and what it calls: nothing to free
    # nothing of the dataset is left on the devices
    assert not [a for a in jax.live_arrays()
                if id(a) not in live and a.size >= 16384]
    Xd, yd = resident.generator.make(resident.config, resident.rows, seed)
    np.testing.assert_array_equal(np.asarray(X).view(np.uint16),
                                  np.asarray(Xd).view(np.uint16))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yd))
    other = cell.generator.make(config, cell.rows, 7)
    assert not np.array_equal(np.asarray(y), np.asarray(other[1]))
    # the harness's placement hands the entry plain host arrays
    Xh, yh = harness.place(cell, X, y)
    assert type(Xh) is np.ndarray and type(yh) is np.ndarray


# -- the reference's copy ---------------------------------------------------------------

@pytest.mark.parametrize("operands", [None, "float8_e4m3fn"])
def test_the_host_reference_is_glm_dense_dp_on_pre_sharded_arrays(
        monkeypatch, operands):
    """From host rows (placed by the reference itself, in several row ranges a
    device) and from arrays that lie sharded: ``glm_dense_dp``'s numbers."""
    cell = _tiny_cell(num_iterations=8)
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 11))
    w0 = np.zeros(64, np.float32)
    monkeypatch.setattr(cell.reference, "PIECE_BYTES", 1500 * 64 * 2)
    placed, mesh = cell.reference.place(X, SHARDS)
    assert [s.device for s in placed.addressable_shards] \
        == jax.devices()[:SHARDS]
    assert placed.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", None)), 2)
    np.testing.assert_array_equal(np.asarray(placed).view(np.uint16),
                                  X.view(np.uint16))
    got = cell.reference.fit(config, X, y, w0, 42, operands)
    dp = cells.load_module("reference", "glm_dense_dp")
    mesh = tpu_sgd.data_mesh(jax.devices()[:SHARDS])
    Xd = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    yd = jax.device_put(y, NamedSharding(mesh, P("data")))
    want = dp.fit(config, Xd, yd, w0, 42, operands)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # and device arrays are taken as they lie
    Xd = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    again = cell.reference.fit(config, Xd, yd, w0, 42, operands)
    np.testing.assert_array_equal(again[0], want[0])


def test_the_host_reference_imports_no_program_code():
    for file in ("glm_dense_dp_host.py",):
        with open(os.path.join(cells.BENCH, "reference", file)) as f:
            assert "tpu_sgd" not in f.read()


# -- the cell's own entry at the tiny sizes ---------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    cell = _tiny_cell()
    run = harness.run_cell(cell, 2**31 + 42, 0.2, False, time.perf_counter(),
                           harness.CompileCounter(), log=lambda line: None)
    return cell, run


def test_the_rehearsal_is_correct(rehearsal):
    cell, run = rehearsal
    assert run["failed"] == 0 and run["attempted"] == run["fits"] + 1
    assert run["compiles_in_window"] == 0
    assert run["rows"] == cell.rows == 16384
    assert run["batch_rows"] == 1638
    assert run["rows_per_s"] == pytest.approx(
        run["fits"] * 100 * 1638 / run["window_s"])
    assert run["loss_last"] < 0.01 * run["loss_first"]
    for name in correct.NUMBERS:
        assert run["checks"][name] <= cell.config["limits"][name]


def test_the_entry_runs_through_run_with_the_mesh_and_the_planner():
    """``run()`` from HOST arrays, the mesh on the optimizer, schedule
    ``auto``: the spans of one fit say what the cell's traced run must."""
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 3))
    fit = cell.entry.prepare(config, X, y, 42)

    class Sink:
        records = []

        def emit(self, kind, payload):
            self.records.append(dict(payload))

    enable_tracing(Sink())
    try:
        w, losses = fit()
    finally:
        disable_tracing()
    named = {r["name"]: r for r in Sink.records}
    assert named["fit.plan"]["schedule"] == "resident_stock"
    assert named["train.h2d"]["shards"] == SHARDS
    assert named["train.h2d"]["bytes"] == X.nbytes + y.nbytes
    assert (named["train.place"]["in_place"],
            named["train.place"]["bytes"]) == (1, 0)
    assert (named["train.run"]["path"], named["train.run"]["shards"]) == (
        "mesh", SHARDS)
    assert losses.shape == (100,) and np.asarray(w).shape == (64,)
    # the same rows through the resident cell's entry: bit for bit
    resident = _tiny_cell(RESIDENT)
    Xd, yd = resident.generator.make(resident.config, resident.rows, 3)
    w_res, losses_res = resident.entry.prepare(resident.config, Xd, yd, 42)()
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_res))
    np.testing.assert_array_equal(losses, losses_res)


@pytest.mark.parametrize("fault", ["staged on one device", "no attribute",
                                   "placement on one device"])
def test_the_entry_refuses_a_hand_off_that_lands_on_one_device(monkeypatch,
                                                                fault):
    """A program from before the sharded destination (the parent): its
    ``train.h2d`` fills one array on the default device and ``train.place``
    re-lays it.  Monkeypatched: the fit's hand-off made to take the road
    without a mesh; the span's attribute dropped; the direct placement made
    to land on one device."""
    from tpu_sgd.optimize import gradient_descent as gd
    from tpu_sgd.parallel import data_parallel

    cell = _tiny_cell()
    config = cell.config
    X, y = harness.place(cell, *cell.generator.make(config, cell.rows, 3))
    if fault == "staged on one device":
        monkeypatch.setattr(gd.GradientDescent, "_hands_off_sharded",
                            lambda self, X: False)
    elif fault == "no attribute":
        real = gd._stage_dense

        class Quiet:
            def __init__(self, h2d):
                self.h2d, self.live = h2d, h2d.live

            def set(self, **attrs):
                attrs.pop("shards", None)
                self.h2d.set(**attrs)

        monkeypatch.setattr(
            gd, "_stage_dense",
            lambda X, h2d=gd.NO_SPAN, mesh=None: real(X, Quiet(h2d), mesh))
    else:
        real = data_parallel.shard_dataset

        def on_one(mesh, X, y, *a):
            if isinstance(X, np.ndarray) and not a:
                one = jax.devices()[0]
                return jax.device_put(X, one), jax.device_put(y, one), None
            return real(mesh, X, y, *a)

        monkeypatch.setattr(tpu_sgd.parallel, "shard_dataset", on_one)
    with pytest.raises(RuntimeError) as refused:
        cell.entry.prepare(config, X, y, 42)
    reason = str(refused.value)
    assert "\n" not in reason and "one chip" in reason
    assert "train.h2d shards=" in reason and "train.place in_place=" in reason


def test_the_entry_states_the_configurations_model():
    cell = _tiny_cell(gradient="LogisticGradient")
    X, y = harness.place(cell, *cell.generator.make(cell.config, cell.rows, 3))
    with pytest.raises(ValueError, match="the configuration states"):
        cell.entry.prepare(cell.config, X, y, 42)


# -- the readers ------------------------------------------------------------------------

#: two fits: (name, start ms, length ms, stats).  Fit 0's hand-off: 40 ms in
#: the span, 10 of them stalled over its shards' threads, 4,000,000 bytes;
#: fit 1's: 20 ms, 5 stalled
def _host(shards=4, stall=True, bytes_=True):
    def h2d(nbytes, stall_ms):
        stats = {"blocks": 8, "block_bytes": 1024}
        if bytes_:
            stats["bytes"] = nbytes
        if shards is not None:
            stats["shards"] = shards
        if stall:
            stats.update(stalls=2, stall_ms=stall_ms)
        return stats

    return [
        ("bench.fit", 0, 100, {}),
        ("fit.run", 1, 98, {"rows": 64}),
        ("fit.plan", 5, 1, {"cached": 1, "schedule": "resident_stock"}),
        ("train.run", 6, 92, {"path": "mesh", "shards": 4}),
        ("train.h2d", 6, 40, h2d(4_000_000, 10)),
        ("train.place", 46, 1, {"shards": 4, "in_place": 1, "bytes": 0}),
        ("train.dispatch", 48, 2, {"built": 0}),
        ("train.fetch", 50, 47, {"recorded": 10}),
        ("bench.fit", 100, 100, {}),
        ("fit.run", 100, 100, {"rows": 64}),
        ("train.run", 105, 95, {"path": "mesh", "shards": 4}),
        ("train.h2d", 105, 20, h2d(4_000_000, 5)),
        ("train.place", 125, 1, {"shards": 4, "in_place": 1, "bytes": 0}),
        ("train.dispatch", 127, 1, {"built": 0}),
        ("train.fetch", 128, 70, {"recorded": 10}),
    ]


FILL, WRITE = "%broadcast.1 = broadcast(zero)", "%dus.2 = dynamic-update-slice"
KERNEL = "%custom-call.3 = custom-call(X, w)"
TF_OPS = {FILL: "jit(_stage_dest)/sgd.stage/broadcast_in_dim:",
          WRITE: "jit(_stage_block)/sgd.stage/dynamic_update_slice:",
          KERNEL: "jit(sgd_run)/while/body/sgd.fused_sums/pallas_call:"}
#: a chip: a fill of 2 ms and writes of 3 + 3 ms in fit 0, a write of 4 ms in
#: fit 1, and the kernel (apart, so that four times as long they still are)
CHIP = [(FILL, 7, 2), (WRITE, 16, 3), (WRITE, 29, 3), (KERNEL, 50, 10),
        (WRITE, 106, 4), (KERNEL, 130, 15)]


def _four_chips(scale=(1, 1, 1, 1)):
    return {f"/device:TPU:{i}": [(n, s, d * k) for n, s, d in CHIP]
            for i, k in enumerate(scale)}


@pytest.mark.parametrize("metric,expected", [
    ("h2d_shards", 4.0),
    # 4,000,000 bytes in 40 ms and in 20 ms: 0.1 and 0.2 GB/s
    ("h2d_gb_s", 0.15),
    # (40 - 10 / 4) and (20 - 5 / 4) ms: a thread a shard stood a quarter
    ("h2d_issue_ms", 28.125),
    # (2 + 3 + 3 + 4) ms over two fits, on every chip
    ("stage_ms", 6.0)])
def test_the_readers_read_an_engaged_hand_off(checkout, metric, expected):
    text = H._text(host=_host(), ops=_four_chips(), tf_ops=TF_OPS)
    assert H._read(metric, *checkout(text)) == pytest.approx(expected)


def test_stage_ms_is_a_mean_over_the_four_chips(checkout):
    """Chips that take 1, 1, 2 and 4 times as long: the mean, not the sum and
    not the longest."""
    text = H._text(host=_host(), ops=_four_chips((1, 1, 2, 4)), tf_ops=TF_OPS)
    assert H._read("stage_ms", *checkout(text)) == pytest.approx(
        6.0 * (1 + 1 + 2 + 4) / 4)


def test_the_readers_read_a_hand_off_that_did_not_engage(checkout):
    """One destination on one device (no mesh, or a program that stages on
    chip 0): ``h2d_shards`` says 1; the others read the same spans."""
    text = H._text(host=_host(shards=1), ops=CHIP, tf_ops=TF_OPS)
    reduced, run = checkout(text)
    assert H._read("h2d_shards", reduced, run) == 1.0
    # (40 - 10) and (20 - 5) ms: the one thread stood all of the wait
    assert H._read("h2d_issue_ms", reduced, run) == pytest.approx(22.5)
    assert H._read("stage_ms", reduced, run) == pytest.approx(6.0)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_attribute_gives_nothing(checkout, metric):
    """The parent: ``train.h2d`` carries no ``shards``; one from before the
    stall counter no ``stall_ms``; a dataset on the devices moves no bytes
    and runs nothing under ``sgd.stage``.  None, and no exception."""
    host = {"h2d_shards": _host(shards=None),
            "h2d_issue_ms": _host(stall=False),
            "h2d_gb_s": _host(bytes_=False),
            "stage_ms": _host()}[metric]
    ops = [e for e in CHIP if e[0] == KERNEL] if metric == "stage_ms" \
        else CHIP
    text = H._text(host=host, ops=ops, tf_ops=TF_OPS)
    assert H._read(metric, *checkout(text)) is None
    # and a trace with no span at all, or none of the run's own
    assert H._read(metric, *checkout(H._text(
        host=[e for e in _host() if e[0] == "bench.fit"], ops=ops,
        tf_ops={}))) is None
    assert cells.load_module("layers", metric).read(
        {"fits": [], "devices": 0}, {"workload": NAME}) is None


def test_the_readers_are_span_metrics_with_a_stated_source():
    entries = {m["name"]: m for m in cells.benchmark()["per_layer"]}
    for metric in METRICS:
        assert metric in H.SPAN_METRICS
        reader = cells.load_module("layers", metric)
        assert reader.__doc__ and entries[metric]["layer"].title()[:4] \
            in reader.__doc__.title()
    assert json.dumps(entries["stage_ms"]["source"]) == '"device_trace"'
