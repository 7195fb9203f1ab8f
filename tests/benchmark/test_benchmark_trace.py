"""``bench/trace.py`` and the per-layer readers on traces built by hand: two
fits, one chip, a ``while`` with its body nested inside it."""

import json
import os

import pytest

from bench import cells, trace

MS = 1e6  # nanoseconds


def _planes(host_events, ops, modules, device="/device:TPU:0"):
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host_events}]},
        {"name": device, "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
    ]


@pytest.fixture
def two_fits():
    """Fit 0: [0, 100) ms, hand-off 30 ms, one program of 60 ms (a while of
    60 ms holding a 40 ms and a 15 ms fusion), 10 ms tail.  Fit 1: [100, 200)
    ms, two programs of 20 ms with 10 ms between them, first at 110."""
    host = [(trace.FIT, 0.0, 100 * MS), (trace.FIT, 100 * MS, 100 * MS),
            ("np.asarray(jax.Array)", 95 * MS, 1 * MS)]
    ops = [("%while.1 = while(...)", 30 * MS, 60 * MS),
           ("%fusion.13 = fusion(X, w)", 31 * MS, 40 * MS),
           ("%fusion.15 = fusion(c, X)", 72 * MS, 15 * MS),
           ("%fusion.13 = fusion(X, w)", 110 * MS, 20 * MS),
           ("%fusion.15 = fusion(c, X)", 140 * MS, 20 * MS)]
    modules = [("jit_run(1)", 30 * MS, 60 * MS),
               ("jit_run(1)", 110 * MS, 20 * MS),
               ("jit_other(2)", 140 * MS, 20 * MS)]
    return trace.reduce(_planes(host, ops, modules))


def test_busy_window_and_fits(two_fits):
    r = two_fits
    assert r["devices"] == 1
    assert r["window_ns"] == 200 * MS and r["busy_ns"] == 100 * MS
    f0, f1 = r["fits"]
    assert (f0["busy_ns"], f0["programs"]) == (60 * MS, 1)
    assert (f1["busy_ns"], f1["programs"]) == (40 * MS, 2)
    # a fit holds durations and counts: no time on the device's clock
    assert set(f0) == {"start_ns", "end_ns", "busy_ns", "programs"}


def test_an_operations_own_time_leaves_out_what_is_nested_in_it(two_fits):
    ops = dict(two_fits["device_ops"])
    assert ops["%fusion.13 = fusion(X, w)"] == pytest.approx(0.060)
    assert ops["%fusion.15 = fusion(c, X)"] == pytest.approx(0.035)
    assert ops["%while.1 = while(...)"] == pytest.approx(0.005)
    assert list(ops)[0] == "%fusion.13 = fusion(X, w)"  # most time first
    assert len(two_fits["device_ops"]) <= 10


def test_idle_gaps_are_named_by_the_part_of_the_fit(two_fits):
    gaps = two_fits["idle_gaps"]
    assert gaps[0] == ["fit 1: after last operation", pytest.approx(0.040)]
    assert gaps[1] == ["fit 0: before first operation", pytest.approx(0.030)]
    names = [g[0] for g in gaps]
    assert "fit 1: between programs" in names
    assert "fit 0: inside a program" not in names  # the while covers it
    assert all(s > 0 for _, s in gaps) and len(gaps) <= 10


def test_a_gap_between_operations_of_one_launch_is_inside_a_program():
    host = [(trace.FIT, 0.0, 50 * MS)]
    ops = [("%a = f()", 0.0, 10 * MS), ("%b = f()", 15 * MS, 35 * MS)]
    r = trace.reduce(_planes(host, ops, [("jit_run(1)", 0.0, 50 * MS)]))
    assert r["idle_gaps"] == [["fit 0: inside a program",
                               pytest.approx(0.005)]]


def test_a_launch_that_starts_before_its_fit_by_clock_skew_still_counts():
    host = [(trace.FIT, 100.0, 50 * MS)]
    ops = [("%fusion = f()", 60.0, 49 * MS)]
    r = trace.reduce(_planes(host, ops, [("jit_run(1)", 60.0, 49 * MS)]))
    assert r["fits"][0]["programs"] == 1
    # and its operations are the fit's from the fit's start on
    assert r["fits"][0]["busy_ns"] == 60.0 + 49 * MS - 100.0


def test_no_device_plane_reduces_to_nothing_to_read():
    r = trace.reduce([{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [(trace.FIT, 0.0, 5 * MS)]}]}])
    assert r["devices"] == 0 and r["fits"] == [] and r["busy_ns"] == 0.0


def test_four_chips_average_their_busy_time():
    host = [(trace.FIT, 0.0, 100 * MS)]
    planes = _planes(host, [("%f = f()", 0.0, 80 * MS)],
                     [("jit_run(1)", 0.0, 80 * MS)])
    planes += _planes(host, [("%f = f()", 0.0, 40 * MS)],
                      [("jit_run(1)", 0.0, 40 * MS)],
                      device="/device:TPU:1")[1:]
    r = trace.reduce(planes)
    assert r["devices"] == 2 and r["busy_ns"] == 60 * MS
    assert r["fits"][0]["programs"] == 1
    assert any(g[0].startswith("/device:TPU:1 ") for g in r["idle_gaps"])


def test_load_reads_an_xplane_file(tmp_path):
    """A small trace recorded as text, through the same reader as a run's."""
    from jax.profiler import ProfileData

    text = """
    planes { name: "/host:CPU"
      lines { name: "python3" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 }
        events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.fit" } }
      event_metadata { key: 2 value { id: 2 name: "shard_args" } } }
    planes { name: "/device:TPU:0"
      lines { name: "XLA Modules" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 70000000000 } }
      lines { name: "XLA Ops" timestamp_ns: 0
        events { metadata_id: 2 offset_ps: 20000000000 duration_ps: 70000000000 } }
      lines { name: "Async XLA Ops" timestamp_ns: 0
        events { metadata_id: 2 offset_ps: 0 duration_ps: 100000000000 } }
      event_metadata { key: 1 value { id: 1 name: "jit_run(7)" } }
      event_metadata { key: 2 value { id: 2 name: "%fusion.13 = fusion()" } } }
    planes { name: "#Chip0 Misc" }
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    planes = trace.load(str(path))
    assert [p["name"] for p in planes] == ["/host:CPU", "/device:TPU:0"]
    assert planes[0]["lines"][0]["events"] == [("bench.fit", 0.0, 100 * MS)]
    assert [line["name"] for line in planes[1]["lines"]] == \
        ["XLA Modules", "XLA Ops"]  # the async line would count busy twice
    r = trace.reduce(planes)
    assert r["busy_ns"] == 70 * MS and r["window_ns"] == 100 * MS
    assert r["fits"][0]["programs"] == 1


# -- the readers, one per per-layer metric ------------------------------------

with open(os.path.join(cells.BENCH, "peaks.json")) as _f:
    PEAKS = json.load(_f)["TPU v5 lite"]


@pytest.fixture
def run():
    cell = cells.Cell("dense1000-logistic.resident")
    return {"iterations": 10, "compiles_in_window": 0, "first_fit_s": 3.5,
            "memory_peak_bytes": 8_409_630_208, "peaks": PEAKS,
            "work": cell.work.step_work(cell.config, cell.rows)}


@pytest.mark.parametrize("metric,expected", [
    ("compiles_in_window", 0),
    ("first_fit_ms", 3500.0),
    ("programs_per_fit", 1.5), ("step_ms", (60 + 40) / 2 / 10),
    ("device_idle_share", 50.0), ("peak_hbm_gb", 8.409630208)])
def test_reader(two_fits, run, metric, expected):
    reader = cells.load_module("layers", metric)
    assert reader.read(two_fits, run) == pytest.approx(expected)


def test_step_roofline_is_least_time_over_measured(two_fits, run):
    reader = cells.load_module("layers", "step_roofline")
    least_ms, bound = reader.least_ms(run)
    assert bound == "bytes"
    assert least_ms == pytest.approx(840_537_720 / 819e9 * 1e3)
    assert reader.read(two_fits, run) == pytest.approx(100 * least_ms / 5.0)
    assert reader.least_ms(run, "as_laid_out")[0] == pytest.approx(
        16_827_547_648 / 819e9 * 1e3)


@pytest.mark.parametrize("metric", ["first_fit_ms", "programs_per_fit",
                                    "step_ms", "step_roofline",
                                    "device_idle_share", "peak_hbm_gb"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    empty = trace.reduce([])
    reader = cells.load_module("layers", metric)
    assert reader.read(empty, {"iterations": 10, "peaks": PEAKS}) is None
