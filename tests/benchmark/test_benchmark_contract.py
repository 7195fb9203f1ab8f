"""``BENCHMARK.json`` against the contract its driver checks before any run,
and against the files it names: every name resolves, a name with no file is
an error that says which file is missing."""

import json
import os
import re

import pytest

from bench import cells, correct

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = cells.benchmark()
# with the cells under bench/prepared/: entries ready to paste, held to the
# same rules and resolved to the same files
ALL = cells.benchmark(with_prepared=True)
CELLS = [w["name"] for w in ALL["workloads"]]
METRICS = BENCH["end_to_end"] + ALL["per_layer"]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(cells.REPO, "BENCHMARK.json")) \
        <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_command_names_only_files_under_paths():
    assert 1 <= len(BENCH["command"]) <= 32
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for word in BENCH["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
    for path in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
        assert os.path.isdir(os.path.join(cells.REPO, path))


def test_files_under_paths_are_named_from_a_names_characters():
    for path in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), cells.REPO)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


@pytest.mark.parametrize("config", ALL["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert _line(config["source"]) and _line(config["why"])
    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert len(config["reduced"]) <= 16
    with open(os.path.join(cells.REPO, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert NAME.match(key) and key in body, key
        # never a width
        assert not key.endswith(("_dim", "_rank")) and key != "features"
    assert any(w["config"] == config["name"] for w in ALL["workloads"])


def test_config_files_and_names_are_distinct():
    files = [c["file"] for c in ALL["configs"]]
    assert len(set(files)) == len(files)
    for group in (ALL["configs"], ALL["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)


@pytest.mark.parametrize("workload", ALL["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry(workload):
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(workload[key])
    assert workload["chips"] in (1, 4) and _line(workload["why"])
    assert workload["config"] in {c["name"] for c in ALL["configs"]}


def test_workloads_pair_once_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in ALL["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in ALL["workloads"])
    assert four <= max(1, len(pairs) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert _line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
        assert metric["name"].endswith("_roofline") or "mfu" in metric["name"]
    for name in metric.get("workloads", []):
        assert name in CELLS


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = cells.Cell(name, ALL)
    assert cell.rows == cell.config["as_run"]["rows"][cell.job["name"]]
    assert cell.job["name"] == cell.workload["traffic"]
    for module, function in ((cell.entry, "prepare"), (cell.generator, "make"),
                             (cell.reference, "fit"),
                             (cell.work, "step_work"),
                             (cell.work, "dataset_bytes")):
        assert callable(getattr(module, function))
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    end_to_end = {m["name"] for m in cell.metrics["end_to_end"]}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert cell.metrics["per_layer"]
    assert set(cell.readers) == {m["name"] for m in cell.metrics["per_layer"]}
    for reader in cell.readers.values():
        assert callable(reader.read)
    assert set(cell.config["limits"]) == set(correct.NUMBERS)


@pytest.mark.parametrize("kind,name,ext", [
    ("jobs", "no-such-job", ".json"), ("layers", "no_such_metric", ".py"),
    ("entries", "no_such_entry", ".py"), ("data", "no_such_generator", ".py"),
    ("reference", "no_such_family", ".py"), ("work", "no_such_step", ".py")])
def test_a_name_with_no_file_says_which_file_is_missing(kind, name, ext):
    load = cells.load_json if ext == ".json" else cells.load_module
    with pytest.raises(cells.MissingFile, match=f"bench/{kind}/{name}{ext}"):
        load(kind, name)


def test_a_new_metric_without_a_reader_is_an_error_not_a_skip():
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"].append({"name": "feed_gb_s", "unit": "GB/s",
                               "better": "higher", "source": "program_span",
                               "layer": "ingest", "moves": "rows_per_s"})
    with pytest.raises(cells.MissingFile, match="bench/layers/feed_gb_s.py"):
        cells.Cell(CELLS[0], bench=bench)


def test_a_prepared_cell_is_not_a_cell_of_a_run():
    """Whatever ``bench/prepared/`` holds: entries ready to paste, held to
    the contract with the rest (``ALL``) and in no run until a PR pastes
    them into ``BENCHMARK.json`` as they stand (the file may stay)."""
    folder = os.path.join(cells.BENCH, "prepared")
    running = {w["name"]: w for w in BENCH["workloads"]}
    for file in sorted(os.listdir(folder)):
        with open(os.path.join(folder, file)) as f:
            prepared = json.load(f)
        assert {"what", "configs", "workloads"} <= set(prepared) \
            <= {"what", "configs", "workloads", "per_layer"}, file
        for metric in prepared.get("per_layer", []):
            # a metric that comes with the cell is the cell's alone
            assert set(metric["workloads"]) <= {
                w["name"] for w in prepared["workloads"]}, file
        for workload in prepared["workloads"]:
            assert workload["name"] in CELLS
            if workload["name"] in running:
                assert running[workload["name"]] == workload, file
            else:
                with pytest.raises(KeyError, match="no workload"):
                    cells.Cell(workload["name"])


def test_rows_for_by_hand():
    cell = cells.Cell("dense1000-logistic.resident")
    job = dict(cell.job, dataset_bytes_cap=2_500_000 * 2000, rows_step=10**6)
    assert cells.rows_for(cell.config, job, cell.work) == 2_000_000
    job = dict(cell.job, dataset_bytes_cap=10**12)
    assert cells.rows_for(cell.config, job, cell.work) == 10_000_000
    job = dict(cell.job, dataset_bytes_cap=1000)
    with pytest.raises(ValueError, match="admits not one step"):
        cells.rows_for(cell.config, job, cell.work)


def test_the_from_host_array_stays_under_the_four_gib_copy_cliff():
    cell = cells.Cell("dense1000-logistic.from-host")
    size = cell.work.dataset_bytes(cell.config, cell.rows)
    assert size < 2**32
    # and with the labels its peak clears a quarter of a 16 GiB chip
    assert size + 4 * cell.rows > 2**34 / 4


def test_an_unknown_workload_is_named():
    with pytest.raises(KeyError, match="no workload 'nope'"):
        cells.Cell("nope")


@pytest.mark.parametrize("name", CELLS)
def test_rows_rule(name):
    """All rows if they fit under the job's cap, else the largest multiple
    of the job's step that does."""
    cell = cells.Cell(name, ALL)
    size = cell.work.dataset_bytes(cell.config, cell.rows)
    assert size <= cell.job["dataset_bytes_cap"]
    if cell.rows != cell.config["rows"]:
        assert cell.rows % cell.job["rows_step"] == 0
        assert cell.work.dataset_bytes(
            cell.config, cell.rows + cell.job["rows_step"]) \
            > cell.job["dataset_bytes_cap"]
        assert "rows" in cell.config["reduced"]


def test_peaks_name_their_source():
    with open(os.path.join(cells.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12 and "TPU v5e" in v5e["source"]


@pytest.mark.parametrize("name,least,laid_out", [
    ("dense1000-logistic.resident", 419430 * 1000 * 2 + 419430 * 4,
     2 * 4194304 * 1000 * 2 + 3 * 4194304 * 4),
    ("dense1000-logistic-sliced.resident", 419430 * 1000 * 2 + 419430 * 4,
     2 * 419430 * 1000 * 2 + 3 * 419430 * 4),
    ("rcv1-hinge-l1.resident", 677399 * 75 * 8 + 677399 * 4 + 2 * 47236 * 4,
     677399 * 75 * 32 + 677399 * 4 + 2 * 47236 * 4)])
def test_work_from_shapes(name, least, laid_out):
    cell = cells.Cell(name, ALL)
    work = cell.work.step_work(cell.config, cell.rows)
    assert work["least"]["bytes"] == least
    assert work["as_laid_out"]["bytes"] == laid_out
    assert work["least"]["bytes"] <= work["as_laid_out"]["bytes"]
    assert work["least"]["flops"] <= work["as_laid_out"]["flops"]
