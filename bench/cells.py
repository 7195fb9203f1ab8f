"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a job; the configuration's file names its
generator, reference and work module; the job's file names the entry point
for the configuration's storage.  A per-layer metric names its reader.  A
name with no file is an error that says which file is missing."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


class MissingFile(FileNotFoundError):
    pass


def _path(kind: str, name: str, ext: str) -> str:
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise MissingFile(
            f"bench/{kind}/{name}{ext} is missing: {kind} {name!r} is named "
            "but has no file")
    return path


def load_json(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark(with_prepared: bool = False) -> dict:
    """``BENCHMARK.json``; ``with_prepared`` adds the cells under
    ``bench/prepared/`` (entries a later PR pastes into ``BENCHMARK.json``:
    a cell, its configuration, the ``per_layer`` metrics that are its alone;
    one that has been pasted is there already and is not added twice), for
    the tests and ``bench/limits.py`` — never for a run."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if with_prepared:
        folder = os.path.join(BENCH, "prepared")
        for name in sorted(os.listdir(folder)):
            with open(os.path.join(folder, name)) as f:
                more = json.load(f)
            for kind in ("configs", "workloads", "per_layer"):
                have = {entry["name"] for entry in bench[kind]}
                bench[kind] = bench[kind] + [
                    entry for entry in more.get(kind, [])
                    if entry["name"] not in have]
    return bench


def rows_for(config: dict, job: dict, work) -> int:
    """All of the configuration's rows if their bytes fit under the job's
    cap, else the largest multiple of the job's ``rows_step`` that does."""
    rows, cap = int(config["rows"]), int(job["dataset_bytes_cap"])
    if work.dataset_bytes(config, rows) <= cap:
        return rows
    step = int(job["rows_step"])
    k = 0
    while work.dataset_bytes(config, (k + 1) * step) <= cap:
        k += 1
    if not k:
        raise ValueError(f"bench/jobs/{job['name']}.json: the cap admits not "
                         f"one step of {step} rows of {config['name']}")
    return k * step


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, bench: dict = None, overrides: dict = None):
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        with open(os.path.join(REPO, entry["file"])) as f:
            self.config = json.load(f)
        # a test's tiny sizes; a run of the benchmark passes none
        self.config.update(overrides or {})
        self.job = load_json("jobs", self.workload["traffic"])
        storage = self.config["storage"]
        if storage not in self.job["entry"]:
            raise KeyError(
                f"bench/jobs/{self.job['name']}.json names no entry for "
                f"storage {storage!r}")
        self.entry = load_module("entries", self.job["entry"][storage])
        self.generator = load_module("data", self.config["generator"])
        self.reference = load_module("reference", self.config["reference"])
        self.work = load_module("work", self.config["work"])
        self.rows = rows_for(self.config, self.job, self.work)
        self.metrics = {kind: [m for m in bench[kind] if name in
                               m.get("workloads", [name])]
                        for kind in ("end_to_end", "per_layer")}
        self.readers = {m["name"]: load_module("layers", m["name"])
                        for m in self.metrics["per_layer"]}
