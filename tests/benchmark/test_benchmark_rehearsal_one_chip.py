"""``test_benchmark_rehearsal.py``'s paste with ``chips`` 1: a configuration,
a job, a cell and a span metric added to a copy of the benchmark as NEW files
and NEW entries only, the prepared cells promoted, and the copy's contract
and span tests run against it.

The older rehearsal pastes a FOUR-chip cell (the shape of the cell that
``dense1000-lsq-dp4.resident-sharded`` now is) where the copy's quota of
four-chip cells has room for it (one in four, rounded down) and a one-chip
cell where it has not.  What both check, that the tests follow
``BENCHMARK.json`` and ``bench/``, so that a cell or a metric is added with
new files and appended entries alone, is checked here at one chip always, by
the older file's own functions."""

import json
import os
import subprocess
import sys

from tests.benchmark import test_benchmark_rehearsal as rehearsal


def test_a_one_chip_cell_and_a_span_metric_are_added_with_new_files_alone(
        tmp_path):
    before = rehearsal.copy_benchmark(str(tmp_path))
    after = rehearsal.paste(str(tmp_path))
    pasted = next(w for w in after["workloads"]
                  if w["name"] == rehearsal.CELL)
    pasted["chips"] = 1
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(after, f)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert after[kind][:len(before[kind])] == before[kind], kind
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{tmp_path}{os.pathsep}{rehearsal.REPO}")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         "tests/benchmark/test_benchmark_contract.py",
         "tests/benchmark/test_benchmark_spans.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    for wanted in (f"test_cell_resolves_to_its_files[{rehearsal.CELL}]",
                   f"test_workload_entry[{rehearsal.CELL}]",
                   f"test_metric_entry[{rehearsal.METRIC}]",
                   "test_workloads_pair_once_and_few_take_four_chips"):
        assert any(line.startswith("PASSED") and wanted in line
                   for line in done.stdout.splitlines()), wanted
