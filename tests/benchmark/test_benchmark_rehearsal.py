"""A later PR's rehearsal: in a copy of the benchmark, a configuration, a job,
a cell and a span metric are added as NEW files and NEW entries only (the
shape of the four-chip cell ``PERF.md`` keeps for later: ``chips`` 4, a
traffic of its own, a metric listed for that cell alone), the cells under
``bench/prepared/`` are promoted by pasting their entries, and the copy's
``test_benchmark_contract.py`` and ``test_benchmark_spans.py`` are run
against it.  No file the benchmark has is edited and no entry it has is
touched; the tests that follow ``BENCHMARK.json`` stay green.

By hand, for all of ``tests/benchmark`` on the copy::

    python3 tests/benchmark/test_benchmark_rehearsal.py /tmp/copy
    (cd /tmp/copy && JAX_PLATFORMS=cpu PYTHONPATH=/tmp/copy:$REPO \\
        python3 -m pytest tests/benchmark -q -p no:cacheprovider \\
        --ignore tests/benchmark/test_benchmark_rehearsal.py)
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, JOB, METRIC = "dense1000-logistic-dp4", "from-host-dp4", "allreduce_ms"
CELL = f"{CONFIG}.{JOB}"
READER = '''"""Step: own time of the device operations traced under
``sgd.allreduce`` (``make_step``'s ``psum``) per iteration."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.allreduce")
'''


def copy_benchmark(root: str) -> dict:
    """``BENCHMARK.json`` and the directories under its ``paths``, as a
    checkout of the benchmark's own files; returns the benchmark."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench


def paste(root: str) -> dict:
    """Add the cell and the metric to the copy at ``root``; new files and
    appended entries only.  Returns the copy's benchmark."""
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "configs",
                           "dense1000-logistic.json")) as f:
        config = json.load(f)
    rows = config["rows"]
    # the accepted configuration's model, so that its limits carry over; a
    # real one brings its own model, reference and measured limits
    config.update(
        name=CONFIG, data_parallel=4, reduced=["data_parallel"],
        as_run={"data_parallel": 4, "rows": {JOB: rows},
                "why": "the source's rows, over four chips"})
    with open(os.path.join(bench_dir, "jobs", "from-host.json")) as f:
        job = json.load(f)
    job.update(name=JOB, dataset_bytes_cap=2 * rows * config["features"])
    new = {os.path.join("configs", CONFIG + ".json"): json.dumps(config),
           os.path.join("jobs", JOB + ".json"): json.dumps(job),
           os.path.join("layers", METRIC + ".py"): READER}
    for rel, text in new.items():
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path), f"{rel} is not a new file"
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": CONFIG, "source": "BASELINE.json config 4, uncut",
        "file": f"bench/configs/{CONFIG}.json", "reduced": ["data_parallel"],
        "why": "the north star's own sentence: an all-reduce across cores"})
    # four chips where the copy's quota has room for one more such cell
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    room = four + 1 <= (len(bench["workloads"]) + 1) // 4
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": JOB,
        "chips": 4 if room else 1,
        "why": "10M x 1000 bf16 over four chips through run(); the psum"})
    bench["per_layer"].append({
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step", "moves": "rows_per_s",
        "workloads": [CELL]})
    # and a prepared cell is promoted by pasting its entries as they stand;
    # one that a PR has pasted already is in the copy and is not added twice
    prepared = os.path.join(bench_dir, "prepared")
    for name in sorted(os.listdir(prepared)):
        with open(os.path.join(prepared, name)) as f:
            more = json.load(f)
        for kind in ("configs", "workloads", "per_layer"):
            have = {entry["name"] for entry in bench[kind]}
            bench[kind] += [entry for entry in more.get(kind, [])
                            if entry["name"] not in have]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench


def test_a_cell_and_a_span_metric_are_added_with_new_files_alone(tmp_path):
    before = copy_benchmark(str(tmp_path))
    after = paste(str(tmp_path))
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert after[kind][:len(before[kind])] == before[kind], kind
    assert {k: after[k] for k in ("command", "paths", "run_seconds")} \
        == {k: before[k] for k in ("command", "paths", "run_seconds")}
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{tmp_path}{os.pathsep}{REPO}")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         "tests/benchmark/test_benchmark_contract.py",
         "tests/benchmark/test_benchmark_spans.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    # the copy's own files were the ones tested, the pasted entries among them
    for wanted in (f"test_cell_resolves_to_its_files[{CELL}]",
                   f"test_workload_entry[{CELL}]",
                   f"test_metric_entry[{METRIC}]",
                   f"test_a_span_metric_is_an_entry_with_a_reader[{METRIC}]"):
        assert f"::{wanted}" in done.stdout, wanted
        assert any(line.startswith("PASSED") and wanted in line
                   for line in done.stdout.splitlines()), wanted

if __name__ == "__main__":
    os.makedirs(sys.argv[1])
    copy_benchmark(sys.argv[1])
    paste(sys.argv[1])
