"""The command itself, where it has to fail: no TPU here, and a directory
that holds only ``BENCHMARK.json`` and the files under ``paths``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells

BENCH = cells.benchmark()
ARGS = ["--workload", BENCH["workloads"][0]["name"], "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra, args=ARGS):
    env = dict(os.environ, **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + BENCH["command"][1:] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _results(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        if isinstance(record, dict) and "metrics" in record:
            out.append(record)
    return out


def test_without_a_tpu_it_fails_and_prints_no_result():
    done = _run(cells.REPO, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert _results(done.stdout) == []
    last = json.loads(done.stderr.strip().splitlines()[-1])
    assert last["ok"] is False and "needs 1 TPU device(s)" in last["error"]
    assert last["device"]["platform"] == "cpu"


def test_an_unknown_workload_fails_and_prints_no_result():
    done = _run(cells.REPO, {"JAX_PLATFORMS": "cpu"},
                ["--workload", "nope"] + ARGS[2:])
    assert done.returncode != 0 and _results(done.stdout) == []
    assert "no workload 'nope'" in done.stderr


def test_with_only_the_benchmarks_own_files_it_fails(tmp_path):
    shutil.copy(os.path.join(cells.REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(cells.REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert done.returncode != 0 and _results(done.stdout) == []
    assert "tpu_sgd" in done.stderr


def test_bench_imports_the_package_by_its_public_names_only():
    """Nothing under ``bench/`` imports ``chip_smoke``, a ``bench_*.py``
    script, the counters, or a private module of ``tpu_sgd``."""
    import re

    pattern = re.compile(
        r"^\s*(?:from|import)\s+(chip_smoke|bench_\w+|tpu_sgd\.\S+)",
        re.M)
    for root, dirs, files in os.walk(cells.BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    assert not pattern.findall(fh.read()), f


def test_the_reference_imports_nothing_of_the_program():
    for root, _, files in os.walk(os.path.join(cells.BENCH, "reference")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    text = fh.read()
                assert "import tpu_sgd" not in text
                assert "from tpu_sgd" not in text


@pytest.mark.parametrize("flag", ["--seed", "--seconds", "--trace",
                                  "--workload"])
def test_every_argument_is_required(flag):
    args = list(ARGS)
    i = args.index(flag)
    del args[i:i + 2]
    done = _run(cells.REPO, {"JAX_PLATFORMS": "cpu"}, args)
    assert done.returncode == 2 and _results(done.stdout) == []
