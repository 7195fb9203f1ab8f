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


STUBBED = '''
import builtins, importlib.util, json, sys, time

sys.path.insert(0, ".")
spec = importlib.util.spec_from_file_location("bench_run", "bench/run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
assert "jax" not in sys.modules and "tpu_sgd" not in sys.modules

seen, real_import = {}, builtins.__import__


def timed_import(name, *args, **kw):
    if name != "tpu_sgd" or "began" in seen:
        return real_import(name, *args, **kw)
    seen.update(began=time.perf_counter(), jax_was_in="jax" in sys.modules)
    try:
        return real_import(name, *args, **kw)
    finally:
        seen["took"] = time.perf_counter() - seen["began"]


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


def no_cache():  # called just before main() reads the devices
    import jax
    jax.devices = lambda: [Device()]
    return "none"


def run_cell(cell, seed, seconds, trace, t0, counter, **kw):
    seen.update(import_s=kw["import_s"], t0_is_the_processes=t0 == run.T0)
    return {"failed": 0, "attempted": 1, "memory_peak_bytes": 1,
            "rows_per_s": 1.0, "setup_s": kw["import_s"] + 2.0,
            "data_s": 1.0, "process_s": 3.0, "import_s": kw["import_s"]}


builtins.__import__ = timed_import
run.configure_compile_cache = no_cache
from bench import harness
harness.run_cell = run_cell
code = run.main(sys.argv[1:])
builtins.__import__ = real_import
print(json.dumps(seen))
sys.exit(code)
'''


def test_import_s_is_the_packages_import_with_jax_already_in():
    """``bench/run.py`` itself, its devices and ``run_cell`` stubbed: the
    ``import_s`` it hands on is the time of ``import tpu_sgd`` and of nothing
    in front of it, and it is in the last line's ``setup_s``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", STUBBED] + ARGS,
                          cwd=cells.REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    line, seen = map(json.loads, done.stdout.strip().splitlines()[-2:])
    assert seen["jax_was_in"] and seen["t0_is_the_processes"]
    assert 0 < seen["import_s"] - seen["took"] < 0.02
    assert line["metrics"]["setup_s"] == {"value": seen["import_s"] + 2.0,
                                          "unit": "s"}
    for key in ("data_s", "import_s", "process_s"):
        assert key in line["run"], key
