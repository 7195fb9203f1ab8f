"""Test harness: emulate an 8-device mesh on CPU.

SURVEY.md §4: the reference tests multi-worker behavior with local threads
(``local[2]`` / ``local-cluster``); the direct analogue here is
``--xla_force_host_platform_device_count=8`` on the CPU backend.  Must run
before jax initializes its backends.
"""

import itertools
import os
import sys
import threading

# the package is not pip-installed: make the repo root importable so the
# suite runs under the bare `pytest` console script too, not only
# `python -m pytest` from the repo root (which happens to prepend cwd)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


class SteppedClock:
    """A module's ``time`` whose ``perf_counter`` advances one second a
    reading and keeps each thread's readings."""

    def __init__(self):
        self.ticks, self.read_by = itertools.count(), {}

    def perf_counter(self):
        tick = float(next(self.ticks))
        self.read_by.setdefault(threading.get_ident(), []).append(tick)
        return tick


class NoClock:
    """A module's ``time`` for code that may read no clock."""

    @staticmethod
    def perf_counter():
        raise AssertionError("an untraced hand-off read the clock")


@pytest.fixture
def stepped_clock():
    return SteppedClock()


@pytest.fixture
def no_clock():
    return NoClock


@pytest.fixture(autouse=True)
def _no_live_runners():
    """Every test starts as a new process does: no runner of an earlier
    test's optimizer is found live (``optimize/run_store.py``: ``_LIVE``), so
    what a test counts of traces, compiles and store files is its own."""
    from tpu_sgd.optimize import run_store

    run_store._LIVE.clear()
    yield


@pytest.fixture
def compile_cache(tmp_path):
    """An empty persistent compile cache directory for the length of a test
    (every program kept, however short its compile): its path."""
    from jax.experimental.compilation_cache import compilation_cache

    wanted = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {key: getattr(jax.config, key) for key in wanted}
    for key, value in wanted.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    yield str(tmp_path / "cache")
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
