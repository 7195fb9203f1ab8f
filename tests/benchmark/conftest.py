"""What every case under ``tests/benchmark/`` starts from, wherever the
directory is run from (``test_benchmark_rehearsal.py`` copies it, with
``bench/``, beside no other test file): a process in which the program runs
nothing yet.  Since PR 62 a new optimizer finds the runner an earlier one of
the process built LIVE (``tpu_sgd/optimize/run_store.py``: ``_LIVE``) and
builds nothing, so a case that reads what a FIRST fit built (``first_fit_*``,
``compiles_in_window``) would read another case's leftovers; a run of the
benchmark is one cell a process and has none.  A program from before PR 62
has no such table and nothing to clear."""

import pytest


@pytest.fixture(autouse=True)
def _no_live_runners():
    from tpu_sgd.optimize import run_store

    getattr(run_store, "_LIVE", {}).clear()
    yield
