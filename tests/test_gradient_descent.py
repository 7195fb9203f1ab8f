"""GradientDescent semantics: convergence, loss history, sampling, reg.

Mirrors the reference's GradientDescentSuite strategy (SURVEY.md §4):
synthetic data from known weights, assert loss decreases and weights approach
truth; regParam changes solutions; convergence tolerance exits early.
"""

import numpy as np
import pytest

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import LeastSquaresGradient, LogisticGradient
from tpu_sgd.ops.updaters import SimpleUpdater, SquaredL2Updater
from tpu_sgd.optimize.gradient_descent import (
    GradientDescent,
    run_mini_batch_sgd,
)
from tpu_sgd.utils.mlutils import linear_data, logistic_data


def test_linear_recovers_truth():
    X, y, w_true = linear_data(2000, 10, eps=0.01, seed=0)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(200)
        .set_convergence_tol(0.0)
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(10, np.float32))
    assert hist[-1] < hist[0]
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.05)


def test_loss_history_decreases_and_matches_contract():
    X, y, _ = linear_data(500, 5, eps=0.0, seed=1)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.2)
        .set_num_iterations(50)
        .set_convergence_tol(0.0)
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(5, np.float32))
    assert len(hist) == 50
    # first recorded loss is the loss at the INITIAL weights (before update)
    expect0 = 0.5 * np.mean((X @ np.zeros(5) - y) ** 2)
    np.testing.assert_allclose(hist[0], expect0, rtol=1e-4)
    assert hist[-1] < 1e-2 * hist[0]


def test_convergence_tol_early_exit():
    X, y, _ = linear_data(500, 5, eps=0.0, seed=2)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(500)
        .set_convergence_tol(1e-3)
    )
    _, hist = opt.optimize_with_history((X, y), np.zeros(5, np.float32))
    assert len(hist) < 500  # exited early


def test_reg_param_changes_solution():
    X, y, _ = logistic_data(1000, 8, seed=3)
    common = dict(step_size=1.0, num_iterations=60, mini_batch_fraction=1.0,
                  convergence_tol=0.0)
    w_low, _ = run_mini_batch_sgd(
        (X, y), LogisticGradient(), SquaredL2Updater(),
        reg_param=0.0, initial_weights=np.zeros(8, np.float32), **common)
    w_high, _ = run_mini_batch_sgd(
        (X, y), LogisticGradient(), SquaredL2Updater(),
        reg_param=1.0, initial_weights=np.zeros(8, np.float32), **common)
    assert np.linalg.norm(np.asarray(w_high)) < np.linalg.norm(np.asarray(w_low))


def test_mini_batch_fraction_path_converges():
    X, y, w_true = linear_data(4000, 6, eps=0.01, seed=4)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(300)
        .set_mini_batch_fraction(0.1)
        .set_convergence_tol(0.0)
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(6, np.float32))
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.1)


def test_sampling_is_deterministic_in_seed():
    X, y, _ = linear_data(1000, 4, seed=5)
    def go(seed):
        return np.asarray(
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_num_iterations(20)
            .set_mini_batch_fraction(0.3)
            .set_seed(seed)
            .optimize((X, y), np.zeros(4, np.float32))
        )
    a, b, c = go(42), go(42), go(7)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_empty_input_returns_initial_weights():
    opt = GradientDescent()
    w0 = np.ones(3, np.float32)
    w, hist = opt.optimize_with_history(
        (np.zeros((0, 3), np.float32), np.zeros((0,), np.float32)), w0
    )
    np.testing.assert_array_equal(np.asarray(w), w0)
    assert len(hist) == 0


def test_tiny_fraction_warns():
    X, y, _ = linear_data(10, 2, seed=6)
    opt = GradientDescent().set_mini_batch_fraction(0.01).set_num_iterations(3)
    with pytest.warns(RuntimeWarning):
        opt.optimize((X, y), np.zeros(2, np.float32))


def test_indexed_sampling_converges():
    """The TPU fast-path sampler reaches the same solution quality."""
    X, y, w_true = linear_data(4000, 6, eps=0.01, seed=4)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(300)
        .set_mini_batch_fraction(0.1)
        .set_sampling("indexed")
        .set_convergence_tol(0.0)
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(6, np.float32))
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.1)
    assert len(hist) == 300


def test_indexed_sampling_dp_parity():
    """Indexed sampling under the 8-device mesh also converges."""
    import jax
    from tpu_sgd.parallel.mesh import data_mesh

    X, y, w_true = linear_data(8000, 8, eps=0.01, seed=5)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(300)
        .set_mini_batch_fraction(0.1)
        .set_sampling("indexed")
        .set_convergence_tol(0.0)
        .set_mesh(data_mesh())
    )
    w, _ = opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.1)


def test_host_streaming_converges():
    """Host-resident dataset, streamed minibatches, same solution quality."""
    X, y, w_true = linear_data(4000, 6, eps=0.01, seed=7)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(300)
        .set_mini_batch_fraction(0.1)
        .set_convergence_tol(0.0)
        .set_host_streaming()
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(6, np.float32))
    assert len(hist) == 300
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.1)


@pytest.mark.parametrize("sampling", ["bernoulli", "indexed", "sliced"])
def test_host_streaming_honors_sampling_mode(sampling):
    """config.sampling is honored host-side (VERDICT r1 weak #4): every mode
    converges, and the 8-way mesh trajectory matches single-device exactly
    (the sampler runs on the host either way)."""
    from tpu_sgd.parallel.mesh import data_mesh

    X, y, w_true = linear_data(6000, 8, eps=0.01, seed=11)
    w0 = np.zeros(8, np.float32)

    def make():
        return (
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_step_size(0.4).set_num_iterations(120)
            .set_mini_batch_fraction(0.15).set_convergence_tol(0.0)
            .set_sampling(sampling)
            .set_host_streaming()
        )

    w1, h1 = make().optimize_with_history((X, y), w0)
    np.testing.assert_allclose(np.asarray(w1), w_true, atol=0.1)
    w8, h8 = make().set_mesh(data_mesh()).optimize_with_history((X, y), w0)
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w1), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(h8, h1, rtol=1e-4)


def test_host_streaming_checkpoint_resume(tmp_path):
    """Streamed path honors checkpointing: interrupt, resume, same result."""
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y, _ = linear_data(2000, 5, seed=9)
    w0 = np.zeros(5, np.float32)

    def make(iters, ck):
        return (
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_step_size(0.5).set_num_iterations(iters)
            .set_mini_batch_fraction(0.2).set_convergence_tol(0.0)
            .set_host_streaming()
            .set_checkpoint(CheckpointManager(ck), every=10)
        )

    full = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5).set_num_iterations(60)
        .set_mini_batch_fraction(0.2).set_convergence_tol(0.0)
        .set_host_streaming()
    )
    w_full, h_full = full.optimize_with_history((X, y), w0)
    ck = str(tmp_path / "ck")
    make(30, ck).optimize_with_history((X, y), w0)
    with pytest.warns(RuntimeWarning):
        w_res, h_res = make(60, ck).optimize_with_history((X, y), w0)
    assert len(h_res) == 60
    np.testing.assert_allclose(np.asarray(w_res), np.asarray(w_full),
                               rtol=1e-5, atol=1e-6)


def test_host_streaming_dp_mesh_parity():
    """Streamed batches sharded over the 8-way mesh match the single-device
    streamed trajectory (same host-side sampler, psum'd combine)."""
    from tpu_sgd.parallel.mesh import data_mesh

    X, y, _ = linear_data(3000, 6, eps=0.05, seed=10)
    w0 = np.zeros(6, np.float32)

    def make():
        return (
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_step_size(0.4).set_num_iterations(40)
            .set_mini_batch_fraction(0.2).set_convergence_tol(0.0)
            .set_host_streaming()
        )

    w1, h1 = make().optimize_with_history((X, y), w0)
    w8, h8 = make().set_mesh(data_mesh()).optimize_with_history((X, y), w0)
    assert len(h8) == 40
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w1), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(h8, h1, rtol=1e-4)


def test_host_streaming_rejects_2d_mesh():
    from tpu_sgd.parallel.mesh import make_mesh

    X, y, _ = linear_data(100, 3, seed=10)
    opt = GradientDescent().set_host_streaming().set_mesh(make_mesh(4, 2))
    with pytest.raises(NotImplementedError, match="host streaming"):
        opt.optimize((X, y), np.zeros(3, np.float32))


def test_host_streaming_full_batch_matches_resident():
    """frac=1.0 streamed == resident path (identical math, no sampling)."""
    X, y, _ = linear_data(600, 5, seed=8)
    w0 = np.zeros(5, np.float32)
    cfg = dict(step_size=0.3, num_iterations=25)
    res = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.3).set_num_iterations(25).set_convergence_tol(0.0)
    )
    w_r, h_r = res.optimize_with_history((X, y), w0)
    st = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.3).set_num_iterations(25).set_convergence_tol(0.0)
        .set_host_streaming()
    )
    w_s, h_s = st.optimize_with_history((X, y), w0)
    np.testing.assert_allclose(np.asarray(w_s), np.asarray(w_r), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(h_s, h_r, rtol=1e-5)


def test_invalid_sampling_mode_rejected():
    with pytest.raises(ValueError, match="sampling"):
        GradientDescent().set_sampling("nope")


def test_bf16_data_f32_weights():
    """Mixed precision: bf16 features keep f32 master weights and converge."""
    import jax.numpy as jnp

    X, y, w_true = linear_data(4000, 6, eps=0.01, seed=6)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(200)
        .set_convergence_tol(0.0)
    )
    w, _ = opt.optimize_with_history((jnp.asarray(X, jnp.bfloat16), y),
                                     np.zeros(6, np.float32))
    assert w.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.1)


def test_integer_features_are_cast():
    X = np.asarray([[0, 1], [1, 0], [1, 1], [0, 0]] * 50, np.int64)
    y = (X[:, 0] + 2 * X[:, 1]).astype(np.int64)
    w = (
        GradientDescent()
        .set_step_size(0.5)
        .set_num_iterations(500)
        .set_convergence_tol(0.0)
        .optimize((X, y), np.zeros(2, np.float32))
    )
    np.testing.assert_allclose(np.asarray(w), [1.0, 2.0], atol=0.15)


def test_repeat_optimize_hits_compile_cache():
    import time

    X, y, _ = linear_data(256, 4, seed=8)
    opt = GradientDescent().set_num_iterations(20).set_convergence_tol(0.0)
    w0 = np.zeros(4, np.float32)
    opt.optimize((X, y), w0)  # compile
    t0 = time.perf_counter()
    for _ in range(5):
        opt.optimize((X, y), w0)
    per_call = (time.perf_counter() - t0) / 5
    assert per_call < 0.05, f"repeat optimize too slow ({per_call:.3f}s) — retracing?"


def test_run_mini_batch_sgd_signature_parity():
    X, y, _ = linear_data(200, 3, seed=7)
    w, hist = run_mini_batch_sgd(
        data=(X, y),
        gradient=LeastSquaresGradient(),
        updater=SimpleUpdater(),
        step_size=0.5,
        num_iterations=30,
        reg_param=0.0,
        mini_batch_fraction=1.0,
        initial_weights=np.zeros(3, np.float32),
        convergence_tol=0.0,
    )
    assert len(hist) == 30
    assert hist[-1] < hist[0]


def test_sliced_sampling_converges():
    """sampling='sliced' (contiguous random window) reaches the same solution
    as bernoulli sampling on i.i.d. data."""
    import numpy as np

    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.utils.mlutils import linear_data

    X, y, w_true = linear_data(4096, 12, eps=0.01, seed=11)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(120)
        .set_mini_batch_fraction(0.25)
        .set_convergence_tol(0.0)
        .set_sampling("sliced")
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(12, np.float32))
    assert len(hist) == 120 and hist[-1] < hist[0] * 0.1
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.05)


def test_sliced_sampling_under_dp_mesh():
    """Sliced sampling composes with shard_map data parallelism: each shard
    takes its own window; gradients are psum-combined."""
    import numpy as np

    from tpu_sgd.ops.gradients import LogisticGradient
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.parallel.mesh import data_mesh
    from tpu_sgd.utils.mlutils import logistic_data

    X, y, w_true = logistic_data(4096, 8, seed=12)
    opt = (
        GradientDescent(LogisticGradient(), SquaredL2Updater())
        .set_step_size(1.0)
        .set_num_iterations(80)
        .set_reg_param(0.001)
        .set_mini_batch_fraction(0.25)
        .set_convergence_tol(0.0)
        .set_sampling("sliced")
        .set_mesh(data_mesh())
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    assert hist[-1] < hist[0]
    acc = np.mean((np.asarray(X @ np.asarray(w)) > 0) == (y > 0.5))
    # ~0.76 is this noisy dataset's ceiling (bernoulli sampling reaches the
    # same); the point is parity, not separability.
    assert acc > 0.7


def test_sliced_sampling_ragged_shards():
    """n not divisible by the mesh: padding rows must stay invisible to the
    window sampler (valid-mask slicing)."""
    import numpy as np

    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.parallel.mesh import data_mesh
    from tpu_sgd.utils.mlutils import linear_data

    X, y, w_true = linear_data(4001, 6, eps=0.01, seed=13)
    opt = (
        GradientDescent(LeastSquaresGradient(), SimpleUpdater())
        .set_step_size(0.5)
        .set_num_iterations(100)
        .set_mini_batch_fraction(0.5)
        .set_convergence_tol(0.0)
        .set_sampling("sliced")
        .set_mesh(data_mesh())
    )
    w, hist = opt.optimize_with_history((X, y), np.zeros(6, np.float32))
    assert np.all(np.isfinite(hist))
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.06)


def test_partial_residency_matches_plain_streaming():
    """resident_rows changes WHERE windows are read from (device prefix vs
    host transfer), never WHICH windows are drawn or what they compute: the
    trajectory must match plain streaming exactly, at every residency level
    including fully resident."""
    X, y, _ = linear_data(5000, 6, eps=0.01, seed=13)
    w0 = np.zeros(6, np.float32)

    def run(resident_rows):
        opt = (
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_step_size(0.4).set_num_iterations(80)
            .set_mini_batch_fraction(0.1).set_convergence_tol(0.0)
            .set_sampling("sliced")
            .set_host_streaming(True, resident_rows=resident_rows)
        )
        return opt.optimize_with_history((X, y), w0)

    w_plain, h_plain = run(0)
    for r in (1000, 3000, 5000):  # partial 20%/60%, fully resident
        w_r, h_r = run(r)
        np.testing.assert_allclose(np.asarray(w_r), np.asarray(w_plain),
                                   rtol=1e-6, atol=1e-7)
        # the two compiled programs (sliced-on-device vs transferred batch)
        # fuse differently -> ~1e-9 absolute reassociation noise in losses
        np.testing.assert_allclose(h_r, h_plain, rtol=1e-5, atol=1e-8)


def test_partial_residency_guards():
    """resident_rows misuse raises actionable errors instead of silently
    changing semantics."""
    from tpu_sgd.parallel.mesh import data_mesh

    X, y, _ = linear_data(1000, 4, seed=14)
    w0 = np.zeros(4, np.float32)

    def make(**hs):
        return (
            GradientDescent(LeastSquaresGradient(), SimpleUpdater())
            .set_num_iterations(3).set_mini_batch_fraction(0.1)
            .set_sampling("sliced")
            .set_host_streaming(True, **hs)
        )

    with pytest.raises(NotImplementedError, match="single device"):
        make(resident_rows=500).set_mesh(data_mesh()).optimize_with_history(
            (X, y), w0
        )
    with pytest.raises(NotImplementedError, match="sliced"):
        make(resident_rows=500).set_sampling("bernoulli") \
            .optimize_with_history((X, y), w0)
    with pytest.raises(ValueError, match="smaller than one window"):
        make(resident_rows=10).optimize_with_history((X, y), w0)


def test_partial_residency_via_train_api():
    """streaming_resident_rows is reachable from the user-facing train()
    and reproduces the plain streamed result."""
    from tpu_sgd.models import LinearRegressionWithSGD

    X, y, _ = linear_data(3000, 5, eps=0.01, seed=15)

    def fit(**kw):
        return LinearRegressionWithSGD.train(
            (X, y), num_iterations=60, step_size=0.4,
            mini_batch_fraction=0.2, sampling="sliced",
            host_streaming=True, **kw,
        )

    m_plain = fit()
    m_res = fit(streaming_resident_rows=2000)
    np.testing.assert_allclose(np.asarray(m_res.weights),
                               np.asarray(m_plain.weights),
                               rtol=1e-6, atol=1e-7)


def test_stepwise_numerics_reports_true_iteration(rng):
    """The stepwise (listener) driver checks one loss at a time; the
    numerics error must name the ACTUAL diverging iteration, not
    'iteration 1'."""
    from tpu_sgd.utils.events import SGDListener

    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = (X @ rng.uniform(-1, 1, 8).astype(np.float32)).astype(np.float32)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(1e12).set_num_iterations(10)
           .set_mini_batch_fraction(1.0).set_check_numerics(True)
           .set_listener(SGDListener()))
    with pytest.raises(FloatingPointError) as exc:
        opt.optimize_with_history((X, y), np.zeros(8, np.float32))
    import re

    reported = int(re.search(r"iteration (\d+)", str(exc.value)).group(1))
    assert reported > 1  # iteration 1 (w0=0) is always finite here


def test_host_streaming_validates_initial_weights(rng):
    """The host-streaming branch must raise the same clear ValueError
    as the resident paths on a wrong-length w0 — not an opaque XLA
    shape error inside the streamed step."""
    X = rng.normal(size=(128, 8)).astype(np.float32)
    y = rng.normal(size=(128,)).astype(np.float32)
    opt = GradientDescent().set_host_streaming(True)
    with pytest.raises(ValueError, match="initial_weights has length"):
        opt.optimize_with_history((X, y), np.zeros(5, np.float32))


# -- the fit's tail: one wait, and a device w0 handed on ---------------------------

class _SpanSink:
    def __init__(self):
        self.records = []

    def emit(self, kind, payload):
        self.records.append(dict(payload))


@pytest.mark.parametrize("sampling", ["bernoulli", "sliced"])
@pytest.mark.parametrize("tol", [0.0, 0.05], ids=["all_iterations",
                                                  "stops_early"])
def test_a_fused_fit_ends_in_one_wait_with_the_runners_own_results(
        tol, sampling):
    """``train.fetch`` reads the count and the loss history after their
    copies were started behind the program: what comes back is bit for bit
    what the compiled runner returns read in series (the parent's fetch),
    for a fit that runs all its iterations and one that stops early
    (``recorded`` < iterations, the history cut there), and the span says
    it made ONE blocking read."""
    import jax.numpy as jnp

    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    X, y, _ = linear_data(512, 8, eps=0.01, seed=5)
    X, y = jnp.asarray(X), jnp.asarray(y)
    w0 = np.zeros(8, np.float32)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(0.5).set_num_iterations(40)
           .set_mini_batch_fraction(0.5).set_sampling(sampling)
           .set_convergence_tol(tol))
    sink = _SpanSink()
    enable_tracing(sink)
    try:
        w, hist = opt.optimize_with_history((X, y), w0)
    finally:
        disable_tracing()
    w_ref, losses_ref, n_ref = opt._runner(with_valid=False)(
        jnp.asarray(w0), X, y, opt._hyper())
    recorded = int(n_ref)
    assert (recorded < 40) == (tol > 0)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_ref))
    np.testing.assert_array_equal(hist, np.asarray(losses_ref)[:recorded])
    assert hist is opt.loss_history and len(hist) == recorded
    fetch, = [p for p in sink.records if p["name"] == "train.fetch"]
    assert (fetch["recorded"], fetch["waits"]) == (recorded, 1)


def test_a_fused_fit_starts_both_copies_before_it_reads_either(monkeypatch):
    """The mechanism itself: the runner's count and loss history have their
    copies to the host started (``copy_to_host_async``) before the first
    blocking read.  The weights' copy is started behind them and the fit
    never reads it: they come back a device array, and the caller's read
    of them is the one that was started at dispatch."""
    import jax
    import jax.numpy as jnp

    X, y, _ = linear_data(256, 8, eps=0.01, seed=6)
    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(0.5).set_num_iterations(5)
           .set_convergence_tol(0.0))
    seen = []
    array_type = type(jnp.zeros(1))
    start_copy, value = array_type.copy_to_host_async, array_type._value

    def spy_copy(self):
        seen.append(("copy", self.shape))
        return start_copy(self)

    def spy_value(self):
        seen.append(("read", self.shape))
        return value.fget(self)

    w0 = jnp.zeros(8, jnp.float32)
    opt.optimize_with_history((jnp.asarray(X), jnp.asarray(y)), w0)  # warm
    monkeypatch.setattr(array_type, "copy_to_host_async", spy_copy)
    monkeypatch.setattr(array_type, "_value", property(spy_value))
    w, hist = opt.optimize_with_history((jnp.asarray(X), jnp.asarray(y)), w0)
    monkeypatch.undo()
    assert seen[:4] == [("copy", ()), ("copy", (5,)), ("copy", (8,)),
                        ("read", ())]
    assert ("read", (8,)) not in seen
    assert isinstance(w, jax.Array) and len(hist) == 5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_device_w0_of_an_inexact_type_is_handed_on_without_a_copy(dtype):
    """``_coerce_w0`` copies no more than it must: a device array of an
    inexact type is the SAME object afterwards (no copy, no program); a
    numpy w0 becomes a device array of its own type, an integer one
    float32."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.optimize.gradient_descent import _coerce_w0

    g = LeastSquaresGradient()
    on_device = jnp.ones(8, dtype)
    assert _coerce_w0(g, on_device, 8) is on_device
    host = np.ones(8, np.float32)
    out = _coerce_w0(g, host, 8)
    assert isinstance(out, jax.Array) and out.dtype == jnp.float32
    assert _coerce_w0(g, np.ones(8, np.int32), 8).dtype == jnp.float32
    with pytest.raises(ValueError, match="initial_weights has length 7"):
        _coerce_w0(g, jnp.ones(7, dtype), 8)


# -- the step's named scopes (what the profiler's trace names kernels by) -----

def _scopes_in(lowered):
    import re

    return set(re.findall(r"sgd\.[a-z]+", lowered.as_text(debug_info=True)))


STEP_SCOPES = {"sgd.sample", "sgd.margins", "sgd.pointwise", "sgd.gradient",
               "sgd.update"}


@pytest.mark.parametrize("case", ["dense_logistic", "bcoo_hinge",
                                  "dp4_step"])
def test_lowered_step_carries_the_named_scopes(case):
    """``jax.named_scope`` at trace time, in the one place each piece is
    defined: every driver inherits the names, ``sgd.allreduce`` exists only
    on a mesh and ``sgd.converge`` only in the whole-run loop, as does
    ``sgd.prepare`` (PR 33: dense labels laid out once, in front of it)."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import HingeGradient
    from tpu_sgd.ops.sparse import sparse_data
    from tpu_sgd.ops.updaters import L1Updater
    from tpu_sgd.optimize.gradient_descent import make_run

    cfg = SGDConfig(step_size=1.0, num_iterations=3, mini_batch_fraction=0.5,
                    convergence_tol=0.001)
    w = jnp.zeros(8, jnp.float32)
    if case == "dense_logistic":
        X, y = jnp.ones((64, 8), jnp.bfloat16), jnp.ones(64, jnp.float32)
        fn = jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), cfg))
        assert fn.__name__ == "sgd_run"  # part of the compile cache's key
        found = _scopes_in(fn.lower(w, X, y, cfg.hyper()))
        assert found == STEP_SCOPES | {"sgd.converge", "sgd.prepare"}
    elif case == "bcoo_hinge":
        X, y, _ = sparse_data(64, 8, nnz_per_row=3, kind="svm")
        fn = jax.jit(make_run(HingeGradient(), L1Updater(), cfg))
        found = _scopes_in(fn.lower(w, X, jnp.asarray(y), cfg.hyper()))
        assert found == STEP_SCOPES | {"sgd.converge"}
    else:
        from tpu_sgd.parallel.data_parallel import dp_step_fn
        from tpu_sgd.parallel.mesh import data_mesh

        X, y = jnp.ones((64, 8), jnp.float32), jnp.ones(64, jnp.float32)
        fn = dp_step_fn(LeastSquaresGradient(), SimpleUpdater(), cfg,
                        data_mesh(jax.devices()[:4]), with_valid=False)
        found = _scopes_in(fn.lower(w, X, y, jnp.asarray(1, jnp.int32),
                                    jnp.asarray(0.0, jnp.float32),
                                    cfg.hyper()))
        assert found == STEP_SCOPES | {"sgd.allreduce"}
