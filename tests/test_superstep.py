"""Superstep executor tests (ISSUE 5): K fused SGD iterations per
compiled program on the host-dispatched paths.

Trajectory contract pinned here (and documented in ``make_superstep``):

* SAME-PROGRAM comparisons are BITWISE — a fused run replayed, resumed
  from a mid-run checkpoint, fault-healed, or prefetch-A/B'd reproduces
  its weights and loss history exactly, in all three sampling modes.
* Fused-vs-legacy comparisons share the per-step math and the
  deterministic ``(seed, i)`` sample sequence, so the loss-history
  LENGTH, the detected convergence iteration, and the checkpoint
  cadence are exactly equal; the weights agree to reassociation noise
  (~1 ulp/step: XLA lowers the batch dot through a different emitter
  inside a scanned program than as a standalone dispatch — measured in
  this repo, same caveat as partial residency's ``resident_step``).
"""

import threading

import numpy as np
import pytest

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import LeastSquaresGradient
from tpu_sgd.ops.updaters import SimpleUpdater
from tpu_sgd.optimize.gradient_descent import GradientDescent
from tpu_sgd.optimize.streamed import optimize_host_streamed

MODES = ("sliced", "indexed", "bernoulli")
TOL = dict(rtol=5e-5, atol=1e-6)


def _data(rng, n=1000, d=12):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=n)).astype(np.float32)
    return X, y


def _cfg(mode="sliced", iters=10, frac=0.25, tol=0.0, seed=7):
    return SGDConfig(step_size=0.1, num_iterations=iters,
                     mini_batch_fraction=frac, convergence_tol=tol,
                     sampling=mode, seed=seed)


def _stream(cfg, X, y, **kw):
    d = X.shape[1]
    return optimize_host_streamed(
        LeastSquaresGradient(), SimpleUpdater(), cfg, X, y,
        np.zeros(d, np.float32), **kw)


def _opt(mode="sliced", iters=12, k=1, seed=7):
    o = (GradientDescent()
         .set_num_iterations(iters).set_step_size(0.1)
         .set_mini_batch_fraction(0.5).set_sampling(mode)
         .set_convergence_tol(0.0).set_seed(seed)
         .set_host_streaming(True))
    if k > 1:
        o.set_superstep(k)
    return o


# ---- superchunk assembly ---------------------------------------------------

def test_stack_superchunk_shapes_and_tail_padding():
    from tpu_sgd.io import stack_superchunk

    xs = [np.full((5, 3), t, np.float32) for t in range(2)]
    ys = [np.full((5,), t, np.float32) for t in range(2)]
    vs = [np.ones((5,), bool) for _ in range(2)]
    Xs, Ys, Vs = stack_superchunk(xs, ys, vs, k=4)
    assert Xs.shape == (4, 5, 3) and Ys.shape == (4, 5)
    assert Vs.shape == (4, 5) and Vs.dtype == bool
    np.testing.assert_array_equal(Xs[1], xs[1])
    # padded trailing steps: zero rows, all-False valid (no-op updates)
    assert not Xs[2:].any() and not Vs[2:].any()
    # k defaults to len(xs); undersized k raises
    Xs2, _, _ = stack_superchunk(xs, ys, vs)
    assert Xs2.shape == (2, 5, 3)
    with pytest.raises(ValueError, match="do not fit"):
        stack_superchunk(xs, ys, vs, k=1)
    with pytest.raises(ValueError, match="matching"):
        stack_superchunk(xs, ys[:1], vs)


# ---- fused vs legacy: streamed path ----------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_streamed_fused_matches_legacy_all_modes(rng, mode):
    """K=4 over 10 iterations (K does not divide: padded tail
    superstep): same sample sequence, same history length, weights and
    losses at reassociation tolerance."""
    X, y = _data(rng, n=2000, d=16)
    cfg = _cfg(mode)
    w1, h1 = _stream(cfg, X, y)
    w4, h4 = _stream(cfg, X, y, superstep_k=4)
    assert len(h1) == len(h4) == 10
    np.testing.assert_allclose(np.asarray(w4), np.asarray(w1), **TOL)
    np.testing.assert_allclose(h4, h1, **TOL)


def test_streamed_fused_full_batch_shared_transfer(rng):
    """frac >= 1: the fused driver transfers the batch ONCE and scans
    over it — trajectory matches the per-iteration re-transfer loop."""
    X, y = _data(rng, n=600, d=8)
    cfg = _cfg(frac=1.0, iters=9)
    w1, h1 = _stream(cfg, X, y)
    w4, h4 = _stream(cfg, X, y, superstep_k=4)
    assert len(h4) == 9
    np.testing.assert_allclose(np.asarray(w4), np.asarray(w1), **TOL)
    np.testing.assert_allclose(h4, h1, **TOL)


@pytest.mark.parametrize("mode", MODES)
def test_streamed_fused_replay_bitwise(rng, mode):
    """Same-program contract: two fused runs are bit-identical."""
    X, y = _data(rng)
    cfg = _cfg(mode)
    wa, ha = _stream(cfg, X, y, superstep_k=4)
    wb, hb = _stream(cfg, X, y, superstep_k=4)
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    np.testing.assert_array_equal(ha, hb)


def test_streamed_fused_prefetch_depth_bitwise(rng):
    """The superchunk lookahead must not change WHAT is sampled: depth=2
    and the synchronous depth=0 feed are bit-identical (the ingest
    pipeline's own invariant, preserved under fusion)."""
    X, y = _data(rng)
    cfg = _cfg("indexed")
    wa, ha = _stream(cfg, X, y, superstep_k=3, prefetch_depth=2)
    wb, hb = _stream(cfg, X, y, superstep_k=3, prefetch_depth=0)
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    np.testing.assert_array_equal(ha, hb)


def test_streamed_fused_closes_prefetcher_on_convergence(rng):
    import time

    X, y = _data(rng, n=512, d=8)
    cfg = SGDConfig(step_size=1e-6, num_iterations=500,
                    mini_batch_fraction=0.5, convergence_tol=0.5,
                    sampling="sliced")
    before = threading.active_count()
    _, hist = _stream(cfg, X, y, superstep_k=8)
    assert len(hist) < 500  # converged early
    time.sleep(0.05)
    assert threading.active_count() <= before + 1


# ---- convergence-tol semantics under fusion --------------------------------

def test_streamed_fused_convergence_reports_true_iteration(rng):
    """Convergence is detected from the scan ys at the TRUE iteration,
    not the superstep boundary: the fused history ends exactly where
    the legacy loop's does, even mid-superstep."""
    X, y = _data(rng, n=512, d=8)
    cfg = SGDConfig(step_size=0.05, num_iterations=400,
                    mini_batch_fraction=0.5, convergence_tol=0.01,
                    sampling="sliced", seed=7)
    w1, h1 = _stream(cfg, X, y)
    w8, h8 = _stream(cfg, X, y, superstep_k=8)
    assert len(h8) == len(h1)
    assert len(h8) % 8 != 0  # genuinely mid-superstep
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w1), **TOL)


def test_stepwise_fused_convergence_reports_true_iteration(rng):
    X, y = _data(rng, n=512, d=8)

    def run(k):
        from tpu_sgd.utils.events import SGDListener

        o = (GradientDescent().set_num_iterations(400).set_step_size(0.05)
             .set_mini_batch_fraction(0.5).set_sampling("sliced")
             .set_convergence_tol(0.01).set_seed(7)
             .set_listener(SGDListener()))
        if k > 1:
            o.set_superstep(k)
        return o.optimize_with_history((X, y), np.zeros(8, np.float32))

    w1, h1 = run(1)
    w8, h8 = run(8)
    assert len(h8) == len(h1)
    assert len(h8) % 8 != 0
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w1), **TOL)


# ---- fused vs legacy: stepwise (observed) path -----------------------------

class _Recorder:
    def __init__(self):
        self.events = []
        self.ended = None

    def on_run_start(self, cfg):
        pass

    def on_iteration(self, e):
        self.events.append(e)

    def on_run_end(self, e):
        self.ended = e


@pytest.mark.parametrize("mode", MODES)
def test_stepwise_fused_matches_legacy_with_events(rng, mode):
    """Listener path: K=4 over 10 iterations — per-iteration events
    still fire, in order, with the exact losses of the fused history."""
    X, y = _data(rng, n=800, d=10)

    def run(k):
        rec = _Recorder()
        o = (GradientDescent().set_num_iterations(10).set_step_size(0.1)
             .set_mini_batch_fraction(0.5).set_sampling(mode)
             .set_convergence_tol(0.0).set_seed(3).set_listener(rec))
        if k > 1:
            o.set_superstep(k)
        w, h = o.optimize_with_history((X, y), np.zeros(10, np.float32))
        return w, h, rec

    w1, h1, _ = run(1)
    w4, h4, rec = run(4)
    assert len(h4) == len(h1) == 10
    np.testing.assert_allclose(np.asarray(w4), np.asarray(w1), **TOL)
    assert [e.iteration for e in rec.events] == list(range(1, 11))
    np.testing.assert_array_equal(
        np.asarray([e.loss for e in rec.events], np.float32), h4)
    assert rec.ended is not None and rec.ended.num_iterations == 10


def test_stepwise_fused_checkpoint_cadence_matches_legacy(rng, tmp_path):
    """Fused checkpoints land on the SAME iterations as legacy ones
    (cadence + final), carrying the exact iteration state from the ys."""
    import glob

    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=400, d=6)

    def run(k, sub):
        o = (GradientDescent().set_num_iterations(10).set_step_size(0.1)
             .set_mini_batch_fraction(0.5).set_sampling("sliced")
             .set_convergence_tol(0.0).set_seed(3)
             .set_checkpoint(CheckpointManager(str(tmp_path / sub),
                                               keep=100), every=3))
        if k > 1:
            o.set_superstep(k)
        o.optimize_with_history((X, y), np.zeros(6, np.float32))
        return sorted(int(f[-12:-4]) for f in
                      glob.glob(str(tmp_path / sub / "ckpt_*.npz")))

    assert run(1, "legacy") == run(4, "fused") == [3, 6, 9, 10]


def test_stepwise_fused_mesh_runs_fused(rng):
    """ISSUE 6 lift: the meshed observed path joins the fused driver
    (dp_shared_superstep_fn) — no fall-back warning, trajectory at the
    usual fused-vs-legacy tolerance, history exact length."""
    import warnings as _warnings

    from tpu_sgd import data_mesh
    from tpu_sgd.utils.events import SGDListener

    X, y = _data(rng, n=256, d=6)

    def run(k):
        o = (GradientDescent().set_num_iterations(10).set_step_size(0.1)
             .set_mini_batch_fraction(0.5).set_sampling("bernoulli")
             .set_convergence_tol(0.0).set_seed(3)
             .set_mesh(data_mesh()).set_listener(SGDListener()))
        if k > 1:
            o.set_superstep(k)
        return o.optimize_with_history((X, y), np.zeros(6, np.float32))

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        w1, h1 = run(1)
        w4, h4 = run(4)
    assert len(h4) == len(h1) == 10
    np.testing.assert_allclose(np.asarray(w4), np.asarray(w1), **TOL)
    np.testing.assert_allclose(h4, h1, **TOL)


# ---- preemption / resume at superstep boundaries ---------------------------

@pytest.mark.parametrize("mode", MODES)
def test_fused_preempt_resumes_bitwise_all_modes(rng, mode, tmp_path):
    """Stop mid-run: the fused driver checkpoints the exact superstep
    BOUNDARY iteration and a resumed fused run finishes bit-identical
    to the uninterrupted fused run (the PR-3 guarantee under fusion)."""
    from tpu_sgd.reliability.supervisor import TrainingPreempted
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    w_ref, h_ref = _opt(mode, iters=18, k=4).optimize_with_history(
        (X, y), w0)

    class StopSecond:
        def __init__(self):
            self.polls = 0

        def __call__(self):
            self.polls += 1
            return self.polls == 2

    opt = (_opt(mode, iters=18, k=4)
           .set_checkpoint(CheckpointManager(str(tmp_path / mode)),
                           every=100))
    opt.set_stop_signal(StopSecond())
    with pytest.raises(TrainingPreempted) as ei:
        opt.optimize_with_history((X, y), w0)
    # polled once per superstep -> preempted at the SECOND boundary
    assert ei.value.iteration == 8
    opt.set_stop_signal(None)
    w_res, h_res = opt.optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_res), np.asarray(w_ref))
    np.testing.assert_array_equal(h_res, h_ref)


def test_supervisor_preempts_fused_run_at_boundary(rng, tmp_path):
    """TrainingSupervisor drives the same path end-to-end: a preempt
    requested mid-superstep lands at the NEXT superstep boundary (the
    scan cannot stop mid-program), the boundary iteration is
    checkpointed exactly, and a second supervised run resumes and
    completes bitwise."""
    from tpu_sgd.reliability.supervisor import TrainingSupervisor
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    w_ref, h_ref = _opt("sliced", iters=16, k=4).optimize_with_history(
        (X, y), w0)

    opt = _opt("sliced", iters=16, k=4)
    sup = TrainingSupervisor(
        opt, checkpoint_manager=CheckpointManager(str(tmp_path)),
        checkpoint_every=100,  # cadence never fires: preempt must save
        install_signal_handlers=False)

    class Stopper:
        def on_run_start(self, c): ...

        def on_iteration(self, ev):
            if ev.iteration == 5:  # mid-superstep [5, 8]
                sup.request_preempt()

        def on_run_end(self, ev): ...

    opt.set_listener(Stopper())
    res = sup.run((X, y), w0)
    assert res.status == "preempted" and res.preempted_at == 8
    assert CheckpointManager(str(tmp_path)).latest_version() == 8
    opt.set_listener(None)
    res2 = sup.run((X, y), w0)  # fresh run(): preempt flag cleared
    assert res2.completed
    np.testing.assert_array_equal(np.asarray(res2.weights),
                                  np.asarray(w_ref))
    np.testing.assert_array_equal(res2.loss_history, h_ref)


def test_fused_crash_resume_unaligned_grid_bitwise(rng, tmp_path):
    """A crash-resume restart from a cadence checkpoint lands MID-GRID
    (every=3, K=4 -> resume at iteration 4, 7, ...): the superstep
    regrouping after the resume must not change the trajectory — the
    per-iteration math is grouping-independent, so the resumed run is
    still bitwise equal to the uninterrupted fused run."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import fail_nth
    from tpu_sgd.reliability.retry import RetryPolicy
    from tpu_sgd.reliability.supervisor import TrainingSupervisor
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    w_ref, h_ref = _opt("sliced", iters=14, k=4).optimize_with_history(
        (X, y), w0)

    sup = TrainingSupervisor(
        _opt("sliced", iters=14, k=4),
        checkpoint_manager=CheckpointManager(str(tmp_path)),
        checkpoint_every=3,
        retry=RetryPolicy(max_attempts=4, base_backoff_s=0.0),
        install_signal_handlers=False)
    # crash the SECOND superstep dispatch: the latest checkpoint is
    # iteration 3, so the resume restarts at 4 — off the original
    # [1,5,9,13] superstep grid
    with fp.inject_faults({"optimize.streamed.step": fail_nth(2)}):
        res = sup.run((X, y), w0)
    assert res.completed and res.attempts == 2
    np.testing.assert_array_equal(np.asarray(res.weights),
                                  np.asarray(w_ref))
    np.testing.assert_array_equal(res.loss_history, h_ref)


# ---- one fused-body program ------------------------------------------------

def test_superstep_builder_compiles_one_program(rng):
    """THE dispatch-count assertion: a full superstep and a padded tail
    superstep share ONE compiled fused-body program (fixed (K, cap)
    shapes — the host pads, the device never re-traces)."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.analysis import assert_compile_count
    from tpu_sgd.io import stack_superchunk
    from tpu_sgd.optimize.gradient_descent import make_superstep

    X, y = _data(rng, n=400, d=6)
    cfg = _cfg(frac=1.0)  # step consumes the whole transferred batch
    fused = jax.jit(make_superstep(
        LeastSquaresGradient(), SimpleUpdater(), cfg))
    cap = 100
    full = [(X[i * cap:(i + 1) * cap], y[i * cap:(i + 1) * cap],
             np.ones((cap,), bool)) for i in range(4)]
    w = jnp.zeros(6, jnp.float32)
    with assert_compile_count(1, of=fused):
        # full superstep
        Xs, Ys, Vs = stack_superchunk([p[0] for p in full],
                                      [p[1] for p in full],
                                      [p[2] for p in full])
        w, ys = fused(w, jnp.asarray(0.0, jnp.float32), cfg.hyper(),
                      jnp.asarray(1, jnp.int32), Xs, Ys, Vs)
        # tail superstep: 2 real batches padded to K=4 — same shapes,
        # same program
        Xs, Ys, Vs = stack_superchunk([p[0] for p in full[:2]],
                                      [p[1] for p in full[:2]],
                                      [p[2] for p in full[:2]], k=4)
        w, ys = fused(w, jnp.asarray(0.0, jnp.float32), cfg.hyper(),
                      jnp.asarray(5, jnp.int32), Xs, Ys, Vs)
        jax.block_until_ready(w)


def test_stepwise_fused_run_compiles_one_program(rng):
    """Integration twin: a whole fused stepwise run (incl. the K ∤ N
    tail) leaves exactly one program in the memoized superstepper."""
    from tpu_sgd.utils.events import SGDListener

    X, y = _data(rng, n=400, d=6)
    o = (GradientDescent().set_num_iterations(10).set_step_size(0.1)
         .set_mini_batch_fraction(0.5).set_sampling("sliced")
         .set_convergence_tol(0.0).set_seed(3)
         .set_listener(SGDListener()).set_superstep(4))
    o.optimize_with_history((X, y), np.zeros(6, np.float32))
    key = ("superstep", o.gradient, o.updater, o.config.structure(), 4,
           None, False)
    fn = o._run_cache[key]
    assert fn._cache_size() == 1


# ---- reliability: io.superstep failpoint -----------------------------------

def test_io_superstep_failpoint_heals_via_retry_policy(rng):
    """An injected fault in superchunk assembly heals through the
    feed's existing RetryPolicy (the producer re-runs; the sample is
    deterministic in (seed, i), so the healed run stays bitwise)."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import FaultInjected, fail_nth
    from tpu_sgd.reliability.retry import RetryPolicy

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    w_ref, h_ref = _opt("indexed", iters=12, k=4).optimize_with_history(
        (X, y), w0)

    opt = (_opt("indexed", iters=12, k=4)
           .set_ingest_options(retry=RetryPolicy(max_attempts=3,
                                                 base_backoff_s=0.0)))
    with fp.inject_faults({"io.superstep": fail_nth(1)}):
        w, h = opt.optimize_with_history((X, y), w0)
        assert fp.triggers("io.superstep") == 1
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_ref))
    np.testing.assert_array_equal(h, h_ref)

    # without a retry policy the same fault propagates — the site is
    # really on the path
    with fp.inject_faults({"io.superstep": fail_nth(1)}):
        with pytest.raises(FaultInjected):
            _opt("indexed", iters=12, k=4).optimize_with_history(
                (X, y), w0)


def test_full_batch_fused_transfer_heals_via_retry(rng):
    """Review regression: the fused full-batch path's ONE-TIME transfer
    runs outside a prefetcher, so the ingest RetryPolicy must wrap it
    directly — a transient device_put fault heals exactly as it does on
    the per-iteration feed."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import FaultInjected, fail_nth
    from tpu_sgd.reliability.retry import RetryPolicy

    X, y = _data(rng, n=256, d=6)
    w0 = np.zeros(6, np.float32)

    def full(k, retry=None):
        o = (GradientDescent().set_num_iterations(6).set_step_size(0.1)
             .set_mini_batch_fraction(1.0).set_convergence_tol(0.0)
             .set_seed(7).set_host_streaming(True).set_superstep(k))
        if retry is not None:
            o.set_ingest_options(retry=retry)
        return o

    w_ref, h_ref = full(4).optimize_with_history((X, y), w0)
    with fp.inject_faults({"io.device_put": fail_nth(1)}):
        w, h = full(4, RetryPolicy(max_attempts=3, base_backoff_s=0.0)
                    ).optimize_with_history((X, y), w0)
        assert fp.triggers("io.device_put") == 1
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_ref))
    np.testing.assert_array_equal(h, h_ref)
    with fp.inject_faults({"io.device_put": fail_nth(1)}):
        with pytest.raises(FaultInjected):
            full(4).optimize_with_history((X, y), w0)


# ---- knob plumbing ---------------------------------------------------------

def test_set_superstep_validates():
    with pytest.raises(ValueError, match="superstep"):
        GradientDescent().set_superstep(0)
    assert GradientDescent().set_superstep(8).superstep == 8


def test_streamed_fused_mesh_and_residency_run_fused(rng):
    """ISSUE 6 lift: a mesh and partial residency both JOIN the fused
    driver — no fall-back warning, trajectories at the usual tolerance
    vs their per-iteration drivers, same-program replays bitwise."""
    import warnings as _warnings

    from tpu_sgd import data_mesh

    X, y = _data(rng, n=512, d=8)
    cfg = _cfg("sliced")

    # partial residency: mixed resident/transferred windows, one fused
    # program (make_resident_window_superstep)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        wl, hl = _stream(cfg, X, y, resident_rows=300)
        wf, hf = _stream(cfg, X, y, superstep_k=4, resident_rows=300)
    assert len(hf) == len(hl) == 10
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wl), **TOL)
    wf2, _ = _stream(cfg, X, y, superstep_k=4, resident_rows=300)
    np.testing.assert_array_equal(np.asarray(wf), np.asarray(wf2))

    # mesh: the sharded superchunk feed (dp_superstep_fn)
    mesh = data_mesh()
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        wm1, hm1 = _stream(cfg, X, y, mesh=mesh)
        wm4, hm4 = _stream(cfg, X, y, mesh=mesh, superstep_k=4)
    assert len(hm4) == len(hm1) == 10
    np.testing.assert_allclose(np.asarray(wm4), np.asarray(wm1), **TOL)


def test_choose_superstep_amortizes_and_respects_budget():
    from tpu_sgd.plan import CostModel, choose_superstep

    cm = CostModel(dispatch_overhead_s=8e-4, superstep_dispatch_frac=0.05)
    # 2 ms/iter feed -> residual tax must drop below 0.1 ms -> K=8
    assert choose_superstep(5000, 16, 4, 2e-3, 1e9, cm) == 8
    # fat iterations: the tax is already below the target -> K=1
    assert choose_superstep(10**6, 1000, 4, 26.0, 1e9, cm) == 1
    # no staging room for a double-buffered 2-batch superchunk -> K=1
    assert choose_superstep(5000, 16, 4, 2e-3, 100.0, cm) == 1
    # the budget clamp binds before the amortization target
    batch = 5000 * (16 * 4 + 5.0)
    assert choose_superstep(5000, 16, 4, 2e-3, 2 * batch * 3, cm) == 3


def test_plan_applies_superstep_and_user_knob_wins():
    from tpu_sgd.plan import Plan

    opt = GradientDescent()
    Plan("host_streamed", "t", superstep=8).apply(opt)
    assert opt.superstep == 8 and opt.host_streaming
    # a non-streamed plan resets the plan-owned knob
    Plan("resident_stock", "t").apply(opt)
    assert opt.superstep == 1
    # user-set superstep survives planning
    opt2 = GradientDescent().set_superstep(16)
    Plan("host_streamed", "t", superstep=4).apply(opt2)
    assert opt2.superstep == 16


def test_planner_picks_superstep_for_small_dim_streams():
    from tpu_sgd.plan import plan

    p = plan(200_000, 16, itemsize=4, sampling="indexed",
             mini_batch_fraction=0.02, num_iterations=1000,
             free_hbm=8e6, host_resident_ok=True)
    assert p.schedule == "host_streamed"
    assert p.superstep > 1
    assert p.estimates["superstep"] == p.superstep
