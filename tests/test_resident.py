"""Device-resident training driver tests (ISSUE 6): the whole run as
ONE ``lax.while_loop`` dispatch, host only at cadence.

Contracts pinned here (and documented in ``optimize/resident_driver.py``):

* The resident driver's trajectory, loss history, listener events, and
  checkpoint bytes are BITWISE the K-superstep driver's in all three
  sampling modes — the while_loop wraps the SAME fused scan, and the
  ring ys replay through the same ``_replay_fused_steps``.
* A converged-or-budget-exhausted run is exactly ONE program dispatch
  (``assert_dispatch_count``), and the whole run compiles exactly ONE
  program (``assert_compile_count``) — tails, resumes, and cadence
  windows included.
* Convergence is detected at the TRUE iteration even mid-window;
  ring-buffer tails (N not dividing C·K) replay without padding
  artifacts; stop signals land within one cadence window (C·K
  iterations) at a window-boundary checkpoint.
"""

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


def _opt(mode="sliced", iters=22, k=4, c=0, seed=7, listener=True):
    from tpu_sgd.utils.events import SGDListener

    o = (GradientDescent()
         .set_num_iterations(iters).set_step_size(0.1)
         .set_mini_batch_fraction(0.5).set_sampling(mode)
         .set_convergence_tol(0.0).set_seed(seed)
         .set_superstep(k))
    if listener:
        o.set_listener(SGDListener())
    if c:
        o.set_residency(c)
    return o


def _stream(cfg, X, y, **kw):
    return optimize_host_streamed(
        LeastSquaresGradient(), SimpleUpdater(), cfg, X, y,
        np.zeros(X.shape[1], np.float32), **kw)


# ---- bitwise replay contract vs the K-superstep driver ---------------------

@pytest.mark.parametrize("mode", MODES)
def test_stepwise_resident_bitwise_vs_superstep_all_modes(rng, mode):
    """THE trajectory contract: resident runs are bitwise-equal to the
    superstep driver's (weights AND history) — the while_loop wraps the
    same fused scan, in every sampling mode."""
    X, y = _data(rng)
    w0 = np.zeros(12, np.float32)
    wS, hS = _opt(mode, c=0).optimize_with_history((X, y), w0)
    wR, hR = _opt(mode, c=2).optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wS))
    np.testing.assert_array_equal(hR, hS)


def test_streamed_full_batch_resident_bitwise_vs_superstep(rng):
    """Streamed full-batch feed: the one-time transfer plus the
    resident while_loop reproduce the superstep driver bitwise."""
    X, y = _data(rng, n=600, d=8)
    cfg = SGDConfig(step_size=0.1, num_iterations=22,
                    mini_batch_fraction=1.0, convergence_tol=0.0,
                    sampling="bernoulli", seed=7)
    wS, hS = _stream(cfg, X, y, superstep_k=4)
    wR, hR = _stream(cfg, X, y, superstep_k=4, resident_cadence=2)
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wS))
    np.testing.assert_array_equal(hR, hS)


def test_streamed_resident_slab_same_windows_and_replay_bitwise(rng):
    """Fully-resident slab feed (resident_rows = n, sliced): the
    precomputed start sequence reproduces the host sampler's windows
    exactly (same history LENGTH and sampled sequence, weights at
    reassociation tolerance vs the cond-structured window superstep —
    the documented cross-program caveat), and resident replays are
    bitwise."""
    X, y = _data(rng, n=800, d=10)
    n = X.shape[0]
    cfg = SGDConfig(step_size=0.1, num_iterations=22,
                    mini_batch_fraction=0.25, convergence_tol=0.0,
                    sampling="sliced", seed=7)
    wS, hS = _stream(cfg, X, y, resident_rows=n, superstep_k=4)
    wR, hR = _stream(cfg, X, y, resident_rows=n, superstep_k=4,
                     resident_cadence=2)
    assert len(hR) == len(hS) == 22
    np.testing.assert_allclose(np.asarray(wR), np.asarray(wS), **TOL)
    np.testing.assert_allclose(hR, hS, **TOL)
    wR2, hR2 = _stream(cfg, X, y, resident_rows=n, superstep_k=4,
                       resident_cadence=2)
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wR2))
    np.testing.assert_array_equal(hR, hR2)


def test_resident_listener_events_match_superstep(rng):
    """Per-iteration listener events fire from the window replays — in
    order, with the exact losses, iterations 1..N."""
    X, y = _data(rng, n=500, d=8)

    class Rec:
        def __init__(self):
            self.events = []
            self.ended = None

        def on_run_start(self, cfg):
            pass

        def on_iteration(self, e):
            self.events.append(e)

        def on_run_end(self, e):
            self.ended = e

    def run(c):
        rec = Rec()
        o = _opt("indexed", iters=10, k=4, c=c, listener=False)
        o.set_listener(rec)
        w, h = o.optimize_with_history((X, y), np.zeros(8, np.float32))
        return w, h, rec

    wS, hS, recS = run(0)
    wR, hR, recR = run(2)
    assert [e.iteration for e in recR.events] == list(range(1, 11))
    np.testing.assert_array_equal(
        np.asarray([e.loss for e in recR.events], np.float32),
        np.asarray([e.loss for e in recS.events], np.float32))
    assert recR.ended is not None and recR.ended.num_iterations == 10


def test_resident_checkpoint_cadence_matches_superstep(rng, tmp_path):
    """Cadence saves fire inside the window callback on the legacy
    iterations with the exact iteration state — same files, same
    restored bytes as the superstep driver."""
    import glob

    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=400, d=6)

    def run(c, sub):
        o = _opt("sliced", iters=10, k=4, c=c, listener=False)
        o.set_checkpoint(CheckpointManager(str(tmp_path / sub),
                                           keep=100), every=3)
        o.optimize_with_history((X, y), np.zeros(6, np.float32))
        return sorted(int(f[-12:-4]) for f in
                      glob.glob(str(tmp_path / sub / "ckpt_*.npz")))

    assert run(0, "superstep") == run(2, "resident") == [3, 6, 9, 10]
    sS = CheckpointManager(str(tmp_path / "superstep")).restore()
    sR = CheckpointManager(str(tmp_path / "resident")).restore()
    np.testing.assert_array_equal(sR["weights"], sS["weights"])
    np.testing.assert_array_equal(sR["loss_history"], sS["loss_history"])


# ---- convergence at the true iteration inside a window ---------------------

def test_resident_convergence_detected_at_true_iteration(rng):
    """The device predicate exits the loop; the host replay pins the
    TRUE converged iteration inside the cadence window — history ends
    exactly where the superstep driver's does, mid-window."""
    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)

    def run(c):
        o = (GradientDescent().set_num_iterations(400)
             .set_step_size(0.05).set_mini_batch_fraction(0.5)
             .set_sampling("sliced").set_convergence_tol(0.01)
             .set_seed(7).set_superstep(8))
        from tpu_sgd.utils.events import SGDListener

        o.set_listener(SGDListener())
        if c:
            o.set_residency(c)
        return o.optimize_with_history((X, y), w0)

    wS, hS = run(0)
    wR, hR = run(4)
    assert len(hR) == len(hS)
    assert len(hR) % (4 * 8) != 0  # genuinely mid-window
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wS))
    np.testing.assert_array_equal(hR, hS)


# ---- ring-buffer tail ------------------------------------------------------

@pytest.mark.parametrize("iters", (7, 19, 23, 37))
def test_resident_ring_tail_when_n_not_dividing_window(rng, iters):
    """N not dividing C·K: the partial tail window (and a padded tail
    superstep inside it) replays from the returned carry without
    length or value artifacts — bitwise vs the superstep driver."""
    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)
    wS, hS = _opt("indexed", iters=iters, k=4, c=0) \
        .optimize_with_history((X, y), w0)
    wR, hR = _opt("indexed", iters=iters, k=4, c=3) \
        .optimize_with_history((X, y), w0)
    assert len(hR) == iters
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wS))
    np.testing.assert_array_equal(hR, hS)


# ---- one dispatch / one program --------------------------------------------

def test_resident_run_is_one_dispatch(rng):
    """THE structural claim: a whole resident run — cadence windows,
    ring writes, tail — is ONE program launch, where the matched
    superstep driver pays one per superstep.  Counted with the runtime
    twin (assert_dispatch_count), not timed."""
    import jax.numpy as jnp

    from tpu_sgd.analysis import assert_dispatch_count
    from tpu_sgd.optimize.resident_driver import ResidentBookkeeper

    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)

    o = _opt("sliced", iters=32, k=4, c=2)
    o.optimize_with_history((X, y), w0)  # warm the compile
    key = ("resident", o.gradient, o.updater, o.config.structure(), 4, 2)
    loop = o._run_cache[key]
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    hooks = ResidentBookkeeper(o.config, 4, 2, losses=[], reg_val=0.0,
                               start_iter=1)
    with assert_dispatch_count(1):
        loop.run(jnp.asarray(w0), 0.0, 1, (o._hyper(), Xd, yd), hooks)
    assert len(hooks.losses) == 32 and hooks.windows_fired == 4


def test_resident_dispatches_independent_of_run_length(rng):
    """Public-API twin of the one-dispatch claim: doubling the
    iteration budget adds ZERO launches on the resident path (more
    cadence windows are host callbacks, not dispatches), while the
    superstep driver pays at least one launch per extra superstep."""
    from tpu_sgd.analysis import count_dispatches

    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)

    def count(iters, c):
        o = _opt("sliced", iters=iters, k=4, c=c)
        o.optimize_with_history((X, y), w0)  # warm the compiles
        with count_dispatches() as got:
            o.optimize_with_history((X, y), w0)
        return got["n"]

    assert count(64, c=2) == count(32, c=2)
    extra_supersteps = (64 - 32) // 4
    assert count(64, c=0) - count(32, c=0) >= extra_supersteps


def test_resident_loop_compiles_one_program(rng):
    """assert_compile_count on the while-loop body: a full run
    (multiple windows + tail) traces and compiles exactly one XLA
    program, and a re-run compiles nothing new."""
    from tpu_sgd.analysis import assert_compile_count

    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)
    o = _opt("bernoulli", iters=23, k=4, c=2)
    o.optimize_with_history((X, y), w0)
    key = ("resident", o.gradient, o.updater, o.config.structure(), 4, 2)
    loop = o._run_cache[key]
    assert loop.compile_cache_size() == 1
    with assert_compile_count(0, of=loop.compile_cache_size):
        o.optimize_with_history((X, y), w0)


def test_resident_warmed_window_no_host_sync(rng):
    """graftlint v2's runtime twin on the real driver: a warmed resident
    run forces host syncs proportional to CADENCE WINDOWS, never to
    iterations — one tiny int32 scalar per window (the ordered
    callback's ``win_start``) plus the three documented end-of-run
    boundary scalars, every one of them shape-() (no bulk fetch rides
    along).  Doubling the iteration budget at fixed cadence doubles
    windows, not per-iteration syncs."""
    import jax.numpy as jnp

    from tpu_sgd.analysis import assert_no_host_sync
    from tpu_sgd.optimize.resident_driver import ResidentBookkeeper

    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)

    def run_counted(iters):
        o = _opt("sliced", iters=iters, k=4, c=2)
        o.optimize_with_history((X, y), w0)  # warm the compile
        key = ("resident", o.gradient, o.updater, o.config.structure(), 4, 2)
        loop = o._run_cache[key]
        hooks = ResidentBookkeeper(o.config, 4, 2, losses=[],
                                   reg_val=0.0, start_iter=1)
        windows = iters // (4 * 2)
        with assert_no_host_sync(allow=windows + 3) as counter:
            loop.run(jnp.asarray(w0), 0.0, 1,
                     (o._hyper(), jnp.asarray(X), jnp.asarray(y)), hooks)
        assert counter["n"] == windows + 3
        assert all(shape == () for shape, _ in counter["shapes"])
        return counter["n"]

    assert run_counted(64) - run_counted(32) == (64 - 32) // (4 * 2)


def test_resident_warmed_sync_pin_holds_with_tracing_on(rng):
    """ISSUE 8: the windows+3 pin is not a tracing-off artifact — with
    span tracing ENABLED (live sink, spans emitted from the callback
    thread and the driver) the warmed resident run still forces exactly
    windows+3 shape-() syncs: the span machinery reuses the window's
    one win_start fetch (``i0_host``) instead of fetching twice, and
    span timestamps never block_until_ready (ADVICE.md "Span
    timestamps are attribution, not truth")."""
    import jax.numpy as jnp

    from tpu_sgd.analysis import assert_no_host_sync
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing
    from tpu_sgd.optimize.resident_driver import ResidentBookkeeper

    X, y = _data(rng, n=400, d=6)
    w0 = np.zeros(6, np.float32)
    iters, windows = 64, 64 // (4 * 2)
    o = _opt("sliced", iters=iters, k=4, c=2)
    o.optimize_with_history((X, y), w0)  # warm the compile
    key = ("resident", o.gradient, o.updater, o.config.structure(), 4, 2)
    loop = o._run_cache[key]

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, payload))

    sink = Sink()
    hooks = ResidentBookkeeper(o.config, 4, 2, losses=[],
                               reg_val=0.0, start_iter=1)
    enable_tracing(sink)
    try:
        with assert_no_host_sync(allow=windows + 3) as counter:
            loop.run(jnp.asarray(w0), 0.0, 1,
                     (o._hyper(), jnp.asarray(X), jnp.asarray(y)), hooks)
    finally:
        disable_tracing()
    assert counter["n"] == windows + 3
    assert all(shape == () for shape, _ in counter["shapes"])
    # tracing really ran: one window span per cadence window, one
    # dispatch span, every win_start attr from the SHARED fetch
    wins = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.window"]
    assert [w["i0"] for w in wins] == [1 + 8 * i for i in range(windows)]
    assert sum(1 for k, p in sink.records if k == "trace_span"
               and p["name"] == "train.resident_dispatch") == 1


# ---- stop signal / preemption ----------------------------------------------

def test_resident_stop_latency_bounded_by_cadence_window(rng, tmp_path):
    """A stop requested before the run begins is honored at the FIRST
    cadence window — preemption latency is bounded by C·K iterations,
    the boundary iteration is checkpointed exactly, and a resumed run
    finishes bitwise."""
    from tpu_sgd.reliability.supervisor import TrainingPreempted
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    K, C = 4, 2
    wRef, hRef = _opt("sliced", iters=24, k=K, c=C) \
        .optimize_with_history((X, y), w0)

    o = _opt("sliced", iters=24, k=K, c=C, listener=False)
    o.set_checkpoint(CheckpointManager(str(tmp_path)), every=100)
    o.set_stop_signal(lambda: True)
    with pytest.raises(TrainingPreempted) as ei:
        o.optimize_with_history((X, y), w0)
    assert ei.value.iteration == C * K  # first window boundary
    assert CheckpointManager(str(tmp_path)).latest_version() == C * K
    o.set_stop_signal(None)
    wR, hR = o.optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wRef))
    np.testing.assert_array_equal(hR, hRef)


@pytest.mark.parametrize("mode", MODES)
def test_resident_preempt_resume_bitwise_all_modes(rng, mode, tmp_path):
    """Supervisor-style mid-run preempt: stop at the second window,
    resume (off the original window grid), finish bitwise."""
    from tpu_sgd.reliability.supervisor import TrainingPreempted
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    wRef, hRef = _opt(mode, iters=30, k=4, c=2) \
        .optimize_with_history((X, y), w0)

    class StopSecond:
        def __init__(self):
            self.polls = 0

        def __call__(self):
            self.polls += 1
            return self.polls == 2

    o = _opt(mode, iters=30, k=4, c=2, listener=False)
    o.set_checkpoint(CheckpointManager(str(tmp_path / mode)), every=100)
    o.set_stop_signal(StopSecond())
    with pytest.raises(TrainingPreempted) as ei:
        o.optimize_with_history((X, y), w0)
    assert ei.value.iteration == 16  # second C*K window boundary
    o.set_stop_signal(None)
    wR, hR = o.optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(wR), np.asarray(wRef))
    np.testing.assert_array_equal(hR, hRef)


# ---- reliability: io.resident_callback failpoint ---------------------------

def test_resident_callback_failpoint_heals_via_retry(rng):
    """An injected fault in the window callback heals through the
    ingest RetryPolicy inside the callback (before any bookkeeping
    mutates) — healed runs are bitwise."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import FaultInjected, fail_nth
    from tpu_sgd.reliability.retry import RetryPolicy

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    wRef, hRef = _opt("indexed", iters=24, k=4, c=2) \
        .optimize_with_history((X, y), w0)

    o = _opt("indexed", iters=24, k=4, c=2)
    o.set_ingest_options(retry=RetryPolicy(max_attempts=3,
                                           base_backoff_s=0.0))
    with fp.inject_faults({"io.resident_callback": fail_nth(2)}):
        w, h = o.optimize_with_history((X, y), w0)
        assert fp.triggers("io.resident_callback") == 1
    np.testing.assert_array_equal(np.asarray(w), np.asarray(wRef))
    np.testing.assert_array_equal(h, hRef)

    # without a retry policy the fault is stashed at the FFI boundary
    # and re-raised host-side with its true class — never an opaque
    # XlaRuntimeError — so the supervisor's retry classifier sees it
    with fp.inject_faults({"io.resident_callback": fail_nth(1)}):
        with pytest.raises(FaultInjected):
            _opt("indexed", iters=24, k=4, c=2) \
                .optimize_with_history((X, y), w0)


def test_resident_crash_resume_bitwise_via_supervisor(rng, tmp_path):
    """Exhausted callback retries crash the run with the original
    exception; the TrainingSupervisor resumes from the cadence
    checkpoint and the finished run is bitwise vs fault-free."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import fail_nth
    from tpu_sgd.reliability.retry import RetryPolicy
    from tpu_sgd.reliability.supervisor import TrainingSupervisor
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y = _data(rng, n=512, d=8)
    w0 = np.zeros(8, np.float32)
    wRef, hRef = _opt("sliced", iters=32, k=4, c=2) \
        .optimize_with_history((X, y), w0)

    sup = TrainingSupervisor(
        _opt("sliced", iters=32, k=4, c=2, listener=False),
        checkpoint_manager=CheckpointManager(str(tmp_path)),
        checkpoint_every=5,
        retry=RetryPolicy(max_attempts=4, base_backoff_s=0.0),
        install_signal_handlers=False)
    # no ingest retry: the 2nd window's callback fault crashes the run;
    # the supervisor restarts and the resume replays from iteration 5's
    # checkpoint — OFF the original window grid (window regrouping)
    with fp.inject_faults({"io.resident_callback": fail_nth(2)}):
        res = sup.run((X, y), w0)
    assert res.completed and res.attempts == 2
    np.testing.assert_array_equal(np.asarray(res.weights),
                                  np.asarray(wRef))
    np.testing.assert_array_equal(res.loss_history, hRef)


# ---- knobs / planner -------------------------------------------------------

def test_set_residency_validates():
    with pytest.raises(ValueError, match="cadence 1"):
        GradientDescent().set_residency(1)
    with pytest.raises(ValueError, match="cadence"):
        GradientDescent().set_residency(-2)
    assert GradientDescent().set_residency(4).resident_cadence == 4
    assert GradientDescent().set_residency(0).resident_cadence == 0


def test_residency_without_superstep_warns_and_falls_back(rng):
    X, y = _data(rng, n=256, d=6)
    o = _opt("sliced", iters=6, k=1, c=2)
    with pytest.warns(RuntimeWarning, match="fused superstep executor"):
        w, h = o.optimize_with_history((X, y), np.zeros(6, np.float32))
    assert len(h) == 6


def test_streamed_host_sampled_residency_warns_and_falls_back(rng):
    X, y = _data(rng, n=512, d=8)
    cfg = SGDConfig(step_size=0.1, num_iterations=8,
                    mini_batch_fraction=0.25, convergence_tol=0.0,
                    sampling="indexed", seed=7)
    with pytest.warns(RuntimeWarning, match="host hop IS the data"):
        w, h = _stream(cfg, X, y, superstep_k=4, resident_cadence=2)
    assert len(h) == 8


def test_choose_residency_crossover_rule():
    from tpu_sgd.plan import choose_residency

    # window must hold >= 2 supersteps: K=4 within checkpoint_every=10
    # fits C=2; checkpoint_every=7 fits only one superstep -> 0
    assert choose_residency(4, checkpoint_every=10) == 2
    assert choose_residency(4, checkpoint_every=7) == 0
    # no fused executor, no residency
    assert choose_residency(1, checkpoint_every=100) == 0
    # the tighter of checkpoint cadence and preemption budget wins
    assert choose_residency(4, checkpoint_every=100,
                            preempt_latency_iters=9) == 2
    # cap bounds the ring
    assert choose_residency(2, checkpoint_every=10 ** 6, cap=16) == 16


def test_plan_applies_residency_and_user_knob_wins():
    from tpu_sgd.plan import Plan

    opt = GradientDescent()
    Plan("host_streamed", "t", superstep=8, residency=4).apply(opt)
    assert opt.resident_cadence == 4 and opt.superstep == 8
    Plan("resident_stock", "t").apply(opt)
    assert opt.resident_cadence == 0
    opt2 = GradientDescent().set_residency(6)
    Plan("host_streamed", "t", superstep=8, residency=2).apply(opt2)
    assert opt2.resident_cadence == 6


def test_planner_picks_residency_for_full_batch_streams():
    from tpu_sgd.plan import plan

    p = plan(200_000, 16, itemsize=4, sampling="bernoulli",
             mini_batch_fraction=1.0, num_iterations=1000,
             free_hbm=8e6, host_resident_ok=True, checkpoint_every=64)
    assert p.schedule == "host_streamed"
    assert p.superstep > 1
    assert p.residency >= 2
    assert p.estimates["residency"] == p.residency
    # sampled feeds stay on the superstep driver
    p2 = plan(200_000, 16, itemsize=4, sampling="indexed",
              mini_batch_fraction=0.02, num_iterations=1000,
              free_hbm=8e6, host_resident_ok=True, checkpoint_every=64)
    assert p2.residency == 0


# ---- runtime twin: dispatch counting ---------------------------------------

def test_count_dispatches_counts_warm_jit_calls():
    import jax
    import jax.numpy as jnp

    from tpu_sgd.analysis import (DispatchCountError,
                                  assert_dispatch_count,
                                  count_dispatches)

    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.ones(4)
    f(x)  # warm (fastpath installed — the hook must still see calls)
    with count_dispatches() as c:
        for _ in range(3):
            jax.block_until_ready(f(x))
    assert c["n"] == 3
    with pytest.raises(DispatchCountError, match="launched 2"):
        with assert_dispatch_count(1):
            f(x)
            jax.block_until_ready(f(x))
    with assert_dispatch_count(2, at_most=True):
        jax.block_until_ready(f(x))
    # restored: the fastpath works again after the region
    assert int(f(x)[0]) == 3
