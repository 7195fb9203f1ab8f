"""Execution-planner decision boundaries + train() auto-planning.

The planner (``tpu_sgd/plan.py``) is the DAGScheduler/``cache()`` analogue
(SURVEY.md §2 #16): ``train()`` with zero schedule flags must land on the
measured-best schedule.  These tests pin the decision boundaries with an
explicit ``free_hbm`` (the probe is environment-dependent) and then drive
the wired-up model layer end to end.
"""

import logging
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sgd.plan import (CostModel, Plan, SCHEDULES,  # noqa: F401
                          choose_block_rows, device_budget, plan, plan_for)


def test_plan_module_attribute_not_shadowed():
    """`import tpu_sgd.plan as m` must resolve to the MODULE: the package
    must not re-export the bare `plan` function under the same name
    (regression: `tpu_sgd.plan.plan(...)` raised AttributeError)."""
    import types

    import tpu_sgd
    import tpu_sgd.plan as m

    assert isinstance(tpu_sgd.plan, types.ModuleType)
    assert isinstance(m, types.ModuleType) and callable(m.plan)

GB = 1e9


# ---- pure decision boundaries --------------------------------------------

def test_resident_gram_for_big_least_squares_full_batch():
    """A full batch on one device reads the totals alone (PR 41): no
    prefix stack, so no block size."""
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             mini_batch_fraction=1.0, num_iterations=5000,
             free_hbm=12 * GB)
    assert p.schedule == "resident_gram"
    assert not p.aligned  # exact mode is the default
    assert p.estimates["stats_form"] == "totals" and p.block_rows is None
    assert p.estimates["build_amortize_iters"] < 5000
    assert "fits" in p.reason and "totals" in p.reason


def test_short_run_amortization_keeps_stock():
    """The one-time statistics build must pay for itself inside the run
    (VERDICT r3 #1: warn/avoid when build_amortize_iters > iterations).
    Re-derived in PR 41: the totals' build over 3M x 1000 bf16 is ~40 ms
    by the chip's own terms (a launch, one read of 6 GB and 6e12 operations
    at the measured bf16 rate) against 16.4 ms saved an iteration, so it
    pays from the third iteration on (it was 1.4 s and ~90 iterations by
    the remote attachment's)."""
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             mini_batch_fraction=1.0, num_iterations=2,
             free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert "amortize" in p.reason
    assert 2 < p.estimates["build_amortize_iters"] < 3
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             mini_batch_fraction=1.0, num_iterations=50,
             free_hbm=12 * GB)
    assert p.schedule == "resident_gram"


@pytest.mark.parametrize("n,iterations", [(100_000, 20), (10_000, 10_000)],
                         ids=["short", "tiny"])
def test_small_problem_keeps_stock(n, iterations):
    """Tiny datasets stay on the bitwise round-2 stock path.  Re-derived in
    PR 41: at 100,000 x 100 f32 the totals' build (1.4 ms: a launch, one
    read of 40 MB, 2e9 operations at HIGHEST) pays from iteration 23 on,
    so a run of 20 keeps stock (by the old 1.2 s no run did); at 10,000
    rows a stock iteration reads 8 MB, under the statistics' own
    per-iteration cost, and no length pays."""
    p = plan(n, 100, gram_able=True, num_iterations=iterations,
             free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert p.estimates["build_amortize_iters"] > iterations


def test_non_least_squares_never_grams():
    p = plan(3_000_000, 1000, itemsize=2, gram_able=False,
             num_iterations=10_000, free_hbm=12 * GB)
    assert p.schedule == "resident_stock"


def test_bernoulli_sampling_is_honored():
    """The planner never changes the user's sampling semantics: bernoulli
    mini-batches disqualify gram (sliced windows only)."""
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             sampling="bernoulli", mini_batch_fraction=0.1,
             num_iterations=10_000, free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert "sampling" in p.reason


def test_sliced_sampling_qualifies_gram():
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             sampling="sliced", mini_batch_fraction=0.1,
             num_iterations=10_000, free_hbm=12 * GB)
    assert p.schedule == "resident_gram"


def test_beyond_hbm_least_squares_goes_virtual_gram():
    """The 10Mx1000 config-4 shape: rows exceed HBM, statistics fit —
    one streaming build pass, then zero-transfer iterations."""
    p = plan(10_000_000, 1000, itemsize=2, gram_able=True,
             sampling="sliced", mini_batch_fraction=0.1,
             num_iterations=1000, free_hbm=12 * GB)
    assert p.schedule == "streamed_virtual_gram"
    assert p.aligned  # virtual stats are aligned by construction...
    assert "ALIGNED" in p.reason  # ...and the plan says so loudly
    assert p.estimates["stack_bytes"] < 12 * GB


def test_beyond_hbm_non_gram_partial_residency():
    """Sliced non-LS (or bernoulli-excluded) data just beyond HBM keeps a
    resident prefix."""
    p = plan(10_000_000, 1000, itemsize=2, gram_able=False,
             sampling="sliced", mini_batch_fraction=0.1,
             num_iterations=1000, free_hbm=12 * GB)
    assert p.schedule == "partial_residency"
    assert p.resident_rows > 0
    assert p.estimates["resident_window_p"] >= 0.05


def test_beyond_hbm_bernoulli_streams():
    p = plan(10_000_000, 1000, itemsize=2, gram_able=False,
             sampling="bernoulli", mini_batch_fraction=0.1,
             num_iterations=1000, free_hbm=12 * GB)
    assert p.schedule == "host_streamed"


def test_beyond_hbm_meshed_goes_virtual_gram():
    """Virtual gram composes with the mesh (round 4): per-shard statistics
    streamed to each device — config 4's 8-way shape at 8x-beyond-HBM
    scale picks it."""
    p = plan(80_000_000, 1000, itemsize=2, gram_able=True,
             sampling="sliced", mini_batch_fraction=0.1,
             num_iterations=1000, n_devices=8, free_hbm=12 * GB)
    assert p.schedule == "streamed_virtual_gram"
    # non-gram data at the same scale still streams
    p2 = plan(80_000_000, 1000, itemsize=2, gram_able=False,
              sampling="sliced", mini_batch_fraction=0.1,
              num_iterations=1000, n_devices=8, free_hbm=12 * GB)
    assert p2.schedule == "host_streamed"


def test_mesh_divides_rows_for_fit():
    """8 devices hold 8x the rows: a dataset that streams on one chip is
    resident on the mesh."""
    one = plan(10_000_000, 1000, itemsize=2, gram_able=False,
               num_iterations=100, free_hbm=12 * GB)
    eight = plan(10_000_000, 1000, itemsize=2, gram_able=False,
                 num_iterations=100, n_devices=8, free_hbm=12 * GB)
    assert one.schedule == "host_streamed"
    assert eight.schedule == "resident_stock"


def test_device_committed_data_never_streams():
    p = plan(10_000_000, 1000, itemsize=2, gram_able=False,
             num_iterations=100, free_hbm=12 * GB,
             host_resident_ok=False)
    assert p.schedule == "resident_stock"
    assert "device-committed" in p.reason


def test_huge_d_disqualifies_gram():
    """Very wide features break the gram economics two ways (ops/gram.py
    module docs): beyond-HBM, no block size makes the O(d²) stack fit;
    resident, the per-iteration d² prefix matvec costs more than the row
    reads it replaces.  Both must fall back."""
    # 200 GB of rows, 40 GB per Gram matrix: nothing fits -> streams
    p = plan(1_000_000, 100_000, itemsize=2, gram_able=True,
             num_iterations=10_000, free_hbm=12 * GB)
    assert p.schedule == "host_streamed"
    # 0.4 GB of rows fit, but reading two (20k, 20k) prefix entries per
    # iteration exceeds the two-pass row traffic -> negative saving
    p = plan(10_000, 20_000, itemsize=2, gram_able=True,
             num_iterations=10_000, free_hbm=12 * GB)
    assert p.schedule == "resident_stock"


def test_force_overrides_with_warning():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # PR 41: the totals' build pays from the third iteration on
        p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
                 mini_batch_fraction=1.0, num_iterations=2,
                 free_hbm=12 * GB, force="resident_gram")
    assert p.schedule == "resident_gram"
    assert any("NET LOSS" in str(r.message) for r in rec)
    assert "forced by caller" in p.reason


def test_force_rejects_unknown_schedule():
    with pytest.raises(ValueError, match="unknown schedule"):
        plan(1000, 10, force="warp_drive")


def test_choose_block_rows_doubles_to_fit():
    # 1M x 1000: stack at B=4096 is ~(245)*4MB ~ 1GB; at a 0.2GB budget
    # the block must grow
    b_small = choose_block_rows(1_000_000, 1000, 0.2 * GB)
    b_big = choose_block_rows(1_000_000, 1000, 4 * GB)
    assert b_big == 4096
    assert b_small is not None and b_small > b_big
    assert choose_block_rows(1_000_000, 1000, 1e6) is None  # nothing fits


def test_estimates_are_recorded():
    p = plan(3_000_000, 1000, itemsize=2, gram_able=True,
             num_iterations=5000, free_hbm=12 * GB)
    for key in ("n", "d", "free_hbm", "stock_iter_s", "gram_iter_s",
                "gram_build_s", "build_amortize_iters", "fits_resident"):
        assert key in p.estimates, key


def test_device_budget_returns_positive():
    free, source = device_budget()
    assert free > 0
    assert source in ("memory_stats", "fallback")


# ---- plan_for probing -----------------------------------------------------

def test_plan_for_probes_optimizer(rng):
    from tpu_sgd import GradientDescent

    X = rng.normal(size=(512, 8)).astype(np.float32)
    y = rng.normal(size=(512,)).astype(np.float32)
    opt = GradientDescent()
    p = plan_for(opt, X, y)
    assert p is not None and p.schedule == "resident_stock"
    p.apply(opt)
    assert opt.last_plan is p


def test_plan_for_skips_sparse_and_non_gd(rng):
    from tpu_sgd import GradientDescent, LBFGS
    from tpu_sgd.ops.sparse import sparse_data

    Xs, ys, _ = sparse_data(64, 32, nnz_per_row=4, seed=0)
    assert plan_for(GradientDescent(), Xs, ys) is None
    X = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    assert plan_for(LBFGS(), X, y) is None


def test_apply_clears_previous_schedule(rng):
    from tpu_sgd import GradientDescent

    opt = GradientDescent().set_host_streaming(True, resident_rows=100)
    Plan("resident_stock", "test").apply(opt)
    assert not opt.host_streaming and opt.streaming_resident_rows == 0
    Plan("resident_gram", "test", block_rows=64).apply(opt)
    assert opt.sufficient_stats and opt.gram_block_rows == 64
    Plan("streamed_virtual_gram", "test", block_rows=32,
         aligned=True).apply(opt)
    assert opt.streamed_stats and not opt.sufficient_stats


def test_apply_always_resets_plan_owned_knobs(rng):
    """A previous dataset's gram knobs (block size, streamed-build chunk
    cap, aligned mode) must not leak into the next plan's build — the
    gram identity caches key on them, so stale values silently rebuild
    with the wrong geometry (ADVICE r4)."""
    from tpu_sgd import GradientDescent
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS

    opt = GradientDescent()
    Plan("streamed_virtual_gram", "small-data plan", block_rows=32,
         batch_rows=64, aligned=True).apply(opt)
    assert opt.gram_batch_rows == 64
    assert opt.gram_block_rows == 32 and opt.gram_aligned
    Plan("resident_stock", "new-data plan").apply(opt)
    assert opt.gram_batch_rows is None
    assert opt.gram_block_rows == DEFAULT_BLOCK_ROWS
    assert not opt.gram_aligned


def test_apply_preserves_user_set_gram_knobs(rng):
    """Knob fields the USER set via set_gram_options survive auto-
    planning: a tight-device batch_rows cap must not be clobbered by a
    plan that carries none (plans only own what the user didn't set)."""
    from tpu_sgd import GradientDescent

    opt = GradientDescent().set_gram_options(batch_rows=256)
    Plan("resident_gram", "auto plan", block_rows=4096).apply(opt)
    assert opt.gram_batch_rows == 256  # user knob preserved
    assert opt.gram_block_rows == 4096  # plan-owned field applied
    opt2 = GradientDescent().set_gram_options(block_rows=64, aligned=True)
    Plan("streamed_virtual_gram", "auto plan", block_rows=4096,
         batch_rows=8192, aligned=False).apply(opt2)
    assert opt2.gram_block_rows == 64 and opt2.gram_aligned
    assert opt2.gram_batch_rows == 8192


def test_knob_setter_keeps_replanning_alive(rng, caplog):
    """set_gram_options is a KNOB, not a schedule choice: after an auto-
    planned run, tweaking a knob must invalidate the plan cache (so the
    next run re-plans, honoring the knob) WITHOUT tripping the manual
    gate that disables planning — a plan-set schedule flag must never
    masquerade as user-set (code-review r5)."""
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(2048, 16)).astype(np.float32)
    w = rng.uniform(-1, 1, 16).astype(np.float32)
    y = (X @ w + 0.05 * rng.normal(size=2048)).astype(np.float32)
    alg = LinearRegressionWithSGD()
    alg.optimizer.set_step_size(1.0)
    alg.run((X, y))
    assert alg.optimizer.last_plan is not None
    alg.optimizer.set_gram_options(batch_rows=256)
    assert alg.optimizer._plan_key is None  # cache invalidated...
    assert alg.optimizer.last_plan is not None  # ...but not the gate
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        alg.run((X, y))
    # re-planning DID run (a fresh plan: line logged, key repopulated)
    assert any(r.message.startswith("plan: ") for r in caplog.records)
    assert alg.optimizer._plan_key is not None
    assert alg.optimizer.gram_batch_rows == 256  # user knob survived


def test_force_resident_beyond_hbm_warns():
    """Forcing a resident_* schedule onto beyond-HBM data must warn that
    the slab does not fit — the no-feasible-block guard alone misses this
    case because the streamed builder DID find a block size (ADVICE r4)."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p = plan(10_000_000, 1000, itemsize=2, gram_able=True,
                 mini_batch_fraction=1.0, num_iterations=100_000,
                 free_hbm=12 * GB, force="resident_gram")
    assert p.schedule == "resident_gram"
    assert any("does not fit" in str(r.message) for r in rec)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p = plan(10_000_000, 1000, itemsize=2, gram_able=False,
                 mini_batch_fraction=1.0, num_iterations=100,
                 free_hbm=12 * GB, force="resident_stock")
    assert p.schedule == "resident_stock"
    assert any("does not fit" in str(r.message) for r in rec)


# ---- wired into the model layer ------------------------------------------

def test_train_zero_flags_plans_and_logs(rng, caplog):
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(2048, 16)).astype(np.float32)
    w = rng.uniform(-1, 1, 16).astype(np.float32)
    y = (X @ w + 0.05 * rng.normal(size=2048)).astype(np.float32)
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        model = LinearRegressionWithSGD.train((X, y), num_iterations=100,
                                              step_size=1.0)
    assert any(r.message.startswith("plan: ") for r in caplog.records)
    err = float(np.linalg.norm(np.asarray(model.weights) - w))
    assert err < 0.1


def test_train_schedule_off_keeps_legacy_path(rng):
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = rng.normal(size=(256,)).astype(np.float32)
    alg = LinearRegressionWithSGD(0.2, 10)
    alg.set_schedule("off")
    alg.run((X, y))
    assert alg.optimizer.last_plan is None


def test_train_manual_flags_win_over_auto(rng):
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(2048, 8)).astype(np.float32)
    w = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ w).astype(np.float32)
    alg = LinearRegressionWithSGD(1.0, 100)
    alg.optimizer.set_sufficient_stats(True)
    model = alg.run((X, y))
    # the planner did not run (it would have cleared/chosen itself)
    assert alg.optimizer.last_plan is None
    assert alg.optimizer.sufficient_stats
    assert np.linalg.norm(np.asarray(model.weights) - w) < 0.1


def test_forced_streamed_virtual_gram_trains(rng):
    """schedule='streamed_virtual_gram' exercises set_streamed_stats end
    to end on a small dataset: build from host rows, iterate from virtual
    statistics, converge."""
    from tpu_sgd import LinearRegressionWithSGD

    n, d = 4096, 12
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=n)).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # net-loss warn ok
        model = LinearRegressionWithSGD.train(
            (X, y), num_iterations=60, step_size=0.3,
            mini_batch_fraction=0.25, sampling="sliced",
            schedule="streamed_virtual_gram",
        )
    assert np.linalg.norm(np.asarray(model.weights) - w) < 0.1


def test_forced_schedule_validates_name():
    from tpu_sgd import LinearRegressionWithSGD

    with pytest.raises(ValueError, match="schedule must be one of"):
        LinearRegressionWithSGD.train(
            (np.zeros((4, 2), np.float32), np.zeros(4, np.float32)),
            schedule="warp_drive",
        )


def test_set_streamed_stats_guards(rng):
    from tpu_sgd import GradientDescent
    from tpu_sgd.ops.gradients import LogisticGradient

    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = rng.normal(size=(256,)).astype(np.float32)
    w0 = jnp.zeros((8,))
    with pytest.raises(NotImplementedError, match="least squares"):
        GradientDescent(LogisticGradient()).set_streamed_stats(True) \
            .optimize((X, np.abs(np.sign(y))), w0)
    from tpu_sgd import make_mesh

    with pytest.raises(NotImplementedError, match="1-D 'data' mesh"):
        GradientDescent().set_streamed_stats(True) \
            .set_mesh(make_mesh(n_data=4, n_model=2)) \
            .optimize((X, y), w0)
    with pytest.raises(ValueError, match="alternative"):
        GradientDescent().set_streamed_stats(True) \
            .set_host_streaming(True).optimize((X, y), w0)
    with pytest.raises(NotImplementedError, match="sliced"):
        GradientDescent().set_streamed_stats(True) \
            .set_mini_batch_fraction(0.5).optimize((X, y), w0)


def test_streamed_stats_matches_manual_virtual_run(rng):
    """set_streamed_stats must reproduce the manual build_streamed +
    GramData-input flow exactly (same build, same aligned windows)."""
    from tpu_sgd import GradientDescent, SimpleUpdater
    from tpu_sgd.ops.gram import GramLeastSquaresGradient

    n, d = 2048, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ w + 0.05 * rng.normal(size=n)).astype(np.float32)

    def mk():
        return (GradientDescent(updater=SimpleUpdater())
                .set_step_size(0.3).set_num_iterations(25)
                .set_mini_batch_fraction(0.25).set_sampling("sliced")
                .set_convergence_tol(0.0).set_seed(5))

    opt1 = mk().set_streamed_stats(True, block_rows=256)
    w1, h1 = opt1.optimize_with_history((X, y), jnp.zeros((d,)))

    g = GramLeastSquaresGradient.build_streamed(X, y, block_rows=256)
    opt2 = mk()
    opt2.set_gradient(g)
    w2, h2 = opt2.optimize_with_history(
        (g.data, y[:g.data.shape[0]]), jnp.zeros((d,)))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-6, atol=1e-7)


def test_schedule_names_stable():
    """The public schedule vocabulary (docs, train(schedule=...), bench)
    must not drift silently."""
    assert SCHEDULES == ("resident_stock", "resident_gram",
                         "partial_residency", "host_streamed",
                         "streamed_virtual_gram")


def test_gram_options_rebuild_on_change(rng):
    """Changing block size between runs on the SAME arrays must rebuild
    (the identity cache keys on the options too)."""
    from tpu_sgd import GradientDescent

    X = rng.normal(size=(1024, 8)).astype(np.float32)
    w = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ w).astype(np.float32)
    # sliced windows: the prefix form (a full batch takes the totals, which
    # have no block size and are built anew every fit: PR 41)
    opt = (GradientDescent().set_num_iterations(5)
           .set_mini_batch_fraction(0.5).set_sampling("sliced")
           .set_sufficient_stats(True).set_gram_options(block_rows=128))
    opt.optimize((X, y), jnp.zeros((8,)))
    g1 = opt._gram_entry[2]
    assert g1.data.block_rows == 128
    opt.set_gram_options(block_rows=256)
    opt.optimize((X, y), jnp.zeros((8,)))
    g2 = opt._gram_entry[2]
    assert g2 is not g1 and g2.data.block_rows == 256


def test_second_run_replans_on_new_dataset(rng, caplog):
    """Planner-set flags must not masquerade as manual flags: a second
    run() on the same algorithm re-plans for the new dataset instead of
    reusing the stale schedule (review r4 finding)."""
    from tpu_sgd import LinearRegressionWithSGD

    X1 = rng.normal(size=(256, 8)).astype(np.float32)
    y1 = rng.normal(size=(256,)).astype(np.float32)
    X2 = rng.normal(size=(512, 8)).astype(np.float32)
    y2 = rng.normal(size=(512,)).astype(np.float32)
    alg = LinearRegressionWithSGD(0.2, 5)
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        alg.run((X1, y1))
        first = alg.optimizer.last_plan
        alg.run((X2, y2))
        second = alg.optimizer.last_plan
    assert first is not None and second is not None and second is not first
    assert sum(r.message.startswith("plan: ")
               for r in caplog.records) == 2


# ---- quasi-Newton planning (round 4 extension) ---------------------------

class _ShapeOnly:
    """Shape/dtype carrier for boundary tests — np.shape reads .shape
    without materializing, so huge logical datasets cost nothing here."""

    def __init__(self, shape, dtype=np.float32):
        self.shape = shape
        self.dtype = np.dtype(dtype)


def test_plan_quasi_newton_boundaries():
    from tpu_sgd import LBFGS, plan_quasi_newton
    from tpu_sgd.ops.gradients import LogisticGradient

    y = None  # unused by the decision

    # big resident least squares: ~4 full passes/iter -> gram amortizes
    big = _ShapeOnly((3_000_000, 1000), np.float16)  # 2-byte rows
    p = plan_quasi_newton(LBFGS(), big, y, free_hbm=12 * GB)
    assert p.schedule == "resident_gram"
    assert p.block_rows is not None
    assert p.estimates["build_amortize_iters"] < 100

    # small data: build overhead dominates -> stock
    small = _ShapeOnly((10_000, 50))
    p = plan_quasi_newton(LBFGS(), small, y, free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert "amortize" in p.reason

    # beyond HBM: the statistics are the only viable schedule — one
    # streaming build pass, then O(d^2) full-batch evaluations
    huge = _ShapeOnly((100_000_000, 1000), np.float16)
    p = plan_quasi_newton(LBFGS(), huge, y, free_hbm=12 * GB)
    assert p.schedule == "streamed_virtual_gram"
    assert p.block_rows is not None
    assert p.estimates["stack_bytes"] < 12 * GB

    # beyond HBM with an impossible stack (huge d): nothing fits
    huge_d = _ShapeOnly((1_000_000, 100_000), np.float16)
    p = plan_quasi_newton(LBFGS(), huge_d, y, free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert "no schedule fits" in p.reason

    # non-least-squares gradient, resident: stock full-batch passes
    p = plan_quasi_newton(LBFGS(LogisticGradient()), big, y,
                          free_hbm=12 * GB)
    assert p.schedule == "resident_stock"
    assert "no fixed-size statistics" in p.reason

    # non-least-squares gradient, beyond HBM: the chunked treeAggregate
    # CostFun (round 5, VERDICT r4 #1) — host_streamed with a chunk cap
    p = plan_quasi_newton(LBFGS(LogisticGradient()), huge, y,
                          free_hbm=12 * GB)
    assert p.schedule == "host_streamed"
    assert p.batch_rows is not None
    # two in-flight chunks fit in half the budget
    assert 2 * p.batch_rows * 1000 * 2 <= 12 * GB
    assert "treeAggregate" in p.reason

    # schedules outside the quasi-Newton menu still reject
    with pytest.raises(ValueError, match="does not exist behind"):
        plan_quasi_newton(LBFGS(), big, y, free_hbm=12 * GB,
                          force="partial_residency")

    # forcing gram on a short run warns
    opt = LBFGS(max_num_iterations=3)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p = plan_quasi_newton(opt, big, y, free_hbm=12 * GB,
                              force="resident_gram")
    assert p.schedule == "resident_gram"
    assert any("NET LOSS" in str(r.message) for r in rec)


def test_train_auto_plans_host_streamed_costfun(rng, caplog, monkeypatch):
    """Zero-flag quasi-Newton train() on beyond-HBM NON-least-squares
    data lands on the chunked-CostFun schedule and still converges — the
    reference's any-size-any-loss CostFun contract (VERDICT r4 #1)."""
    import tpu_sgd.plan as plan_mod
    from tpu_sgd.models import LogisticRegressionWithLBFGS

    monkeypatch.setattr(plan_mod, "device_budget",
                        lambda *a, **k: (8e3, "test"))  # 8 KB "HBM"
    X = rng.normal(size=(512, 8)).astype(np.float32)
    w = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        alg = LogisticRegressionWithLBFGS()
        model = alg.run((X, y))
    msgs = [r.message for r in caplog.records
            if r.message.startswith("plan: ")]
    assert msgs and "host_streamed" in msgs[0]
    assert alg.optimizer.host_streaming
    assert alg.optimizer.stream_batch_rows is not None
    acc = float((np.asarray(model.predict(X)) == y).mean())
    assert acc > 0.9


def test_stale_plan_flags_reset_on_unplannable_input(rng, monkeypatch):
    """A later run on an un-plannable input (BCOO) must not crash on the
    PREVIOUS plan's host_streaming flag — plan-owned flags reset when the
    planner has nothing to say (code-review r5)."""
    import tpu_sgd.plan as plan_mod
    from tpu_sgd.models import LogisticRegressionWithLBFGS
    from tpu_sgd.ops.sparse import sparse_data

    monkeypatch.setattr(plan_mod, "device_budget",
                        lambda *a, **k: (8e3, "test"))
    X = rng.normal(size=(512, 8)).astype(np.float32)
    w = rng.uniform(-1, 1, 8).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    alg = LogisticRegressionWithLBFGS(max_num_iterations=5)
    alg.run((X, y))
    assert alg.optimizer.host_streaming  # planner picked the CostFun
    Xs, ys, _ = sparse_data(64, 8, nnz_per_row=3, seed=0)
    ys = np.abs(np.sign(np.asarray(ys)))
    model = alg.run((Xs, ys))  # must not raise "needs dense rows"
    assert not alg.optimizer.host_streaming  # stale flag was reset
    assert model is not None


def test_force_gram_rejected_for_non_ls_gradient():
    """Forcing a statistics schedule onto a loss with no fixed-size
    statistics must raise a clear error naming the loss family, not warn
    about block sizes and silently run stock (code-review r5)."""
    from tpu_sgd import LBFGS, plan_quasi_newton
    from tpu_sgd.ops.gradients import LogisticGradient

    big = _ShapeOnly((3_000_000, 1000), np.float16)
    for force in ("resident_gram", "streamed_virtual_gram"):
        with pytest.raises(ValueError, match="LogisticGradient"):
            plan_quasi_newton(LBFGS(LogisticGradient()), big, None,
                              free_hbm=12 * GB, force=force)


def test_meshed_coercion_defers_device_commit(rng):
    """Meshed quasi-Newton inputs stay HOST arrays through coercion: a
    jnp.asarray there would stage the whole beyond-one-HBM matrix through
    the default device before sharding (code-review r5)."""
    import jax

    from tpu_sgd.optimize.lbfgs import _coerce_inputs

    X = rng.normal(size=(64, 4)).astype(np.float64)
    y = rng.integers(0, 2, 64)
    w0 = np.zeros(4, np.float32)
    Xc, yc, wc = _coerce_inputs(X, y, w0, defer_commit=True)
    assert isinstance(Xc, np.ndarray) and not isinstance(Xc, jax.Array)
    assert isinstance(yc, np.ndarray) and not isinstance(yc, jax.Array)
    assert yc.dtype == np.float32  # int labels still coerce
    assert isinstance(wc, jax.Array)
    # unmeshed coercion commits as before
    Xc2, _, _ = _coerce_inputs(X, y, w0)
    assert isinstance(Xc2, jax.Array)


def test_plan_quasi_newton_meshed_boundaries():
    """VERDICT r4 #5: the quasi-Newton planner divides the HBM budget by
    the data-shard count like the GD planner, and plans the per-shard
    statistics substitution."""
    from tpu_sgd import LBFGS, data_mesh, plan_quasi_newton
    from tpu_sgd.ops.gradients import LogisticGradient

    y = None
    mesh = data_mesh()  # 8-way

    # 8 devices hold 8x the rows: a dataset that must stream on one chip
    # is resident (and gram-able) on the mesh
    mid = _ShapeOnly((40_000_000, 1000), np.float16)  # ~80 GB total
    one = plan_quasi_newton(LBFGS(), mid, y, free_hbm=12 * GB)
    eight = plan_quasi_newton(LBFGS().set_mesh(mesh), mid, y,
                              free_hbm=12 * GB)
    assert one.schedule == "streamed_virtual_gram"
    assert eight.schedule == "resident_gram"
    assert eight.estimates["n_devices"] == 8
    assert "per-shard totals" in eight.reason

    # beyond even the meshed budget: per-shard streamed TOTALS builds
    # (exact — no dropped tail, unlike the single-device prefix build)
    huge = _ShapeOnly((800_000_000, 1000), np.float16)
    p = plan_quasi_newton(LBFGS().set_mesh(mesh), huge, y,
                          free_hbm=12 * GB)
    assert p.schedule == "streamed_virtual_gram"
    assert "EXACT totals" in p.reason

    # meshed non-LS beyond HBM: the chunked CostFun composes with the
    # mesh (per-shard chunk streams + psum)
    p = plan_quasi_newton(LBFGS(LogisticGradient()).set_mesh(mesh),
                          huge, y, free_hbm=12 * GB)
    assert p.schedule == "host_streamed"
    assert p.batch_rows is not None

    # a model-sharded mesh is left alone
    from tpu_sgd import make_mesh

    opt = LBFGS()
    opt.mesh = make_mesh(n_data=4, n_model=2)  # bypass the setter guard
    assert plan_quasi_newton(opt, mid, y, free_hbm=12 * GB) is None


def test_lbfgs_train_auto_plans_and_forced_gram(rng, caplog):
    from tpu_sgd import LinearRegressionWithLBFGS

    X = rng.normal(size=(2048, 12)).astype(np.float32)
    w = rng.uniform(-1, 1, 12).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=2048)).astype(np.float32)

    # zero flags: small data -> stock, but the plan ran and logged
    alg = LinearRegressionWithLBFGS()
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        m0 = alg.run((X, y))
    assert alg.optimizer.last_plan is not None
    assert alg.optimizer.last_plan.schedule == "resident_stock"
    assert not alg.optimizer.sufficient_stats
    assert any(r.message.startswith("plan: ") for r in caplog.records)

    # forced gram engages the substitution and reproduces the solution
    alg2 = LinearRegressionWithLBFGS().set_schedule("resident_gram")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        m1 = alg2.run((X, y))
    assert alg2.optimizer.sufficient_stats
    assert alg2.optimizer._gram_entry is not None
    np.testing.assert_allclose(np.asarray(m1.weights),
                               np.asarray(m0.weights), rtol=1e-3,
                               atol=1e-4)


def test_owlqn_forced_gram_plans(rng):
    from tpu_sgd.models.regression import LassoWithOWLQN

    X = rng.normal(size=(1024, 10)).astype(np.float32)
    w = rng.uniform(-1, 1, 10).astype(np.float32)
    y = (X @ w).astype(np.float32)
    alg = LassoWithOWLQN(reg_param=1e-4).set_schedule("resident_gram")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        m = alg.run((X, y))
    assert alg.optimizer.sufficient_stats
    assert alg.optimizer._gram_entry is not None
    assert np.all(np.isfinite(np.asarray(m.weights)))


def test_manual_flag_after_auto_plan_wins(rng):
    """A user setter called AFTER an auto-planned run must win on the next
    run — the setters clear last_plan, so the planner steps aside (review
    r4 finding: the planner used to clobber the user's choice)."""
    from tpu_sgd import LinearRegressionWithSGD

    X1 = rng.normal(size=(256, 8)).astype(np.float32)
    y1 = rng.normal(size=(256,)).astype(np.float32)
    X2 = rng.normal(size=(300, 8)).astype(np.float32)
    y2 = rng.normal(size=(300,)).astype(np.float32)
    alg = LinearRegressionWithSGD(0.2, 5)
    alg.run((X1, y1))
    assert alg.optimizer.last_plan is not None  # auto-planned
    alg.optimizer.set_sufficient_stats(True)    # user takes the wheel
    assert alg.optimizer.last_plan is None
    alg.run((X2, y2))
    assert alg.optimizer.sufficient_stats       # NOT clobbered
    assert alg.optimizer.last_plan is None      # planner stayed out


def test_forced_schedule_on_unplanned_input_raises_clearly(rng):
    """Forcing a schedule on an input the planner declines (sparse) must
    raise a clear error, not a quasi-Newton-flavored one."""
    from tpu_sgd import LinearRegressionWithSGD
    from tpu_sgd.ops.sparse import sparse_data

    Xs, ys, _ = sparse_data(64, 16, nnz_per_row=4, seed=0)
    with pytest.raises(ValueError, match="cannot be applied here"):
        LinearRegressionWithSGD.train((Xs, ys), num_iterations=3,
                                      schedule="host_streamed")


def test_forced_partial_residency_messages():
    # data fits: accurate "already fits" error
    with pytest.raises(ValueError, match="already fits"):
        plan(1000, 8, sampling="sliced", mini_batch_fraction=0.1,
             free_hbm=1 * GB, force="partial_residency")
    # beyond HBM but bernoulli: accurate requirements error
    with pytest.raises(ValueError, match="sliced sampling"):
        plan(10_000_000, 1000, itemsize=2, sampling="bernoulli",
             mini_batch_fraction=0.1, free_hbm=1 * GB,
             force="partial_residency")


def test_repeat_runs_skip_replanning(rng, caplog):
    """Identically-shaped repeat runs (the streaming micro-batch loop)
    plan once, not per batch."""
    from tpu_sgd import LinearRegressionWithSGD

    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = rng.normal(size=(256,)).astype(np.float32)
    alg = LinearRegressionWithSGD(0.2, 5)
    with caplog.at_level(logging.INFO, logger="tpu_sgd.plan"):
        for _ in range(4):
            alg.run((X, y))
    assert sum(r.message.startswith("plan: ")
               for r in caplog.records) == 1


def test_device_budget_probe_shapes():
    """memory_stats-reporting devices are probed; zero/absent stats (a
    backend that reports none, like the CPU's) take the cost model's
    default; a device whose probe RAISES is broken and says so."""

    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 16e9, "bytes_in_use": 4e9}

    free, source = device_budget(Dev())
    assert source == "memory_stats"
    assert free == pytest.approx(12e9 * 0.8)
    # what it would have free with nothing on it (a caller that sizes ALL
    # it will hold, part of which is there already)
    assert device_budget(Dev(), empty=True) == (pytest.approx(16e9 * 0.8),
                                                "memory_stats")

    class DevZeros:  # a backend that answers with zeros
        def memory_stats(self):
            return {"bytes_limit": 0, "bytes_in_use": 0}

    free, source = device_budget(DevZeros())
    assert source == "fallback" and free > 0

    class DevNone:  # the CPU backend
        def memory_stats(self):
            return None

    free, source = device_budget(DevNone())
    assert source == "fallback" and free > 0

    class DevRaises:
        def memory_stats(self):
            raise RuntimeError("no stats")

    with pytest.raises(RuntimeError, match="no stats"):
        device_budget(DevRaises())


def test_lbfgs_streamed_stats_matches_manual_virtual_flow(rng):
    """LBFGS.set_streamed_stats must reproduce the manual build_streamed +
    GramData-input flow exactly, for both LBFGS and OWL-QN."""
    from tpu_sgd import LBFGS
    from tpu_sgd.ops.gram import GramLeastSquaresGradient
    from tpu_sgd.optimize.owlqn import OWLQN

    n, d = 2048, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.uniform(-1, 1, d).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=n)).astype(np.float32)
    w0 = np.zeros((d,), np.float32)

    opt1 = LBFGS(max_num_iterations=10).set_streamed_stats(
        True, block_rows=256)
    w1, h1 = opt1.optimize_with_history((X, y), w0)
    assert opt1._streamed_gram_entry is not None

    g = GramLeastSquaresGradient.build_streamed(X, y, block_rows=256)
    opt2 = LBFGS(g, max_num_iterations=10)
    w2, h2 = opt2.optimize_with_history((g.data, y[:g.data.shape[0]]), w0)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))

    # repeat call hits the identity cache (no rebuild)
    entry = opt1._streamed_gram_entry
    opt1.optimize_with_history((X, y), w0)
    assert opt1._streamed_gram_entry is entry
    opt1.release_sufficient_stats()
    assert opt1._streamed_gram_entry is None

    # OWL-QN through the same flag
    ow = OWLQN(reg_param=1e-4, max_num_iterations=8).set_streamed_stats(
        True, block_rows=256)
    w3, h3 = ow.optimize_with_history((X, y), w0)
    assert ow._streamed_gram_entry is not None
    assert np.all(np.isfinite(np.asarray(w3))) and h3[-1] <= h3[0]


def test_lbfgs_streamed_stats_guards(rng):
    from tpu_sgd import LBFGS, data_mesh
    from tpu_sgd.ops.gradients import LogisticGradient

    X = rng.normal(size=(128, 6)).astype(np.float32)
    y = rng.normal(size=(128,)).astype(np.float32)
    w0 = np.zeros((6,), np.float32)
    with pytest.raises(NotImplementedError, match="least squares"):
        LBFGS(LogisticGradient()).set_streamed_stats(True) \
            .optimize_with_history((X, np.abs(np.sign(y))), w0)
    # meshed streamed statistics are SUPPORTED since round 5 (per-shard
    # totals builds — tests/test_lbfgs.py) — the old single-device guard
    # is gone; the remaining mesh guard is the model-axis rejection
    from tpu_sgd import make_mesh

    with pytest.raises(ValueError, match="data-only mesh"):
        LBFGS().set_mesh(make_mesh(n_data=4, n_model=2))


def test_choose_streamed_build_budgets_chunk():
    """The streamed build's device footprint is stack + TWO in-flight
    chunks — the double-buffered ingest pipeline stages chunk k+1 while
    chunk k's kernel consumes its buffer (review r4 established the
    single-chunk accounting; the io-layer prefetcher doubles it)."""
    from tpu_sgd.plan import _stack_bytes, choose_streamed_build

    B, batch = choose_streamed_build(100_000_000, 1000, 2, 12 * GB)
    assert B is not None and batch is not None
    stack = _stack_bytes(100_000_000, B, 1000)
    chunk = batch * (1000 * 2 + 4)
    assert stack + 2 * chunk <= 12 * GB  # double-buffer staging
    assert batch >= B  # at least one whole block per transfer
    # impossible O(d^2) stack: nothing fits
    assert choose_streamed_build(1_000_000, 100_000, 2,
                                 12 * GB) == (None, None)


def test_forced_gram_infeasible_budget_warns():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p = plan(1_000_000, 100_000, itemsize=2, gram_able=True,
                 sampling="sliced", mini_batch_fraction=0.1,
                 num_iterations=1000, free_hbm=12 * GB,
                 force="streamed_virtual_gram")
    assert p.schedule == "streamed_virtual_gram"
    assert p.block_rows is None
    assert any("NO feasible block size" in str(r.message) for r in rec)


def test_plan_batch_rows_plumbs_to_optimizer():
    from tpu_sgd import GradientDescent

    p = plan(10_000_000, 1000, itemsize=2, gram_able=True,
             sampling="sliced", mini_batch_fraction=0.1,
             num_iterations=1000, free_hbm=12 * GB)
    assert p.schedule == "streamed_virtual_gram"
    assert p.batch_rows is not None and p.batch_rows >= p.block_rows
    opt = p.apply(GradientDescent())
    assert opt.gram_batch_rows == p.batch_rows
    assert opt.gram_block_rows == p.block_rows


def test_manual_setter_clears_planned_sibling_flags(rng):
    """A manual schedule setter after an auto-planned run must clear the
    PLAN's sibling flags — the mutual-exclusion guards must never blame
    the user for a flag the planner set (code-review r5)."""
    from tpu_sgd import GradientDescent, LBFGS
    from tpu_sgd.ops.gradients import LeastSquaresGradient

    opt = GradientDescent()
    Plan("host_streamed", "auto plan").apply(opt)
    assert opt.host_streaming
    opt.set_streamed_stats(True)
    assert not opt.host_streaming  # plan-set sibling cleared
    assert opt.streamed_stats

    lb = LBFGS(LeastSquaresGradient(), max_num_iterations=3)
    lb.host_streaming = True  # as the QN planner leaves it...
    lb.last_plan = Plan("host_streamed", "auto plan")  # ...with last_plan
    lb.set_streamed_stats(True, block_rows=32)
    assert not lb.host_streaming
    # and the run proceeds without the exclusion guard firing
    X = rng.normal(size=(256, 6)).astype(np.float32)
    y = rng.normal(size=(256,)).astype(np.float32)
    w, h = lb.optimize_with_history((X, y), np.zeros(6, np.float32))
    assert np.all(np.isfinite(np.asarray(w)))
    # USER-set flags (last_plan is None) are never cleared by a sibling
    lb2 = LBFGS().set_host_streaming(True)
    with pytest.raises(ValueError, match="alternative"):
        lb2.set_streamed_stats(True).optimize_with_history(
            (X, y), np.zeros(6, np.float32))


def test_meshed_resident_gram_skips_stack_feasibility():
    """Meshed quasi-Newton resident gram carries O(d²) totals, not a
    prefix stack: slim headroom that forbids a stack must not push the
    planner back to stock (code-review r5)."""
    from tpu_sgd import LBFGS, data_mesh, plan_quasi_newton

    # per-device slab ~11.9 GB of 12 GB: no prefix stack fits, but the
    # 3*d² totals carry (12 MB) does
    tight = _ShapeOnly((47_500_000, 1000), np.float16)
    p = plan_quasi_newton(LBFGS().set_mesh(data_mesh()), tight, None,
                          free_hbm=12 * GB)
    assert p.schedule == "resident_gram"


# ---- self-calibration (round 5: VERDICT r4 #6) -----------------------------

def test_cost_model_calibrate_probe():
    """The ~2 s probe returns measured positive rates and keeps the
    other constants (plus explicit overrides)."""
    cm = CostModel.calibrate(copy_mb=4, feed_mb=4)
    assert cm.hbm_gb_s > 0 and cm.host_feed_gb_s > 0
    # A collapsed/elided measurement reads ~700,000 GB/s (the
    # constant-trip-count failure, CALIBRATION_TPU_CHECK round 5); no
    # real memory system exceeds ~20 TB/s, so a sane probe stays under.
    assert cm.hbm_gb_s < 20_000
    assert cm.hbm_bytes == CostModel().hbm_bytes  # defaults untouched
    cm2 = CostModel.calibrate(copy_mb=4, feed_mb=4, hbm_safety=0.5)
    assert cm2.hbm_safety == 0.5
    # overrides win over the measured fields too (probe one, pin one)
    cm3 = CostModel.calibrate(copy_mb=4, feed_mb=4, host_feed_gb_s=50.0)
    assert cm3.host_feed_gb_s == 50.0 and cm3.hbm_gb_s > 0
    # feed_mb so small both probe buffers clamp to the same 1024-element
    # minimum: zero byte delta must fall back to the default rate, never
    # 0.0 (plan() divides by host_feed_gb_s)
    cm4 = CostModel.calibrate(copy_mb=4, feed_mb=0.003)
    assert cm4.host_feed_gb_s == CostModel().host_feed_gb_s
    # the report says WHICH probes fell back (hardware checks gate on it)
    assert cm4.calibration_report["feed_fell_back"] is True
    assert cm.calibration_report["hbm_fell_back"] is False
    # report is advisory: excluded from model equality
    assert CostModel(calibration_report={"x": 1}) == CostModel()


def test_fed_cost_model_flips_streaming_boundary():
    """Decision boundaries must MOVE with the cost model: on the slow
    calibrated default feed (0.15 GB/s) a 20-iteration beyond-HBM run
    amortizes the one-time virtual-gram build in ~10 iterations; on a
    pod-local 50 GB/s feed the same build needs ~40 — the planner must
    flip away from the build (VERDICT r4 #6: the persisted constants are
    single-environment calibrations)."""
    kw = dict(itemsize=2, gram_able=True, sampling="sliced",
              mini_batch_fraction=0.1, num_iterations=20,
              free_hbm=12 * GB)
    slow = plan(10_000_000, 1000, **kw)
    assert slow.schedule == "streamed_virtual_gram"
    fast = plan(10_000_000, 1000,
                cost_model=CostModel(host_feed_gb_s=50.0), **kw)
    assert fast.schedule == "partial_residency"
    assert fast.estimates["streamed_iter_s"] < \
        slow.estimates["streamed_iter_s"] / 100


def test_host_streamed_plan_does_not_leak_stream_chunk_into_gram_knob():
    """A host_streamed quasi-Newton plan sizes batch_rows as the STREAM
    chunk (a global, mesh-scaled row count owned by stream_batch_rows);
    applying it must leave the gram build's chunk cap alone — a later
    manual streamed-gram build on the same optimizer would otherwise
    inherit an absurd host->device chunk (VERDICT r4's knob-ownership
    class)."""
    from tpu_sgd import LBFGS, LeastSquaresGradient, SquaredL2Updater
    from tpu_sgd.plan import Plan

    opt = LBFGS(LeastSquaresGradient(), SquaredL2Updater())
    p = Plan("host_streamed", "test", batch_rows=6_400_000)
    p.apply_quasi_newton(opt)
    assert opt.host_streaming
    assert opt.stream_batch_rows == 6_400_000   # the stream chunk knob
    assert opt.gram_batch_rows is None          # the gram knob untouched
    # ...and a gram-building plan still owns the gram knob as before
    p2 = Plan("streamed_virtual_gram", "test", block_rows=256,
              batch_rows=4096, aligned=True)
    p2.apply_quasi_newton(opt)
    assert opt.streamed_stats and not opt.host_streaming
    assert opt.stream_batch_rows is None
    assert opt.gram_batch_rows == 4096


def test_manual_schedule_after_plan_resets_plan_owned_knobs():
    """A manual schedule setter taking the wheel after an auto-planned
    run must reset the plan's SIZING knobs too: a block size / chunk cap
    sized for the planned dataset leaking into a manual build on a
    different dataset is the same class as the host_streamed batch_rows
    leak (round-5 fix), via the manual-after-plan path."""
    from tpu_sgd import GradientDescent
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS

    opt = GradientDescent()
    p = Plan("streamed_virtual_gram", "test", block_rows=512,
             batch_rows=4096, aligned=True)
    p.apply(opt)
    assert opt.gram_block_rows == 512 and opt.gram_batch_rows == 4096
    opt.set_streamed_stats(True)  # user takes the wheel, new dataset
    assert opt.gram_block_rows == DEFAULT_BLOCK_ROWS
    assert opt.gram_batch_rows is None
    assert opt.gram_aligned is False
    # ...but a USER-set knob survives the reset
    opt2 = GradientDescent().set_gram_options(block_rows=128)
    Plan("streamed_virtual_gram", "t", block_rows=512,
         batch_rows=4096).apply(opt2)
    assert opt2.gram_block_rows == 128  # user knob held through the plan
    opt2.set_sufficient_stats(True)
    assert opt2.gram_block_rows == 128  # and through the manual reset
    assert opt2.gram_batch_rows is None


def test_set_gram_options_validates_before_applying():
    """A bad LATER knob must not leave earlier knobs half-applied (and
    unrecorded in _user_gram_opts)."""
    from tpu_sgd import GradientDescent, LBFGS
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS

    for opt in (GradientDescent(), LBFGS()):
        with pytest.raises(ValueError, match="batch_rows must be positive"):
            opt.set_gram_options(block_rows=4096, batch_rows=0)
        assert opt.gram_block_rows == DEFAULT_BLOCK_ROWS
        assert "block_rows" not in opt._user_gram_opts


def test_a_batch_staged_on_the_device_keeps_the_plan(rng, monkeypatch):
    """The streaming fold plans a pass's first micro-batch with the device
    empty and every later one with another micro-batch staged beside it,
    when the probe would read far less free memory: one shape keeps one plan
    (the probe is not asked again), and rows that already lie on the device
    are never sent to a streaming schedule whatever the probe reads."""
    import tpu_sgd.plan as plan_mod
    from tpu_sgd import LinearRegressionWithSGD

    budgets = [1e9, 8e3, 8e3]  # then 8 KB "free": a host array would stream
    asked = []

    def budget(*a, **k):
        asked.append(budgets[len(asked)])
        return asked[-1], "test"

    monkeypatch.setattr(plan_mod, "device_budget", budget)
    X = rng.normal(size=(512, 8)).astype(np.float32)
    y = rng.normal(size=(512,)).astype(np.float32)
    alg = LinearRegressionWithSGD(0.2, 5)
    alg.run((X, y))
    first = alg.optimizer.last_plan
    assert first.schedule == "resident_stock" and len(asked) == 1
    w_host = np.asarray(alg.run((X, y)).weights)
    assert alg._apply_plan(jnp.asarray(X), y) is True  # the key held
    w_dev = np.asarray(alg.run((jnp.asarray(X), y)).weights)
    assert alg.optimizer.last_plan is first and len(asked) == 1
    assert not alg.optimizer.host_streaming
    np.testing.assert_array_equal(w_dev, w_host)
    # planned afresh under the small budget: a device array stays resident,
    # the same rows on the host would be streamed
    fresh = LinearRegressionWithSGD(0.2, 5)
    fresh.run((jnp.asarray(X), y))
    assert fresh.optimizer.last_plan.schedule == "resident_stock"
    assert not fresh.optimizer.host_streaming and len(asked) == 2
    hosted = LinearRegressionWithSGD(0.2, 5)
    hosted._apply_plan(X, y)
    assert hosted.optimizer.last_plan.schedule == "host_streamed"


# ---- the statistics' totals form, on the chip's own terms (PR 41) ---------

CELL_N, CELL_D = 2_097_152, 1000  # the stream cell's micro-batch, bf16


def _plan_for_the_cell(case):
    """``plan_for`` of an optimizer over an array of the stream cell's
    micro-batch shape that holds no memory: a broadcast view of one bf16
    zero on the host, an abstract value on the device."""
    import jax
    import ml_dtypes

    from tpu_sgd import (GradientDescent, LeastSquaresGradient,
                         LogisticGradient, SimpleUpdater, data_mesh)

    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(0.1).set_num_iterations(50)
           .set_convergence_tol(0.0))
    if case == "bernoulli_tenth":
        opt.set_mini_batch_fraction(0.1)
    elif case == "sliced_tenth":
        opt.set_mini_batch_fraction(0.1).set_sampling("sliced")
        opt.set_num_iterations(10_000)
    elif case == "logistic":
        opt.set_gradient(LogisticGradient())
    elif case == "mesh":
        opt.set_mesh(data_mesh(jax.devices()[:4]))
    elif case == "two_iterations":
        opt.set_num_iterations(2)
    y = np.broadcast_to(np.float32(0), (CELL_N,))
    if case == "cell_device":
        made = []

        def ask(X):
            assert isinstance(X, jax.Array)
            made.append(plan_for(opt, X, y))
            return 0

        jax.eval_shape(ask, jax.ShapeDtypeStruct((CELL_N, CELL_D),
                                                 jnp.bfloat16))
        return made[0]
    X = np.broadcast_to(np.zeros((), ml_dtypes.bfloat16), (CELL_N, CELL_D))
    return plan_for(opt, X, y)


@pytest.mark.parametrize("case,schedule,form", [
    ("cell_host", "resident_gram", "totals"),
    ("cell_device", "resident_gram", "totals"),
    ("bernoulli_tenth", "resident_stock", None),
    ("logistic", "resident_stock", None),
    ("mesh", "resident_stock", "prefix"),
    ("two_iterations", "resident_stock", "totals"),
    ("sliced_tenth", "resident_gram", "prefix"),
])
def test_the_stream_cells_micro_batch_is_planned_on_the_chips_terms(
        case, schedule, form):
    """A full batch of least squares on one device runs from the totals of
    its rows where one read's build pays inside the run; a Bernoulli
    fraction, another loss, a mesh and a run of two iterations stay stock;
    sliced windows keep the prefix form and its terms.  A bf16 array is
    two bytes an element on the host as on the device."""
    p = _plan_for_the_cell(case)
    est = p.estimates
    assert p.schedule == schedule
    assert est["itemsize"] == 2 and est.get("stats_form") == form
    assert (p.block_rows is not None) == (
        schedule == "resident_gram" and form == "prefix")
    if form == "totals":
        # a launch, one read of 4.19 GB and 4.4e12 operations at the
        # measured rate: 29.2 ms for the 27.94 + 1.1 measured (PR 41)
        assert est["gram_build_s"] == pytest.approx(0.0292, abs=3e-4)
        assert est["build_amortize_iters"] < 10
    if case == "mesh":  # the prefix form's build, the attachment's 1.2 s
        assert est["n_local"] == CELL_N // 4 and est["gram_build_s"] > 1.2


@pytest.mark.parametrize("reads", [1, 2])
def test_the_totals_pay_whether_a_stock_step_reads_its_rows_once_or_twice(
        reads):
    """On a TPU the cell's stock step is the one-read kernel (5.7 ms an
    iteration by the model, 5.56 measured): the build still pays from the
    sixth iteration on, of 50."""
    p = plan(CELL_N, CELL_D, itemsize=2, gram_able=True,
             mini_batch_fraction=1.0, num_iterations=50, free_hbm=12 * GB,
             stock_reads=reads)
    est = p.estimates
    assert p.schedule == "resident_gram" and est["stock_reads"] == reads
    assert est["stock_iter_s"] == pytest.approx(
        reads * CELL_N * CELL_D * 2 / 730e9)
    assert est["build_amortize_iters"] < (6 if reads == 1 else 3)
    # 12 MB of statistics beside the rows: it fits where a prefix stack
    # (514 prefixes of 4 MB at blocks of 4,096 rows) would not
    tight = plan(CELL_N, CELL_D, itemsize=2, gram_able=True,
                 mini_batch_fraction=1.0, num_iterations=50,
                 free_hbm=CELL_N * (CELL_D * 2 + 4) + 13e6,
                 stock_reads=reads)
    assert tight.schedule == "resident_gram"
    from tpu_sgd.plan import _stack_bytes, _totals_bytes

    assert _totals_bytes(CELL_D) < 13e6 < 2e9 < _stack_bytes(CELL_N, 4096,
                                                             CELL_D)


def test_stock_reads_asks_the_steps_kernel_on_a_tpu_alone(monkeypatch):
    import jax

    from tpu_sgd import GradientDescent, LeastSquaresGradient, SimpleUpdater
    from tpu_sgd.plan import _stock_reads

    opt = GradientDescent(LeastSquaresGradient(), SimpleUpdater())
    bf16 = jnp.dtype(jnp.bfloat16)
    assert _stock_reads(opt, CELL_N, CELL_D, bf16) == 2  # a CPU: two matvecs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _stock_reads(opt, CELL_N, CELL_D, bf16) == 1  # the one-read kernel
    # 1,020 features: the chip stores X by rows and no kernel reads it once
    assert _stock_reads(opt, CELL_N, 1020, bf16) == 2
    opt.set_mini_batch_fraction(0.1).set_sampling("indexed")
    assert _stock_reads(opt, CELL_N, CELL_D, bf16) == 2  # a gathered batch
