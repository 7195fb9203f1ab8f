"""Pallas fused gradient kernel vs the XLA reference path (interpret mode
on CPU; the same kernel compiles to Mosaic on TPU)."""

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
)
from tpu_sgd.ops.pallas_kernels import PallasGradient, fused_gradient_sums


GRADS = [LeastSquaresGradient(), LogisticGradient(), HingeGradient()]


def _data(n=300, d=24, seed=0, classify=False):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, d)).astype(np.float32)
    if classify:
        y = (r.uniform(size=(n,)) < 0.5).astype(np.float32)
    else:
        y = r.normal(size=(n,)).astype(np.float32)
    w = r.normal(size=(d,)).astype(np.float32)
    return X, y, w


@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_fused_matches_xla_path(g):
    X, y, w = _data(classify=not isinstance(g, LeastSquaresGradient))
    gs_ref, ls_ref, c_ref = g.batch_sums(X, y, w)
    gs, ls, c = fused_gradient_sums(g.pointwise, X, y, w, tile_m=128,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref)


def test_fused_with_mask_and_ragged_rows():
    """n not a tile multiple AND a sampling mask: padding must be invisible."""
    g = LeastSquaresGradient()
    X, y, w = _data(n=333, d=16, seed=1)
    mask = np.random.default_rng(2).uniform(size=(333,)) < 0.3
    gs_ref, ls_ref, c_ref = g.batch_sums(X, y, w, mask)
    gs, ls, c = fused_gradient_sums(g.pointwise, X, y, w, mask, tile_m=128,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == mask.sum()


def test_pallas_gradient_drop_in_optimizer():
    """PallasGradient behind the unchanged optimizer boundary converges to
    the same solution as the XLA path."""
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.utils.mlutils import linear_data

    X, y, w_true = linear_data(1024, 16, eps=0.01, seed=3)
    w0 = np.zeros(16, np.float32)

    def fit(gradient):
        return np.asarray(
            GradientDescent(gradient, SimpleUpdater())
            .set_step_size(0.5)
            .set_num_iterations(80)
            .set_convergence_tol(0.0)
            .optimize((X, y), w0)
        )

    w_xla = fit(LeastSquaresGradient())
    w_pal = fit(PallasGradient(LeastSquaresGradient(), tile_m=256,
                               interpret=True))
    np.testing.assert_allclose(w_pal, w_xla, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(w_pal, w_true, atol=0.05)


def test_pallas_gradient_raises_off_tpu():
    """Default (interpret=None) on CPU: an error that says what to do, on
    both entry points — never a silent hand-over to the XLA path."""
    g = PallasGradient(LogisticGradient())
    X, y, w = _data(classify=True)
    with pytest.raises(RuntimeError, match="interpret=True"):
        g.batch_sums(X, y, w)
    with pytest.raises(RuntimeError, match="interpret=True"):
        g.window_sums(X, y, w, 0, X.shape[0])


def test_pallas_gradient_weight_dim_delegates():
    assert PallasGradient(LeastSquaresGradient()).weight_dim(7) == 7


def test_pallas_gradient_under_dp_mesh():
    """The fused kernel composes with shard_map data parallelism."""
    from tpu_sgd.optimize.gradient_descent import GradientDescent
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.parallel.mesh import data_mesh
    from tpu_sgd.utils.mlutils import linear_data

    X, y, w_true = linear_data(1024, 16, eps=0.01, seed=5)
    w = (
        GradientDescent(
            PallasGradient(LeastSquaresGradient(), tile_m=64, interpret=True),
            SimpleUpdater(),
        )
        .set_step_size(0.5)
        .set_num_iterations(60)
        .set_convergence_tol(0.0)
        .set_mesh(data_mesh())
        .optimize((X, y), np.zeros(16, np.float32))
    )
    np.testing.assert_allclose(np.asarray(w), w_true, atol=0.05)


def test_fused_bf16_inputs():
    import jax.numpy as jnp

    g = LeastSquaresGradient()
    X, y, w = _data(n=256, d=32, seed=4)
    gs, ls, c = fused_gradient_sums(
        g.pointwise, jnp.asarray(X, jnp.bfloat16), y, w, tile_m=128,
        interpret=True
    )
    gs_ref, ls_ref, c_ref = g.batch_sums(X, y, w)
    assert gs.dtype == jnp.float32  # f32 accumulation
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=0.05,
                               atol=0.5)


def test_window_sums_matches_manual_slice():
    """Zero-copy offset kernel == batch_sums on the same materialized rows."""
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import fused_window_sums

    g = LeastSquaresGradient()
    X, y, w = _data(n=512, d=24, seed=7)
    start_tile, num_tiles, tile = 2, 3, 64
    gs, ls, c = fused_window_sums(
        g.pointwise, X, y, w, jnp.asarray(start_tile), num_tiles,
        tile_m=tile, interpret=True,
    )
    lo, hi = start_tile * tile, (start_tile + num_tiles) * tile
    gs_ref, ls_ref, c_ref = g.batch_sums(X[lo:hi], y[lo:hi], w)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == num_tiles * tile


def test_pallas_window_sums_drop_in():
    """PallasGradient.window_sums clamps the start and matches the base
    gradient's dynamic-slice path on tile-aligned starts."""
    import jax.numpy as jnp

    base = LeastSquaresGradient()
    g = PallasGradient(base, tile_m=64, interpret=True)
    X, y, w = _data(n=640, d=16, seed=8)
    m = 128  # two tiles
    for start in (0, 64, 576):  # 576 clamps to 512 so the window fits
        gs, ls, c = g.window_sums(X, y, w, jnp.asarray(start), m)
        eff = min(start, 640 - m)
        gs_ref, ls_ref, c_ref = base.batch_sums(
            X[eff:eff + m], y[eff:eff + m], w
        )
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref),
                                   rtol=2e-4, atol=2e-3)
        assert float(c) == m


def test_pallas_window_sums_fallback_unaligned():
    """Non-tile-multiple datasets fall back to the base dynamic-slice path."""
    import jax.numpy as jnp

    base = LeastSquaresGradient()
    g = PallasGradient(base, tile_m=64, interpret=True)
    X, y, w = _data(n=333, d=16, seed=9)
    gs, ls, c = g.window_sums(X, y, w, jnp.asarray(10), 100)
    gs_ref, ls_ref, c_ref = base.batch_sums(X[10:110], y[10:110], w)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)


def test_pallas_window_sums_subtile_remainder():
    """m not a tile multiple: kernel bulk + base-path remainder == exactly m
    rows, matching the pure dynamic-slice path."""
    import jax.numpy as jnp

    base = LeastSquaresGradient()
    g = PallasGradient(base, tile_m=64, interpret=True)
    X, y, w = _data(n=640, d=16, seed=10)
    m = 150  # 2 tiles + 22-row remainder
    gs, ls, c = g.window_sums(X, y, w, jnp.asarray(128), m)
    gs_ref, ls_ref, c_ref = base.batch_sums(X[128:128 + m], y[128:128 + m], w)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == m


def test_vmem_guard_rejects_oversized_tile():
    """Tiles whose double-buffered footprint cannot compile raise an
    actionable error instead of a Mosaic scoped-VMEM OOM (seen on hardware
    at tile 8192 x d=1000 bf16 = 40 MB vs the 16 MB budget)."""
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import fused_window_sums

    n, d = 16384, 1000
    X = jnp.zeros((n, d), jnp.bfloat16)
    y = jnp.zeros((n,), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    g = LeastSquaresGradient()
    with pytest.raises(ValueError, match="VMEM"):
        fused_window_sums(g.pointwise, X, y, w, 0, 2, tile_m=8192)


def test_vpu_window_kernel_matches_base():
    """The VPU-reduction window kernel (round-3 experiment) computes the
    same sums as the MXU variant and the base path, for every pointwise
    gradient rule."""
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import (
        HingeGradient,
        LeastSquaresGradient,
        LogisticGradient,
    )
    from tpu_sgd.ops.pallas_kernels import (
        fused_window_sums,
        fused_window_sums_vpu,
    )

    X, y, w = _data(n=512, d=24, seed=11)
    start_tile, num_tiles, tile = 1, 4, 64
    lo, hi = start_tile * tile, (start_tile + num_tiles) * tile
    for g in (LeastSquaresGradient(), LogisticGradient(), HingeGradient()):
        gs_v, ls_v, c_v = fused_window_sums_vpu(
            g.pointwise, X, y, w, jnp.asarray(start_tile), num_tiles,
            tile_m=tile, interpret=True,
        )
        gs_m, ls_m, c_m = fused_window_sums(
            g.pointwise, X, y, w, jnp.asarray(start_tile), num_tiles,
            tile_m=tile, interpret=True,
        )
        gs_ref, ls_ref, c_ref = g.batch_sums(X[lo:hi], y[lo:hi], w)
        np.testing.assert_allclose(np.asarray(gs_v), np.asarray(gs_ref),
                                   rtol=2e-4, atol=2e-3)
        np.testing.assert_allclose(np.asarray(gs_v), np.asarray(gs_m),
                                   rtol=2e-4, atol=2e-3)
        np.testing.assert_allclose(float(ls_v), float(ls_ref), rtol=2e-4)
        assert float(c_v) == float(c_ref) == num_tiles * tile


def test_pallas_gradient_vpu_window_kernel_selection():
    """window_kernel='vpu' routes window_sums through the VPU variant with
    identical results (interpret mode); bad names raise."""
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.pallas_kernels import PallasGradient

    X, y, w = _data(n=512, d=24, seed=13)
    start, m, tile = 64, 256, 64
    base = LeastSquaresGradient()
    g_mxu = PallasGradient(base, tile_m=tile, interpret=True)
    g_vpu = PallasGradient(base, tile_m=tile, interpret=True,
                           window_kernel="vpu")
    # prove the flag actually routes (the two variants agree numerically,
    # so result comparison alone cannot falsify the selection)
    import tpu_sgd.ops.pallas_kernels as PK

    calls = []
    real_vpu = PK.fused_window_sums_vpu
    PK.fused_window_sums_vpu = (
        lambda *a, **k: (calls.append("vpu"), real_vpu(*a, **k))[1]
    )
    try:
        out_m = g_mxu.window_sums(X, y, w, jnp.asarray(start), m)
        assert calls == []
        out_v = g_vpu.window_sums(X, y, w, jnp.asarray(start), m)
        assert calls == ["vpu"]
    finally:
        PK.fused_window_sums_vpu = real_vpu
    np.testing.assert_allclose(np.asarray(out_v[0]), np.asarray(out_m[0]),
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(float(out_v[1]), float(out_m[1]), rtol=2e-4)
    assert float(out_v[2]) == float(out_m[2])
    with pytest.raises(ValueError, match="window_kernel"):
        PallasGradient(base, window_kernel="gpu")
