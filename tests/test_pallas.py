"""Pallas fused gradient kernel vs the XLA reference path (interpret mode
on CPU; the same kernel compiles to Mosaic on TPU), and the selection of
the one-read kernel inside ``Gradient.batch_sums``."""

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
)
from tpu_sgd.ops.pallas_kernels import fused_gradient_sums


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


#: row counts against a 256-row tile: a multiple of it; 2,145,000's
#: remainder over a lane group (104) past two tiles; less than one tile
ROWS = {"tile_multiple": 512, "ragged_104": 2 * 256 + 104, "under_a_tile": 77}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_feature_major_kernel_matches_two_matvecs(g, dtype, rows, masked):
    """The one-read kernel over ``X.T`` against ``Gradient``'s two matvecs
    on the same (already rounded) rows.  The interpreter fills what lies
    past the end of a ragged last block with NaN, so every ragged case is
    also the poisoned-tail case."""
    import jax.numpy as jnp

    n = ROWS[rows]
    X, y, w = _data(n=n, d=40, seed=n,
                    classify=not isinstance(g, LeastSquaresGradient))
    X = jnp.asarray(X, dtype)
    mask = (np.random.default_rng(n).uniform(size=n) < 0.3) if masked \
        else None
    gs_ref, ls_ref, c_ref = g._two_read_sums(
        X.astype(jnp.float32), y, w, mask)
    gs, ls, c = fused_gradient_sums(g.pointwise, X, y, w, mask, tile_m=256,
                                    interpret=True)
    assert gs.dtype == ls.dtype == c.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == (mask.sum() if masked else n)


def test_interpreter_poisons_what_lies_past_the_end():
    """What the ragged cases above rest on: a block that reaches past the
    array reads NaN there in interpret mode (on the chip: whatever lay in
    VMEM), so a kernel that multiplied the tail by zero would return NaN."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    block = pl.BlockSpec((8, 128), lambda i: (0, i))
    out = pl.pallas_call(
        copy, grid=(2,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
        interpret=True)(jnp.ones((8, 200), jnp.float32))
    assert np.isnan(np.asarray(out)[:, 200:]).all()
    # and the kernel, on NaN-free data with such a tail, returns no NaN
    g = LogisticGradient()
    X, y, w = _data(n=200, d=16, seed=3, classify=True)
    for mask in (None, np.arange(200) % 3 == 0):
        out = fused_gradient_sums(g.pointwise, X, y, w, mask, tile_m=128,
                                  interpret=True)
        assert all(np.isfinite(np.asarray(o)).all() for o in out)


def test_feature_major_tile_choice():
    """``fm_tile`` halves until the block fits the kernel's VMEM, never
    passes the rows there are, and gives up on a width no lane group
    fits; an explicit tile is floored to whole lane groups."""
    from tpu_sgd.ops import pallas_kernels as PK

    assert PK.fm_tile(4_194_304, 1000, 2) == PK.FM_TILE
    assert PK.fm_tile(2_145_000, 1000, 4) == PK.FM_TILE  # f32: 17.4 MB
    assert PK.fm_tile(1 << 20, 4000, 4) == PK.FM_TILE // 4
    assert PK.fm_tile(300, 24, 4) == 384  # one block over all 300 rows
    assert PK.fm_tile(1 << 16, 47_236, 2) is None  # 128 lanes: 24 MB x 2
    assert PK._fm_round(200, 10_000) == 128
    assert PK._fm_round(64, 10_000) == 128
    assert [PK._fm_lane_chunk(t) for t in (128, 384, 1920, 2048, 4096)] \
        == [128, 384, 640, 1024, 1024]
    X = np.zeros((1 << 16, 47_236), np.float32)
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_gradient_sums(GRADS[0].pointwise, X, X[:, 0], X[0])


# -- the selection inside Gradient.batch_sums ----------------------------------

def _selection_case(name):
    """``(X, y, w, mask, margin_axis_name)`` of (1024, 1000) bf16 rows —
    a shape the chip stores feature-major — with one observable changed."""
    import jax.numpy as jnp

    n, d = 1024, 1000
    X = jnp.zeros((n, d), jnp.bfloat16)
    y, w, mask = jnp.zeros((n,)), jnp.zeros((d,)), jnp.zeros((n,), bool)
    axis = None
    if name == "bcoo":
        from jax.experimental.sparse import BCOO

        X = BCOO.fromdense(jnp.zeros((n, d), jnp.float32), nse=4)
    elif name == "matrix_weights":
        w = jnp.zeros((3, d))
    elif name == "margin_axis_name":
        axis = "model"
    elif name == "integer_rows":
        X = jnp.zeros((n, d), jnp.int8)
    elif name == "row_major_width":
        X = jnp.zeros((n, 1024), jnp.bfloat16)  # pads nothing by rows
        w = jnp.zeros((1024,))
    elif name == "too_wide":
        X = jnp.zeros((256, 47_236), jnp.bfloat16)
        w = jnp.zeros((47_236,))
        y, mask = y[:256], mask[:256]
    elif name == "labels_per_class":
        y = jnp.zeros((n, 2))
    else:
        assert name == "feature_major", name
    return X, y, w, mask, axis


OFF = ["bcoo", "matrix_weights", "margin_axis_name", "integer_rows",
       "row_major_width", "too_wide", "labels_per_class"]


@pytest.mark.parametrize("case", ["feature_major"] + OFF)
def test_one_read_sums_follows_what_the_operands_look_like(case):
    from tpu_sgd.ops.gradients import one_read_sums

    X, y, w, mask, axis = _selection_case(case)
    assert one_read_sums(X, y, w, mask, axis) == (case == "feature_major")
    assert one_read_sums(X, y, w, None, axis) == (case == "feature_major")


def _lowered_for(platform, fn, *args):
    import jax

    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text(debug_info=True)


@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_batch_sums_lowers_the_kernel_for_a_tpu_and_two_matvecs_here(g):
    """The platform is decided at LOWERING: from this CPU process the
    program lowered for a TPU holds the Mosaic call under
    ``sgd.fused_sums`` and no matvec; lowered for the CPU it is the two
    ``dot_general`` it always was and holds no kernel."""
    X, y, w, mask, _ = _selection_case("feature_major")
    tpu = _lowered_for("tpu", g.batch_sums, X, y, w, mask)
    assert "tpu_custom_call" in tpu and "stablehlo.dot_general" not in tpu
    cpu = _lowered_for("cpu", g.batch_sums, X, y, w, mask)
    assert cpu.count("stablehlo.dot_general") == 2 and "tpu_custom_call" not in cpu
    assert "sgd.fused_sums/jit(_fused_gradient_sums)" in tpu
    # each half keeps its own scope on what it leaves outside the kernel
    # (w along the lanes, the fold of the lane partials): the benchmark's
    # margins_ms and gradient_ms still find an operation to read
    assert "sgd.margins/broadcast_in_dim" in tpu
    assert "sgd.gradient/reduce_sum" in tpu
    assert "sgd.fused_sums" not in cpu and "sgd.margins" in cpu


@pytest.mark.parametrize("case", ["row_major_width", "integer_rows",
                                  "too_wide"])
def test_batch_sums_keeps_two_matvecs_on_a_tpu_where_the_kernel_is_off(case):
    X, y, w, mask, _ = _selection_case(case)
    tpu = _lowered_for("tpu", LogisticGradient().batch_sums, X, y, w, mask)
    assert "tpu_custom_call" not in tpu and tpu.count("stablehlo.dot_general") == 2


def test_batch_sums_on_the_cpu_is_bitwise_the_two_matvecs():
    """Run here, the selection changes no bit of any sum."""
    import jax

    g = LogisticGradient()
    X, y, w = _data(n=1024, d=1000, seed=21, classify=True)
    mask = np.random.default_rng(22).uniform(size=1024) < 0.1
    new = jax.jit(g.batch_sums)(X, y, w, mask)
    old = jax.jit(g._two_read_sums)(X, y, w, mask)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_kernel_under_shard_map_matches_two_matvecs_on_all_rows(g, dtype):
    """The four-chip cell's step, numerically: each of four devices runs
    the kernel on its shard under its own mask (a last block's ragged
    tail on every shard) and one ``psum`` adds the sums up, against the
    two matvecs over all the rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_sgd.parallel.mesh import DATA_AXIS, data_mesh, shard_map_fn

    n = 4 * 200  # 200 rows a shard: one full 128-lane block and 72 rows
    X, y, w = _data(n=n, d=40, seed=17,
                    classify=not isinstance(g, LeastSquaresGradient))
    X = jnp.asarray(X, dtype)
    mask = np.random.default_rng(18).uniform(size=n) < 0.3

    def local(X, y, w, mask):
        sums = fused_gradient_sums(g.pointwise, X, y, w, mask, tile_m=128,
                                   interpret=True)
        return jax.lax.psum(sums, DATA_AXIS)

    rows = P(DATA_AXIS)
    gs, ls, c = jax.jit(shard_map_fn(
        data_mesh(jax.devices()[:4]), local,
        (P(DATA_AXIS, None), rows, P(), rows), (P(), P(), P())))(
            X, y, w, mask)
    gs_ref, ls_ref, c_ref = g._two_read_sums(
        X.astype(jnp.float32), y, w, mask)
    assert gs.dtype == ls.dtype == c.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == mask.sum()


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
