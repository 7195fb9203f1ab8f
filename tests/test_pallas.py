"""Pallas fused gradient kernel vs the XLA reference path (interpret mode
on CPU; the same kernel compiles to Mosaic on TPU), its run over the blocks
of a row window (``sampling="sliced"``), and the selection of the one-read
kernel inside ``Gradient.batch_sums`` and ``Gradient.window_sums``."""

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
)
from tpu_sgd.ops.pallas_kernels import (fused_gradient_sums,
                                        fused_wide_sums, fused_window_sums)


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
    """``one_read``'s tile halves until the block fits the kernel's VMEM, never
    passes the rows there are, takes the wide form's tile where the
    ``(d, 128)`` operands do not fit (RCV1's width) and gives up on a
    width no lane group fits in either form; an explicit tile is floored
    to whole lane groups."""
    from tpu_sgd.ops import pallas_kernels as PK

    def tile(*shape, **kw):
        own = PK.one_read(*shape, **kw)
        return own and own.tile

    assert tile(4_194_304, 1000, 2) == PK.FM_TILE
    assert tile(2_145_000, 1000, 4) == PK.FM_TILE  # f32: 17.4 MB
    assert tile(1 << 20, 4000, 4) == PK.FM_TILE // 4
    assert tile(300, 24, 4) == 384  # one block over all 300 rows
    # RCV1's width: 96.7 MB of (d, 128) f32 operands, so the wide form:
    # 256 rows a grid step (two blocks of 24.2 MB), the width in 8 blocks
    assert tile(1 << 16, 47_236, 2) == 256
    wide = PK.one_read(131_072, 47_236, 2, False)
    assert (wide.tile, wide.feature_blocks) == (256, 8)
    assert (wide.body, wide.tile, wide.fblock) == ("wide", 256, 6400)
    narrow = PK.one_read(4_194_304, 1000, 2)
    assert narrow.body == "scan"  # _fm_kernel takes it
    assert (narrow.tile, narrow.feature_blocks) == (PK.FM_TILE, 1)
    assert tile(1 << 16, 400_004, 2) is None  # 128 lanes: 102 MB x 2
    assert tile(1 << 16, 47_236, 2, class_rows=16) is None
    assert PK._fm_round(200, 10_000) == 128
    assert PK._fm_round(64, 10_000) == 128
    assert [PK._fm_lane_chunk(t) for t in (128, 384, 1920, 2048, 4096)] \
        == [128, 384, 640, 1024, 1024]
    X = np.zeros((1 << 16, 47_236), np.float32)
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_gradient_sums(GRADS[0].pointwise, X, X[:, 0], X[0])
    import jax

    X = jax.ShapeDtypeStruct((1 << 10, 400_004), np.float32)
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_wide_sums(GRADS[0].pointwise, X, X, X)


# -- the wide form: a vector of weights as rows, the width in feature blocks ---

#: scoped VMEM that puts a width of 600 into several feature blocks (the
#: cut is the limit's: a thirty-second of it is one block of a lane chunk)
WIDE_LIMIT = 5 << 19
WIDE_N, WIDE_D = 700, 600


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_wide_kernel_in_feature_blocks_matches_two_matvecs_and_the_rules(
        g, dtype, masked):
    """The wide form in interpret mode at a small width that the limit
    cuts into three feature blocks or more, rows no multiple of the row
    tile (the last block is cut, and the interpreter fills what lies past
    row n with NaN), against ``margins_of`` / ``grad_sum_of`` and against
    the benchmark's own rules in float64.  The weights ride as parts in
    X's type that add up to the f32 vector, so bf16 rows lose nothing
    against f32 operands."""
    import jax.numpy as jnp

    from bench.reference import rules
    from tpu_sgd.ops import pallas_kernels as PK

    n, d = WIDE_N, WIDE_D
    itemsize = jnp.dtype(dtype).itemsize
    tile, fblock = PK._fm_wide_plan(n, d, itemsize, masked, WIDE_LIMIT)
    assert n % tile and n > tile and -(-d // fblock) >= 3 and d % fblock
    X, y, w = _data(n=n, d=d, seed=7 + masked,
                    classify=not isinstance(g, LeastSquaresGradient))
    X = np.asarray(jnp.asarray(0.1 * X, dtype).astype(jnp.float32))
    mask = (np.random.default_rng(5).uniform(size=n) < 0.4) if masked \
        else None
    gs, ls, c = fused_wide_sums(g.pointwise, jnp.asarray(X, dtype), y, w,
                                mask, vmem_limit=WIDE_LIMIT, interpret=True)
    gs_ref, ls_ref, c_ref = g._two_read_sums(X, y, w, mask)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-5,
                               atol=2e-4)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-5)
    assert float(c) == float(c_ref) == (n if mask is None else mask.sum())
    X64, keep = X.astype(np.float64), (1.0 if mask is None else mask)
    coeff, loss = rules.pointwise(np, type(g).__name__, X64 @ w, y)
    np.testing.assert_allclose(np.asarray(gs), (coeff * keep) @ X64,
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(float(ls), np.sum(loss * keep), rtol=2e-5)


def test_wide_kernel_takes_a_tile_of_the_callers_and_one_block():
    """``tile_m`` is the caller's, as in ``fused_gradient_sums``; under the
    kernel's own limit a width of 600 is ONE feature block, and the sums
    are the same."""
    from tpu_sgd.ops import pallas_kernels as PK

    g = HingeGradient()
    X, y, w = _data(n=WIDE_N, d=WIDE_D, seed=9, classify=True)
    assert PK._fm_feature_block(WIDE_D, 128, 4, PK._FM_WIDE_VMEM_LIMIT) \
        == WIDE_D
    one = fused_wide_sums(g.pointwise, X, y, w, tile_m=128, interpret=True)
    cut = fused_wide_sums(g.pointwise, X, y, w, vmem_limit=WIDE_LIMIT,
                          interpret=True)
    ref = g._two_read_sums(X, y, w, None)
    for a, b, r in zip(one, cut, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-5,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(b), np.asarray(r), rtol=2e-5,
                                   atol=2e-4)


def test_wide_parts_add_up_to_the_f32_vector():
    """Three bf16 parts carry an f32's 24 bits; an f32 row is one part."""
    import jax.numpy as jnp

    from tpu_sgd.ops import pallas_kernels as PK

    assert PK.wide_rows_of(jnp.bfloat16) == (3, 16)
    assert PK.wide_rows_of(jnp.float32) == (1, 8)
    w = np.random.default_rng(3).normal(size=4096).astype(np.float32) * 37.0
    for in_kernel in (False, True):  # reduce_precision; a pair of casts
        parts = PK._parts_of(jnp.asarray(w), jnp.bfloat16, 3, in_kernel)
        assert all(p.dtype == jnp.bfloat16 for p in parts)
        total = sum(np.asarray(p.astype(jnp.float32), np.float64)
                    for p in parts)
        np.testing.assert_array_equal(total.astype(np.float32), w)
        assert float(jnp.max(jnp.abs(parts[1]))) > 0  # not all in the first


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_batch_sums_lowers_the_wide_kernel_for_a_tpu_at_rcv1s_width(
        g, masked):
    """At 47,236 features ``batch_sums`` lowered for a TPU is ONE Mosaic
    call under ``sgd.wide_sums`` in a jitted function of its own name and
    no matvec; lowered for the CPU the two ``dot_general`` it always
    was."""
    X, y, w, mask, _ = _selection_case("wide")
    mask = mask if masked else None
    tpu = _lowered_for("tpu", g.batch_sums, X, y, w, mask)
    assert "tpu_custom_call" in tpu and "stablehlo.dot_general" not in tpu
    assert "sgd.wide_sums/jit(_fused_wide_sums)" in tpu
    assert "sgd.fused_sums" not in tpu
    cpu = _lowered_for("cpu", g.batch_sums, X, y, w, mask)
    assert cpu.count("stablehlo.dot_general") == 2
    assert "tpu_custom_call" not in cpu and "sgd.wide_sums" not in cpu


@pytest.mark.parametrize("case", ["full_batch", "bernoulli", "sliced",
                                  "indexed", "narrow"])
def test_step_sums_names_the_kernels_row_tile_and_feature_blocks(case):
    """``train.run``'s ``row_tile`` and ``feature_blocks``, from shapes
    alone: RCV1's width under a full batch or a drawn mask is the wide
    form (256 rows a grid step, the width in 8 blocks); the window's
    kernel has no wide form and a gathered batch is no kernel (0 rows, one
    block); the north star's width is one block of 2048 rows."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import step_sums

    n, d = (131_072, 47_236) if case != "narrow" else (4_194_304, 1000)
    X = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    w = jax.ShapeDtypeStruct((d,), jnp.float32)
    cfg = SGDConfig(
        mini_batch_fraction=1.0 if case == "full_batch" else 0.1,
        sampling=case if case in ("sliced", "indexed") else "bernoulli")
    kernel = step_sums(HingeGradient(), cfg, X, y, w).kernel
    assert (kernel and (kernel.tile, kernel.feature_blocks)) == {
        "full_batch": (256, 8), "bernoulli": (256, 8), "sliced": None,
        "indexed": None, "narrow": (2048, 1)}[case]


# -- the kernel over a window of rows -----------------------------------------

#: (rows, window, tile): n = 333 / m = 100 as ``tests/test_gradients.py``'s
#: contract has them (three blocks of 128 lanes, a ragged last one); a
#: window over two tiles, at two tiles; a window shorter than one tile
WINDOWS = {"n333_m100_t128": (333, 100, 128),
           "n5000_m2300_t1024": (5000, 2300, 1024),
           "n5000_m2300_t2048": (5000, 2300, 2048),
           "n5000_m300_t1024": (5000, 300, 1024)}


def _window_start(name, n, m, tile):
    return {"first_row": 0, "unaligned_middle": (n - m) // 2 + 3,
            "last_window": n - m, "past_the_end": n - m // 3,
            "tile_boundary": tile}[name]


@pytest.mark.parametrize("with_valid", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("start", ["first_row", "unaligned_middle",
                                   "last_window", "past_the_end",
                                   "tile_boundary"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_lane_window_kernel_matches_two_matvecs_on_the_sliced_rows(
        case, dtype, start, with_valid):
    """The kernel over the window's own blocks at a prefetched block offset
    against the two matvecs on ``X[lo:lo + m]``, ``lo`` clamped as
    ``lax.dynamic_slice`` clamps.  Every row OUTSIDE the window is NaN (and
    the interpreter fills what lies past row n with NaN too): a kernel
    that read one such value into its sums, or multiplied it by zero,
    would return NaN."""
    import jax.numpy as jnp

    n, m, tile = WINDOWS[case]
    g = LogisticGradient()
    X, y, w = _data(n=n, d=40, seed=n + m, classify=True)
    X = np.asarray(jnp.asarray(X, dtype).astype(jnp.float32))  # rounded
    valid = (np.random.default_rng(m).uniform(size=n) < 0.7) if with_valid \
        else None
    at = _window_start(start, n, m, tile)
    lo = min(at, n - m)
    rows = slice(lo, lo + m)
    poisoned, y_poisoned = X.copy(), y.copy()
    poisoned[:lo] = poisoned[lo + m:] = np.nan
    y_poisoned[:lo] = y_poisoned[lo + m:] = np.nan
    gs, ls, c = fused_window_sums(
        g.pointwise, jnp.asarray(poisoned, dtype), y_poisoned, w,
        jnp.int32(at), m, valid, tile_m=tile, interpret=True)
    gs_ref, ls_ref, c_ref = g._two_read_sums(
        X[rows], y[rows], w, None if valid is None else valid[rows])
    assert gs.dtype == ls.dtype == c.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == float(c_ref) == (valid[rows].sum() if with_valid
                                        else m)


@pytest.mark.parametrize("start", [-5, 0, 100, 128, 233, 300])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_lane_window_kernel_under_jit_with_a_traced_start(g, start):
    """One compiled program for every offset (``start`` is an operand),
    each gradient's rule, a negative start clamped to row 0."""
    import jax
    import jax.numpy as jnp

    n, m = 333, 100
    X, y, w = _data(n=n, d=16, seed=5,
                    classify=not isinstance(g, LeastSquaresGradient))
    fn = jax.jit(lambda s: fused_window_sums(
        g.pointwise, X, y, w, s, m, tile_m=128, interpret=True))
    gs, ls, c = fn(jnp.int32(start))
    lo = min(max(start, 0), n - m)
    gs_ref, ls_ref, _ = g._two_read_sums(X[lo:lo + m], y[lo:lo + m], w, None)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=2e-4,
                               atol=2e-3)
    np.testing.assert_allclose(float(ls), float(ls_ref), rtol=2e-4)
    assert float(c) == m


def test_lane_window_kernel_refuses_a_window_larger_than_x():
    X, y, w = _data(n=300, d=16)
    with pytest.raises(ValueError, match="a window of 301 rows in 300"):
        fused_window_sums(GRADS[0].pointwise, X, y, w, 0, 301, interpret=True)


# -- the selection inside Gradient.batch_sums ----------------------------------

def _selection_case(name):
    """``(X, y, w, mask, margin_axis_name)`` of (1024, 1000) bf16 rows —
    a shape the chip stores feature-major — with one observable changed."""
    import jax
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
    elif name == "row_major_odd_width":
        # stored by rows (1020 pads to 1024 either way) with padded lanes:
        # no by-rows block, and a block of X.T would be handed a copy
        X = jnp.zeros((n, 1020), jnp.bfloat16)
        w = jnp.zeros((1020,))
    elif name in ("too_wide", "wide"):
        # RCV1's width: the wide form; ten times it: no form's
        d = {"wide": 47_236, "too_wide": 472_364}[name]
        X = jax.ShapeDtypeStruct((256, d), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((d,), jnp.float32)
        y, mask = y[:256], mask[:256]
    elif name == "labels_per_class":
        y = jnp.zeros((n, 2))
    else:
        assert name == "feature_major", name
    return X, y, w, mask, axis


OFF = ["bcoo", "matrix_weights", "margin_axis_name", "integer_rows",
       "row_major_odd_width", "too_wide", "labels_per_class"]


@pytest.mark.parametrize("case", ["feature_major", "wide",
                                  "row_major_width"] + OFF)
def test_one_read_of_follows_what_the_operands_look_like(case):
    from tpu_sgd.ops.gradients import one_read_of

    X, y, w, mask, axis = _selection_case(case)
    on = case in ("feature_major", "wide", "row_major_width")
    assert (one_read_of(X, y, w, mask, axis) is not None) == on
    assert (one_read_of(X, y, w, None, axis) is not None) == on
    # the window's kernel has no wide form and no by-rows form
    assert (one_read_of(X, y, w, None, axis, window=X.shape[0] // 10)
            is not None) == (case == "feature_major")
    own = one_read_of(X, y, w, mask, axis)
    assert (own and (own.tile, own.feature_blocks)) == {
        "feature_major": (1024, 1), "wide": (256, 8),
        "row_major_width": (1024, 1)}.get(case)


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
    assert "sgd.fused_sums/jit(_fused_scan_sums)" in tpu
    # each half keeps its own scope on what it leaves outside the kernel
    # (w along the lanes, the fold of the lane partials): the benchmark's
    # margins_ms and gradient_ms still find an operation to read
    assert "sgd.margins/broadcast_in_dim" in tpu
    assert "sgd.gradient/reduce_sum" in tpu
    assert "sgd.fused_sums" not in cpu and "sgd.margins" in cpu


@pytest.mark.parametrize("case", ["row_major_odd_width", "integer_rows",
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


@pytest.mark.parametrize("with_valid", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_window_sums_lowers_the_kernel_for_a_tpu_and_two_matvecs_here(
        g, with_valid):
    """``window_sums`` selects as ``batch_sums`` does: lowered for a TPU the
    window is ONE Mosaic call under ``sgd.fused_sums`` in a jitted function
    of its own name, X is not sliced (the only ``dynamic_slice`` left would
    be of X's size: there is none), and each half keeps its scope outside
    the kernel; lowered for the CPU it is the slice and the two
    ``dot_general`` it always was."""
    import jax.numpy as jnp

    X, y, w, mask, _ = _selection_case("feature_major")
    valid = mask if with_valid else None
    fn = lambda X, y, w, s, v: g.window_sums(X, y, w, s, 100, v)  # noqa: E731
    tpu = _lowered_for("tpu", fn, X, y, w, jnp.int32(7), valid)
    assert tpu.count("tpu_custom_call") >= 1
    assert "stablehlo.dot_general" not in tpu
    assert "stablehlo.dynamic_slice" not in tpu
    assert "sgd.fused_sums/jit(_fused_lane_window_sums)" in tpu
    assert "sgd.margins/broadcast_in_dim" in tpu
    assert "sgd.gradient/reduce_sum" in tpu
    cpu = _lowered_for("cpu", fn, X, y, w, jnp.int32(7), valid)
    assert cpu.count("stablehlo.dot_general") == 2
    assert "tpu_custom_call" not in cpu and "sgd.fused_sums" not in cpu
    assert "stablehlo.dynamic_slice" in cpu


@pytest.mark.parametrize("case", ["row_major_width", "row_major_odd_width",
                                  "integer_rows", "too_wide",
                                  "margin_axis_name", "wide"])
def test_window_sums_keeps_two_matvecs_on_a_tpu_where_the_kernel_is_off(case):
    import jax
    import jax.numpy as jnp

    X, y, w, mask, axis = _selection_case(case)
    fn = lambda X, y, w, s: LogisticGradient().window_sums(  # noqa: E731
        X, y, w, s, 100, margin_axis_name=axis)
    if axis is not None:  # a feature-sharded run: the psum needs its axis
        from jax.sharding import Mesh, PartitionSpec as P

        from tpu_sgd.parallel.mesh import MODEL_AXIS, shard_map_fn

        mesh = Mesh(np.asarray(jax.devices()[:1]), (MODEL_AXIS,))
        fn = shard_map_fn(mesh, fn, (P(), P(), P(), P()), (P(), P(), P()))
    tpu = _lowered_for("tpu", fn, X, y, w, jnp.int32(7))
    assert "tpu_custom_call" not in tpu
    assert tpu.count("stablehlo.dot_general") == 2


def test_window_sums_on_the_cpu_is_bitwise_the_two_matvecs():
    """Run here, the selection changes no bit of any sum."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import _window_sums

    g = LogisticGradient()
    X, y, w = _data(n=1024, d=1000, seed=23, classify=True)
    valid = np.random.default_rng(24).uniform(size=1024) < 0.9
    for v in (None, valid):
        new = jax.jit(g.window_sums, static_argnums=4)(
            X, y, w, jnp.int32(500), 102, v)
        old = jax.jit(lambda s: _window_sums(
            g._two_read_sums, X, y, w, s, 102, v, None))(jnp.int32(500))
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


# -- row operands laid out once a fit (PR 33) ---------------------------------

#: the three kernel entries and which masks they take: the labels (and a mask
#: that is the same every step) reach them as ``(n,)`` or already as the
#: ``(1, n)`` float32 rows ``Gradient.row_operands`` lays out before a loop
ROW_ENTRIES = ["gradient_all", "gradient_masked", "window_all",
               "window_valid", "classes_all", "classes_masked"]


def _entry_sums(entry, labels, mask):
    """The entry's sums over 333 rows (no multiple of 128: two blocks and
    a cut one) in the interpreter, with the labels and the mask as handed."""
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.ops.pallas_kernels import fused_class_sums

    n, d, K = 333, 24, 10
    X, _, w = _data(n=n, d=d, seed=31)
    kind = entry.split("_")[0]
    if kind == "classes":
        g = MultinomialLogisticGradient(K)
        W = np.random.default_rng(32).normal(size=(K - 1, d)) * 0.1
        return fused_class_sums(g.class_rule, jnp.asarray(X, jnp.bfloat16),
                                labels, jnp.asarray(W, jnp.float32), mask,
                                tile_m=128, interpret=True)
    g = LogisticGradient()
    if kind == "window":
        return fused_window_sums(g.pointwise, X, labels, w, jnp.int32(57),
                                 100, mask, tile_m=128, interpret=True)
    return fused_gradient_sums(g.pointwise, X, labels, w, mask, tile_m=128,
                               interpret=True)


@pytest.mark.parametrize("entry", ROW_ENTRIES)
def test_rows_laid_out_before_the_call_give_the_sums_bit_for_bit(entry):
    """Labels (and the mask) handed as ``(1, n)`` float32 go to the kernel
    as they are and give the sums of the ``(n,)`` ones, bit for bit: the
    entries select on the SHAPE they are handed, no argument says which."""
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import row_operand

    n = 333
    r = np.random.default_rng(33)
    y = jnp.asarray(r.integers(0, 10 if entry.startswith("classes") else 2,
                               n), jnp.float32)
    mask = None if entry.endswith("_all") else jnp.asarray(
        r.uniform(size=n) < 0.6)
    flat = _entry_sums(entry, y, mask)
    y_row = row_operand(y, n)
    assert y_row.shape == (1, n) and row_operand(y_row, n) is y_row
    laid = _entry_sums(entry, y_row,
                       None if mask is None else row_operand(mask, n))
    for a, b in zip(laid, flat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the labels alone laid out, the mask as the step draws it: (n,)
    if mask is not None:
        for a, b in zip(_entry_sums(entry, y_row, mask), flat):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_case(name):
    """``(gradient, config, X, y, w0, valid)`` of a 333-row fit."""
    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient

    n, d, K = 333, 24, 10
    X, y, _ = _data(n=n, d=d, seed=41, classify=True)
    r = np.random.default_rng(42)
    valid = (r.uniform(size=n) < 0.8) if name.endswith("_valid") else None
    base = dict(step_size=0.5, num_iterations=4, reg_param=0.01,
                convergence_tol=0.0)
    if name.startswith("logistic_masked"):
        g, cfg = LogisticGradient(), SGDConfig(mini_batch_fraction=0.4,
                                               **base)
    elif name.startswith("least_squares_sliced"):
        g, cfg = LeastSquaresGradient(), SGDConfig(
            mini_batch_fraction=0.4, sampling="sliced", **base)
    else:
        assert name.startswith("ten_classes_full_batch"), name
        g, cfg = MultinomialLogisticGradient(K), SGDConfig(
            mini_batch_fraction=1.0, **base)
        y = r.integers(0, K, n).astype(np.float32)
    return g, cfg, X, y, np.zeros(g.weight_dim(d), np.float32), valid


RUN_CASES = ["logistic_masked", "logistic_masked_valid",
             "least_squares_sliced", "least_squares_sliced_valid",
             "ten_classes_full_batch", "ten_classes_full_batch_valid"]


def _as_lowered_for_a_tpu(monkeypatch):
    """``ops/gradients.py`` as a program lowered for a TPU has it, run here:
    ``platform_dependent`` takes its ``tpu`` branch and the kernels run in
    the interpreter.  Returns the list the entries' label shapes go to."""
    import functools

    import jax

    from tpu_sgd.ops import gradients, pallas_kernels

    class Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def platform_dependent(*args, tpu, default):
            return tpu(*args)

    class Jax:
        lax = Lax()

        def __getattr__(self, name):
            return getattr(jax, name)

    monkeypatch.setattr(gradients, "jax", Jax())
    seen = []
    for name in ("fused_gradient_sums", "fused_window_sums",
                 "fused_class_sums"):
        def entry(rule, X, y, *args, _kernel=getattr(pallas_kernels, name),
                  **kw):
            seen.append(tuple(y.shape))
            return _kernel(rule, X, y, *args, tile_m=128, interpret=True,
                           **kw)

        monkeypatch.setattr(pallas_kernels, name,
                            functools.wraps(getattr(pallas_kernels, name))(
                                entry))
    return seen


@pytest.mark.parametrize("case", RUN_CASES)
def test_a_fit_with_the_rows_prepared_is_the_fit_without_bit_for_bit(
        case, monkeypatch):
    """``make_run``'s fit on the path a TPU takes (the kernel, here in the
    interpreter): with the labels laid out once before the loop the kernel
    is handed the ``(1, n)`` row every step, without them the ``(n,)``
    labels, and weights, loss history and count are the same bits.  So are
    they on the two-read path this CPU takes, where the row rides unread."""
    import jax

    from tpu_sgd.ops.gradients import step_sums
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize import gradient_descent as gd

    g, cfg, X, y, w0, valid = _run_case(case)
    n = X.shape[0]
    assert step_sums(g, cfg, X, y, w0, valid).kernel is not None

    def fit(prepared):
        with monkeypatch.context() as m:
            if not prepared:
                m.setattr(gd, "prepare_rows", lambda *a, **k: None)
            run = jax.jit(gd.make_run(g, SquaredL2Updater(), cfg))
            return [np.asarray(a)
                    for a in run(w0, X, y, cfg.hyper(), valid)]

    here = fit(True)
    for a, b in zip(here, fit(False)):
        np.testing.assert_array_equal(a, b)
    seen = _as_lowered_for_a_tpu(monkeypatch)
    with_rows = fit(True)
    assert seen and set(seen) == {(1, n)}
    del seen[:]
    without = fit(False)
    assert seen and set(seen) == {(n,)}
    for a, b in zip(with_rows, without):
        np.testing.assert_array_equal(a, b)
    assert int(with_rows[2]) == cfg.num_iterations
    # and the kernel's fit is the two-read fit to rounding
    np.testing.assert_allclose(with_rows[0], here[0], rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("case", ["indexed", "bcoo", "row_major_width",
                                  "feature_sharded", "statistics",
                                  "classes_sliced"])
def test_no_rows_are_prepared_where_no_kernel_reads_them(case):
    """Where the step's sums are not the one-read kernel the step takes
    ``y`` as it is: ``prepare_rows`` makes nothing and ``labels_prepared``
    reads 0."""
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import (MultinomialLogisticGradient,
                                       step_sums)
    from tpu_sgd.ops.gram import GramLeastSquaresGradient
    from tpu_sgd.optimize import gradient_descent as gd

    n, d = 1024, 1000
    X, y, w = jnp.zeros((n, d), jnp.bfloat16), jnp.zeros(n), jnp.zeros(d)
    g, axis = LogisticGradient(), None
    kw = dict(mini_batch_fraction=0.1)
    if case == "indexed":
        kw["sampling"] = "indexed"
    elif case == "bcoo":
        X, _, _, _, _ = _selection_case("bcoo")
    elif case == "row_major_width":
        # by rows with padded lanes: no by-rows block (1024 has one, PR 39)
        X, w = jnp.zeros((n, 1020), jnp.bfloat16), jnp.zeros(1020)
    elif case == "feature_sharded":
        axis = "model"
    elif case == "statistics":
        g = GramLeastSquaresGradient()
    else:
        g, kw["sampling"] = MultinomialLogisticGradient(3), "sliced"
        w = jnp.zeros(2 * d)
    cfg = SGDConfig(**kw)
    assert step_sums(g, cfg, X, y, w, None, axis).kernel is None
    assert gd.prepare_rows(g, cfg, X, y, w, None, axis) is None


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_train_run_says_whether_the_labels_were_prepared(backend,
                                                         monkeypatch):
    """``train.run``'s ``labels_prepared``: 1 where the fit's program lays
    the labels out before its loop for the kernel (a TPU, the one-read
    step; a shard's operands under a mesh), 0 where the step takes ``y``
    as it is: every fit on a CPU, a gathered batch, rows stored by rows at
    a width that is no multiple of 128 (at one that is, the by-rows kernel
    reads the row: PR 39)."""
    import jax

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, dict(payload)))

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    X, y, _ = _data(n=512, d=24, seed=51, classify=True)
    wide = _data(n=512, d=128, seed=52)[0]  # (512, 128): stored by rows
    odd = _data(n=512, d=124, seed=53)[0]  # by rows too, lanes padded

    def fit(X, mesh=None, **kw):
        opt = tpu_sgd.GradientDescent(
            LogisticGradient(), tpu_sgd.SquaredL2Updater()
        ).set_num_iterations(2).set_mini_batch_fraction(0.5)
        if mesh is not None:
            opt.set_mesh(mesh)
        if "sampling" in kw:
            opt.set_sampling(kw["sampling"])
        opt.optimize_with_history((X, y), np.zeros(X.shape[1], np.float32))

    sink = Sink()
    enable_tracing(sink)
    try:
        fit(X)
        fit(X, sampling="sliced")
        fit(X, mesh=tpu_sgd.data_mesh(jax.devices()[:4]))
        fit(X, sampling="indexed")
        fit(odd)
        fit(wide)
    finally:
        disable_tracing()
    runs = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.run"]
    assert [r["path"] for r in runs] == ["fused", "fused", "mesh", "fused",
                                         "fused", "fused"]
    assert [r["labels_prepared"] for r in runs] == (
        [1, 1, 1, 0, 0, 1] if backend == "tpu" else [0] * 6)
    # the rows a grid step of the step's kernel takes (a shard's 128 under
    # the mesh), 0 where the step is no kernel; the width is never cut
    assert [r["row_tile"] for r in runs] == (
        [512, 512, 128, 0, 0, 512] if backend == "tpu" else [0] * 6)
    assert [r["feature_blocks"] for r in runs] == [1] * 6
    assert [r["by_rows"] for r in runs] == (
        [0, 0, 0, 0, 0, 1] if backend == "tpu" else [0] * 6)


# -- the full scan bounded by a row count that is an operand (PR 52) ----------

@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
@pytest.mark.parametrize("rows", [1, 127, 128, 129, 300, 511, 512, 1000,
                                  1024])
def test_bounded_kernel_is_the_full_scan_over_the_real_rows(g, rows):
    """``fused_bound_sums`` over a capacity of 1,024 rows whose tail holds
    NaN: the sums are the full scan's over the first ``rows`` rows at the
    same tile (bit for bit where the same bodies run over the same blocks
    in the same order: a last block that is cut in both), the count is
    ``rows``, and nothing past them is read into a sum."""
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import fused_bound_sums

    X, y, w = _data(n=1024, d=40, seed=5, classify=True)
    X[rows:], y[rows:] = np.nan, np.nan
    got = fused_bound_sums(g.pointwise, jnp.asarray(X, jnp.bfloat16), y, w,
                           jnp.int32(rows), tile_m=256, interpret=True)
    # the full scan at the same tile (a block count the rows fill)
    want = fused_gradient_sums(
        g.pointwise, jnp.asarray(X[:rows], jnp.bfloat16), y[:rows], w,
        tile_m=256, interpret=True)
    assert float(got[2]) == float(want[2]) == rows
    # the full scan floors its tile to the rows there are, and runs the
    # uncut body over a last block that the rows fill
    if rows > 256 and rows % 256:
        for a, b in zip(got[:2], want[:2]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(float(got[1]), float(want[1]), rtol=1e-5)


def test_bounded_kernel_takes_its_count_traced_and_clamps_it():
    """One jitted program for every count; a count of 0 or over the
    capacity is clamped to ``[1, capacity]`` (the caller hands real
    counts)."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import fused_bound_sums

    g = LogisticGradient()
    X, y, w = _data(n=512, d=16, seed=6, classify=True)
    fn = jax.jit(lambda rows: fused_bound_sums(
        g.pointwise, X, y, w, rows, tile_m=128, interpret=True))
    for rows in (5, 200, 512):
        got = fn(jnp.int32(rows))
        want = g._two_read_sums(jnp.asarray(X[:rows]), jnp.asarray(y[:rows]),
                                jnp.asarray(w), None)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=2e-4, atol=2e-3)
        assert float(got[2]) == rows
    assert fn._cache_size() == 1
    assert float(fn(jnp.int32(0))[2]) == 1
    assert float(fn(jnp.int32(9999))[2]) == 512


def test_a_row_count_goes_to_the_kernel_as_a_scalar_on_a_tpu_and_a_mask_here():
    """``batch_sums`` handed a ``RowCount`` in the mask's place: lowered for
    a TPU the bounded call (no ``(n,)`` mask is made), lowered for the CPU
    the two matvecs under ``arange(n) < rows``; its sums here are the
    masked ones bit for bit.  ``rows_valid`` keeps the count where the
    step's kernel bounds its grid by it and makes the array elsewhere."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import (MultinomialLogisticGradient,
                                       RowCount, rows_valid)

    g = LogisticGradient()
    X, y, w, _, _ = _selection_case("feature_major")
    n = X.shape[0]
    count = RowCount(jnp.int32(n - 37))
    tpu = _lowered_for("tpu", g.batch_sums, X, y, w, count)
    assert "tpu_custom_call" in tpu and "stablehlo.dot_general" not in tpu
    assert "sgd.fused_sums/jit(_fused_bound_sums)" in tpu
    assert "tensor<%dxi1>" % n not in tpu
    cpu = _lowered_for("cpu", g.batch_sums, X, y, w, count)
    assert cpu.count("stablehlo.dot_general") == 2
    mask = jnp.arange(n) < n - 37
    for a, b in zip(g.batch_sums(X, y, w, count),
                    g.batch_sums(X, y, w, mask)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full = SGDConfig(mini_batch_fraction=1.0)
    assert rows_valid(g, full, X, y, w, count) is count
    assert rows_valid(g, full, X, y, w, None) is None
    assert rows_valid(g, full, X, y, w, mask) is mask
    # a sampled fit, a matrix of weights: the array a padded shard hands on
    for grad, weights, cfg in (
            (g, w, SGDConfig(mini_batch_fraction=0.5)),
            (MultinomialLogisticGradient(3),
             jnp.zeros(2 * X.shape[1], jnp.float32), full)):
        made = rows_valid(grad, cfg, X, y, weights, count)
        np.testing.assert_array_equal(np.asarray(made), np.asarray(mask))
