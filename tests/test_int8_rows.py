"""Rows kept in 8 bits (PR 57): ``int8`` rows are trained AS THE INTEGERS THEY
ARE.  Every int8 is exact in bfloat16, so their products run as a bf16
dataset's of the same values do (``ops/pallas_kernels.operand_dtype``, which
``ops/gradients.matmul_dtype`` reads): the by-rows class kernel reads each
``(tile, d)`` int8 block once and widens it in VMEM, the two matmuls widen
inside their fusions, and a fit's numbers do not depend on which body ran.
Here, on the CPU (the kernels in the interpreter): the contract over the
types; the kernel against the plain f32 sums and BIT-EQUAL to the bf16 body
on ``X.astype(bfloat16)``, for a matrix of weights and a vector as rows; the
whole fit on both paths; the optimizer boundary keeps the bytes; the runner
store's key; and ``train.run``'s two attributes."""

import contextlib
import functools

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (HingeGradient, LeastSquaresGradient,
                                   LogisticGradient,
                                   MultinomialLogisticGradient, matmul_dtype,
                                   one_read_of)
from tpu_sgd.ops.pallas_kernels import (class_rows_of, fused_class_sums,
                                        fused_rows_sums, one_read,
                                        operand_dtype)

TILE = 256
#: rows against a tile of 256: whole tiles, one row past them (the cut
#: block's 255 other rows are NaN in the interpreter), a ragged count
ROWS = {"whole_tiles": 512, "one_past": 513, "ragged": 500}


def _int8_rows(n, d, seed):
    """``clip(round(64 z))`` as the cell's generator draws them, and the
    edges of the type in the first row."""
    import jax.numpy as jnp

    r = np.random.default_rng(seed)
    q = np.clip(np.round(64 * r.normal(size=(n, d))), -128, 127)
    q[0, :4] = (-128, 127, 0, -1)
    return r, jnp.asarray(q, jnp.int8)


# -- the contract -------------------------------------------------------------------

@pytest.mark.parametrize("dtype,operand", [
    ("bfloat16", "bfloat16"), ("float32", "float32"), ("int8", "bfloat16"),
    ("uint8", "bfloat16"), ("bool", "float32"), ("int32", "float32")])
def test_matmul_dtype_is_the_operand_type_of_the_rows_type(dtype, operand):
    """Float rows in their own type; 8-bit integers as bf16 (exact); bool
    and wider integers (one-hot paths) in f32, the weights never truncated
    for them.  One home: the kernels' ``operand_dtype``."""
    import jax
    import jax.numpy as jnp

    X = jax.ShapeDtypeStruct((8, 128), jnp.dtype(dtype))
    assert jnp.dtype(matmul_dtype(X)) == jnp.dtype(operand)
    assert operand_dtype(dtype) == jnp.dtype(operand)
    # the class rows are padded to the OPERANDS' packed register
    assert class_rows_of(4, dtype) == (16 if operand == "bfloat16" else 8)
    assert class_rows_of(999, dtype) == (1008 if operand == "bfloat16"
                                         else 1000)


def test_every_int8_is_exact_in_bfloat16():
    import jax.numpy as jnp

    q = jnp.arange(-128, 128, dtype=jnp.int32).astype(jnp.int8)
    back = q.astype(jnp.bfloat16).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(back), np.arange(-128, 128))
    u = jnp.arange(0, 256, dtype=jnp.int32).astype(jnp.uint8)
    np.testing.assert_array_equal(
        np.asarray(u.astype(jnp.bfloat16).astype(jnp.int32)),
        np.arange(0, 256))


# -- the selection -------------------------------------------------------------------

@pytest.mark.parametrize("case,n,d,classes,masked,want", [
    ("the_cell", 4_001_792, 3072, 10, False, ("class", 2048, 16)),
    ("one_part", 1_000_448, 3072, 10, False, ("class", 2048, 16)),
    ("masked", 4_001_792, 3072, 10, True, ("class", 2048, 16)),
    ("vector_1024", 2_097_152, 1024, None, False, ("class", 2048, 0)),
    ("feature_major_width", 2**20, 1000, None, False, None),
    ("feature_major_classes", 8_100_000, 784, 10, False, None),
    ("by_rows_no_lane_multiple", 2**20, 1020, None, False, None)])
def test_one_read_of_admits_int8_rows_by_rows_alone(case, n, d, classes,
                                                    masked, want):
    import jax
    import jax.numpy as jnp

    shape = jax.ShapeDtypeStruct
    wd = d if classes is None else (classes - 1) * d
    k = one_read_of(shape((n, d), jnp.int8), shape((n,), jnp.float32),
                    shape((wd,), jnp.float32),
                    shape((n,), bool) if masked else None, classes=classes)
    if want is None:
        assert k is None
        return
    assert (k.body, k.tile, k.class_rows) == want and k.by_rows
    assert (k.item_bytes, k.operand) == (1, "bfloat16")
    assert not k.draws and not k.bounds and not k.ahead
    # the same rows as bf16: the record of the bf16 body, two bytes a feature
    twin = one_read_of(shape((n, d), jnp.bfloat16), shape((n,), jnp.float32),
                       shape((wd,), jnp.float32),
                       shape((n,), bool) if masked else None, classes=classes)
    assert (twin.item_bytes, twin.operand, twin.body, twin.class_rows) == (
        2, "bfloat16", k.body, k.class_rows)
    assert twin.tile <= k.tile  # a block of int8 rows is half the bytes


def test_the_record_says_what_a_feature_costs_and_what_the_operands_are():
    assert [(one_read(2**20, 1024, i, False, 16).item_bytes,
             one_read(2**20, 1024, i, False, 16).operand)
            for i in (1, 2, 4)] == [(1, "bfloat16"), (2, "bfloat16"),
                                    (4, "float32")]
    assert one_read(4_194_304, 1000, 2).item_bytes == 2
    # uint8, bool, int32 rows: no kernel; two reads under the contract
    import jax
    import jax.numpy as jnp

    shape = jax.ShapeDtypeStruct
    for dtype in (jnp.uint8, jnp.bool_, jnp.int32):
        assert one_read_of(shape((2**20, 1024), dtype),
                           shape((2**20,), jnp.float32),
                           shape((1024,), jnp.float32)) is None


# -- the kernel, in the interpreter ---------------------------------------------------

@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("K,d", [(10, 128), (3, 128), (10, 384)])
def test_int8_class_sums_match_f32_and_are_the_bf16_bodys_bit_for_bit(
        K, d, rows, masked):
    import jax.numpy as jnp

    n = ROWS[rows]
    r, X = _int8_rows(n, d, seed=K * d + n)
    y = jnp.asarray(r.integers(0, K, n), jnp.float32)
    W = jnp.asarray(r.normal(size=(K - 1, d)) * 0.3 / (64 * np.sqrt(d)),
                    jnp.float32)
    mask = (r.uniform(size=n) < 0.4) if masked else None
    g = MultinomialLogisticGradient(K)
    got = fused_class_sums(g.class_rule, X, y, W, mask, tile_m=TILE,
                           interpret=True, by_rows=True)
    bf16 = fused_class_sums(g.class_rule, X.astype(jnp.bfloat16), y, W, mask,
                            tile_m=TILE, interpret=True, by_rows=True)
    for a, b in zip(got, bf16):
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the plain reference: f32 rows, f32 weights and coefficients
    exact = g._two_read_sums(X.astype(jnp.float32), y, W.reshape(-1), mask)
    scale = float(jnp.max(jnp.abs(exact[0])))
    np.testing.assert_allclose(np.asarray(got[0]).reshape(-1),
                               np.asarray(exact[0]), atol=4e-3 * scale)
    np.testing.assert_allclose(float(got[1]), float(exact[1]), rtol=2e-4)
    assert float(got[2]) == float(exact[2]) == (mask.sum() if masked else n)
    # and the two matmuls on the int8 rows themselves (contract 1)
    two = g._two_read_sums(X, y, W.reshape(-1), mask)
    np.testing.assert_allclose(np.asarray(got[0]).reshape(-1),
                               np.asarray(two[0]), atol=1e-5 * scale)
    np.testing.assert_allclose(float(got[1]), float(two[1]), rtol=1e-6)


def test_the_sums_do_not_depend_on_the_row_tile_at_one_lane_chunk():
    """Full blocks are taken in lane chunks, in order, whatever the tile:
    the int8 body's own tile (2,048 at 3,072 features, where a bf16 block
    of that many rows does not fit) adds the same chunks in the same order
    as the bf16 body's 1,024."""
    import jax.numpy as jnp

    n, d, K = 2048 + 37, 128, 10
    r, X = _int8_rows(n, d, seed=5)
    y = jnp.asarray(r.integers(0, K, n), jnp.float32)
    W = jnp.asarray(r.normal(size=(K - 1, d)) * 0.004, jnp.float32)
    g = MultinomialLogisticGradient(K)
    at_2048 = fused_class_sums(g.class_rule, X, y, W, None, tile_m=2048,
                               interpret=True, by_rows=True)
    at_1024 = fused_class_sums(g.class_rule, X.astype(jnp.bfloat16), y, W,
                               None, tile_m=1024, interpret=True,
                               by_rows=True)
    for a, b in zip(at_2048, at_1024):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


GRADS = {"logistic": LogisticGradient(), "hinge": HingeGradient(),
         "least_squares": LeastSquaresGradient()}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("rows", ["one_past", "ragged"])
@pytest.mark.parametrize("name", sorted(GRADS))
def test_int8_rows_sums_take_a_vector_as_rows_like_the_bf16_body(
        name, rows, masked):
    import jax.numpy as jnp

    n, d, g = ROWS[rows], 128, GRADS[name]
    r, X = _int8_rows(n, d, seed=n + len(name))
    y = jnp.asarray(r.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(r.normal(size=(d,)) / (64 * np.sqrt(d)), jnp.float32)
    mask = (r.uniform(size=n) < 0.4) if masked else None
    got = fused_rows_sums(g.pointwise, X, y, w, mask, tile_m=TILE,
                          interpret=True)
    bf16 = fused_rows_sums(g.pointwise, X.astype(jnp.bfloat16), y, w, mask,
                           tile_m=TILE, interpret=True)
    for a, b in zip(got, bf16):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the vector rides as three bf16 rows: against f32 operands it loses
    # nothing, so these are the float32 sums
    exact = g._two_read_sums(X.astype(jnp.float32), y, w, mask)
    scale = float(jnp.max(jnp.abs(exact[0])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(exact[0]),
                               atol=2e-5 * scale)
    np.testing.assert_allclose(float(got[1]), float(exact[1]), rtol=2e-5)
    assert float(got[2]) == float(exact[2]) == (mask.sum() if masked else n)


# -- the fit ------------------------------------------------------------------------

def _as_lowered_for_a_tpu(monkeypatch, tile):
    """``ops/gradients.py`` as a program lowered for a TPU has it, run here:
    ``platform_dependent`` takes its ``tpu`` branch and the class kernel runs
    in the interpreter at ``tile`` rows a block.  Returns the types of the
    rows the entry was handed."""
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
    kernel = pallas_kernels.fused_class_sums

    @functools.wraps(kernel)
    def entry(rule, X, y, *args, **kw):
        seen.append(str(X.dtype))
        return kernel(rule, X, y, *args, tile_m=tile, interpret=True, **kw)

    monkeypatch.setattr(pallas_kernels, "fused_class_sums", entry)
    return seen


def _fit(X, y, K=10, iterations=12):
    import jax

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize import gradient_descent as gd

    cfg = SGDConfig(step_size=2.0 ** -12, num_iterations=iterations,
                    reg_param=4.096, mini_batch_fraction=1.0,
                    convergence_tol=0.0)
    g = MultinomialLogisticGradient(K)
    run = jax.jit(gd.make_run(g, SquaredL2Updater(), cfg))
    w0 = np.zeros(g.weight_dim(X.shape[1]), np.float32)
    return [np.asarray(a) for a in run(w0, X, y, cfg.hyper())]


def test_the_int8_fit_is_the_bf16_fit_bit_for_bit_on_both_paths(monkeypatch):
    """The configuration's check, small: the fit on int8 rows and the fit on
    the same values as bf16 rows give the same weights and the same losses,
    through the two matmuls (this CPU) and through the kernel at one row
    tile (``tile_m`` passed); the one-read fit is the two-read fit within
    the configuration's limits."""
    import json
    import os

    import jax.numpy as jnp

    n, d, K = 1000, 128, 10
    r, X = _int8_rows(n, d, seed=57)
    y = jnp.asarray(r.integers(0, K, n), jnp.float32)
    two = _fit(X, y)
    for a, b in zip(two, _fit(X.astype(jnp.bfloat16), y)):
        np.testing.assert_array_equal(a, b)
    seen = _as_lowered_for_a_tpu(monkeypatch, tile=256)
    one = _fit(X, y)
    assert seen and set(seen) == {"int8"}  # the kernel read the bytes
    del seen[:]
    for a, b in zip(one, _fit(X.astype(jnp.bfloat16), y)):
        np.testing.assert_array_equal(a, b)
    assert set(seen) == {"bfloat16"}
    assert two[1][-1] < 0.95 * two[1][0] and int(one[2]) == 12
    # one read against two: inside every limit of the cell's configuration
    from bench import correct  # the comparison that decides ``correct``

    with open(os.path.join(os.path.dirname(__file__), "..", "bench",
                           "configs", "cifar5m-int8-multinomial.json")) as f:
        limits = json.load(f)["limits"]
    w0 = np.zeros_like(one[0])
    got = correct.readings(one[0], one[1], two[0], two[1], w0)
    for name in correct.NUMBERS:
        assert got[name] <= limits[name], (name, got)


@contextlib.contextmanager
def _rows_handed_on(monkeypatch):
    """The types of the rows ``_optimize`` hands its routes, fit by fit."""
    from tpu_sgd.optimize import gradient_descent as gd

    handed = []
    routed = gd.GradientDescent._optimize_routed

    def spy(self, X, *a, **kw):
        handed.append(str(X.dtype))
        return routed(self, X, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(gd.GradientDescent, "_optimize_routed", spy)
        yield handed


def test_the_optimizer_boundary_keeps_8_bit_rows_and_widens_the_others(
        monkeypatch):
    """``optimize_with_history`` on int8 rows trains the bytes it was handed
    (a device array and a numpy one alike) and gives the bf16 fit's bits;
    ``bool`` and wider integers are cast to f32 as before."""
    import jax.numpy as jnp

    import tpu_sgd

    n, d, K = 600, 128, 10
    r, X = _int8_rows(n, d, seed=8)
    y = r.integers(0, K, n).astype(np.float32)

    def fit(X):
        opt = (tpu_sgd.GradientDescent(MultinomialLogisticGradient(K),
                                       tpu_sgd.SquaredL2Updater())
               .set_step_size(2.0 ** -12).set_num_iterations(8)
               .set_reg_param(4.096).set_mini_batch_fraction(1.0)
               .set_convergence_tol(0.0))
        w, losses = opt.optimize_with_history(
            (X, y), np.zeros((K - 1) * d, np.float32))
        return np.asarray(w), np.asarray(losses)

    with _rows_handed_on(monkeypatch) as handed:
        fits = [fit(a) for a in (X, np.asarray(X), X.astype(jnp.bfloat16),
                                 np.asarray(X).astype(np.uint8),
                                 np.asarray(X).astype(np.int32),
                                 np.asarray(X) > 0)]
    assert handed == ["int8", "int8", "bfloat16", "uint8", "float32",
                      "float32"]
    for w, losses in fits[1:3]:
        np.testing.assert_array_equal(w, fits[0][0])
        np.testing.assert_array_equal(losses, fits[0][1])
    # wider integers: f32 operands, the weights not rounded to bf16
    assert np.isfinite(fits[4][1]).all()
    assert not np.array_equal(fits[4][0], fits[0][0])
    np.testing.assert_allclose(fits[4][0], fits[0][0], rtol=0.05, atol=1e-6)


@pytest.mark.parametrize("dtype", ["int8", "uint8", "int32"])
def test_the_model_harness_still_trains_integer_input_in_float32(
        monkeypatch, dtype):
    """``run()`` is not changed: its integer input goes to the device in
    its own type and is cast to float32 there, 8-bit rows too; said with
    the call, so the optimizer holds no state of it and the same instance
    keeps 8-bit rows at its own boundary afterwards."""
    import tpu_sgd

    r = np.random.default_rng(2)
    X = r.integers(0, 100, size=(300, 16)).astype(dtype)
    y = (X.astype(np.float32) @ r.uniform(-1, 1, 16).astype(np.float32))
    with _rows_handed_on(monkeypatch) as handed:
        alg = tpu_sgd.LinearRegressionWithSGD(1e-5, 5)
        state = dict(vars(alg.optimizer))
        model = alg.run((X, y))
        ref = tpu_sgd.LinearRegressionWithSGD(1e-5, 5).run(
            (X.astype(np.float32), y))
        alg.optimizer.optimize((X, y), np.zeros(16, np.float32))
    assert handed == ["float32", "float32",
                      dtype if X.itemsize == 1 else "float32"]
    np.testing.assert_array_equal(np.asarray(model.weights),
                                  np.asarray(ref.weights))
    assert set(vars(alg.optimizer)) == set(state)


def _classes_data(dtype, n=400, d=16, K=4, seed=5):
    r = np.random.default_rng(seed)
    X = r.integers(0, 100, size=(n, d)).astype(dtype)
    return X, r.integers(0, K, n).astype(np.float32), K


@pytest.mark.parametrize("dtype", ["int8", "uint8", "int32", "bool"])
@pytest.mark.parametrize("intercept", [False, True],
                         ids=["no_intercept", "intercept"])
def test_a_multinomial_run_trains_integer_input_in_float32_on_both_branches(
        monkeypatch, dtype, intercept):
    """``LogisticRegressionWithLBFGS.run`` with more than two classes calls
    the optimizer from a branch of its own where there is an intercept;
    with a ``GradientDescent`` put in the L-BFGS's place both go through
    the harness's one call, so one model has one numerics, the float32
    fit's bit for bit."""
    import tpu_sgd

    X, y, K = _classes_data(dtype)

    def run(X):
        alg = tpu_sgd.LogisticRegressionWithLBFGS()
        alg.set_num_classes(K).set_intercept(intercept)
        alg.optimizer = (
            tpu_sgd.GradientDescent(MultinomialLogisticGradient(K),
                                    tpu_sgd.SquaredL2Updater())
            .set_step_size(2.0 ** -12).set_num_iterations(6))
        return np.asarray(alg.run((X, y)).weights)

    with _rows_handed_on(monkeypatch) as handed:
        w, ref = run(X), run(X.astype(np.float32))
    assert handed == ["float32", "float32"]
    assert np.abs(ref).max() > 0
    np.testing.assert_array_equal(w, ref)


@pytest.mark.parametrize("dtype", ["int8", "uint8", "int32"])
@pytest.mark.parametrize("through", ["run", "optimize", "mesh"])
def test_the_normal_equations_solve_integer_rows_in_float32(dtype, through):
    """The solver is exact: 8-bit integer rows are not ``matmul_dtype``'s
    bf16 operands there (``y`` would be rounded to bf16 in ``X^T y``), at
    the harness and at the optimizer's own boundary, one device and a
    mesh: the float32 solve, on one device bit for bit."""
    import jax

    import tpu_sgd
    from tpu_sgd.optimize.normal import NormalEquations

    r = np.random.default_rng(4)
    X = r.integers(0, 100, size=(512, 12)).astype(dtype)
    # labels that bf16 does not hold
    y = (X.astype(np.float32) @ r.uniform(-1, 1, 12).astype(np.float32)
         + r.normal(size=512).astype(np.float32))

    def solve(X):
        if through == "run":
            return tpu_sgd.LinearRegressionWithNormal(0.01).run(
                (X, y)).weights
        opt = NormalEquations(0.01)
        if through == "mesh":
            opt.set_mesh(tpu_sgd.data_mesh(jax.devices()[:4]))
        return opt.optimize((X, y), np.zeros(12, np.float32))

    w, ref = np.asarray(solve(X)), np.asarray(solve(X.astype(np.float32)))
    assert np.isfinite(ref).all() and np.abs(ref).max() > 0
    if through == "mesh":
        # the shards' sums of rows widened inside the program are added in
        # another order on the CPU (1e-6, as before 8-bit rows had a
        # contract of their own); bf16 operands would read 1e-3
        np.testing.assert_allclose(w, ref, rtol=2e-5, atol=0)
    else:
        np.testing.assert_array_equal(w, ref)


@pytest.mark.parametrize("dtype", ["int8", "uint8"])
def test_a_host_streamed_run_trains_8_bit_input_in_float32(dtype):
    """No cast follows a streamed chunk's copy, so under ``run()`` 8-bit
    rows are cast on the host; at the optimizer's own boundary the streamed
    steps take them as they are (bf16 operands)."""
    import tpu_sgd

    r = np.random.default_rng(6)
    X = r.integers(0, 100, size=(600, 16)).astype(dtype)
    y = (X.astype(np.float32) @ r.uniform(-1, 1, 16).astype(np.float32))

    def run(X):
        alg = tpu_sgd.LinearRegressionWithSGD(1e-5, 5,
                                              mini_batch_fraction=0.5)
        alg.optimizer.set_host_streaming(True)
        return alg, np.asarray(alg.run((X, y)).weights)

    (alg, w), (_, ref) = run(X), run(X.astype(np.float32))
    np.testing.assert_array_equal(w, ref)
    at_boundary = np.asarray(alg.optimizer.optimize(
        (X, y), np.zeros(16, np.float32)))
    assert np.isfinite(at_boundary).all()
    assert not np.array_equal(at_boundary, ref)
    np.testing.assert_allclose(at_boundary, ref, rtol=0.05, atol=1e-7)


def test_the_runner_stores_key_tells_int8_rows_from_bf16_ones():
    """``run_store._leaf_state`` keys a leaf by its dtype: nothing to change
    for 8-bit rows, and an int8 fit never restores a bf16 fit's export."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize import run_store

    cfg = SGDConfig(step_size=2.0 ** -12, num_iterations=5, reg_param=4.096,
                    mini_batch_fraction=1.0)
    plugins = tuple(run_store.plugin_state(p) for p in (
        MultinomialLogisticGradient(10), SquaredL2Updater(), cfg))
    assert None not in plugins
    keys = {}
    for dtype in (jnp.int8, jnp.bfloat16, jnp.uint8):
        args = (jnp.zeros((9 * 128,), jnp.float32),
                jnp.zeros((512, 128), dtype), jnp.zeros((512,), jnp.float32))
        leaves, tree = jax.tree_util.tree_flatten(args)
        keys[jnp.dtype(dtype).name] = run_store.key_of(
            plugins, None, False, tree, leaves)
        assert run_store._leaf_state(leaves[1])[1] == jnp.dtype(dtype).name
    assert len(set(keys.values())) == 3


# -- train.run ----------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_train_run_carries_row_item_bytes_and_operand(monkeypatch, backend):
    """On both paths: from the kernel's record where the step is the
    one-read kernel (a TPU), from the rows' type where it takes two reads
    (a CPU; a width with no by-rows form; BCOO)."""
    import jax

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing

    class Sink:
        def __init__(self):
            self.records = []

        def emit(self, kind, payload):
            self.records.append((kind, dict(payload)))

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    r = np.random.default_rng(7)
    y = r.integers(0, 2, 512).astype(np.float32)

    def fit(d, dtype, gradient=None):
        X = np.clip(np.round(40 * r.normal(size=(512, d))), -128, 127)
        gradient = gradient or LogisticGradient()
        opt = tpu_sgd.GradientDescent(
            gradient, tpu_sgd.SquaredL2Updater()
        ).set_num_iterations(2).set_mini_batch_fraction(1.0)
        opt.optimize_with_history(
            (jax.numpy.asarray(X, dtype), y),
            np.zeros(gradient.weight_dim(d), np.float32))

    sink = Sink()
    enable_tracing(sink)
    try:
        fit(128, "int8", MultinomialLogisticGradient(3))
        fit(128, "int8")
        fit(128, "bfloat16")
        fit(128, "float32")
        fit(24, "int8")  # feature-major: two reads on a TPU too
        fit(128, "int32")  # widened at the boundary, as before
    finally:
        disable_tracing()
    runs = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.run"]
    assert [(s["row_item_bytes"], s["operand"]) for s in runs] == [
        (1, "bfloat16"), (1, "bfloat16"), (2, "bfloat16"), (4, "float32"),
        (1, "bfloat16"), (4, "float32")]
    assert [s["by_rows"] for s in runs] == (
        [1, 1, 1, 1, 0, 1] if backend == "tpu" else [0] * 6)
    assert [s["row_tile"] for s in runs] == (
        [512, 512, 512, 512, 0, 512] if backend == "tpu" else [0] * 6)
    assert [s["class_rows"] for s in runs] == (
        [16, 0, 0, 0, 0, 0] if backend == "tpu" else [0] * 6)
