"""PR 36: the masked step's Bernoulli draw inside the one-read kernel.

The kernel computes each row's mask bit from the step's key and the row's
index (``ops/pallas_kernels._row_draw``); these tests hold it to
``jax.random.bernoulli`` row for row (the kernel in interpret mode), the
sums and a whole fit to the same fit handed the materialised mask bit for
bit, the shard fold to the reference's, and the selection
(``step_sums``' ``mask_in_kernel``, ``train.run``'s ``mask_in_kernel``) to what the
code can observe."""

import functools

import numpy as np
import pytest

from tpu_sgd.ops.gradients import (HingeGradient, LeastSquaresGradient,
                                   LogisticGradient)

from test_pallas import (_as_lowered_for_a_tpu, _data, _run_case,
                         _selection_case)

GRADS = [LeastSquaresGradient(), LogisticGradient(), HingeGradient()]
FRACTIONS = [0.1, 0.5, 0.937]

#: (rows drawn, the first row's index, lanes a pass): a multiple of the
#: tile; the from-host cell's last rows at their own indices (2,145,000 is
#: no multiple of 1024: the last block is cut); a tiny n under one group
DRAWS = {"tile_multiple": (4096, 0, 1024),
         "from_host_tail": (2_145_000 - 2_143_232, 2_143_232, 1024),
         "cut_groups_of_384": (2145, 0, 384),
         "tiny": (37, 0, 128)}


def _drawn_rows(key, fraction, count, first, lw):
    """``_row_draw`` for the rows ``[first, first + count)``, a pass of
    ``lw`` lanes a grid step, in the interpreter."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_sgd.ops import pallas_kernels as pk

    def kernel(k_ref, o_ref):
        row0 = first + pl.program_id(0) * lw
        o_ref[:] = pk._row_draw(k_ref, row0, lw, fraction)

    steps = -(-count // lw)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,), in_specs=[],
            out_specs=pl.BlockSpec((1, lw), lambda i, k: (0, i))),
        out_shape=jax.ShapeDtypeStruct((1, steps * lw), jnp.float32),
        interpret=True,
    )(jax.lax.bitcast_convert_type(key, jnp.int32))
    return np.asarray(out)[0, :count]


@pytest.mark.parametrize("fraction", FRACTIONS)
@pytest.mark.parametrize("case", sorted(DRAWS))
def test_the_kernels_draw_is_jax_bernoulli_row_for_row(case, fraction):
    """Every row's bit, at its own index: ``bernoulli(key, p, (n,))[i]``
    depends on i alone under ``jax_threefry_partitionable``, so the rows a
    block draws are a slice of the whole array's."""
    import jax

    count, first, lw = DRAWS[case]
    key = jax.random.fold_in(jax.random.PRNGKey(42), 17)
    whole = np.asarray(jax.random.bernoulli(key, fraction, (first + count,)))
    got = _drawn_rows(key, fraction, count, first, lw)
    np.testing.assert_array_equal(got, whole[first:].astype(np.float32))
    assert got.sum() > 0 and (count < 100 or got.sum() < count)


def test_the_draws_bits_are_threefrys_for_any_key_and_counter():
    """``_threefry_bits`` in int32 against ``jax.random.bits`` (uint32) at
    keys with the high bit set and counters up to the last below 2**31."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import _threefry_bits

    for seed in (0, 42, 2**31 + 12345, 2**32 - 1):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), seed % 97)
        words = jax.lax.bitcast_convert_type(key, jnp.int32)
        count = jnp.arange(5000, dtype=jnp.int32)
        bits = _threefry_bits(words[0], words[1], count)
        want = jax.random.bits(key, (5000,), jnp.uint32)
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(bits, jnp.uint32)),
            np.asarray(want))


#: (rows, tile): whole tiles; a cut last block and lane chunks of 1024 and
#: of 128; a tiny n
SUMS = {"tile_multiple": (2048, 1024), "cut_last_block": (2145, 1024),
        "cut_small_tile": (333, 128), "tiny": (37, 128)}


@pytest.mark.parametrize("with_valid", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("fraction", FRACTIONS)
@pytest.mark.parametrize("case", sorted(SUMS))
def test_sums_with_the_draw_in_the_kernel_are_the_masked_sums_bit_for_bit(
        case, fraction, with_valid):
    """``fused_gradient_sums(..., draw=(key, p))`` against the same kernel
    handed ``bernoulli(key, p, (n,)) & valid``: the same rows selected and
    the sums in the same order, so gradient, loss and count are equal bit
    for bit; what lies past row n is NaN in the interpreter and a bit
    drawn for such a row must not count."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.pallas_kernels import fused_gradient_sums

    n, tile = SUMS[case]
    g = LogisticGradient()
    X, y, w = _data(n=n, d=40, seed=n, classify=True)
    X = jnp.asarray(X, jnp.bfloat16)
    key = jax.random.fold_in(jax.random.PRNGKey(7), n)
    valid = (np.random.default_rng(n).uniform(size=n) < 0.8) \
        if with_valid else None
    mask = np.asarray(jax.random.bernoulli(key, fraction, (n,)))
    if with_valid:
        mask = mask & valid
    want = fused_gradient_sums(g.pointwise, X, y, w, mask, tile_m=tile,
                               interpret=True)
    got = fused_gradient_sums(g.pointwise, X, y, w, valid, tile_m=tile,
                              interpret=True, draw=(key, fraction))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(got[2]) == mask.sum()


def test_the_kernels_entry_refuses_a_key_that_is_no_raw_threefry_key():
    import jax

    from tpu_sgd.ops.pallas_kernels import fused_gradient_sums

    X, y, w = _data(n=256, d=16)
    with jax.default_prng_impl("rbg"):
        key = jax.random.PRNGKey(0)  # four words
    with pytest.raises(ValueError, match="two uint32 words"):
        fused_gradient_sums(GRADS[0].pointwise, X, y, w, interpret=True,
                            draw=(key, 0.1))


# -- the step: the same rows, every step, every shard ---------------------------

@pytest.mark.parametrize("with_valid", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("g", GRADS, ids=lambda g: type(g).__name__)
def test_the_shard_fold_draws_the_references_rows_both_ways(
        g, with_valid, monkeypatch):
    """``_make_local_sums`` on the path a TPU takes: under a mesh the key
    is the shard's (``axis_index`` folded in) and the index the shard's
    local row; a replica worker folds its static ``shard_index`` in the
    same place.  Both give, bit for bit, the kernel's sums under the mask
    ``bench/reference/glm_dense_dp.py`` draws for that shard,
    ``bernoulli(fold_in(fold_in(PRNGKey(seed), t), s), p, (rows,))``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.pallas_kernels import fused_gradient_sums
    from tpu_sgd.optimize import gradient_descent as gd
    from tpu_sgd.parallel.mesh import DATA_AXIS, data_mesh, shard_map_fn

    shards, local, t = 4, 200, 5
    n = shards * local
    X, y, w = _data(n=n, d=40, seed=61,
                    classify=not isinstance(g, LeastSquaresGradient))
    valid = jnp.asarray(np.random.default_rng(62).uniform(size=n) < 0.8) \
        if with_valid else None
    cfg = SGDConfig(mini_batch_fraction=0.3, seed=11)
    key = jax.random.PRNGKey(cfg.seed)
    kernel = fused_gradient_sums  # before the wrapper below replaces it
    _as_lowered_for_a_tpu(monkeypatch)

    meshed = gd._make_local_sums(g, cfg, key, DATA_AXIS, None)
    rows = P(DATA_AXIS)
    stack = lambda sums: tuple(s[None] for s in sums)  # noqa: E731
    if with_valid:
        fn = shard_map_fn(
            data_mesh(jax.devices()[:shards]),
            lambda w, X, y, v: stack(meshed(w, X, y, jnp.int32(t), v)),
            (P(), P(DATA_AXIS, None), rows, rows), (rows, rows, rows))
        by_axis = jax.jit(fn)(w, X, y, valid)
    else:
        fn = shard_map_fn(
            data_mesh(jax.devices()[:shards]),
            lambda w, X, y: stack(meshed(w, X, y, jnp.int32(t), None)),
            (P(), P(DATA_AXIS, None), rows), (rows, rows, rows))
        by_axis = jax.jit(fn)(w, X, y)
    for s in range(shards):
        part = slice(s * local, (s + 1) * local)
        v = None if valid is None else valid[part]
        alone = gd._make_local_sums(g, cfg, key, None, None, shard_index=s)(
            w, X[part], y[part], jnp.int32(t), v)
        k = jax.random.fold_in(jax.random.fold_in(key, t), s)
        mask = jax.random.bernoulli(k, 0.3, (local,))
        want = kernel(g.pointwise, X[part], y[part], w,
                      mask if v is None else mask & v, tile_m=128,
                      interpret=True)
        for a, b, c in zip(alone, want, by_axis):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(c[s]), np.asarray(b))


FIT_CASES = ["logistic_masked", "logistic_masked_valid"]


@pytest.mark.parametrize("case", FIT_CASES)
def test_a_fit_that_draws_in_the_kernel_is_the_fit_handed_the_mask(
        case, monkeypatch):
    """``make_run``'s fit on the path a TPU takes, the kernel in the
    interpreter: with the draw in the kernel and with ``_make_mask``'s
    array handed to the same kernel (``counter_draws`` saying no), weights,
    loss history and count are the same bits; and the two-read fit this
    CPU takes, which draws the array, agrees to rounding."""
    import jax

    from tpu_sgd.ops import gradients
    from tpu_sgd.ops.updaters import SquaredL2Updater
    from tpu_sgd.optimize import gradient_descent as gd

    g, cfg, X, y, w0, valid = _run_case(case)
    assert gradients.step_sums(g, cfg, X, y, w0, valid).mask_in_kernel
    seen = []

    def fit(in_kernel):
        with monkeypatch.context() as m:
            if not in_kernel:
                m.setattr(gradients, "counter_draws", lambda: False)
            run = jax.jit(gd.make_run(g, SquaredL2Updater(), cfg))
            return [np.asarray(a)
                    for a in run(w0, X, y, cfg.hyper(), valid)]

    here = fit(True)
    for a, b in zip(here, fit(False)):  # on the CPU both draw the array
        np.testing.assert_array_equal(a, b)

    from tpu_sgd.ops import pallas_kernels

    _as_lowered_for_a_tpu(monkeypatch)
    entry = pallas_kernels.fused_gradient_sums

    @functools.wraps(entry)
    def spy(*args, **kw):
        seen.append((kw.get("draw") is not None,
                     None if len(args) < 5 or args[4] is None
                     else tuple(args[4].shape)))
        return entry(*args, **kw)

    monkeypatch.setattr(pallas_kernels, "fused_gradient_sums", spy)
    n = X.shape[0]
    drawn = fit(True)
    # the kernel draws; its one mask operand is ``valid``, laid out once
    assert set(seen) == {(True, None if valid is None else (1, n))}
    del seen[:]
    handed = fit(False)
    assert set(seen) == {(False, (n,))}  # the step's array, every step
    for a, b in zip(drawn, handed):
        np.testing.assert_array_equal(a, b)
    assert int(drawn[2]) == cfg.num_iterations
    np.testing.assert_allclose(drawn[0], here[0], rtol=2e-3, atol=2e-4)


def test_a_meshed_fit_that_draws_in_the_kernel_is_the_fit_handed_the_mask(
        monkeypatch):
    """The four-chip cell's fit in small: ``dp_run_fn`` over four devices,
    least squares at fraction 0.1, every shard's kernel drawing its own
    rows from its own key, against the same fit handed each shard's mask
    array: the same bits."""
    import jax

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops import gradients
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.parallel.data_parallel import dp_run_fn
    from tpu_sgd.parallel.mesh import data_mesh

    n, d = 4 * 300, 24
    X, y, _ = _data(n=n, d=d, seed=71)
    cfg = SGDConfig(step_size=0.5, num_iterations=4, mini_batch_fraction=0.1,
                    convergence_tol=0.0)
    g = LeastSquaresGradient()
    mesh = data_mesh(jax.devices()[:4])
    w0 = np.zeros(d, np.float32)
    _as_lowered_for_a_tpu(monkeypatch)

    def fit(in_kernel):
        with monkeypatch.context() as m:
            if not in_kernel:
                m.setattr(gradients, "counter_draws", lambda: False)
            run = dp_run_fn(g, SimpleUpdater(), cfg, mesh, with_valid=False)
            return [np.asarray(a) for a in run(w0, X, y, cfg.hyper())]

    for a, b in zip(fit(True), fit(False)):
        np.testing.assert_array_equal(a, b)


# -- the selection ------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    "feature_major", "bcoo", "row_major_width", "feature_sharded",
    "statistics", "classes", "wide", "flag_off", "rbg"])
def test_the_draw_follows_what_the_code_can_observe(case):
    """The kernel draws where the sums are the one-read kernel's vector
    body with all d in one feature block and the draw is a counter's;
    everywhere else ``_make_mask`` makes the array it always made."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import (MultinomialLogisticGradient,
                                       RowDraw, step_sums)
    from tpu_sgd.ops.gram import GramLeastSquaresGradient
    from tpu_sgd.optimize import gradient_descent as gd

    X, y, w, _, axis = _selection_case(
        {"feature_sharded": "margin_axis_name"}.get(
            case, case if case in ("bcoo", "row_major_width", "wide")
            else "feature_major"))
    g, how = LogisticGradient(), contextlib.nullcontext()
    if case == "statistics":
        g = GramLeastSquaresGradient()
    elif case == "classes":
        g, w = MultinomialLogisticGradient(4), jnp.zeros((3 * 1000,))
    elif case == "flag_off":
        how = jax.threefry_partitionable(False)
    elif case == "rbg":
        how = jax.default_prng_impl("rbg")
    on = case == "feature_major"
    cfg = SGDConfig(mini_batch_fraction=0.1)
    with how:
        plan = step_sums(g, cfg, X, y, w, None, axis)
        assert plan.mask_in_kernel == on and plan.drawn == (not on)
        # the body that could draw is the vector's over all d, whatever
        # the PRNG; only under a counter's draw does the step hand it on
        assert (plan.kernel is not None and plan.kernel.draws) == (
            case in ("feature_major", "flag_off", "rbg"))
        if case in ("wide", "bcoo"):
            return  # shapes alone, or no dense rows to draw over
        key = jax.random.PRNGKey(3)
        mask = jax.eval_shape(
            lambda k: gd._make_mask(g, cfg, k, 1, X, y, w, None, None, axis),
            key)
    assert isinstance(mask, RowDraw) == on
    if not on:
        assert mask.shape == (X.shape[0],) and mask.dtype == jnp.bool_
    # nothing is drawn at fraction 1.0, a window draws an offset, a
    # gathered batch its indices
    for kw in (dict(mini_batch_fraction=1.0),
               dict(mini_batch_fraction=0.1, sampling="sliced"),
               dict(mini_batch_fraction=0.1, sampling="indexed")):
        assert not step_sums(g, SGDConfig(**kw), X, y, w, None,
                             axis).mask_in_kernel


@pytest.mark.parametrize("how", ["counter", "flag_off", "rbg"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_train_run_says_whether_the_kernel_draws_the_mask(backend, how,
                                                          monkeypatch):
    """``train.run``'s ``mask_in_kernel``: 1 where this fit's steps draw
    in the kernel (a TPU, the one-read vector step under Bernoulli
    sampling, a counter-based draw; a shard's operands under a mesh), 0
    where they are handed an array (``jax_threefry_partitionable`` off,
    an ``rbg`` key, rows stored by rows, any CPU) or draw no mask (a
    window, a gathered batch, a full batch)."""
    import contextlib

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
    by_rows = _data(n=512, d=128, seed=52)[0]  # (512, 128): stored by rows

    def fit(X, mesh=None, fraction=0.5, **kw):
        opt = tpu_sgd.GradientDescent(
            LogisticGradient(), tpu_sgd.SquaredL2Updater()
        ).set_num_iterations(2).set_mini_batch_fraction(fraction)
        if mesh is not None:
            opt.set_mesh(mesh)
        if "sampling" in kw:
            opt.set_sampling(kw["sampling"])
        opt.optimize_with_history((X, y), np.zeros(X.shape[1], np.float32))

    sink = Sink()
    enable_tracing(sink)
    context = {"counter": contextlib.nullcontext(),
               "flag_off": jax.threefry_partitionable(False),
               "rbg": jax.default_prng_impl("rbg")}[how]
    try:
        with context:
            fit(X)
            fit(X, mesh=tpu_sgd.data_mesh(jax.devices()[:4]))
            fit(X, sampling="sliced")
            fit(X, sampling="indexed")
            fit(X, fraction=1.0)
            fit(by_rows)
    finally:
        disable_tracing()
    runs = [p for k, p in sink.records
            if k == "trace_span" and p["name"] == "train.run"]
    assert [r["mask_in_kernel"] for r in runs] == (
        [1, 1, 0, 0, 0, 0] if (backend, how) == ("tpu", "counter")
        else [0] * 6)
    # the kernel is the step either way: the rule moves the draw alone
    # (over rows stored by rows the class body, which reads an array: PR 39)
    assert [r["row_tile"] for r in runs] == (
        [512, 128, 512, 0, 512, 512] if backend == "tpu" else [0] * 6)
    assert [r["by_rows"] for r in runs] == (
        [0, 0, 0, 0, 0, 1] if backend == "tpu" else [0] * 6)
