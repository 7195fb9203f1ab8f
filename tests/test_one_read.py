"""ONE table of what the step's selection answers (PR 47): the record
``ops/pallas_kernels.one_read`` makes, through ``ops/gradients.step_sums``,
over the nine cells' per-device shapes and the edge shapes that the
predicates it replaced (three in ``ops/gradients.py``, four methods of
``Gradient``, four askers in ``optimize/gradient_descent.py``) each had a
test for.  The expected rows were written by the PARENT's functions
(c3a93dd) from these same case descriptions, not by the code under test;
PR 48 took the class body past 128 class rows, so ``class_rows_over_128``
answers a record where that parent answered None, and the tenth cell's row
is new (``tests/test_class_rows.py`` holds the table by class rows).
PR 50 gave the record ``ahead`` (the class body's lane chunks a chunk ahead,
past 128 class rows): a second table over the same cases, ``AHEAD``.
PR 57 admitted int8 rows where the chip stores them by rows (a block read as
the bytes it is, widened in VMEM): the twelfth cell's row and the ``int8_*``
edges are new, ``integer_rows`` (a feature-major width) keeps its None, and a
third table, ``ROW_BYTES``, holds the record's ``item_bytes`` and ``operand``.
And the arrows between the layers that ask: ``ops`` <- ``plan`` <-
``optimize``, one way."""

import ast
import contextlib
import pathlib

import pytest

#: case -> what makes its operands: rows and width a device, the type, the
#: gradient ("logistic", "least_squares", "hinge", "statistics", or a class
#: count), the sampling, and what else the selection can observe (a padded
#: shard's ``valid``, a feature-sharded axis, BCOO rows, the PRNG)
CASES = {
    # the nine cells
    "dense1000-logistic.resident": dict(n=4_194_304, d=1000),
    "dense1000-logistic.from-host": dict(n=2_145_000, d=1000),
    "dense1000-lsq-dp4.resident-sharded": dict(
        n=2_500_000, d=1000, gradient="least_squares"),
    "dense1000-logistic-sliced.resident": dict(
        n=4_194_304, d=1000, sampling="sliced"),
    "mnist8m-multinomial.resident-classes": dict(
        n=8_100_000, d=784, gradient=10, fraction=1.0),
    "rcv1-dense-hinge-l1.resident-wide": dict(
        n=131_072, d=47_236, gradient="hinge", fraction=1.0),
    "cifar5m-multinomial.resident-classes": dict(
        n=2_000_896, d=3072, gradient=10, fraction=1.0),
    "dense1000-lsq-stream.first-fit-by-rows": dict(
        n=2_097_152, d=1000, gradient="least_squares", fraction=1.0),
    "dense1000-lsq-stream.from-totals": dict(
        n=2_097_152, d=1000, gradient="statistics", fraction=1.0),
    "dense1000-lsq-dp4-run.padded-shard": dict(
        n=2_500_000, d=1000, gradient="least_squares", valid=True),
    "imagenet1k-r50-multinomial.resident-classes": dict(
        n=1_281_167, d=2048, gradient=1000, fraction=1.0),
    # PR 57: int8 rows, admitted where the chip stores them by rows
    "cifar5m-int8-multinomial.resident-classes": dict(
        n=4_001_792, d=3072, dtype="int8", gradient=10, fraction=1.0),
    # the edges
    "by_rows_no_lane_multiple": dict(n=2**20, d=1020),
    "by_rows_11648_bf16": dict(n=2**16, d=11_648, fraction=1.0),
    "by_rows_11776_bf16": dict(n=2**16, d=11_776, fraction=1.0),
    "by_rows_vector_masked": dict(n=2_097_152, d=1024),
    "by_rows_vector_full_batch": dict(n=2_097_152, d=1024, fraction=1.0),
    "by_rows_f32_embeddings": dict(n=2**20, d=768, dtype="float32",
                                   fraction=1.0),
    "window_by_rows": dict(n=2_097_152, d=1024, sampling="sliced"),
    "window_at_the_wide_width": dict(n=131_072, d=47_236, gradient="hinge",
                                     sampling="sliced"),
    "window_under_a_padded_shard": dict(n=2_500_000, d=1000,
                                        sampling="sliced", valid=True),
    "window_of_ten_classes": dict(n=8_100_000, d=784, gradient=10,
                                  sampling="sliced"),
    "wide_masked": dict(n=131_072, d=47_236, gradient="hinge"),
    "ten_times_the_wide_width": dict(n=2**16, d=472_364, gradient="hinge",
                                     fraction=1.0),
    "f32_masked": dict(n=2_145_000, d=1000, dtype="float32"),
    "f32_4000_features": dict(n=2**20, d=4000, dtype="float32",
                              fraction=1.0),
    "few_rows": dict(n=100, d=1000),
    "one_cut_block": dict(n=300, d=24, dtype="float32", fraction=1.0),
    "ten_classes_masked": dict(n=8_100_000, d=784, gradient=10),
    "class_rows_128": dict(n=2**20, d=784, gradient=129, fraction=1.0),
    "class_rows_over_128": dict(n=2**20, d=784, gradient=130, fraction=1.0),
    "bcoo": dict(n=64, d=1000, sparse=True),
    "feature_sharded": dict(n=2**20, d=1000, axis="model"),
    "integer_rows": dict(n=2**20, d=1000, dtype="int8"),
    "int8_by_rows_vector_full_batch": dict(n=2_097_152, d=1024, dtype="int8",
                                           fraction=1.0),
    "int8_by_rows_vector_masked": dict(n=2_097_152, d=1024, dtype="int8"),
    "int8_by_rows_padded_shard": dict(n=1_000_448, d=3072, dtype="int8",
                                      gradient=10, fraction=1.0, valid=True),
    "int8_window_by_rows": dict(n=2_097_152, d=1024, dtype="int8",
                                sampling="sliced"),
    "int8_feature_major_classes": dict(n=8_100_000, d=784, dtype="int8",
                                       gradient=10, fraction=1.0),
    "int8_by_rows_11648": dict(n=2**16, d=11_648, dtype="int8",
                               fraction=1.0),
    "uint8_by_rows": dict(n=2_097_152, d=1024, dtype="uint8", fraction=1.0),
    "indexed": dict(n=4_194_304, d=1000, sampling="indexed"),
    "rbg": dict(n=4_194_304, d=1000, prng="rbg"),
    "partitionable_flag_off": dict(n=4_194_304, d=1000, prng="flag_off"),
}

#: case -> (the record: body, by_rows, row tile, feature block, feature
#: blocks, VMEM limit in MiB, scope, whether the body can draw; or None),
#: (``train.run``'s labels_prepared, row_tile, feature_blocks,
#: mask_in_kernel, by_rows), whether the step draws its mask as an array
EXPECT = {
    "dense1000-logistic.resident": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 1, 0), False),
    "dense1000-logistic.from-host": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 1, 0), False),
    "dense1000-lsq-dp4.resident-sharded": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 1, 0), False),
    "dense1000-logistic-sliced.resident": (
        ("window", False, 2048, 1000, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 0), False),
    "mnist8m-multinomial.resident-classes": (
        ("class", False, 2048, 784, 1, 32, "sgd.class_sums", False),
        (1, 2048, 1, 0, 0), False),
    "rcv1-dense-hinge-l1.resident-wide": (
        ("wide", False, 256, 6400, 8, 100, "sgd.wide_sums", False),
        (1, 256, 8, 0, 0), False),
    "cifar5m-multinomial.resident-classes": (
        ("class", True, 1024, 3072, 1, 32, "sgd.class_sums", False),
        (1, 1024, 1, 0, 1), False),
    "dense1000-lsq-stream.first-fit-by-rows": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 0, 0), False),
    "dense1000-lsq-stream.from-totals": (
        None,
        (0, 0, 1, 0, 0), False),
    "dense1000-lsq-dp4-run.padded-shard": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 1, 0), False),
    "imagenet1k-r50-multinomial.resident-classes": (
        ("class", True, 2048, 2048, 1, 100, "sgd.class_sums", False),
        (1, 2048, 1, 0, 1), False),
    "cifar5m-int8-multinomial.resident-classes": (
        ("class", True, 2048, 3072, 1, 32, "sgd.class_sums", False),
        (1, 2048, 1, 0, 1), False),
    "by_rows_no_lane_multiple": (
        None,
        (0, 0, 1, 0, 0), True),
    "by_rows_11648_bf16": (
        ("class", True, 128, 11648, 1, 32, "sgd.fused_sums", False),
        (1, 128, 1, 0, 1), False),
    "by_rows_11776_bf16": (
        None,
        (0, 0, 1, 0, 0), False),
    "by_rows_vector_masked": (
        ("class", True, 2048, 1024, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 1), True),
    "by_rows_vector_full_batch": (
        ("class", True, 2048, 1024, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 1), False),
    "by_rows_f32_embeddings": (
        ("class", True, 2048, 768, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 1), False),
    "window_by_rows": (
        None,
        (0, 0, 1, 0, 0), False),
    "window_at_the_wide_width": (
        None,
        (0, 0, 1, 0, 0), False),
    "window_under_a_padded_shard": (
        ("window", False, 2048, 1000, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 0), False),
    "window_of_ten_classes": (
        None,
        (0, 0, 1, 0, 0), False),
    "wide_masked": (
        ("wide", False, 256, 6400, 8, 100, "sgd.wide_sums", False),
        (1, 256, 8, 0, 0), True),
    "ten_times_the_wide_width": (
        None,
        (0, 0, 1, 0, 0), False),
    "f32_masked": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 1, 0), False),
    "f32_4000_features": (
        ("scan", False, 512, 4000, 1, 32, "sgd.fused_sums", True),
        (1, 512, 1, 0, 0), False),
    "few_rows": (
        None,
        (0, 0, 1, 0, 0), True),
    "one_cut_block": (
        ("scan", False, 384, 24, 1, 32, "sgd.fused_sums", True),
        (1, 384, 1, 0, 0), False),
    "ten_classes_masked": (
        ("class", False, 2048, 784, 1, 32, "sgd.class_sums", False),
        (1, 2048, 1, 0, 0), True),
    "class_rows_128": (
        ("class", False, 2048, 784, 1, 32, "sgd.class_sums", False),
        (1, 2048, 1, 0, 0), False),
    "class_rows_over_128": (
        ("class", False, 2048, 784, 1, 100, "sgd.class_sums", False),
        (1, 2048, 1, 0, 0), False),
    "bcoo": (
        None,
        (0, 0, 1, 0, 0), True),
    "feature_sharded": (
        None,
        (0, 0, 1, 0, 0), True),
    "integer_rows": (
        None,
        (0, 0, 1, 0, 0), True),
    "int8_by_rows_vector_full_batch": (
        ("class", True, 2048, 1024, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 1), False),
    "int8_by_rows_vector_masked": (
        ("class", True, 2048, 1024, 1, 32, "sgd.fused_sums", False),
        (1, 2048, 1, 0, 1), True),
    "int8_by_rows_padded_shard": (
        ("class", True, 2048, 3072, 1, 32, "sgd.class_sums", False),
        (1, 2048, 1, 0, 1), False),
    "int8_window_by_rows": (
        None,
        (0, 0, 1, 0, 0), False),
    "int8_feature_major_classes": (
        None,
        (0, 0, 1, 0, 0), False),
    "int8_by_rows_11648": (
        ("class", True, 256, 11648, 1, 32, "sgd.fused_sums", False),
        (1, 256, 1, 0, 1), False),
    "uint8_by_rows": (
        None,
        (0, 0, 1, 0, 0), False),
    "indexed": (
        None,
        (0, 0, 1, 0, 0), False),
    "rbg": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 0, 0), True),
    "partitionable_flag_off": (
        ("scan", False, 2048, 1000, 1, 32, "sgd.fused_sums", True),
        (1, 2048, 1, 0, 0), True),
}


def _operands(n, d, dtype="bfloat16", gradient="logistic", fraction=0.1,
              sampling="bernoulli", valid=False, axis=None, sparse=False,
              prng=None):
    import jax
    import jax.numpy as jnp

    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops import gradients as G
    from tpu_sgd.ops.gram import GramLeastSquaresGradient

    shape = jax.ShapeDtypeStruct
    g = (G.MultinomialLogisticGradient(gradient)
         if isinstance(gradient, int) else {
             "logistic": G.LogisticGradient,
             "least_squares": G.LeastSquaresGradient,
             "hinge": G.HingeGradient,
             "statistics": GramLeastSquaresGradient}[gradient]())
    X = shape((n, d), jnp.dtype(dtype))
    if sparse:
        from jax.experimental import sparse as jsparse

        X = jsparse.BCOO.fromdense(jnp.zeros((n, d), jnp.float32), nse=4)
    cfg = SGDConfig(mini_batch_fraction=fraction, sampling=sampling)
    how = {None: contextlib.nullcontext,
           "rbg": lambda: jax.default_prng_impl("rbg"),
           "flag_off": lambda: jax.threefry_partitionable(False)}[prng]()
    return (g, cfg, X, shape((n,), jnp.float32),
            shape((g.weight_dim(d),), jnp.float32),
            shape((n,), bool) if valid else None, axis, how)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_selection_answers_what_the_parents_predicates_answered(case):
    from tpu_sgd.ops.gradients import step_sums

    g, cfg, X, y, w, valid, axis, how = _operands(**CASES[case])
    with how:
        plan = step_sums(g, cfg, X, y, w, valid, axis)
    k = plan.kernel
    if k is None:
        record, run = None, (0, 0, 1, 0, 0)
    else:
        record = (k.body, k.by_rows, k.tile, k.fblock, k.feature_blocks,
                  k.vmem_limit >> 20, k.scope, k.draws)
        run = (1, k.tile, k.feature_blocks, int(plan.mask_in_kernel),
               int(k.by_rows))
    assert (record, run, plan.drawn) == EXPECT[case]


#: the cases whose record says the class body runs its lane chunks AHEAD
#: (PR 50): a matrix of more than 128 padded class rows, so the tenth cell
#: and the edge one row past the pass; every other record is in turn (a
#: vector's bodies, ten classes, 128 class rows, a by-rows vector's 16 rows
#: in the class body) and where there is no record nothing is asked
AHEAD = {"imagenet1k-r50-multinomial.resident-classes",
         "class_rows_over_128"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_record_says_which_order_the_class_bodys_chunks_take(case):
    from tpu_sgd.ops import pallas_kernels as PK
    from tpu_sgd.ops.gradients import step_sums

    g, cfg, X, y, w, valid, axis, how = _operands(**CASES[case])
    with how:
        k = step_sums(g, cfg, X, y, w, valid, axis).kernel
    if k is None:
        assert case not in AHEAD
        return
    assert k.ahead == (case in AHEAD) == PK._fm_ahead(k.class_rows)
    assert k.ahead <= (k.body == "class")


#: the cases whose record reads ONE byte a feature from HBM (int8 rows,
#: widened to bf16 operands in VMEM: PR 57); float32 rows read four and
#: keep float32 operands; every other record two, bf16
ROW_BYTES = {**{case: (1, "bfloat16") for case in (
    "cifar5m-int8-multinomial.resident-classes",
    "int8_by_rows_vector_full_batch", "int8_by_rows_vector_masked",
    "int8_by_rows_padded_shard", "int8_by_rows_11648")},
    **{case: (4, "float32") for case in (
        "by_rows_f32_embeddings", "f32_masked", "f32_4000_features",
        "one_cut_block")}}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_record_says_what_a_feature_costs_and_the_operands_type(case):
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import matmul_dtype, step_sums

    g, cfg, X, y, w, valid, axis, how = _operands(**CASES[case])
    with how:
        k = step_sums(g, cfg, X, y, w, valid, axis).kernel
    if k is None:
        assert case not in ROW_BYTES
        return
    assert (k.item_bytes, k.operand) == ROW_BYTES.get(case, (2, "bfloat16"))
    # the record and the two-read contract name one operand type
    assert k.item_bytes == X.dtype.itemsize
    assert k.operand == jnp.dtype(matmul_dtype(X)).name


#: layer -> the packages it lies below and imports nothing of
BELOW = {"ops": ("tpu_sgd.optimize", "tpu_sgd.plan"),
         "plan.py": ("tpu_sgd.optimize",)}


@pytest.mark.parametrize("layer", sorted(BELOW))
def test_the_arrows_between_the_layers_point_one_way(layer):
    """Nothing under ``tpu_sgd/ops/`` imports ``tpu_sgd.optimize`` or
    ``tpu_sgd.plan`` and ``tpu_sgd/plan.py`` imports nothing of
    ``tpu_sgd.optimize``, at module level or inside a function: every
    ``import`` statement of their sources, wherever it stands."""
    root = pathlib.Path(__file__).resolve().parent.parent / "tpu_sgd"
    path = root / layer
    found = []
    for source in sorted(path.rglob("*.py")) if path.is_dir() else [path]:
        for node in ast.walk(ast.parse(source.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                names = [node.module] + [
                    f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            found += [(source.name, node.lineno, name) for name in names
                      if any(name == up or name.startswith(up + ".")
                             for up in BELOW[layer])]
    assert found == []
