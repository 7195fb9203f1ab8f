"""The one file that describes the chip: real-width programs compiled for
a *described* TPU v5e (2x2) with the chip's own compiler, no chip attached.

Nothing runs — these are compiles, so they say nothing about results or
times.  They catch what interpret-mode and CPU tests cannot: a kernel
over the scoped-VMEM limit, a program that does not fit HBM, a mesh
program without its all-reduce.

Everything that touches the topology (the description itself, shardings,
the mesh, shapes carrying shardings) is built inside module-scoped
fixtures in THIS file — never at import, in a ``skipif``, in a
``parametrize`` argument or in ``conftest.py`` — because only one process
may load the TPU library and every xdist worker imports every test file.
Compiles happen in the test's own process, with the persistent
compilation cache off (a described-device executable cannot be read back
without a chip).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import (HingeGradient, LeastSquaresGradient,
                                   LogisticGradient)
from tpu_sgd.ops.updaters import L1Updater, SimpleUpdater, SquaredL2Updater

#: the north-star width (BASELINE.json) and the smoke's resident rows
N, D = 2**20, 1000
#: RCV1's published width and the smoke's sparse rows / nnz per row
SPARSE_N, SPARSE_D, SPARSE_NNZ = 200_000, 47_236, 75
#: the window kernels' rehearsal slab
KERNEL_N, TILE_M, NUM_TILES = 262_144, 2048, 12

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    from tpu_sgd.parallel.mesh import DATA_AXIS

    return Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Described-device compiles are written to the persistent cache but
    cannot be read back without a chip; keep these tests silent."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def S(one_chip, no_persistent_cache):
    """Shape factory: ``S(shape, dtype)`` on the one described chip."""
    def make(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    return make


def _cfg(**kw):
    base = dict(step_size=0.5, num_iterations=20, mini_batch_fraction=0.1,
                reg_param=0.01)
    base.update(kw)
    return SGDConfig(**base)


def _dense_args(S):
    return (S((D,), F32), S((N, D), BF16), S((N,), F32), S((), I32),
            S((), F32))


# -- the dense trainer's programs ------------------------------------------

@pytest.mark.parametrize("family", ["least_squares", "logistic_l2"])
def test_dense_step_compiles(S, family):
    from tpu_sgd.optimize.gradient_descent import make_step

    grad, upd = {
        "least_squares": (LeastSquaresGradient(), SimpleUpdater()),
        "logistic_l2": (LogisticGradient(), SquaredL2Updater()),
    }[family]
    step = make_step(grad, upd, _cfg())
    compiled = jax.jit(step).lower(*_dense_args(S)).compile()
    mem = compiled.memory_analysis()
    # X (2 GB bf16) + y dominate the arguments; the whole thing must fit
    # one chip's 16 GB with room for the smoke's second dataset
    assert mem.argument_size_in_bytes >= N * D * 2
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 8 * 2**30


def test_whole_run_program_compiles(S):
    """``make_run``: the fused while_loop behind the default
    ``GradientDescent.optimize`` (no listener, no mesh)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(LeastSquaresGradient(), SimpleUpdater(), _cfg())
    compiled = jax.jit(run).lower(
        S((D,), F32), S((N, D), BF16), S((N,), F32)).compile()
    assert "while" in compiled.as_text()


def test_whole_run_fusions_keep_the_step_scopes(S):
    """After the chip's compiler has fused the step, one fusion still
    carries ``sgd.margins`` in its ``op_name`` and one ``sgd.gradient``
    (the two reads of all of X, 98.9% of the step on the chip): what
    ``bench/spans.py`` keys the device's time by."""
    import re

    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(LogisticGradient(), SquaredL2Updater(), _cfg())
    text = jax.jit(run).lower(
        S((D,), F32), S((N, D), BF16), S((N,), F32)).compile().as_text()
    scopes = {m.group(1) for m in re.finditer(
        r' fusion\(.*op_name="[^"]*(sgd\.[a-z]+)', text)}
    assert {"sgd.margins", "sgd.gradient"} <= scopes, scopes


def test_superstep_k8_compiles(S):
    """The host-streamed feed's fused program: K=8 per-step batches of
    one bf16-wire superchunk (frac 0.1 of 2**19 host rows)."""
    from tpu_sgd.optimize.gradient_descent import make_superstep

    K, m = 8, 52_429
    sstep = make_superstep(LeastSquaresGradient(), SimpleUpdater(),
                           _cfg(mini_batch_fraction=1.0))
    jax.jit(sstep).lower(
        S((D,), F32), S((), F32), S((), I32),
        S((K, m, D), BF16), S((K, m), F32), S((K, m), jnp.bool_),
    ).compile()


def test_resident_while_loop_compiles(S):
    """The device-resident whole-run driver: ``lax.while_loop`` over
    fused supersteps with its ordered ``io_callback`` window hook."""
    from tpu_sgd.optimize.gradient_descent import make_step
    from tpu_sgd.optimize.resident_driver import ResidentLoop

    cfg = _cfg(num_iterations=64)
    step = make_step(LeastSquaresGradient(), SimpleUpdater(), cfg)
    loop = ResidentLoop(
        lambda w, i, rv, X, y: step(w, X, y, i, rv, None), cfg, 8, 4)
    compiled = loop._fn.lower(
        S((D,), F32), S((), F32), S((), I32),
        S((N, D), BF16), S((N,), F32)).compile()
    assert "while" in compiled.as_text()


def test_dp_step_4_devices_has_all_reduce(mesh4, S):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_step_fn
    from tpu_sgd.parallel.mesh import DATA_AXIS

    rep = NamedSharding(mesh4, P())
    rows = NamedSharding(mesh4, P(DATA_AXIS))
    fn = dp_step_fn(LeastSquaresGradient(), SimpleUpdater(),
                    _cfg(mini_batch_fraction=1.0), mesh4, with_valid=False)
    compiled = fn.lower(
        S((D,), F32, rep),
        S((N, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
        S((N,), F32, rows), S((), I32, rep), S((), F32, rep),
    ).compile()
    assert "all-reduce" in compiled.as_text()
    # rows are sharded: each device holds a quarter of X
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    assert N * D * 2 // 4 <= per_dev < N * D * 2 // 2


def test_dp_whole_run_4_devices_has_all_reduce(mesh4, S):
    """``dp_run_fn``: what ``train(..., mesh=data_mesh())`` dispatches and
    ``chip_smoke.py --chips 4`` checks — the fused loop under shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_run_fn
    from tpu_sgd.parallel.mesh import DATA_AXIS

    fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                   _cfg(mini_batch_fraction=1.0, num_iterations=5), mesh4,
                   with_valid=False)
    text = fn.lower(
        S((D,), F32, NamedSharding(mesh4, P())),
        S((N, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
        S((N,), F32, NamedSharding(mesh4, P(DATA_AXIS))),
    ).compile().as_text()
    assert "all-reduce" in text and "while" in text


# -- sparse ------------------------------------------------------------------

def test_sparse_hinge_l1_step_compiles(S):
    """Hinge + L1 on BCOO at RCV1's published width, never densified."""
    from jax.experimental.sparse import BCOO

    from tpu_sgd.optimize.gradient_descent import make_step

    step = make_step(HingeGradient(), L1Updater(), _cfg())
    nse = SPARSE_N * SPARSE_NNZ

    def sparse_step(w, data, idx, y, i, rv):
        X = BCOO((data, idx), shape=(SPARSE_N, SPARSE_D),
                 indices_sorted=True, unique_indices=True)
        return step(w, X, y, i, rv, None)

    compiled = jax.jit(sparse_step).lower(
        S((SPARSE_D,), F32), S((nse,), F32), S((nse, 2), I32),
        S((SPARSE_N,), F32), S((), I32), S((), F32)).compile()
    mem = compiled.memory_analysis()
    # a densified (200000, 47236) f32 would be 37.8 GB
    assert mem.temp_size_in_bytes < 4 * 2**30


def test_sparse_whole_run_program_compiles(S):
    """What ``SVMWithSGD.train`` on BCOO dispatches: ``make_run`` over the
    sparse step."""
    from jax.experimental.sparse import BCOO

    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(HingeGradient(), L1Updater(),
                   _cfg(mini_batch_fraction=1.0))
    nse = SPARSE_N * SPARSE_NNZ

    def sparse_run(w, data, idx, y):
        X = BCOO((data, idx), shape=(SPARSE_N, SPARSE_D),
                 indices_sorted=True, unique_indices=True)
        return run(w, X, y)

    jax.jit(sparse_run).lower(
        S((SPARSE_D,), F32), S((nse,), F32), S((nse, 2), I32),
        S((SPARSE_N,), F32)).compile()


# -- the smoke's own device-side generators ----------------------------------

def test_smoke_generators_compile(S):
    """``chip_smoke.py`` makes its data on the device; a generator the
    chip's compiler refuses would fail the smoke before any trainer ran."""
    import chip_smoke

    key = S((2,), jnp.uint32)
    mem = chip_smoke.dense_generator(N, D).lower(
        key).compile().memory_analysis()
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 12 * 2**30
    mem = chip_smoke.rcv1_columns_generator(
        SPARSE_N, SPARSE_D, SPARSE_NNZ).lower(key).compile().memory_analysis()
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 12 * 2**30


# -- serving -----------------------------------------------------------------

@pytest.mark.parametrize("activation", [None, "sigmoid"])
def test_serving_bucket_programs_compile(S, activation):
    """Every row bucket of the canonical predict program at d=1000 —
    what ``Server.submit`` and ``model.predict`` both score through."""
    from tpu_sgd.ops.bucketed import DEFAULT_BUCKETS, _matvec_program

    for rows in DEFAULT_BUCKETS:
        key = (rows, D, "float32", 1, 0, "float32", activation)
        _matvec_program(key).lower(
            S((rows, D), F32), S((D,), F32), S((), F32)).compile()


# -- the Pallas kernels (opt-in path) ----------------------------------------

def _window_args(S, dtype):
    return (S((KERNEL_N, D), dtype), S((KERNEL_N,), F32), S((D,), F32),
            S((), I32))


def _lower_window(S, dtype, use_vpu, tile_m):
    from tpu_sgd.ops.pallas_kernels import _fused_window_sums

    return _fused_window_sums.lower(
        LeastSquaresGradient().pointwise, *_window_args(S, dtype),
        num_tiles=NUM_TILES, tile_m=tile_m, use_vpu=use_vpu)


def _lower_masked(S, dtype, tile_m):
    from tpu_sgd.ops.pallas_kernels import _fused_gradient_sums

    return _fused_gradient_sums.lower(
        LeastSquaresGradient().pointwise, S((KERNEL_N, D), dtype),
        S((KERNEL_N,), F32), S((D,), F32), S((KERNEL_N,), jnp.bool_),
        tile_m=tile_m)


@pytest.mark.parametrize("use_vpu", [False, True], ids=["mxu", "vpu"])
def test_window_kernels_bf16_compile(S, use_vpu):
    from tpu_sgd.ops.pallas_kernels import _check_tile_vmem

    _check_tile_vmem(TILE_M, S((KERNEL_N, D), BF16), False)  # admits
    compiled = _lower_window(S, BF16, use_vpu, TILE_M).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: what the chip's compiler refuses at d=1000 and the default tile 2048
#: (scoped VMEM against its 16.00M limit): both window kernels on f32
#: (18.00M), the masked full scan behind ``PallasGradient.batch_sums`` on
#: bf16 (16.58M) and f32 (20.00M)
REFUSED = {
    "window_mxu_f32": ("window", F32, False),
    "window_vpu_f32": ("window", F32, True),
    "masked_bf16": ("masked", BF16, None),
    "masked_f32": ("masked", F32, None),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_vmem_estimate_refuses_what_the_compiler_refuses(S, case):
    """``_check_tile_vmem`` raises its actionable ``ValueError`` BEFORE
    compiling exactly where the chip's compiler would refuse the kernel —
    and the tile its hint names does compile."""
    import re

    from tpu_sgd.ops.pallas_kernels import _check_tile_vmem

    kind, dtype, use_vpu = REFUSED[case]
    columns = 2 if kind == "masked" else 1
    X = S((KERNEL_N, D), dtype)

    def lower(tile_m):
        if kind == "masked":
            return _lower_masked(S, dtype, tile_m)
        return _lower_window(S, dtype, use_vpu, tile_m)

    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        _check_tile_vmem(TILE_M, X, False, column_operands=columns)
    with pytest.raises(Exception, match="(?i)vmem"):
        lower(TILE_M).compile()  # the compiler's own verdict agrees
    hint = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    tile = 1 << (hint.bit_length() - 1)  # the window needs n % tile == 0
    _check_tile_vmem(tile, X, False, column_operands=columns)
    assert "tpu_custom_call" in lower(tile).compile().as_text()


def test_public_kernel_entry_points_refuse_before_compiling(S):
    """The public wrappers run the check first: the default-tile bf16
    full scan that every interpret-mode test passes is a ``ValueError``
    here, not a Mosaic compile error on the chip."""
    from tpu_sgd.ops.pallas_kernels import (fused_gradient_sums,
                                            fused_window_sums)

    pw = LeastSquaresGradient().pointwise
    with pytest.raises(ValueError, match="scoped VMEM"):
        fused_gradient_sums(pw, S((KERNEL_N, D), BF16), S((KERNEL_N,), F32),
                            S((D,), F32), S((KERNEL_N,), jnp.bool_))
    with pytest.raises(ValueError, match="scoped VMEM"):
        fused_window_sums(pw, *_window_args(S, F32), NUM_TILES)
