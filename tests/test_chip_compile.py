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
#: the kernel's rehearsal slab
KERNEL_N = 262_144
#: the rows of the benchmark's two cells (bench/jobs/: 8.39 and 4.29 GB bf16)
CELL_ROWS = {"resident": 4_194_304, "from-host": 2_145_000}

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


def _hyper(like=None):
    """The step size and the regulariser as a fit hands them to its program
    (``config.Hyper``): two weakly typed float32 scalars, placed as ``like``
    (a spec of the same lowering: the one chip, or replicated on the mesh)."""
    from tpu_sgd.config import Hyper

    one = jax.ShapeDtypeStruct((), F32, weak_type=True,
                               sharding=getattr(like, "sharding", None))
    return Hyper(one, one)


def _dense_args(S):
    return (S((D,), F32), S((N, D), BF16), S((N,), F32), S((), I32),
            S((), F32), _hyper(S((), F32)))


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
        S((D,), F32), S((N, D), BF16), S((N,), F32),
        _hyper(S((), F32))).compile()
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("d", [D, 1024], ids=["feature_major", "row_major"])
def test_whole_run_fusions_keep_the_step_scopes(S, d):
    """What ``bench/spans.py`` keys the device's time by, after the chip's
    compiler has fused the step.  Where the chip stores X feature-major
    (d = 1000) the one-read kernel is selected and its custom call carries
    ``sgd.fused_sums``, the weights' broadcast in front of it
    ``sgd.margins`` and the fold of its lane partials ``sgd.gradient``; where it stores X by rows (d = 1024 pads nothing
    either way) the step is the by-rows kernel since PR 39: its custom call
    carries ``sgd.fused_sums`` in a jitted function of its own name, the
    vector goes in as rows (no broadcast along the lanes) and no fusion
    carries ``sgd.margins`` or ``sgd.gradient``: the two reads are gone."""
    import re

    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(LogisticGradient(), SquaredL2Updater(), _cfg())
    text = jax.jit(run).lower(
        S((d,), F32), S((N, d), BF16), S((N,), F32),
        _hyper(S((), F32))).compile().as_text()

    def scopes(opcode):
        return {m.group(1) for m in re.finditer(
            r" %s\(.*op_name=\"[^\"]*(sgd\.[a-z_]+)" % opcode, text)}

    if d == D:
        assert scopes("custom-call") == {"sgd.fused_sums"}
        assert not {"sgd.margins", "sgd.gradient"} & scopes("fusion")
        # what each half leaves outside the kernel stays an operation of its
        # own under the half's scope (margins_ms, gradient_ms read them)
        assert "sgd.margins" in scopes("broadcast")
        assert "sgd.gradient" in scopes("reduce")
    else:
        assert scopes("custom-call") == {"sgd.fused_sums"}
        assert "_fused_rows_sums" in text
        assert not {"sgd.margins", "sgd.gradient"} & scopes("fusion")


#: instructions that hand an array on without moving it
_NO_MOVE = {"parameter", "get-tuple-element", "bitcast", "tuple"}


def _moves_of(text, n, d):
    """Opcodes of the instructions that PRODUCE an array of X's size
    (either orientation, bf16 or f32) and are not a mere hand-on."""
    import re

    made = re.findall(
        r"= (?:bf16|f32)\[(?:%d,%d|%d,%d)\]\S* ([a-z-]+)\(" % (n, d, d, n),
        text)
    return [op for op in made if op not in _NO_MOVE]


@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_whole_run_at_the_cells_shapes_reads_x_in_place(S, cell):
    """The benchmark's fit at each cell's shape: the kernel is in the
    program, ``X.T`` reaches it as a bitcast of the parameter (no copy,
    transpose or fusion makes an X-sized array) and the program's
    temporaries are under 1% of X — nothing pads the from-host cell's
    2,145,000 rows (not a multiple of 128) either."""
    from tpu_sgd.optimize.gradient_descent import make_run

    n = CELL_ROWS[cell]
    cfg = _cfg(step_size=5.0, num_iterations=100, reg_param=0.001,
               convergence_tol=0.0)
    compiled = jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), cfg)
                       ).lower(S((D,), F32), S((n, D), BF16),
                               S((n,), F32), _hyper(S((), F32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (D, n) in text
    assert _moves_of(text, n, D) == []
    assert compiled.memory_analysis().temp_size_in_bytes < n * D * 2 // 100


@pytest.mark.parametrize("shards", [1, 4], ids=["one_chip", "dp4_padded"])
def test_sliced_run_at_the_cells_shape_reads_the_window_once_in_place(
        S, mesh4, shards):
    """``sampling="sliced"`` at the windowed cell's shape (4,194,304 x 1000
    bf16, a window of 419,430 rows a step): ONE Mosaic call a step, X handed
    to it as a bitcast of the parameter, no ``dynamic-slice`` of X and no
    array of the window's size (the two matvecs read it twice; the one-read
    kernel on ``X[o:o + m]`` had the window copied out, an 841.5 MB
    temporary a step).  On four chips each shard's window runs the same
    kernel under its ``valid`` mask (a padded shard brings one)."""
    import re

    from tpu_sgd.optimize.gradient_descent import make_run

    n = CELL_ROWS["resident"]
    cfg = _cfg(step_size=5.0, num_iterations=100, reg_param=0.001,
               convergence_tol=0.0, sampling="sliced")
    if shards == 1:
        compiled = jax.jit(
            make_run(LogisticGradient(), SquaredL2Updater(), cfg)).lower(
                S((D,), F32), S((n, D), BF16), S((n,), F32),
                _hyper(S((), F32))).compile()
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_sgd.parallel.data_parallel import dp_run_fn
        from tpu_sgd.parallel.mesh import DATA_AXIS

        rows = NamedSharding(mesh4, P(DATA_AXIS))
        compiled = dp_run_fn(LogisticGradient(), SquaredL2Updater(), cfg,
                             mesh4, with_valid=True).lower(
            S((D,), F32, NamedSharding(mesh4, P())),
            S((n, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
            S((n,), F32, rows),
            _hyper(S((), F32, NamedSharding(mesh4, P()))),
            S((n,), jnp.bool_, rows)).compile()
    text = compiled.as_text()
    local, m = n // shards, round(0.1 * (n // shards))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "sgd.fused_sums" in call and "_fused_lane_window_sums" in call
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (D, local) in text
    assert _moves_of(text, local, D) == [] and _moves_of(text, m, D) == []
    assert not re.search(r"= bf16\[[0-9,]+\]\S* dynamic-slice\(", text)
    memory = compiled.memory_analysis()
    window = m * D * 2
    assert memory.temp_size_in_bytes < window // 100
    assert memory.argument_size_in_bytes < local * D * 2 * 1.01


#: mnist8m's rows, width and classes (bench/configs/mnist8m-multinomial.json)
MNIST8M = (8_100_000, 784, 10)


def _classes_run(S):
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    n, d, K = MNIST8M
    cfg = _cfg(step_size=1.0, num_iterations=100, mini_batch_fraction=1.0,
               reg_param=0.001, convergence_tol=0.0)
    return jax.jit(make_run(MultinomialLogisticGradient(K),
                            SquaredL2Updater(), cfg)).lower(
        S(((K - 1) * d,), F32), S((n, d), BF16), S((n,), F32),
        _hyper(S((), F32))).compile()


def test_classes_run_at_the_cells_shape_reads_x_once_in_place(S):
    """``mnist8m-multinomial.resident-classes``: all 8,100,000 x 784 bf16
    rows (12.70 GB of a chip's 15.75) under a ``(9, 784)`` matrix of
    weights at fraction 1.0 (no mask).  ONE Mosaic call a step under
    ``sgd.class_sums``, ``X.T`` handed to it as a bitcast of the parameter,
    nothing of X's size made, NO array of a row's class count in HBM (the
    two matmuls hold ``f32[8100000,9]`` margins and ``[8100000,10]``
    logits between them) and temporaries under 1% of X."""
    import re

    n, d, K = MNIST8M
    compiled = _classes_run(S)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "sgd.class_sums" in call and "_fused_class_sums" in call
    assert "sgd.fused_sums" not in text
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (d, n) in text
    assert _moves_of(text, n, d) == []
    # X, its bitcast and the labels' row aside, no 2-D array has n rows
    rest = text
    for known in ("bf16[%d,%d]" % (n, d), "bf16[%d,%d]" % (d, n),
                  "f32[1,%d]" % n):
        rest = rest.replace(known, "")
    assert not re.search(r"\[%d,\d+\]|\[\d+,%d\]" % (n, n), rest)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n * d * 2 // 100
    assert memory.argument_size_in_bytes < n * d * 2 * 1.01


def _computations(text):
    """``{name: [instruction lines]}`` of a compiled program's text, the
    entry computation also under ``"ENTRY"``."""
    import re

    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                comps["ENTRY"] = comps[name]
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


def _reach(comps, root):
    """``root`` and every computation it calls, however deep (fusions,
    inner ``while`` bodies and conditions, reducers, branches)."""
    import re

    seen, todo = [], [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.append(name)
        for line in comps[name]:
            for called in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line):
                todo.append(called)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%?([\w.\-]+)", group)
    return seen


def _fit_loop_body(comps):
    """The body of the fit's ``while``: the one in the entry computation
    whose reach holds the Mosaic call."""
    import re

    for line in comps["ENTRY"]:
        loop = re.search(r" while\(.*body=%?([\w.\-]+)", line)
        if loop and any("tpu_custom_call" in inner
                        for name in _reach(comps, loop.group(1))
                        for inner in comps[name]):
            return loop.group(1)
    raise AssertionError("no while in ENTRY holds the kernel")


def _made_with(comps, names, elements):
    """``(opcode, shape)`` of every instruction in the computations
    ``names`` that MAKES an array of ``elements`` entries (a hand-on,
    ``_NO_MOVE``, makes none)."""
    import re

    made = []
    for name in names:
        for line in comps[name]:
            inst = re.match(
                r"(?:ROOT )?%?[\w.\-]+ = (\w+\[([\d,]+)\])\S* ([a-z\-]+)\(",
                line)
            if inst and inst.group(3) not in _NO_MOVE and np.prod(
                    [int(k) for k in inst.group(2).split(",")]) == elements:
                made.append((inst.group(3), inst.group(1)))
    return made


def test_the_class_fits_loop_holds_the_kernel_and_no_array_of_the_labels(S):
    """PR 33.  8,100,000 is no multiple of the flat layout's 1024, so
    ``f32[n] -> f32[1, n]`` moves every label, and at this size the chip's
    compiler writes it as a copy, a fill and an inner ``while`` of
    ``dynamic-slice`` + ``dynamic-update-slice`` which it left INSIDE the
    fit's loop while the source had the reshape there (0.296 ms of every
    17.08 ms step, PERF.md).  ``sgd_run`` lays the row out in front of the
    loop: the loop's body, and everything it calls, makes NO array of
    8,100,000 entries (the parent made six), the relayout stands in the
    entry computation, once, and the kernel reads the row the loop
    carries.  If a later compiler sinks it back, fence the row
    (``optimization_barrier``) or pad it once to a multiple of 1024."""
    n, d, K = MNIST8M
    comps = _computations(_classes_run(S).as_text())
    body = _reach(comps, _fit_loop_body(comps))
    assert _made_with(comps, body, n) == []
    outside = [name for name in _reach(comps, "ENTRY") if name not in body]
    made = _made_with(comps, outside, n)
    assert {"copy-done", "broadcast", "dynamic-update-slice"} <= {
        op for op, _ in made}
    assert all(shape in ("f32[%d]" % n, "f32[1,1,%d]" % n)
               for _, shape in made)
    call, = [line for name in body for line in comps[name]
             if "tpu_custom_call" in line]
    assert "f32[1,%d]" % n in call


#: a shard's rows in the three masked cells: the two one-chip cells' and the
#: four-chip cell's 10,000,000 over four
MASKED_ROWS = {**CELL_ROWS, "resident-sharded": 2_500_000}


@pytest.mark.parametrize("cell", sorted(MASKED_ROWS))
def test_the_masked_fits_loop_makes_no_array_of_the_masks_size(
        S, mesh4, cell):
    """PR 36, at the three masked cells' shapes.  The kernel draws each
    row's Bernoulli bit itself from the step's key (two prefetched words)
    and the row's index, so the fit's loop, and everything it calls, makes
    NO array of n entries: no ``f32[n]`` draw, no ``f32[1, n]`` relayout
    of one (the parent made the draw's fusion every step, 0.044 to 0.086
    ms, and where n is no multiple of 1024 a ``reshape`` of it besides,
    0.014 to 0.026; PERF.md, PR 36).  ONE Mosaic call a step, handed the
    key as ``s32[2]``, X as a bitcast and the labels' row the loop carries;
    what is left of ``sgd.sample`` is the key's folds.  Outside the loop
    the one array of n entries is the labels' ``reshape`` under
    ``sgd.prepare`` (PR 33; a bitcast at 4,194,304, where nothing is
    made)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    n = MASKED_ROWS[cell]
    if cell == "resident-sharded":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_sgd.parallel.data_parallel import dp_run_fn
        from tpu_sgd.parallel.mesh import DATA_AXIS

        text = dp_run_fn(
            LeastSquaresGradient(), SimpleUpdater(),
            _cfg(step_size=1.0, num_iterations=100, reg_param=0.0,
                 convergence_tol=0.0), mesh4, with_valid=False).lower(
            S((D,), F32, NamedSharding(mesh4, P())),
            S((4 * n, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
            S((4 * n,), F32, NamedSharding(mesh4, P(DATA_AXIS))),
            _hyper(S((), F32, NamedSharding(mesh4, P()))),
        ).compile().as_text()
    else:
        cfg = _cfg(step_size=5.0, num_iterations=100, reg_param=0.001,
                   convergence_tol=0.0)
        text = jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), cfg)
                       ).lower(S((D,), F32), S((n, D), BF16), S((n,), F32),
                               _hyper(S((), F32))).compile().as_text()
    comps = _computations(text)
    body = _reach(comps, _fit_loop_body(comps))
    assert _made_with(comps, body, n) == []
    outside = [name for name in _reach(comps, "ENTRY") if name not in body]
    moved = n % 1024 != 0
    assert _made_with(comps, outside, n) == [
        ("reshape", "f32[1,%d]" % n)] * moved
    if moved:
        laid, = [line for name in outside for line in comps[name]
                 if " reshape(" in line and "f32[1,%d]" % n in line]
        assert "sgd.prepare" in laid
    call, = [line for name in body for line in comps[name]
             if "tpu_custom_call" in line]
    assert "sgd.fused_sums" in call and "_fused_scan_sums" in call
    assert ("operand_layout_constraints={s32[2]{0}, bf16[%d,%d]{1,0}, "
            "f32[1,%d]{1,0}, f32[%d,128]{1,0}}" % (D, n, n, D)) in call
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (D, n) in text
    assert _moves_of(text, n, D) == []


#: sha256 (16 hex digits) of the masked fit's program lowered for a TPU at
#: the three masked cells' shapes: the StableHLO outside the Mosaic call,
#: and the call's body parsed and printed WITHOUT locations (the serialized
#: body carries file paths and line numbers).  Both of each pair are PR
#: 36's, which meant to change the masked step: the StableHLO no longer
#: draws ``bernoulli`` (no ``f32[n]`` mask, no third row operand; the step's
#: key goes to ``@_fused_scan_sums`` as ``tensor<2xui32>`` and to the call
#: as two prefetched int32 words), and the kernel's body draws the rows of
#: its block itself (``_row_draw``: twenty rounds of threefry on the row's
#: index, folded over the sublanes).  Before it the bodies were PR 30's (PR
#: 31 put the window's grid beside the masked one on the same body and left
#: them as they were) and the StableHLO PR 33's (the labels' ``reshape`` in
#: front of the ``while``, ``sgd.prepare``).
#: PR 62 moved the FIRST digest of every pair here and no second: the step
#: size and the regulariser are operands of the program (``config.Hyper``:
#: two scalar parameters more, their constants gone from the update), the
#: kernels' bodies are as they were.
#: A PR that means to change the masked step changes them here, and says so.
MASKED_PROGRAMS = {
    "resident": ("cdd4654faf10badf", "ebe1ae5e828b7d7c"),
    "from-host": ("e4fad55c2050a9d3", "a1e7fb6617a1c983"),
    "resident-sharded": ("687231a842a079b4", "03e9de77b6558f08"),
}


@pytest.mark.parametrize("cell", sorted(MASKED_PROGRAMS))
def test_the_masked_cells_lowered_program_is_the_pinned_one(cell):
    """Lowered from this CPU process for a TPU (nothing is compiled, so no
    described chip is needed)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    shape = jax.ShapeDtypeStruct
    if cell == "resident-sharded":
        from tpu_sgd.parallel.data_parallel import dp_run_fn
        from tpu_sgd.parallel.mesh import data_mesh

        n = 10_000_000
        fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                       _cfg(step_size=1.0, num_iterations=100, reg_param=0.0,
                            convergence_tol=0.0),
                       data_mesh(jax.devices()[:4]), False)
    else:
        n = CELL_ROWS[cell]
        fn = jax.jit(make_run(
            LogisticGradient(), SquaredL2Updater(),
            _cfg(step_size=5.0, num_iterations=100, reg_param=0.001,
                 convergence_tol=0.0)))
    assert _program_digests(fn, shape((D,), F32), shape((n, D), BF16),
                            shape((n,), F32), _hyper()
                            ) == MASKED_PROGRAMS[cell]


def _program_digests(fn, *shapes):
    """``(StableHLO outside the Mosaic call, the call's body without
    locations)`` of ``fn`` lowered from this CPU process for a TPU, as 16
    hex digits of sha256 each."""
    import hashlib

    outside, kernel = _lowered_for_a_tpu(fn, *shapes)

    def digest(s):
        return hashlib.sha256(s.encode()).hexdigest()[:16]

    return digest(outside), digest(kernel)


def _lowered_for_a_tpu(fn, *shapes):
    """``(StableHLO with the Mosaic call's body taken out, the body parsed
    and printed without locations)`` of ``fn`` lowered from this CPU
    process for a TPU; a program that holds no Mosaic call: an empty
    body."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    text = fn.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    body = r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22"
    calls = re.findall(body, text)
    kernel = ""
    if calls:
        serialized, = calls
        with jax_mlir.make_ir_context() as context:
            context.allow_unregistered_dialects = True
            kernel = ir.Module.parse(base64.b64decode(serialized)) \
                .operation.get_asm(enable_debug_info=False)
    return re.sub(body, '"body": ""', text), kernel


#: the same pair for the cells whose step is no masked scan: the windowed
#: cell's (PR 31's grid on PR 36's body), the class cell's and the wide
#: cell's (PR 32's and PR 34's, the labels laid out by PR 33), all three as
#: PR 37 left them and as PR 39 found and kept them while it gave the class
#: body its by-rows orientation; and the CIFAR-5m cell's by-rows program,
#: which is PR 39's.
#: PR 62 moved the FIRST digest of every pair here and no second: the step
#: size and the regulariser are operands of the program (``config.Hyper``:
#: two scalar parameters more, their constants gone from the update), the
#: kernels' bodies are as they were.
#: A PR that means to change one of these steps changes its pair here.
OTHER_PROGRAMS = {
    "sliced": ("bafa874aaf596ab7", "40c8672db62c8475"),
    "classes": ("23aaccb6d1ca7888", "29123780d775e1cd"),
    "wide": ("5a9a6b3024e69e5a", "e267abd75882cd0a"),
    "cifar5m": ("a31e82e867ab897f", "20c7220b35aa989c"),
}


@pytest.mark.parametrize("cell", sorted(OTHER_PROGRAMS))
def test_the_other_cells_lowered_program_is_the_pinned_one(cell):
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    base = dict(num_iterations=100, convergence_tol=0.0)
    grad, upd, cfg, n, d, wd = {
        "sliced": (LogisticGradient(), SquaredL2Updater(),
                   _cfg(step_size=5.0, reg_param=0.001, sampling="sliced",
                        **base), 4_194_304, D, D),
        "classes": (MultinomialLogisticGradient(10), SquaredL2Updater(),
                    _cfg(step_size=1.0, reg_param=0.001,
                         mini_batch_fraction=1.0, **base),
                    8_100_000, 784, 9 * 784),
        "wide": (HingeGradient(), L1Updater(),
                 _cfg(step_size=100.0, reg_param=1e-5,
                      mini_batch_fraction=1.0, **base),
                 131_072, 47_236, 47_236),
        "cifar5m": (MultinomialLogisticGradient(10), SquaredL2Updater(),
                    _cfg(step_size=1.0, reg_param=0.001,
                         mini_batch_fraction=1.0, **base),
                    2_000_896, 3072, 9 * 3072),
    }[cell]
    shape = jax.ShapeDtypeStruct
    assert _program_digests(
        jax.jit(make_run(grad, upd, cfg)), shape((wd,), F32),
        shape((n, d), BF16), shape((n,), F32), _hyper()
    ) == OTHER_PROGRAMS[cell]


#: the same pair for the step programs no pair above holds, PR 47's, taken
#: on its parent's code before the kernel's selection moved behind one
#: function (``ops/pallas_kernels.one_read``): the stream cell's fit from
#: the totals and its block fold (no Mosaic call: the second digest is an
#: empty body's), the stream's first fit by rows (the unmasked full scan), a
#: padded shard of a meshed masked fit (the draw times ``valid``), and the
#: selection's exclusions at the cells' own widths: a Bernoulli mask under
#: the by-rows and the wide form (an array operand, no draw in the class
#: body), a window at the wide width and by rows (two matvecs, no call).
#: PR 62 moved the FIRST digest of every pair here but the fold's and no second: the step
#: size and the regulariser are operands of the program (``config.Hyper``:
#: two scalar parameters more, their constants gone from the update), the
#: kernels' bodies are as they were.
#: A PR that means to change one of these programs changes its pair here.
SELECTED_PROGRAMS = {
    "stream_totals": ("2c8059dc84f194bd", "e3b0c44298fc1c14"),
    "stream_fold": ("9a66ecd2b3100030", "e3b0c44298fc1c14"),
    "stream_first": ("7b86996e009a0fb8", "0485639c28be3e02"),
    "padded_shard": ("e728ef480b1b1fdc", "f17d32ed29ba13f4"),
    "rows_masked": ("437b33a5f8685047", "cca4706f4d6f187f"),
    "wide_masked": ("af8544c54b1a2058", "426f73b1e6508f0d"),
    "wide_window": ("a2cdc76c502bb7fd", "e3b0c44298fc1c14"),
    "rows_window": ("a3feef3f74493697", "e3b0c44298fc1c14"),
}


@pytest.mark.parametrize("cell", sorted(SELECTED_PROGRAMS))
def test_the_selected_step_programs_are_the_pinned_ones(cell):
    from tpu_sgd.ops import gram
    from tpu_sgd.optimize.gradient_descent import make_run

    shape = jax.ShapeDtypeStruct
    base = dict(num_iterations=100, convergence_tol=0.0)
    n = 2_097_152
    if cell == "stream_fold":
        fn, shapes = gram._stats_fold, (
            shape((D, D), F32), shape((D,), F32), shape((), F32),
            shape((n,), F32), shape((), I32), shape((16_384, D), BF16))
    elif cell == "stream_totals":
        fn = jax.jit(make_run(
            gram.GramLeastSquaresGradient(), SimpleUpdater(),
            _cfg(step_size=0.1, num_iterations=50, mini_batch_fraction=1.0,
                 reg_param=0.0, convergence_tol=0.0)))
        stats = gram.GramData(
            None, None, None, None, shape((D, D), F32), shape((D,), F32),
            shape((), F32), n, logical_shape=(n, D), logical_dtype=BF16)
        shapes = (shape((D,), F32), stats, shape((n,), F32), _hyper())
    elif cell == "padded_shard":
        from tpu_sgd.parallel.data_parallel import dp_run_fn
        from tpu_sgd.parallel.mesh import data_mesh

        n = 10_000_000
        fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                       _cfg(step_size=1.0, reg_param=0.0, **base),
                       data_mesh(jax.devices()[:4]), True)
        shapes = (shape((D,), F32), shape((n, D), BF16), shape((n,), F32),
                  _hyper(), shape((n,), jnp.bool_))
    else:
        grad, upd, cfg, n, d = {
            "stream_first": (
                LeastSquaresGradient(), SimpleUpdater(),
                _cfg(step_size=0.1, num_iterations=50, reg_param=0.0,
                     mini_batch_fraction=1.0, convergence_tol=0.0), n, D),
            "rows_masked": (LogisticGradient(), SquaredL2Updater(),
                            _cfg(step_size=5.0, reg_param=0.001, **base),
                            n, 1024),
            "rows_window": (LogisticGradient(), SquaredL2Updater(),
                            _cfg(step_size=5.0, reg_param=0.001,
                                 sampling="sliced", **base), n, 1024),
            "wide_masked": (HingeGradient(), L1Updater(),
                            _cfg(step_size=100.0, reg_param=1e-5, **base),
                            131_072, 47_236),
            "wide_window": (HingeGradient(), L1Updater(),
                            _cfg(step_size=100.0, reg_param=1e-5,
                                 sampling="sliced", **base),
                            131_072, 47_236),
        }[cell]
        fn = jax.jit(make_run(grad, upd, cfg))
        shapes = (shape((d,), F32), shape((n, d), BF16), shape((n,), F32),
                  _hyper())
    assert _program_digests(fn, *shapes) == SELECTED_PROGRAMS[cell]


#: (rows, features): what ``feature_major`` must say of each, and the
#: chip's compiler does: the issue's three (f32 at d = 1000; bf16 at 1024
#: and 128), the two cells, the rule's edges (a tie, few rows, d not
#: a multiple of 8, a narrow d), and mnist8m's (PR 32: 784 pads to 896
#: columns by rows and to nothing by features)
LAYOUTS = {
    "f32_d1000": (F32, N, D), "bf16_d1024": (BF16, N, 1024),
    "bf16_d128": (BF16, N, 128), "resident": (BF16, 4_194_304, D),
    "from_host": (BF16, 2_145_000, D), "square_tie": (BF16, 1000, 1000),
    "few_rows": (BF16, 1024, D), "d1001": (F32, N, 1001),
    "narrow": (F32, 300, 24), "mnist8m": (BF16, 8_100_000, 784),
    "rcv1_dense": (BF16, 131_072, 47_236),
    # PR 39: 3,072 pixels (the CIFAR-5m cell's rows; LIBSVM SVHN's, whose
    # count is no multiple of 128) and an embedding's width: by rows
    "cifar5m": (BF16, 2_000_896, 3072), "svhn": (BF16, 604_388, 3072),
    "bf16_d768": (BF16, N, 768),
}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_feature_major_is_the_layout_the_compiler_gives(S, case):
    """``feature_major(n, d)`` — what the step's selection rests on —
    against the layout the chip's compiler gives an entry parameter of
    that shape (the device's default for it: a program that gathers rows
    and would rather have them contiguous is given the same)."""
    from tpu_sgd.ops.pallas_kernels import feature_major

    dtype, n, d = LAYOUTS[case]

    def layout_of(fn, *more):
        compiled = jax.jit(fn).lower(S((n, d), dtype), *more).compile()
        return compiled.input_formats[0][0].layout.major_to_minor

    by_rows, by_features = (0, 1), (1, 0)
    expect = by_features if feature_major(n, d) else by_rows
    assert layout_of(lambda X, w: jnp.dot(
        X, w.astype(X.dtype), preferred_element_type=F32),
        S((d,), F32)) == expect
    if n * d <= N * 1024:  # a gather of rows copies X: keep the copy small
        assert layout_of(lambda X, idx: X[idx], S((1000,), I32)) == expect
    assert feature_major(n, d) == (case not in (
        "bf16_d1024", "bf16_d128", "square_tie", "cifar5m", "svhn",
        "bf16_d768"))


def test_superstep_k8_compiles(S):
    """The host-streamed feed's fused program: K=8 per-step batches of
    one bf16-wire superchunk (frac 0.1 of 2**19 host rows)."""
    from tpu_sgd.optimize.gradient_descent import make_superstep

    K, m = 8, 52_429
    sstep = make_superstep(LeastSquaresGradient(), SimpleUpdater(),
                           _cfg(mini_batch_fraction=1.0))
    text = jax.jit(sstep).lower(
        S((D,), F32), S((), F32), _hyper(S((), F32)), S((), I32),
        S((K, m, D), BF16), S((K, m), F32), S((K, m), jnp.bool_),
    ).compile().as_text()
    assert "tpu_custom_call" in text  # each step's sums: the one-read kernel


def test_resident_while_loop_compiles(S):
    """The device-resident whole-run driver: ``lax.while_loop`` over
    fused supersteps with its ordered ``io_callback`` window hook."""
    from tpu_sgd.optimize.gradient_descent import make_step
    from tpu_sgd.optimize.resident_driver import ResidentLoop

    cfg = _cfg(num_iterations=64)
    step = make_step(LeastSquaresGradient(), SimpleUpdater(), cfg)
    loop = ResidentLoop(
        lambda w, i, rv, hyper, X, y: step(w, X, y, i, rv, hyper), cfg, 8, 4)
    compiled = loop._fn.lower(
        S((D,), F32), S((), F32), S((), I32), _hyper(S((), F32)),
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
        _hyper(S((), F32, rep)),
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
        _hyper(S((), F32, NamedSharding(mesh4, P()))),
    ).compile().as_text()
    assert "all-reduce" in text and "while" in text
    # each shard's block is stored as the whole is: the kernel, no copy
    assert "tpu_custom_call" in text
    assert _moves_of(text, N // 4, D) == []


def test_dp_whole_run_at_the_four_chip_cells_shape_trains_each_shard_in_place(
        mesh4, S):
    """The benchmark's four-chip fit (``dense1000-lsq-dp4.resident-sharded``:
    10,000,000 x 1000 bf16, least squares, fraction 0.1) as ``set_mesh``
    dispatches it: every chip holds its 2,500,000 rows (5.01 GB of
    arguments), the one-read kernel takes the shard as it is stored (no
    X-sized array is made, temporaries under 1% of the shard), and one
    all-reduce a step carries the gradient, loss and count sums."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_run_fn
    from tpu_sgd.parallel.mesh import DATA_AXIS

    n = 10_000_000
    fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                   _cfg(step_size=1.0, num_iterations=100, reg_param=0.0,
                        convergence_tol=0.0), mesh4, with_valid=False)
    compiled = fn.lower(
        S((D,), F32, NamedSharding(mesh4, P())),
        S((n, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
        S((n,), F32, NamedSharding(mesh4, P(DATA_AXIS))),
        _hyper(S((), F32, NamedSharding(mesh4, P()))),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert text.count(" all-reduce(") == 1 and "sgd.allreduce" in text
    assert "all-gather" not in text
    assert _moves_of(text, n // 4, D) == [] and _moves_of(text, n, D) == []
    memory = compiled.memory_analysis()
    shard = n // 4 * D * 2
    assert shard <= memory.argument_size_in_bytes < shard * 1.01
    assert memory.temp_size_in_bytes < shard // 100


def test_the_four_chip_cells_run_restored_from_its_export_is_the_traced_one(
        mesh4, S):
    """What the store of exported runners (``optimize/run_store.py``) hands
    XLA for the four-chip cell: ``dp_run_fn``'s program exported with its
    shardings, serialized, read back and called under a ``jax.jit``.  It
    compiles for the four described chips to what the traced program does:
    one kernel a shard over the shard as it is stored, one all-reduce, the
    same arguments and temporaries, the ``sgd.*`` scopes in place."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_run_fn
    from tpu_sgd.parallel.mesh import DATA_AXIS

    n = 10_000_000
    fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                   _cfg(step_size=1.0, num_iterations=100, reg_param=0.0,
                        convergence_tol=0.0), mesh4, with_valid=False)
    shapes = (S((D,), F32, NamedSharding(mesh4, P())),
              S((n, D), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
              S((n,), F32, NamedSharding(mesh4, P(DATA_AXIS))),
              _hyper(S((), F32, NamedSharding(mesh4, P()))))
    # over the arguments' flat leaves, as the store exports it
    # (``run_store.export``: the operands' ``config.Hyper`` is a node of the
    # tree, in the key, and needs no serialization registry)
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    exported = jax.export.export(
        jax.jit(lambda *flat: fn(*jax.tree_util.tree_unflatten(tree, flat))),
        platforms=("tpu",))(*leaves)
    assert exported.nr_devices == 4
    restored = jax.export.deserialize(exported.serialize())
    compiled = jax.jit(lambda *a: restored.call(
        *jax.tree_util.tree_leaves(a))).lower(*shapes).compile()
    traced = fn.lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert text.count(" all-reduce(") == 1 and "all-gather" not in text
    assert "sgd.allreduce" in text and "sgd.fused_sums" in text
    assert _moves_of(text, n // 4, D) == [] and _moves_of(text, n, D) == []
    mine, theirs = compiled.memory_analysis(), traced.memory_analysis()
    # (to the operands' two scalars: the restored call holds them as
    # arguments of its own, 256 bytes each)
    assert abs(mine.argument_size_in_bytes
               - theirs.argument_size_in_bytes) <= 1024
    assert mine.temp_size_in_bytes == theirs.temp_size_in_bytes
    assert mine.output_size_in_bytes == theirs.output_size_in_bytes


# -- the hand-off -------------------------------------------------------------

@pytest.mark.parametrize("rows", ["full", "remainder"])
def test_the_hand_offs_writer_writes_its_destination_in_place(S, rows):
    """``_stage_block`` at the from-host cell's shape (2,145,000 x 1000 bf16
    in blocks of ``_STAGE_BLOCK_BYTES``: 130 full ones and a remainder): the
    donated destination IS the result (all of it aliased, no temporary, no
    copy of it to another layout, the one X-sized array made is the
    ``dynamic-update-slice`` itself), the block arrives in the layout the
    chip gives a host array of its shape (feature-major: a window of rows is
    a window of lanes), and the write carries the ``sgd.stage`` scope."""
    from tpu_sgd.optimize.gradient_descent import (_STAGE_BLOCK_BYTES,
                                                   _STAGE_ROWS, _stage_block)

    n = CELL_ROWS["from-host"]
    full = _STAGE_BLOCK_BYTES // (2 * D) // _STAGE_ROWS * _STAGE_ROWS
    block = full if rows == "full" else n % full
    assert full == 16_384 and -(-n // full) == 131 and 0 < block <= full
    compiled = _stage_block.lower(S((n, D), BF16), S((block, D), BF16),
                                  S((), I32)).compile()
    text = compiled.as_text()
    assert _moves_of(text, n, D) == ["dynamic-update-slice"]
    assert "sgd.stage/dynamic_update_slice" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes >= n * D * 2  # rows pad to 128
    by_features = (1, 0)
    assert [f.layout.major_to_minor for f in compiled.input_formats[0][:2]] \
        == [by_features, by_features]


#: the four-chip from-host cell (``dense1000-lsq-dp4-run.from-host-sharded``):
#: a shard of 10,000,000 x 1000 bf16 rows over four chips
SHARD_ROWS = 2_500_000


@pytest.mark.parametrize("program", ["fill", "full", "remainder"])
@pytest.mark.parametrize("chip", range(4))
def test_the_sharded_hand_offs_programs_compile_for_each_chip(
        topo, no_persistent_cache, chip, program):
    """Under a mesh ``_stage_dense`` has a destination a DEVICE: the fill of
    a ``(2,500,000, 1000)`` bf16 shard and its in-place writes (152 blocks
    of 16,384 rows and a remainder of 9,632) compile for every one of the
    four described chips as they do for one: the destination aliased, no
    temporary, and nothing of a shard's size made besides the write itself
    (the fill makes the shard and nothing else)."""
    import functools

    from jax.sharding import SingleDeviceSharding

    from tpu_sgd.optimize.gradient_descent import (_STAGE_BLOCK_BYTES,
                                                   _STAGE_ROWS, _stage_block,
                                                   _stage_dest)

    here = SingleDeviceSharding(topo.devices[chip])
    n = SHARD_ROWS
    full = _STAGE_BLOCK_BYTES // (2 * D) // _STAGE_ROWS * _STAGE_ROWS
    assert full == 16_384 and -(-n // full) == 153 and n % full == 9_632
    if program == "fill":
        compiled = jax.jit(
            functools.partial(_stage_dest.__wrapped__, (n, D), BF16),
            out_shardings=here).lower().compile()
        memory = compiled.memory_analysis()
        assert n * D * 2 <= memory.output_size_in_bytes < n * D * 2 * 1.01
        assert memory.temp_size_in_bytes == 0
        assert "sgd.stage" in compiled.as_text()
        return
    block = full if program == "full" else n % full

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=here)

    compiled = _stage_block.lower(shape((n, D), BF16), shape((block, D), BF16),
                                  shape((), I32)).compile()
    text = compiled.as_text()
    assert _moves_of(text, n, D) == ["dynamic-update-slice"]
    assert "sgd.stage/dynamic_update_slice" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes >= n * D * 2
    by_features = (1, 0)
    assert [f.layout.major_to_minor for f in compiled.input_formats[0][:2]] \
        == [by_features, by_features]


def _entry_instructions(text):
    """``(opcode, result shape, op_name or None)`` of the instructions of a
    compiled module's entry computation."""
    import re

    out = []
    for line in text[text.index("ENTRY"):].split("\n")[1:]:
        found = re.search(r"= (\(?\S+) ([a-z-]+)\(", line)
        if found:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((found.group(2), found.group(1),
                        name and name.group(1)))
    return out


@pytest.mark.parametrize("rows", ["full", "remainder"])
@pytest.mark.parametrize("form", ["flat", "words"])
@pytest.mark.parametrize("cell", ["from-host", "from-host-sharded"])
def test_the_writer_makes_the_chips_layout_of_a_block_as_it_crossed(
        S, cell, form, rows):
    """``_stage_block`` on a block in the forms PR 49 hands the runtime, at
    both from-host cells' destinations (2,145,000 and 2,500,000 x 1000 bf16;
    a full block of 16,384 rows and the cell's remainder): a C-ordered
    array's rows as ONE flat run, and a Fortran-ordered array's as
    ``(rows / 2, 1000)`` 32-bit words, which arrive feature-major in plain
    4-byte tiles (no 2-byte packing left for the host to do).  The chip
    re-tiles the block and writes it in place: the donated destination IS
    the result, the one X-sized array made is the ``dynamic-update-slice``
    itself, no temporary of even a block's size is left in device memory,
    and every operation that makes an array carries ``sgd.stage`` (so
    ``stage_ms`` and ``step_unscoped_share`` read the re-tiling)."""
    from tpu_sgd.optimize.gradient_descent import (_STAGE_BLOCK_BYTES,
                                                   _STAGE_ROWS, _stage_block)

    n = {"from-host": CELL_ROWS["from-host"],
         "from-host-sharded": SHARD_ROWS}[cell]
    full = _STAGE_BLOCK_BYTES // (2 * D) // _STAGE_ROWS * _STAGE_ROWS
    block = full if rows == "full" else n % full
    assert full == 16_384 and 0 < block <= full and not block % 2
    crossed = (S((block * D,), BF16) if form == "flat"
               else S((block // 2, D), jnp.uint32))
    compiled = _stage_block.lower(S((n, D), BF16), crossed,
                                  S((), I32)).compile()
    text = compiled.as_text()
    assert _moves_of(text, n, D) == ["dynamic-update-slice"]
    assert "sgd.stage/dynamic_update_slice" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= n * D * 2  # rows pad to 128
    assert memory.temp_size_in_bytes < block * D * 2
    made = [(op, shape, name) for op, shape, name in _entry_instructions(text)
            if op not in ("parameter", "constant", "tuple",
                          "get-tuple-element") and "[]" not in shape]
    assert made and all(name and "sgd.stage/" in name
                        for _, _, name in made), made
    dest, arrived = compiled.input_formats[0][:2]
    by_features = (1, 0)
    assert dest.layout.major_to_minor == by_features
    if form == "words":
        assert arrived.layout.major_to_minor == by_features
        assert tuple(arrived.layout.tiling) == ((8, 128),)
    else:
        assert arrived.layout.major_to_minor == (0,)


def test_a_batch_staged_ahead_is_made_whole_in_one_program(S):
    """``_stage_join`` at the stream cell's shape (a micro-batch of
    2,097,152 x 1000 bf16 as 128 blocks of 16,384 rows): one program whose
    result is the one array in the layout the fit reads (feature-major, as
    its blocks arrive), with no temporary of a block's size beside the
    blocks and the result, one write a block and no fill of the array."""
    from tpu_sgd.optimize.gradient_descent import _stage_join

    n, block = 2_097_152, 16_384
    compiled = _stage_join.lower(
        *[S((block, D), BF16)] * (n // block)).compile()
    # one write a block, the first one of block 0 alone: no fill
    text = compiled.as_text()
    writes = [line for line in text.split("\n")
              if " fusion(" in line and "ENTRY" not in line]
    assert len(writes) == n // block and " pad(" not in text
    assert "sgd.stage/concatenate" in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == n * D * 2
    assert memory.argument_size_in_bytes == n * D * 2
    assert memory.temp_size_in_bytes < block * D * 2
    by_features = (1, 0)
    assert compiled.output_formats.layout.major_to_minor == by_features
    assert {f.layout.major_to_minor for f in compiled.input_formats[0]} \
        == {by_features}


# -- a stream's micro-batch at a row capacity (PR 52) ----------------------

#: the uneven logistic stream's capacity: every micro-batch of 1,048,577 to
#: 2,097,152 rows (bench/configs/dense1000-logistic-stream.json)
CAPACITY = 2_097_152


def _bounded_run(S):
    from tpu_sgd.ops.gradients import RowCount
    from tpu_sgd.optimize.gradient_descent import make_run

    cfg = _cfg(step_size=0.1, num_iterations=50, reg_param=0.0,
               mini_batch_fraction=1.0, convergence_tol=0.0)
    return jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), cfg)).lower(
        S((D,), F32), S((CAPACITY, D), BF16), S((CAPACITY,), F32),
        _hyper(S((), F32)), RowCount(S((), I32)))


def test_the_bounded_run_at_the_stream_cells_capacity_reads_x_in_place(S):
    """The uneven stream's fit: ONE program for every row count up to the
    capacity (the count is a scalar operand), the kernel in it, ``X.T`` a
    bitcast of the parameter, no array of X's size or of a mask's made, and
    the loop's body holds no relayout of the labels."""
    compiled = _bounded_run(S).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (D, CAPACITY) in text
    assert _moves_of(text, CAPACITY, D) == []
    assert compiled.memory_analysis().temp_size_in_bytes \
        < CAPACITY * D * 2 // 100
    comps = _computations(text)
    body = _reach(comps, _fit_loop_body(comps))
    assert _made_with(comps, body, CAPACITY) == []


def test_the_bounded_kernels_grid_is_the_capacitys_and_its_count_a_scalar():
    """Lowered from this CPU process for a TPU: the bounded fit's Mosaic
    call takes its row count as a prefetched scalar (two of them: the count
    and its last block), runs the full scan's grid over the capacity, and
    no ``(n,)`` mask is an operand of it; a count in ``valid``'s place
    under a Bernoulli fraction is made the padded shard's array."""
    from tpu_sgd.ops.gradients import RowCount
    from tpu_sgd.optimize.gradient_descent import make_run

    shape = jax.ShapeDtypeStruct
    args = (shape((D,), F32), shape((CAPACITY, D), BF16),
            shape((CAPACITY,), F32), _hyper(), RowCount(shape((), I32)))
    cfg = _cfg(step_size=0.1, num_iterations=50, reg_param=0.0,
               mini_batch_fraction=1.0, convergence_tol=0.0)
    outside, kernel = _lowered_for_a_tpu(
        jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), cfg)), *args)
    assert "_fm_kernel" in kernel and "memref<2xi32" in kernel
    assert "i1[%d]" % CAPACITY not in outside
    assert "tensor<%dxi1>" % CAPACITY not in outside
    sampled = _cfg(step_size=0.1, num_iterations=50, reg_param=0.0,
                   mini_batch_fraction=0.5, convergence_tol=0.0)
    outside, kernel = _lowered_for_a_tpu(
        jax.jit(make_run(LogisticGradient(), SquaredL2Updater(), sampled)),
        *args)
    assert "tensor<%dxi1>" % CAPACITY in outside  # arange(n) < rows


def test_a_batch_at_a_capacity_is_made_whole_in_one_program(S):
    """``_stage_join`` under ``sgd.whole`` at the uneven stream's capacity:
    128 block operands whatever the micro-batch's own count (the rest the
    one block of zeros), one write a block, the result the capacity's array in the
    layout the fit reads, no temporary of a block's size; and the labels'
    program beside it."""
    from tpu_sgd.optimize.gradient_descent import _stage_join

    block = 16_384
    compiled = _stage_join.lower(
        *[S((block, D), BF16)] * (CAPACITY // block),
        scope="sgd.whole").compile()
    text = compiled.as_text()
    assert "sgd.whole/concatenate" in text and " pad(" not in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == CAPACITY * D * 2
    assert memory.temp_size_in_bytes < block * D * 2
    assert compiled.output_formats.layout.major_to_minor == (1, 0)
    labels = _stage_join.lower(
        *[S((block,), F32)] * (CAPACITY // block),
        scope="sgd.whole").compile()
    assert labels.memory_analysis().output_size_in_bytes == CAPACITY * 4
    # every array of a stream but its first is written over the one trained
    # before it: the result IS the operand given up, and nothing is copied
    over = _stage_join.lower(
        *[S((block, D), BF16)] * (CAPACITY // block), scope="sgd.whole",
        into=S((CAPACITY, D), BF16)).compile()
    memory = over.memory_analysis()
    assert memory.alias_size_in_bytes == CAPACITY * D * 2
    assert memory.temp_size_in_bytes < block * D * 2
    assert " pad(" not in over.as_text() and " copy(" not in over.as_text()


@pytest.mark.parametrize("case", ["stream_cell", "by_rows", "f32"])
def test_the_statistics_build_reads_x_where_it_lies(S, case):
    """``ops.gram._stats_build`` (PR 41) at the stream cell's micro-batch
    (2,097,152 x 1000 bf16, which the chip stores feature-major: ``X.T`` is a
    bitcast), at a width it stores by rows, and over f32 rows: X is an
    operand of the products as it lies, no copy, and NO temporary of X's size
    (the build runs beside a micro-batch staged ahead: 8.4 GB of a 16 GB
    chip are rows); every operation carries the scope."""
    from tpu_sgd.ops import gram

    n, d, dtype = {"stream_cell": (2_097_152, D, BF16),
                   "by_rows": (2_097_152, 1024, BF16),
                   "f32": (N, D, F32)}[case]
    compiled = gram._stats_build.lower(S((n, d), dtype),
                                       S((n,), F32)).compile()
    memory = compiled.memory_analysis()
    item = jnp.dtype(dtype).itemsize
    assert memory.temp_size_in_bytes < 64 << 20 < n * d * item // 16
    assert memory.output_size_in_bytes < 3 * (d * d + d + 1) * 4
    text = compiled.as_text()
    assert " copy(" not in text and " transpose(" not in text
    assert text.count(" convolution(") == (2 if dtype == BF16 else 1)
    assert "sgd.stats_build/dot_general" in text
    by_features, by_rows = (1, 0), (0, 1)
    assert compiled.input_formats[0][0].layout.major_to_minor == (
        by_rows if case == "by_rows" else by_features)


@pytest.mark.parametrize("case", ["stream_cell", "by_rows", "f32"])
def test_the_blocks_fold_reads_the_block_where_it_lies(S, case):
    """``ops.gram._stats_fold`` (PR 44) at the hand-off's row block of the
    stream cell (16,384 x 1000 bf16: ``_block_rows``), at a width the chip
    stores by rows and over f32 rows, beside a micro-batch's 2,097,152
    labels: the block is an operand of both products as it lies (the layout
    the 2M-row build reads, so a block lands as the build would have it: no
    relayout), the temporaries are under the block's size, and ``G``, ``b``,
    ``yy`` are donated and aliased: added to in place, 12 MB a bundle
    whatever the stream does."""
    from tpu_sgd.ops import gram

    n = 2_097_152
    d, dtype = {"stream_cell": (D, BF16), "by_rows": (1024, BF16),
                "f32": (D, F32)}[case]
    item = jnp.dtype(dtype).itemsize
    rows = (32 << 20) // (d * item) // 1024 * 1024
    assert rows == {"stream_cell": 16_384, "by_rows": 16_384,
                    "f32": 8_192}[case]
    compiled = gram._stats_fold.lower(
        S((d, d), F32), S((d,), F32), S((), F32), S((n,), F32), S((), I32),
        S((rows, d), dtype)).compile()
    memory = compiled.memory_analysis()
    totals = (d * d + d + 1) * 4
    assert memory.temp_size_in_bytes < rows * d * item // 4
    # all three outputs but the scalar that says "done" are the arguments'
    # own buffers (a width of 1000 is padded to the tile)
    assert totals <= memory.alias_size_in_bytes \
        <= memory.output_size_in_bytes < memory.alias_size_in_bytes + 4096
    assert memory.output_size_in_bytes < 1.1 * totals
    text = compiled.as_text()
    assert " transpose(" not in text
    # the compiler's own copies are of scalars (the offset, yy)
    assert all("[]" in line.split(" copy(")[0].split("=")[1]
               for line in text.split("\n") if " copy(" in line)
    assert text.count(" convolution(") == (2 if dtype == BF16 else 1)
    assert "jit(_stats_fold)/sgd.stats_build/dot_general" in text
    by_features, by_rows = (1, 0), (0, 1)
    block = compiled.input_formats[0][5]
    assert block.layout.major_to_minor == (
        by_rows if case == "by_rows" else by_features)
    whole = gram._stats_build.lower(S((n, d), dtype), S((n,), F32)).compile()
    assert whole.input_formats[0][0].layout.major_to_minor \
        == block.layout.major_to_minor


def test_a_fit_from_the_totals_holds_nothing_of_xs_size(S):
    """``sgd_run`` over the totals' bundle at the stream cell's shape: its
    arguments are G, b, yy, the labels and the weights (12.4 MB), so a fit
    that runs while the next micro-batch lands holds none of the rows."""
    from tpu_sgd.ops.gram import GramData, GramLeastSquaresGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    n = 2_097_152
    run = jax.jit(make_run(
        GramLeastSquaresGradient(), SimpleUpdater(),
        _cfg(step_size=0.1, num_iterations=50, mini_batch_fraction=1.0,
             reg_param=0.0, convergence_tol=0.0)))
    stats = GramData(None, None, None, None, S((D, D), F32), S((D,), F32),
                     S((), F32), n, logical_shape=(n, D),
                     logical_dtype=BF16)
    compiled = run.lower(S((D,), F32), stats, S((n,), F32),
                         _hyper(S((), F32))).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 4 * n + 2 * (D * D + 2 * D) * 4
    assert memory.temp_size_in_bytes < 16 << 20
    assert "sgd.stats_sums/dot_general" in compiled.as_text()


# -- sparse ------------------------------------------------------------------

def test_sparse_hinge_l1_step_compiles(S):
    """Hinge + L1 on BCOO at RCV1's published width, never densified."""
    from jax.experimental.sparse import BCOO

    from tpu_sgd.optimize.gradient_descent import make_step

    step = make_step(HingeGradient(), L1Updater(), _cfg())
    nse = SPARSE_N * SPARSE_NNZ

    def sparse_step(w, data, idx, y, i, rv, hyper):
        X = BCOO((data, idx), shape=(SPARSE_N, SPARSE_D),
                 indices_sorted=True, unique_indices=True)
        return step(w, X, y, i, rv, hyper)

    compiled = jax.jit(sparse_step).lower(
        S((SPARSE_D,), F32), S((nse,), F32), S((nse, 2), I32),
        S((SPARSE_N,), F32), S((), I32), S((), F32),
        _hyper(S((), F32))).compile()
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

    def sparse_run(w, data, idx, y, hyper):
        X = BCOO((data, idx), shape=(SPARSE_N, SPARSE_D),
                 indices_sorted=True, unique_indices=True)
        return run(w, X, y, hyper)

    jax.jit(sparse_run).lower(
        S((SPARSE_D,), F32), S((nse,), F32), S((nse, 2), I32),
        S((SPARSE_N,), F32), _hyper(S((), F32))).compile()


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


# -- the Pallas kernel at a tile of the caller's -------------------------------

def _lower_masked(S, dtype, tile_m):
    from tpu_sgd.ops.pallas_kernels import _fused_scan_sums

    return _fused_scan_sums.lower(
        LeastSquaresGradient().pointwise, S((KERNEL_N, D), dtype),
        S((KERNEL_N,), F32), S((D,), F32), S((KERNEL_N,), jnp.bool_),
        tile_m=tile_m)


#: what the chip's compiler refuses at d=1000: the masked full scan over
#: ``(d, tile)`` blocks of ``X.T`` at the tiles whose two buffers alone pass
#: the 32M it asks for (bf16: 16384 lanes, 65.5M; f32: 8192 lanes, 65.5M)
REFUSED = {"masked_bf16": (BF16, 16384), "masked_f32": (F32, 8192)}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_vmem_estimate_refuses_what_the_compiler_refuses(S, case):
    """``_check_fm_vmem`` raises its actionable ``ValueError`` BEFORE
    compiling exactly where the chip's compiler would refuse the kernel —
    and the tile its hint names does compile."""
    import re

    from tpu_sgd.ops.pallas_kernels import _check_fm_vmem

    dtype, refused_tile = REFUSED[case]
    X = S((KERNEL_N, D), dtype)
    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        _check_fm_vmem(refused_tile, X, True)
    with pytest.raises(Exception, match="(?i)vmem"):
        # the compiler's own verdict agrees
        _lower_masked(S, dtype, refused_tile).compile()
    tile = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    _check_fm_vmem(tile, X, True)
    assert "tpu_custom_call" in _lower_masked(
        S, dtype, tile).compile().as_text()


def test_public_kernel_entry_points_refuse_before_compiling(S):
    """The public wrapper runs the check first: a tile that every
    interpret-mode test passes is a ``ValueError`` here, not a Mosaic
    compile error on the chip."""
    from tpu_sgd.ops.pallas_kernels import fused_gradient_sums

    pw = LeastSquaresGradient().pointwise
    with pytest.raises(ValueError, match="scoped VMEM"):
        fused_gradient_sums(pw, S((KERNEL_N, D), BF16), S((KERNEL_N,), F32),
                            S((D,), F32), S((KERNEL_N,), jnp.bool_),
                            tile_m=16384)



# -- the wide form: a vector of weights as rows, at RCV1's width ----------------

#: the wide cell's rows and width (bench/configs/rcv1-dense-hinge-l1.json)
RCV1_DENSE = (131_072, 47_236)


def _lower_wide(S, n, dtype, masked, tile_m, limit=None):
    from tpu_sgd.ops import pallas_kernels as PK

    d = RCV1_DENSE[1]
    limit = PK._FM_WIDE_VMEM_LIMIT if limit is None else limit
    args = [S((n, d), dtype), S((n,), F32), S((d,), F32)]
    if masked:
        args.append(S((n,), jnp.bool_))
    fblock = PK._fm_feature_block(d, tile_m, jnp.dtype(dtype).itemsize, limit)
    return PK._fused_wide_sums.lower(
        HingeGradient().pointwise, *args, tile_m=tile_m, fblock=fblock,
        vmem_limit=limit)


def test_wide_run_at_the_cells_shape_reads_x_once_in_place(S):
    """``rcv1-dense-hinge-l1.resident-wide``: 131,072 x 47,236 bf16 rows
    (12.38 GB of a chip's 15.75) under hinge and the L1 prox at fraction
    1.0.  ONE Mosaic call a step under ``sgd.wide_sums`` in a jitted
    function of its own name, ``X.T`` handed to it as a bitcast of the
    parameter, nothing of X's size made, no ``(n,)`` margins between two
    products, temporaries under 1% of X."""
    from tpu_sgd.optimize.gradient_descent import make_run

    n, d = RCV1_DENSE
    cfg = _cfg(step_size=100.0, num_iterations=100, mini_batch_fraction=1.0,
               reg_param=1e-5, convergence_tol=0.0)
    compiled = jax.jit(make_run(HingeGradient(), L1Updater(), cfg)).lower(
        S((d,), F32), S((n, d), BF16), S((n,), F32),
        _hyper(S((), F32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "sgd.wide_sums" in call and "_fused_wide_sums" in call
    assert "sgd.fused_sums" not in text
    assert "bf16[%d,%d]{1,0:T(8,128)(2,1)} bitcast(" % (d, n) in text
    assert _moves_of(text, n, d) == []
    # the weights go in as 16 rows in X's type and the gradient comes out
    # as 16 rows in f32: no (d, 128) lane-broadcast operand
    assert "bf16[16,%d]" % d in call and "f32[16,%d]" % d in call
    assert "f32[%d,128]" % d not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n * d * 2 // 100
    assert memory.argument_size_in_bytes < n * d * 2 * 1.01


#: (type, masked): the cell's bf16 rows; f32 rows under a mask
WIDE_CASES = {"rcv1": (BF16, False), "f32": (F32, True)}


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_the_wide_kernels_vmem_count_admits_what_the_compiler_admits(S, case):
    """``_fm_vmem_bytes`` for the wide form stays above the compiler's own
    count: the kernel's own tile at 47,236 features compiles when the
    compiler is asked for exactly what the tile was counted at (so under
    the limit the kernel asks for too); twice that tile is refused by the
    count, and by the compiler under those same bytes; the largest tile
    the count's hint names compiles."""
    import re

    from tpu_sgd.ops import pallas_kernels as PK

    dtype, masked = WIDE_CASES[case]
    n, d = 6144, RCV1_DENSE[1]  # whole tiles of 128, 256 and 384 rows
    itemsize = jnp.dtype(dtype).itemsize
    limit = PK._FM_WIDE_VMEM_LIMIT
    wide = PK.one_read(n, d, itemsize, masked)
    assert (wide.body, wide.vmem_limit) == ("wide", limit)
    own, fblock = wide.tile, wide.fblock
    assert (own, fblock) == PK._fm_wide_plan(n, d, itemsize, masked, limit)
    _, rows = PK.wide_rows_of(dtype)
    X = S((n, d), dtype)
    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        PK._check_fm_vmem(2 * own, X, masked, rows, fblock, limit)
    counted = PK._fm_vmem_bytes(own, d, itemsize, masked, rows, fblock)
    assert counted <= limit
    assert "tpu_custom_call" in _lower_wide(
        S, n, dtype, masked, own, counted).compile().as_text()
    with pytest.raises(Exception, match="(?i)vmem"):
        _lower_wide(S, n, dtype, masked, 2 * own, counted).compile()
    tile = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    assert own <= tile < 2 * own
    PK._check_fm_vmem(tile, X, masked, rows, fblock, limit)
    assert "tpu_custom_call" in _lower_wide(
        S, n, dtype, masked, tile).compile().as_text()


def test_the_wide_entry_refuses_a_width_no_tile_fits_before_compiling(S):
    from tpu_sgd.ops.pallas_kernels import fused_wide_sums

    n, d = 8192, 400_004
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_wide_sums(HingeGradient().pointwise, S((n, d), BF16),
                        S((n,), F32), S((d,), F32))


# -- the class kernel: a (K-1, d) matrix of weights --------------------------

def _lower_classes(S, dtype, classes, masked, tile_m):
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.ops.pallas_kernels import _fused_class_sums, class_rows_of

    d = MNIST8M[1]
    args = [S((KERNEL_N, d), dtype), S((KERNEL_N,), F32),
            S((classes - 1, d), F32)]
    if masked:
        args.append(S((KERNEL_N,), jnp.bool_))
    return _fused_class_sums.lower(
        MultinomialLogisticGradient(classes).class_rule, *args,
        rows=class_rows_of(classes - 1, dtype), tile_m=tile_m)


#: (type, classes, masked): mnist8m's ten classes with and without a mask,
#: float32 rows, and as many class rows as one pass takes (128)
CLASS_CASES = {"mnist8m": (BF16, 10, False), "mnist8m_masked": (BF16, 10, True),
               "f32": (F32, 10, True), "rows_128": (BF16, 129, True)}


@pytest.mark.parametrize("case", sorted(CLASS_CASES))
def test_the_class_kernels_vmem_count_admits_what_the_compiler_admits(
        S, case):
    """``_check_fm_vmem`` with the class rows counted: the kernel's own
    tile compiles, a tile of 16384 lanes is refused by the count and by
    the compiler alike, and the largest tile the count's hint names
    compiles."""
    import re

    from tpu_sgd.ops.pallas_kernels import (_check_fm_vmem, class_rows_of,
                                            one_read)

    dtype, classes, masked = CLASS_CASES[case]
    d = MNIST8M[1]
    rows = class_rows_of(classes - 1, dtype)
    X = S((KERNEL_N, d), dtype)
    own = one_read(KERNEL_N, d, jnp.dtype(dtype).itemsize, masked, rows)
    assert own is not None and (own.body, own.by_rows) == ("class", False)
    own = own.tile
    assert "tpu_custom_call" in _lower_classes(
        S, dtype, classes, masked, own).compile().as_text()
    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        _check_fm_vmem(16384, X, masked, rows)
    with pytest.raises(Exception, match="(?i)vmem"):
        _lower_classes(S, dtype, classes, masked, 16384).compile()
    tile = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    assert tile >= own
    _check_fm_vmem(tile, X, masked, rows)
    assert "tpu_custom_call" in _lower_classes(
        S, dtype, classes, masked, tile).compile().as_text()


# -- the by-rows form: row blocks of an X the chip stores by rows (PR 39) -------

#: the cell's rows, width and classes (bench/configs/
#: cifar5m-multinomial.json), and a vector's shape at a hashed space's width
CIFAR5M = (2_000_896, 3072, 10)
ROWS_VECTOR = (2_097_152, 1024)


@pytest.mark.parametrize("case", ["cifar5m_classes", "vector_full_batch",
                                  "vector_bernoulli"])
def test_by_rows_run_at_the_cells_shape_reads_x_once_where_it_lies(S, case):
    """``cifar5m-multinomial.resident-classes`` (2,000,896 x 3,072 bf16 rows, a
    ``(9, 3072)`` matrix of weights, fraction 1.0) and a vector of weights
    at 2,097,152 x 1,024 (a full batch, and a Bernoulli mask that the step
    draws as an array): ONE Mosaic call a step in the by-rows form's own
    jitted function, under the scope its feature-major sibling has; X goes
    to it AS THE PARAMETER LIES, ``{1,0}``: no bitcast to ``X.T``, no copy
    or transpose of X's size in front (the verdict PR 30 deleted the row
    kernels on was a ``copy(X)`` at d = 1000), no ``(n, classes)`` array
    between two products, temporaries under 1% of X."""
    import re

    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    if case == "cifar5m_classes":
        n, d, K = CIFAR5M
        grad, wd, scope, fn = (MultinomialLogisticGradient(K), (K - 1) * d,
                               "sgd.class_sums", "_fused_rows_class_sums")
    else:
        n, d = ROWS_VECTOR
        grad, wd, scope, fn = (LogisticGradient(), d, "sgd.fused_sums",
                               "_fused_rows_sums")
    cfg = _cfg(step_size=1.0, num_iterations=100, reg_param=0.001,
               convergence_tol=0.0, mini_batch_fraction=0.1
               if case == "vector_bernoulli" else 1.0)
    compiled = jax.jit(make_run(grad, SquaredL2Updater(), cfg)).lower(
        S((wd,), F32), S((n, d), BF16), S((n,), F32),
        _hyper(S((), F32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert scope in call and fn in call
    assert "operand_layout_constraints={bf16[%d,%d]{1,0}, " % (n, d) in call
    assert compiled.input_formats[0][1].layout.major_to_minor == (0, 1)
    assert _moves_of(text, n, d) == []
    assert "bf16[%d,%d]" % (d, n) not in text  # no X.T, not even a bitcast
    # the weights go in as 16 rows in X's type, the gradient comes out as
    # 16 rows in f32; X and the row operands aside no 2-D array has n rows
    assert "bf16[16,%d]" % d in call and "f32[16,%d]" % d in call
    rest = re.sub(r"\w+\[1,%d\]" % n, "",  # the labels' row, a mask's
                  text.replace("bf16[%d,%d]" % (n, d), ""))
    assert not re.search(r"\[%d,\d+\]|\[\d+,%d\]" % (n, n), rest)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n * d * 2 // 100
    assert memory.argument_size_in_bytes < n * d * 2 * 1.01


def _lower_rows(S, n, d, dtype, classes, masked, tile_m, limit=None):
    """The by-rows call at a tile and under a compiler limit of the
    test's (None: the one the kernel asks for)."""
    from tpu_sgd.ops import pallas_kernels as PK
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient

    limit = PK._FM_VMEM_LIMIT if limit is None else limit
    args = [S((n, d), dtype), S((n,), F32)]
    mask = [S((n,), jnp.bool_)] if masked else []
    if classes is None:
        return jax.jit(lambda X, y, w, m=None: PK._vector_sums(
            HingeGradient().pointwise, X, y, w, m, tile_m, d, limit, False,
            True)).lower(*args, S((d,), F32), *mask)
    rows = PK.class_rows_of(classes - 1, dtype)
    rule = MultinomialLogisticGradient(classes).class_rule
    return jax.jit(lambda X, y, W, m=None: PK._class_call(
        rule, X, y, W, m, tile_m, d, limit, False, True)).lower(
            *args, S((rows, d), dtype), *mask)


#: (type, d, classes or None for a vector, masked): the cell's ten classes, a
#: vector at a hashed space's width under a mask, float32 embeddings, and
#: a width whose own tile is one lane chunk's quarter
ROWS_CASES = {"cifar5m": (BF16, 3072, 10, False),
              "vector_1024_masked": (BF16, 1024, None, True),
              "f32_768_classes": (F32, 768, 10, True),
              "vector_4096_f32": (F32, 4096, None, False)}


@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_the_by_rows_vmem_count_admits_what_the_compiler_admits(S, case):
    """``_fm_vmem_bytes(by_rows=True)`` stays above the compiler's own
    count: the form's own tile compiles when the compiler is asked for
    exactly what the tile was counted at; a tile the count refuses under
    the kernel's limit is refused by the compiler too; the largest tile
    the count's hint names compiles."""
    import re

    from tpu_sgd.ops import pallas_kernels as PK

    dtype, d, classes, masked = ROWS_CASES[case]
    n = KERNEL_N
    itemsize = jnp.dtype(dtype).itemsize
    rows = (PK.class_rows_of(classes - 1, dtype) if classes
            else PK.wide_rows_of(dtype)[1])
    own = PK.one_read(n, d, itemsize, masked, rows if classes else 0)
    assert own is not None and own.by_rows and PK.by_rows_form(n, d)
    own = own.tile
    counted = PK._fm_vmem_bytes(own, d, itemsize, masked, rows, by_rows=True)
    assert counted <= PK._FM_VMEM_LIMIT
    assert "tpu_custom_call" in _lower_rows(
        S, n, d, dtype, classes, masked, own, counted).compile().as_text()
    X = S((n, d), dtype)
    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        PK._check_fm_vmem(16384, X, masked, rows, by_rows=True)
    with pytest.raises(Exception, match="(?i)vmem"):
        _lower_rows(S, n, d, dtype, classes, masked, 16384).compile()
    tile = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    assert own <= tile < 16384
    PK._check_fm_vmem(tile, X, masked, rows, by_rows=True)
    assert "tpu_custom_call" in _lower_rows(
        S, n, d, dtype, classes, masked, tile).compile().as_text()


def test_the_by_rows_entries_refuse_a_width_no_tile_fits_before_compiling(S):
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.ops.pallas_kernels import fused_class_sums, fused_rows_sums

    n, d = 8192, 32_768
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_rows_sums(HingeGradient().pointwise, S((n, d), BF16),
                        S((n,), F32), S((d,), F32))
    with pytest.raises(ValueError, match="too wide for this kernel"):
        fused_class_sums(MultinomialLogisticGradient(10).class_rule,
                         S((n, d), BF16), S((n,), F32), S((9, d), F32))


# -- past 128 class rows: ImageNet's thousand classes (PR 48) ------------------

#: the cell's rows, width and classes (bench/configs/
#: imagenet1k-r50-multinomial.json)
IMAGENET = (1_281_167, 2048, 1000)


def test_the_thousand_class_run_at_the_cells_shape_reads_x_once_where_it_lies(
        S):
    """``imagenet1k-r50-multinomial.resident-classes``: 1,281,167 x 2,048
    bf16 rows under a ``(999, 2048)`` matrix of weights at fraction 1.0.
    ONE Mosaic call a step in the by-rows form's jitted function under
    ``sgd.class_sums``, all 1,008 padded class rows held at once (the
    weights in X's type, the gradient's sums in f32), X handed over as the
    parameter lies, and NO array of a row's class count in HBM: the two
    matmuls held ``f32[1281167,999]`` margins and coefficients, 5.12 GB
    each, between them."""
    import re

    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    n, d, K = IMAGENET
    cfg = _cfg(step_size=1.0, num_iterations=100, reg_param=0.001,
               convergence_tol=0.0, mini_batch_fraction=1.0)
    compiled = jax.jit(make_run(MultinomialLogisticGradient(K),
                                SquaredL2Updater(), cfg)).lower(
        S(((K - 1) * d,), F32), S((n, d), BF16), S((n,), F32),
        _hyper(S((), F32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "sgd.class_sums" in call and "_fused_rows_class_sums" in call
    assert "operand_layout_constraints={bf16[%d,%d]{1,0}, " % (n, d) in call
    assert "bf16[1008,%d]" % d in call and "f32[1008,%d]" % d in call
    assert _moves_of(text, n, d) == []
    assert "bf16[%d,%d]" % (d, n) not in text
    rest = re.sub(r"\w+\[1,%d\]" % n, "",
                  text.replace("bf16[%d,%d]" % (n, d), ""))
    assert not re.search(r"\[%d,\d+\]|\[\d+,%d\]" % (n, n), rest)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n * d * 2 // 100
    assert memory.argument_size_in_bytes < n * d * 2 * 1.01


@pytest.mark.parametrize("rows,classes", [(1008, 1000), (16, 10)])
def test_the_class_call_is_ahead_past_128_rows_and_its_cut_block_in_turn(
        rows, classes):
    """The body as lowered for a TPU at the cell's rows and width (PR 50).
    At 1,008 class rows the full blocks take the AHEAD order, a prologue's
    margins product, a loop whose trip holds the next chunk's margins
    product and this chunk's gradient product, an epilogue's gradient
    product, and the cut last block stays IN TURN, a loop of both
    products: six products, three copies of the rule (two exponentials
    each), two loops.  A second ahead body for the cut block would take
    the kernel from 53,187 instruction bundles to 69,619, and on the chip
    the call read 57.86 ms against 56.77 (PERF.md, PR 50).  At 16 class
    rows both blocks are in turn, as ever: four products, two rules."""
    from tpu_sgd.ops import pallas_kernels as PK
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient

    n, d, _ = IMAGENET
    shape = jax.ShapeDtypeStruct
    rule = MultinomialLogisticGradient(classes).class_rule
    own = PK.one_read(n, d, 2, False, rows)
    assert own.ahead == (rows > PK.FM_CLASS_ROWS) and n % own.tile
    _, kernel = _lowered_for_a_tpu(
        jax.jit(lambda X, y, W: PK._class_call(
            rule, X, y, W, None, own.tile, d, own.vmem_limit, False, True)),
        shape((n, d), BF16), shape((n,), F32), shape((rows, d), BF16))
    counts = tuple(kernel.count(op) for op in (
        "tpu.matmul", "math.exp", "scf.for"))
    assert counts == ((6, 6, 2) if own.ahead else (4, 4, 2))
    # where the next chunk's margins wait: one f32 array of the rule's size
    held = "memref<%dx%dxf32" % (rows, PK._fm_lane_chunk(own.tile, rows))
    assert (held in kernel) == own.ahead


#: (rows, width, by rows): the cell's shape, and a thousand classes over
#: rows the chip stores feature-major (the north star's width)
ROWS_1008 = {"imagenet_by_rows": (IMAGENET[0], 2048, True),
             "feature_major_1000": (KERNEL_N, 1000, False)}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("case", sorted(ROWS_1008))
def test_the_vmem_count_at_1008_class_rows_admits_what_the_compiler_admits(
        S, case, masked):
    """``_fm_vmem_bytes`` with 1,008 class rows stays above the compiler's
    own count in both orientations: the record's tile compiles when the
    compiler is asked for exactly what it was counted at, and a tile the
    count refuses under the wide form's limit is refused by the compiler
    too."""
    from tpu_sgd.ops import pallas_kernels as PK
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient

    n, d, by_rows = ROWS_1008[case]
    rows = PK.class_rows_of(999, BF16)
    own = PK.one_read(n, d, 2, masked, rows)
    assert (own.body, own.by_rows, own.class_rows, own.vmem_limit) == (
        "class", by_rows, 1008, PK._FM_WIDE_VMEM_LIMIT)
    counted = PK._fm_vmem_bytes(own.tile, d, 2, masked, rows,
                                by_rows=by_rows)
    assert counted <= own.vmem_limit
    rule = MultinomialLogisticGradient(1000).class_rule
    args = [S((n, d), BF16), S((n,), F32), S((rows, d), BF16)]
    if masked:
        args.append(S((n,), jnp.bool_))

    def lower(tile, limit):
        return jax.jit(lambda X, y, W, m=None: PK._class_call(
            rule, X, y, W, m, tile, d, limit, False, by_rows)).lower(*args)

    assert "tpu_custom_call" in lower(own.tile, counted).compile().as_text()
    with pytest.raises(ValueError, match=r"tile_m <= \d+"):
        PK._check_fm_vmem(32768, args[0], masked, rows, by_rows=by_rows)
    with pytest.raises(Exception, match="(?i)vmem"):
        lower(32768, PK._FM_WIDE_VMEM_LIMIT).compile()


def test_a_by_rows_shard_of_a_meshed_fit_takes_the_kernel_where_it_lies(
        mesh4, S):
    """A shard of a by-rows X is by rows: ``dp_run_fn`` at 4 x 524,288 x
    1,024 trains every shard through the by-rows kernel in place (no copy
    of a shard, one all-reduce a step)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.data_parallel import dp_run_fn
    from tpu_sgd.parallel.mesh import DATA_AXIS

    n, d = ROWS_VECTOR
    fn = dp_run_fn(LeastSquaresGradient(), SimpleUpdater(),
                   _cfg(step_size=1.0, num_iterations=100, reg_param=0.0,
                        convergence_tol=0.0), mesh4, with_valid=False)
    text = fn.lower(
        S((d,), F32, NamedSharding(mesh4, P())),
        S((n, d), BF16, NamedSharding(mesh4, P(DATA_AXIS, None))),
        S((n,), F32, NamedSharding(mesh4, P(DATA_AXIS))),
        _hyper(S((), F32, NamedSharding(mesh4, P()))),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert "_fused_rows_sums" in call and "sgd.fused_sums" in call
    assert "bf16[%d,%d]{1,0}" % (n // 4, d) in call
    assert text.count(" all-reduce(") == 1
    assert _moves_of(text, n // 4, d) == [] and _moves_of(text, n, d) == []


# -- rows kept in 8 bits: CIFAR-5m's pixels as they are published (PR 57) ------

#: the cell's rows, width and classes (bench/configs/
#: cifar5m-int8-multinomial.json): four of the source's six parts in the
#: bytes that hold two as bfloat16
CIFAR5M_INT8 = (4_001_792, 3072, 10)
I8 = jnp.int8


@pytest.mark.parametrize("case", ["the_cell", "vector_as_rows",
                                  "padded_shard"])
def test_the_int8_run_at_the_cells_shape_reads_the_bytes_once_where_they_lie(
        S, case):
    """``cifar5m-int8-multinomial.resident-classes`` (4,001,792 x 3,072 int8
    rows, a ``(9, 3072)`` matrix of weights, fraction 1.0), a vector of
    weights over int8 rows at 2,097,152 x 1,024, and the cell's shape under
    a row mask (compile only): ONE Mosaic call a step in the by-rows form's
    own jitted function, handed X AS THE ``s8`` PARAMETER LIES, ``{1,0}``;
    no convert, copy, transpose or fusion outside it makes an array of X's
    size in ANY type (a bf16 copy would be 24.6 GB, an f32 one 49.2: the
    parent's, which could not run); the weights go in as 16 bf16 rows (the
    operands' type, not X's), the gradient comes out as 16 f32 rows;
    temporaries are under 1% of X and the arguments are X, one byte a
    feature."""
    import re

    from tpu_sgd.ops.gradients import MultinomialLogisticGradient
    from tpu_sgd.optimize.gradient_descent import make_run

    n, d, K = CIFAR5M_INT8
    grad, wd, scope, fn = (MultinomialLogisticGradient(K), (K - 1) * d,
                           "sgd.class_sums", "_fused_rows_class_sums")
    if case == "vector_as_rows":
        n, d = ROWS_VECTOR
        grad, wd, scope, fn = (LogisticGradient(), d, "sgd.fused_sums",
                               "_fused_rows_sums")
    cfg = _cfg(step_size=2.0 ** -12, num_iterations=100, reg_param=4.096,
               convergence_tol=0.0, mini_batch_fraction=1.0)
    args = [S((wd,), F32), S((n, d), I8), S((n,), F32), _hyper(S((), F32))]
    if case == "padded_shard":
        args.append(S((n,), jnp.bool_))
    compiled = jax.jit(make_run(grad, SquaredL2Updater(), cfg)).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert scope in call and fn in call
    assert "operand_layout_constraints={s8[%d,%d]{1,0}, " % (n, d) in call
    assert compiled.input_formats[0][1].layout.major_to_minor == (0, 1)
    # nothing but the parameter and its hand-ons has X's size, in any type
    made = re.findall(
        r"= (\w+)\[(?:%d,%d|%d,%d)\]\S* ([a-z-]+)\(" % (n, d, d, n), text)
    assert {t for t, _ in made} == {"s8"}
    assert [op for _, op in made if op not in _NO_MOVE] == []
    assert "convert" not in {op for _, op in made}
    # the weights in the OPERANDS' type, 16 rows; the gradient 16 f32 rows
    assert "bf16[16,%d]" % d in call and "f32[16,%d]" % d in call
    assert "s8[16,%d]" % d not in text
    rest = re.sub(r"\w+\[1,%d\]" % n, "",  # the labels' row, a mask's
                  text.replace("s8[%d,%d]" % (n, d), ""))
    assert not re.search(r"\[%d,\d+\]|\[\d+,%d\]" % (n, n), rest)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n * d // 100
    assert n * d <= memory.argument_size_in_bytes < n * d * 1.01


def test_the_int8_kernels_vmem_count_admits_what_the_compiler_admits(S):
    """``_fm_vmem_bytes`` at one byte a feature (the block in int8, the
    weights and the lane chunk's widened copy in bf16) stays above the
    compiler's own count: the form's own tile, 2,048 rows where a bf16 block
    takes 1,024, compiles when the compiler is asked for exactly what the
    tile was counted at; a tile the count refuses is refused by the compiler
    too; the largest tile the count's hint names compiles."""
    import re

    from tpu_sgd.ops import pallas_kernels as PK
    from tpu_sgd.ops.gradients import MultinomialLogisticGradient

    n, d, K = KERNEL_N, 3072, 10
    rows = PK.class_rows_of(K - 1, I8)
    rule = MultinomialLogisticGradient(K).class_rule

    def lower(tile, limit=PK._FM_VMEM_LIMIT):
        return jax.jit(lambda X, y, W: PK._class_call(
            rule, X, y, W, None, tile, d, limit, False, True)).lower(
                S((n, d), I8), S((n,), F32), S((rows, d), BF16))

    own = PK.one_read(n, d, 1, False, rows)
    assert own.by_rows and (own.tile, own.item_bytes, rows) == (2048, 1, 16)
    assert PK.one_read(n, d, 2, False, rows).tile == 1024
    counted = PK._fm_vmem_bytes(own.tile, d, 1, False, rows, by_rows=True)
    assert counted <= PK._FM_VMEM_LIMIT
    assert "tpu_custom_call" in lower(own.tile, counted).compile().as_text()
    X = S((n, d), I8)
    with pytest.raises(ValueError, match=r"tile_m <= \d+") as refused:
        PK._check_fm_vmem(16384, X, False, rows, by_rows=True)
    with pytest.raises(Exception, match="(?i)vmem"):
        lower(16384).compile()
    tile = int(re.search(r"tile_m <= (\d+)", str(refused.value)).group(1))
    assert own.tile <= tile < 16384
    PK._check_fm_vmem(tile, X, False, rows, by_rows=True)
    assert "tpu_custom_call" in lower(tile).compile().as_text()
