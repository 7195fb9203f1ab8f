"""The store of exported runners beside the compile cache
(``tpu_sgd/optimize/run_store.py``): a process's first call of
``GradientDescent._runner``'s program restores it from
``<jax_compilation_cache_dir>/tpu_sgd_runs/<key>`` where it is there, exports
and stores it where it is not, and runs the RESTORED form either way; anything
the key cannot hold bypasses the store and trains as before; a second
optimizer of the process finds the program LIVE.  Tiny, on the
CPU: the restored program against the traced one bit for bit for every step
family ``_runner`` selects, what the key holds, every bypass, a fresh
interpreter on a warm store, and what a steady fit pays."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_sgd
from tpu_sgd import obs
from tpu_sgd.config import SGDConfig
from tpu_sgd.obs import builds, spans as obs_spans
from tpu_sgd.ops import gram
from tpu_sgd.ops.gradients import RowCount
from tpu_sgd.optimize import run_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESTORE = "build.restore"


@pytest.fixture
def cache_dir(compile_cache):
    """The store's folder under an empty compile cache directory."""
    return os.path.join(compile_cache, run_store.FOLDER)


@pytest.fixture(autouse=True)
def _no_roots():
    builds._ROOTS.clear()
    del builds._BUILT[:]
    _another_process()
    yield


def _another_process():
    """What another process has of this one's runners: none live."""
    run_store._LIVE.clear()


def _args(opt, w0, X, y, *valid):
    """``_runner``'s arguments as a fit hands them over: the step size and
    the regulariser ride as operands behind the labels."""
    return (w0, X, y, opt._hyper(), *valid)


def _rows(n, d, dtype=jnp.float32, classes=2, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    labels = (np.arange(n) % classes).astype(np.float32)
    return jnp.asarray(X, dtype), jnp.asarray(labels)


def _optimizer(gradient=None, updater=None, **config):
    opt = tpu_sgd.GradientDescent(gradient or tpu_sgd.LogisticGradient(),
                                  updater or tpu_sgd.SquaredL2Updater())
    opt.config = SGDConfig(**dict(dict(
        step_size=0.5, num_iterations=6, reg_param=0.01,
        mini_batch_fraction=0.5, convergence_tol=0.0, seed=42), **config))
    return opt


def _masked_vector():
    X, y = _rows(256, 16, jnp.bfloat16)
    opt = _optimizer()
    return opt, _args(opt, jnp.zeros((16,)), X, y)


def _windowed():
    X, y = _rows(256, 16, jnp.bfloat16)
    opt = _optimizer(sampling="sliced", mini_batch_fraction=0.25)
    return opt, _args(opt, jnp.zeros((16,)), X, y)


def _bound():
    """A stream's micro-batch in an array of a row capacity, its row count
    an operand in ``valid``'s place."""
    X, y = _rows(256, 16, jnp.bfloat16)
    opt = _optimizer()
    return opt, _args(opt, jnp.zeros((16,)), X, y,
                      RowCount(jnp.asarray(200, jnp.int32)))


def _class_feature_major():
    X, y = _rows(256, 24, jnp.bfloat16, classes=4)
    opt = _optimizer(tpu_sgd.MultinomialLogisticGradient(4),
                     mini_batch_fraction=1.0)
    return opt, _args(opt, jnp.zeros((3 * 24,)), X, y)


def _class_by_rows():
    X, y = _rows(128, 128, jnp.bfloat16, classes=4)
    opt = _optimizer(tpu_sgd.MultinomialLogisticGradient(4),
                     mini_batch_fraction=1.0)
    return opt, _args(opt, jnp.zeros((3 * 128,)), X, y)


def _wide():
    X, y = _rows(64, 2048, jnp.bfloat16)
    opt = _optimizer(tpu_sgd.HingeGradient(), tpu_sgd.L1Updater(),
                     mini_batch_fraction=1.0, step_size=0.05)
    return opt, _args(opt, jnp.zeros((2048,)), X, y)


def _gram_totals():
    X, y = _rows(256, 16)
    opt = _optimizer(gram.GramLeastSquaresGradient(),
                     tpu_sgd.SimpleUpdater(), mini_batch_fraction=1.0,
                     step_size=0.05)
    return opt, _args(opt, jnp.zeros((16,)), gram.stats_build(X, y), y)


FAMILIES = {"masked_vector": _masked_vector, "windowed": _windowed,
            "bound": _bound, "class_feature_major": _class_feature_major,
            "class_by_rows": _class_by_rows, "wide": _wide,
            "gram_totals": _gram_totals}


def _same(a, b):
    return all(np.array_equal(np.asarray(p), np.asarray(q), equal_nan=True)
               and np.asarray(p).dtype == np.asarray(q).dtype
               for p, q in zip(a, b, strict=True))


def _text(exported) -> str:
    """The exported module without its locations (they hold the line every
    frame of the exporting call stood at, this file's among them)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with mlir.make_ir_context():
        return ir.Module.parse(exported.mlir_module()).operation.get_asm(
            enable_debug_info=False)


def _call(stored, args):
    """One call under a root, and the ``build.restore`` it left (or None)."""
    with builds.root("train.run", obs_spans.NO_SPAN):
        out = jax.block_until_ready(stored(*args))
    roots = obs.build_roots()
    left = [s for s in roots[-1]["spans"] if s["name"] == RESTORE] \
        if roots else []
    builds._ROOTS.clear()
    return out, (left[0] if left else None)


# -- (a) restored against freshly traced ------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_the_restored_program_is_the_traced_one_bit_for_bit(cache_dir,
                                                            family):
    opt, args = FAMILIES[family]()
    with_valid = len(args) == 5
    first = opt._runner(with_valid)
    assert isinstance(first, run_store.StoredRun)
    stored_out, stored = _call(first, args)
    assert stored["hit"] == 0 and "reason" not in stored, stored
    (name,) = os.listdir(cache_dir)
    # another process's view: a new optimizer, nothing traced yet
    _another_process()
    again = FAMILIES[family]()[0]._runner(with_valid)
    restored_out, restored = _call(again, args)
    assert restored["hit"] == 1 and restored["fun"] == first.name
    assert os.listdir(cache_dir) == [name]
    traced_out = first.fresh(*args)
    assert _same(stored_out, traced_out) and _same(restored_out, traced_out)
    # and they are left where the runner leaves its own: free to follow
    for out in (stored_out, restored_out):
        assert [o.committed for o in out] == [False] * 3 \
            == [o.committed for o in traced_out]
    recorded = int(traced_out[2])
    assert recorded == 6 and np.isfinite(
        np.asarray(traced_out[1])[:recorded]).all()
    # the stored module is what a fresh export makes
    leaves, tree = jax.tree_util.tree_flatten(args)
    kept = jax.export.deserialize(
        bytearray(run_store._read(os.path.join(cache_dir, name))))
    fresh = run_store.export(first.fresh, first.name, tree, leaves)
    assert _text(kept) == _text(fresh) and "stablehlo.while" in _text(kept)
    # (a weak type does not survive the serialization: the operands' two
    # scalars come back plain float32, their arithmetic already lowered)
    assert [(a.shape, a.dtype) for a in kept.in_avals] \
        == [(a.shape, a.dtype) for a in fresh.in_avals]
    assert kept.fun_name == "sgd_run"


def test_outputs_are_committed_only_where_an_argument_is(cache_dir):
    """``jax.jit`` commits every output of a program that calls an exported
    one; the store hands them back as the runner would have: committed to
    the device of a committed argument, free otherwise, so that a stream
    feeding its weights back meets ONE program."""
    opt, (w0, X, y, hyper) = _masked_vector()
    pinned = jax.device_put(X, jax.devices()[1])
    runner = opt._runner(False)
    for args, committed in (((w0, X, y, hyper), False),
                            ((w0, pinned, y, hyper), True)):
        for _ in range(2):  # the call that stores, then the steady one
            out = runner(*args)
            traced = runner.fresh(*args)
            assert [o.committed for o in out] == [committed] * 3 \
                == [o.committed for o in traced]
            assert {d for o in out for d in o.devices()} \
                == {d for o in traced for d in o.devices()}
            assert _same(out, traced)
    assert len(runner._fns) == 2
    # fed back, the free weights are the first call's signature again
    w, _, _ = runner(w0, X, y, hyper)
    runner(w, X, y, hyper)
    assert len(runner._fns) == 2


def test_the_mesh_runner_goes_through_the_store(cache_dir):
    X, y = _rows(256, 16, jnp.bfloat16)
    w0 = np.zeros((16,), np.float32)

    def fit():
        opt = _optimizer().set_mesh(tpu_sgd.data_mesh(jax.devices()[:4]))
        out = opt.optimize_with_history((X, y), w0)
        return opt, out, [s for s in obs.build_roots()[-1]["spans"]
                          if s["name"] == RESTORE]

    _, (w_stored, l_stored), (stored,) = fit()
    _another_process()
    opt, (w, losses), (restored,) = fit()
    assert (stored["hit"], restored["hit"]) == (0, 1)
    (runner,) = opt._run_cache.values()
    placed = opt._place(X, y)
    traced = runner.fresh(jnp.asarray(w0), *placed[:2], opt._hyper())
    assert _same((w_stored, w), (traced[0], traced[0]))
    assert _same((l_stored, losses), (np.asarray(traced[1]),) * 2)
    assert len(os.listdir(cache_dir)) == 1


def test_the_cold_and_the_warm_process_hand_xla_the_same_module(cache_dir):
    """The process that stores runs the RESTORED form: the executable it
    caches is the one every later process reads."""
    opt, args = _windowed()
    with builds.root("train.run", obs_spans.NO_SPAN):
        opt._runner(False)(*args)
    jax.clear_caches()
    with builds.root("train.run", obs_spans.NO_SPAN):
        _windowed()[0]._runner(False)(*args)
    cold, warm = obs.build_roots()

    def compiles(root):
        return [s["cache_hit"] for s in root["spans"]
                if s["name"] == "build.compile" and "sgd_run" in s["fun"]]

    assert compiles(cold) == [0] and compiles(warm) == [1]


# -- (b) the key -------------------------------------------------------------------

def _key(opt, args, with_valid=False, mesh=None):
    leaves, tree = jax.tree_util.tree_flatten(args)
    plugins = tuple(run_store.plugin_state(p) for p in (
        opt.gradient, opt.updater, opt.config.structure()))
    assert None not in plugins
    return run_store.key_of(plugins, mesh, with_valid, tree, leaves)


OTHER_CONFIG = {"step_size": 0.25, "num_iterations": 7, "reg_param": 0.02,
                "mini_batch_fraction": 0.75, "convergence_tol": 0.01,
                "seed": 43, "sampling": "sliced"}


def test_every_field_of_the_config_has_another_value_here():
    assert set(OTHER_CONFIG) == {f.name for f in dataclasses.fields(SGDConfig)}


@pytest.mark.parametrize("field", OTHER_CONFIG)
def test_the_key_changes_with_each_field_of_the_configs_structure(field):
    """And with no operand's value: the step size and the regulariser are
    two of the program's arguments (``config.Hyper``)."""
    from tpu_sgd.config import Hyper

    opt, args = _masked_vector()
    other = _optimizer(**{field: OTHER_CONFIG[field]})
    assert getattr(other.config, field) != getattr(opt.config, field)
    assert (_key(other, args) == _key(opt, args)) \
        == (field in Hyper._fields)
    assert _key(_masked_vector()[0], args) == _key(opt, args)


def _other_device(x):
    return jax.device_put(x, jax.devices()[1])


def _sharded(x):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = tpu_sgd.data_mesh(jax.devices()[:4])
    return jax.device_put(x, NamedSharding(mesh, P("data", None)))


#: each: (optimizer, arguments, with_valid, mesh) that must not share the
#: key of ``_masked_vector()``, ``with_valid`` False, no mesh
OTHERS = {
    "the gradient's class": lambda o, a: (
        _optimizer(tpu_sgd.HingeGradient()), a, False, None),
    "the updater's class": lambda o, a: (
        _optimizer(updater=tpu_sgd.L1Updater()), a, False, None),
    "with_valid": lambda o, a: (o, a, True, None),
    "a mesh": lambda o, a: (o, a, False,
                            tpu_sgd.data_mesh(jax.devices()[:4])),
    "the mesh's shape": lambda o, a: (
        o, a, False, tpu_sgd.data_mesh(jax.devices()[:2])),
    "a leaf's shape": lambda o, a: (
        o, (a[0], a[1][:128], a[2][:128], a[3]), False, None),
    "a leaf's dtype": lambda o, a: (
        o, (a[0], a[1].astype(jnp.float32), a[2], a[3]), False, None),
    "a leaf's weak type": lambda o, a: (
        o, (jax.lax.full((16,), 0.0), a[1], a[2], a[3]), False, None),
    "a leaf's device": lambda o, a: (
        o, (a[0], _other_device(a[1]), a[2], a[3]), False, None),
    "a leaf's sharding": lambda o, a: (
        o, (a[0], _sharded(a[1]), a[2], a[3]), False, None),
    "the tree's structure": lambda o, a: (
        o, (*a, jnp.ones((256,), bool)), False, None),
    "a row count for a mask": lambda o, a: (
        o, (*a, RowCount(jnp.asarray(256, jnp.int32))), False, None),
}


@pytest.mark.parametrize("what", OTHERS)
def test_the_key_changes_with(what):
    opt, args = _masked_vector()
    base = _key(opt, args)
    if what == "the mesh's shape":
        base = _key(opt, args, mesh=tpu_sgd.data_mesh(jax.devices()[:4]))
    if what == "a row count for a mask":
        base = _key(opt, args + (jnp.ones((256,), bool),))
    other, other_args, with_valid, mesh = OTHERS[what](opt, args)
    if what == "a leaf's weak type":
        assert other_args[0].weak_type and not args[0].weak_type
    assert _key(other, other_args, with_valid, mesh) != base


def test_the_key_changes_with_a_plugins_state():
    _, args = _class_feature_major()
    three, four = (_optimizer(tpu_sgd.MultinomialLogisticGradient(k))
                   for k in (3, 4))
    assert _key(three, args) != _key(four, args)


def test_the_key_changes_with_the_source_digest(monkeypatch):
    opt, args = _masked_vector()
    base = _key(opt, args)
    assert len(run_store.source_digest()) == 64
    monkeypatch.setattr(run_store, "source_digest", lambda: "0" * 64)
    assert _key(opt, args) != base


@pytest.mark.parametrize("module", ["jax", "jaxlib"])
def test_the_key_changes_with_the_version_of(monkeypatch, module):
    import importlib

    opt, args = _masked_vector()
    base = _key(opt, args)
    monkeypatch.setattr(importlib.import_module(module), "__version__",
                        "0.0.1")
    assert _key(opt, args) != base


@pytest.mark.parametrize("name, value", [
    ("jax_default_matmul_precision", "highest"),
    ("jax_numpy_dtype_promotion", "strict"),
    ("jax_threefry_partitionable", False),
    ("jax_default_prng_impl", "rbg"),
    ("jax_enable_x64", True)])
def test_the_key_changes_with_the_config_value(name, value):
    opt, args = _masked_vector()
    base = _key(opt, args)
    before = getattr(jax.config, name)
    assert before != value
    jax.config.update(name, value)
    try:
        changed = _key(opt, args)
    finally:
        jax.config.update(name, before)
    assert changed != base and _key(opt, args) == base


def test_the_digest_reads_every_file_of_the_package(tmp_path, monkeypatch):
    package = tmp_path / "tpu_sgd"
    (package / "ops").mkdir(parents=True)
    (package / "a.py").write_text("x = 1\n")
    (package / "ops" / "b.py").write_text("y = 2\n")
    (package / "ops" / "notes.txt").write_text("not code\n")
    monkeypatch.setattr(run_store, "_PACKAGE", str(package))

    def digest():
        run_store.source_digest.cache_clear()
        return run_store.source_digest()

    try:
        base = digest()
        (package / "ops" / "notes.txt").write_text("still not code\n")
        assert digest() == base
        (package / "ops" / "b.py").write_text("y = 3\n")
        edited = digest()
        assert edited != base
        (package / "ops" / "b.py").rename(package / "ops" / "c.py")
        assert digest() not in (base, edited)
    finally:
        run_store.source_digest.cache_clear()


# -- (c) every bypass ---------------------------------------------------------------

class UsersGradient(tpu_sgd.LogisticGradient):
    """A plugin of the user's own: its code is not in the digest."""


def _users_gradient(cache_dir):
    return _optimizer(UsersGradient()), "a plugin from outside the package"


def _state_that_is_no_scalar(cache_dir):
    opt = _optimizer()
    opt.gradient.table = np.arange(3)
    return opt, "a plugin from outside the package"


def _no_cache_directory(cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    return _optimizer(), "no compile cache directory"


def _a_truncated_file(cache_dir):
    opt, args = _masked_vector()
    opt._runner(False)(*args)
    (name,) = os.listdir(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:
        f.write(whole[:len(whole) // 2])
    _another_process()
    return _optimizer(), "a stored file that does not read back"


def _an_unwritable_directory(cache_dir):
    os.makedirs(os.path.dirname(cache_dir), exist_ok=True)
    with open(cache_dir, "w") as f:  # a file where the folder would be
        f.write("in the way")
    return _optimizer(), "a directory that cannot be written"


def _debug_nans(cache_dir):
    jax.config.update("jax_debug_nans", True)
    return _optimizer(), "a debugging mode of jax.jit"


BYPASSES = {"a_users_gradient": _users_gradient,
            "debug_nans": _debug_nans,
            "state_that_is_no_scalar": _state_that_is_no_scalar,
            "no_cache_directory": _no_cache_directory,
            "a_truncated_file": _a_truncated_file,
            "an_unwritable_directory": _an_unwritable_directory}


@pytest.mark.parametrize("case", BYPASSES)
def test_a_bypass_trains_as_before_and_says_why(cache_dir, case):
    _, args = _masked_vector()
    expected = _optimizer()._runner(False).fresh(*args)
    opt, reason = BYPASSES[case](cache_dir)
    runner = opt._runner(False)
    try:
        out, left = _call(runner, args)
    finally:
        jax.config.update("jax_debug_nans", False)
    assert left["hit"] is None and left["reason"] == reason, left
    assert _same(out, expected)
    # the runner as it was: the parent's jitted function itself
    ((fn, origin),) = runner._fns.values()
    assert fn is runner.fresh and origin == "as_was"
    if case == "a_truncated_file":  # gone, so the next first fit stores anew
        assert os.listdir(cache_dir) == []
        _, healed = _call(_optimizer()._runner(False), args)
        assert healed["hit"] == 0 and len(os.listdir(cache_dir)) == 1
    elif os.path.isdir(cache_dir):
        assert os.listdir(cache_dir) == []


def test_a_runner_jax_export_refuses_runs_as_it_is(cache_dir):
    from jax.experimental import io_callback

    seen = []

    def observed(w, X, y, hyper):
        io_callback(lambda v: seen.append(float(v)), None, w.sum(),
                    ordered=True)
        return w + X.sum(0).astype(w.dtype), y[:3], jnp.asarray(3)

    opt, args = _masked_vector()
    runner = run_store.StoredRun(lambda: jax.jit(observed), opt.gradient,
                                 opt.updater, opt.config, None, False)
    out, left = _call(runner, args)
    assert left["hit"] is None \
        and left["reason"] == "jax.export refused: NotImplementedError"
    assert _same(out, observed(*args)) and len(seen) == 2
    assert os.listdir(cache_dir) == []


def test_a_store_that_raises_fails_no_fit(cache_dir, monkeypatch):
    def broken(*args):
        raise RuntimeError("the store is broken")

    monkeypatch.setattr(run_store, "key_of", broken)
    opt, args = _masked_vector()
    out, left = _call(opt._runner(False), args)
    assert left["reason"] == "error: RuntimeError"
    assert _same(out, _optimizer()._runner(False).fresh(*args))


def test_the_folder_keeps_the_newest_files(cache_dir, monkeypatch):
    monkeypatch.setattr(run_store, "KEPT", 3)
    os.makedirs(cache_dir)
    for i in range(5):
        path = os.path.join(cache_dir, f"key{i}")
        run_store._write(cache_dir, path, b"payload %d" % i)
        os.utime(path, (1000 + i, 1000 + i))
    run_store._write(cache_dir, os.path.join(cache_dir, "key5"), b"last")
    assert sorted(os.listdir(cache_dir)) == ["key3", "key4", "key5"]
    assert run_store._read(os.path.join(cache_dir, "key4")) == b"payload 4"
    assert run_store._read(os.path.join(cache_dir, "key0")) is None


# -- (d) a fresh interpreter on a warm store ------------------------------------------

FRESH = """
import json, os, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import tpu_sgd
from tpu_sgd import obs
rng = np.random.default_rng(0)
X = jnp.asarray(rng.standard_normal((256, 16)), jnp.bfloat16)
y = jnp.asarray((np.arange(256) % 2).astype(np.float32))
opt = (tpu_sgd.GradientDescent(tpu_sgd.LogisticGradient(),
                               tpu_sgd.SquaredL2Updater())
       .set_step_size(0.5).set_num_iterations(6).set_reg_param(0.01)
       .set_mini_batch_fraction(0.25).set_sampling("sliced")
       .set_convergence_tol(0.0).set_seed(42))
before = sorted(m for m in sys.modules if "pallas" in m and "tpu_sgd" not in m)
w, losses = opt.optimize_with_history((X, y), np.zeros((16,), np.float32))
(root,) = obs.build_roots()
print("REPORT " + json.dumps({
    "pallas_at_import": before,
    "pallas": sorted(m for m in sys.modules
                     if "pallas" in m and "tpu_sgd" not in m),
    "spans": [{k: s.get(k) for k in ("name", "fun", "hit", "cache_hit")}
              for s in root["spans"]],
    "short_traces": root["short_traces"],
    "w": [float(v) for v in np.asarray(w)],
    "losses": [float(v) for v in losses]}))
"""


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """The same first fit in two fresh interpreters on one cache directory:
    the first stores, the second restores."""
    cache = str(tmp_path_factory.mktemp("cache"))
    reports = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", FRESH, cache], cwd=REPO,
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
        assert out.returncode == 0, out.stderr[-3000:]
        reports.append(json.loads(out.stdout.split("REPORT ", 1)[1]))
    return reports


def _spans(report, name):
    return [s for s in report["spans"] if s["name"] == name]


def test_a_fresh_interpreter_restores_sgd_run(two_processes):
    cold, warm = two_processes
    (stored,), (restored,) = (_spans(r, RESTORE) for r in (cold, warm))
    assert stored["hit"] == 0 and restored["hit"] == 1
    assert restored["fun"] == "sgd_run"
    assert warm["w"] == cold["w"] and warm["losses"] == cold["losses"]
    assert len(warm["losses"]) == 6


def test_a_restore_traces_nothing_of_the_package(two_processes):
    cold, warm = two_processes
    kernel = [s["fun"] for s in _spans(cold, "build.trace")
              if s["fun"].startswith("_fused_")]
    assert kernel, "the miss traced the window's kernel entry"
    assert [s["fun"] for s in _spans(warm, "build.trace")
            if s["fun"].startswith("_fused_") or s["fun"] == "wrapped"] == []
    assert warm["short_traces"] < cold["short_traces"] / 10


def test_a_restore_compiles_nothing(two_processes):
    cold, warm = two_processes
    (compiled,) = [s for s in _spans(cold, "build.compile")
                   if "sgd_run" in s["fun"]]
    (read,) = [s for s in _spans(warm, "build.compile")
               if "sgd_run" in s["fun"]]
    assert compiled["cache_hit"] == 0 and read["cache_hit"] == 1


def test_a_restore_never_imports_pallas(two_processes):
    cold, warm = two_processes
    assert cold["pallas_at_import"] == [] and warm["pallas_at_import"] == []
    assert "jax.experimental.pallas" in cold["pallas"]  # the miss built one
    assert warm["pallas"] == []


def test_import_tpu_sgd_does_not_import_pallas():
    code = ("import sys, jax, tpu_sgd; from tpu_sgd.ops import pallas_kernels"
            " as pk; assert pk.one_read(4096, 1000, 2).feature_blocks == 1;"
            " print([m for m in sys.modules if m.startswith("
            "('jax.experimental.pallas', 'jax._src.pallas'))])")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


# -- (e) what a steady fit pays --------------------------------------------------------

def test_a_steady_fit_makes_no_store_call_and_no_key(cache_dir, monkeypatch):
    X, y = _rows(256, 16, jnp.bfloat16)
    w0 = jnp.zeros((16,))
    opt = _optimizer()
    opt.optimize_with_history((X, y), w0)
    calls = []
    for name in ("key_of", "folder", "plugin_state", "source_digest",
                 "_read", "_write", "export"):
        monkeypatch.setattr(
            run_store, name,
            lambda *a, _name=name, **k: calls.append(_name) or 1 / 0)
    monkeypatch.setattr(builds, "restored",
                        lambda *a: calls.append("restored"))
    kept = obs.build_roots()
    for _ in range(10):
        opt.optimize_with_history((X, y), w0)
    assert calls == [] and obs.build_roots() == kept
    # arguments of another shape are another first call
    opt.optimize_with_history((X[:128], y[:128]), w0)
    assert calls[0] == "plugin_state" and calls[-1] == "restored"


@pytest.mark.parametrize("family", ["masked_vector", "bound", "gram_totals"])
def test_a_steady_call_costs_microseconds(family):
    opt, args = FAMILIES[family]()
    runner = opt._runner(len(args) == 5)
    runner(*args)
    (signature,) = runner._fns
    # the wrapper's own cost alone
    runner._fns[signature] = (lambda *a: None, "as_was")
    best = min(_per_call(runner, args) for _ in range(5))
    # 3 to 5 microseconds (15 with a GramData's seven leaves) on the
    # sandbox's CPU; the bound leaves room for a loaded test machine
    assert best < 100e-6, f"{best * 1e6:.1f} us a call"


def _per_call(runner, args, n=2000):
    t = time.perf_counter()
    for _ in range(n):
        runner(*args)
    return (time.perf_counter() - t) / n
