"""A store of exported runners beside JAX's persistent compile cache.

In a warm checkout a job's first fit spends 0.2 to 0.9 s above a steady one
tracing and lowering ``sgd_run``, a program whose COMPILED form is already on
disk (PERF.md section 6, PRs 55 and 56): JAX's cache is keyed by the lowered
module, so every process traces the Pallas kernel body and lowers it again
only to find the key.  This module keeps the module itself: where
``GradientDescent._runner``'s program is first asked for by an optimizer,
:class:`StoredRun` computes a key WITHOUT tracing and looks for
``<jax_compilation_cache_dir>/tpu_sgd_runs/<key>``: first among the
runners the PROCESS already runs, then on disk.

* live: another optimizer of this process resolved the same file's name
  (a tuning loop builds a new ``GradientDescent`` a grid point:
  ``run_mini_batch_sgd``) and its program still holds its executable: that
  program is run, nothing is read, traced, lowered or looked up in the
  compile cache, and no ``build.*`` span is left.  The table (``_LIVE``)
  holds the last ``LIVE_KEPT`` programs by the store's directory (another
  directory is another table), the key and whether an argument was
  committed; an entry whose executable is gone (``jax.clear_caches()``)
  is dropped and the file is read.  It needs no store: with no compile
  cache directory the runner as it was is what the next optimizer finds;
* a hit: the file is read, ``jax.export.deserialize``d (a millisecond) and
  called under a ``jax.jit`` named as the runner is; nothing of the package
  is traced, and ``jax.experimental.pallas`` is never imported
  (``ops/pallas_kernels.py`` loads it at the first kernel build);
* a miss: the jitted runner is ``jax.export``ed (that IS its one trace and
  lowering), the bytes are written (a temporary file, ``os.replace``) and the
  RESTORED form is run, so that the process that stores and every process
  that restores hand XLA the same module and the executable the first caches
  is the one the others read;
* no compile cache directory: no store, the runner as it was (and live for
  the process's next optimizer).  That is the only switch.

The key holds everything the trace reads: a digest of every ``.py`` file of
this package, the versions of ``jax``, ``jaxlib`` and the backend, the device
kind, the mesh's shape and axis names, the gradient's and the updater's class
and state, the config's STRUCTURE (``SGDConfig.structure``: the step size and
the regulariser are operands of the program, two of its arguments' leaves, and
their values are in no key), ``with_valid``, the arguments' tree structure,
each leaf's shape, dtype, weak type, sharding and device layout, and the
``jax.config`` values a trace depends on (``_TRACE_CONFIG``).  Eligibility is
OBSERVED: a plugin whose class is defined outside the package (its code is
not in the digest) or whose state is not plain scalars, strings and tuples of
them, a leaf that is no ``jax.Array``, a debugging mode of ``jax.jit``
(``jax_debug_nans``: the runner as it was names the operation at fault), a
runner ``jax.export`` refuses, a file that does not read back, a directory
that cannot be written: each BYPASSES the store, trains with the runner as it
was and never raises.  Custom pytree arguments (``GramData``, ``RowCount``)
are exported over their flat leaves, the tree structure in the key.  Every
first call leaves a ``build.restore`` span under the fit's root
(``obs/builds.py``): ``hit`` 1 restored, 0 exported and stored, None with the
``reason`` of a bypass; a live one leaves none.  ``train.select`` says which
(``runner``: ``live``, ``restored``, ``stored``, ``as_was``).  A steady fit of
one optimizer makes no store call and no key: a flatten of the arguments, one
dictionary lookup and, where no argument is committed to a device, the outputs
handed back uncommitted as the runner's own are (``_jit_restored``).

Deleting the directory is always safe: the next first fit stores again."""

from __future__ import annotations

import collections
import functools
import hashlib
import os
import tempfile
import threading
import time
from typing import Optional

import jax
import jaxlib

from tpu_sgd.obs import builds

FOLDER = "tpu_sgd_runs"
KEPT = 64  # files in the folder, the newest by modification time
LIVE_KEPT = 32  # programs in ``_LIVE``, the last used
_HIT = {"restored": 1, "stored": 0}  # ``build.restore``'s ``hit``; else None
_MAGIC = b"tpu_sgd run 1\n"  # then the payload's sha256, then the payload

#: the ``jax.config`` values a trace of the runner depends on
_TRACE_CONFIG = (
    "jax_enable_x64", "jax_threefry_partitionable", "jax_default_prng_impl",
    "jax_default_matmul_precision", "jax_numpy_dtype_promotion",
    "jax_numpy_rank_promotion", "jax_enable_custom_prng",
    "jax_default_dtype_bits", "jax_use_shardy_partitioner",
)
#: under these a jitted function is run, or run again, operation by
#: operation: the runner as it was names the operation at fault
_DEBUG_CONFIG = ("jax_disable_jit", "jax_debug_nans", "jax_debug_infs")

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``(the store's directory or None, the key, an argument committed) -> (the
#: program as it is called, its ``jax.jit``)`` of the programs this process
#: runs: the restored ones and, with no store, the runners as they were
_LIVE: collections.OrderedDict = collections.OrderedDict()
_LIVE_LOCK = threading.Lock()


def _live(at):
    """The program resolved under ``at`` earlier in the process, None where
    there is none or its executable is no longer loaded."""
    with _LIVE_LOCK:
        found = _LIVE.get(at)
        if found is None:
            return None
        if not found[1]._cache_size():
            del _LIVE[at]
            return None
        _LIVE.move_to_end(at)
        return found[0]


def _keep_live(at, fn, jitted) -> None:
    with _LIVE_LOCK:
        _LIVE[at] = (fn, jitted)
        _LIVE.move_to_end(at)
        while len(_LIVE) > LIVE_KEPT:
            _LIVE.popitem(last=False)


def folder() -> Optional[str]:
    """The store's directory, or None where no compile cache is set."""
    base = jax.config.jax_compilation_cache_dir
    return os.path.join(base, FOLDER) if base else None


@functools.lru_cache(maxsize=1)
def source_digest() -> str:
    """Every ``.py`` file of the package, by path and bytes; once a
    process."""
    digest = hashlib.sha256()
    for parent, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(parent, name)
            with open(path, "rb") as f:
                data = f.read()
            digest.update(b"%s\0%d\0" % (
                os.path.relpath(path, _PACKAGE).encode(), len(data)))
            digest.update(data)
    return digest.hexdigest()


def _plain(value) -> bool:
    if isinstance(value, tuple):
        return all(_plain(v) for v in value)
    return value is None or isinstance(value, (bool, int, float, str))


def plugin_state(obj):
    """``(module, class, state)`` of a plugin the key can hold, None of one
    it cannot: a class defined outside this package, or state that cannot
    be read (no ``__dict__``) or is not plain scalars, strings and tuples of
    them."""
    cls = type(obj)
    if cls.__module__.split(".")[0] != __name__.split(".")[0]:
        return None
    state = getattr(obj, "__dict__", None)
    if state is None or not _plain(tuple(state.values())):
        return None
    return cls.__module__, cls.__qualname__, tuple(sorted(state.items()))


def _mesh_state(mesh):
    if mesh is None:
        return None
    return tuple(mesh.axis_names), tuple(mesh.devices.shape)


def _leaf_state(leaf):
    try:
        layout = repr(leaf.format.layout)
    except Exception:  # a backend that reports none
        layout = None
    return (tuple(leaf.shape), str(leaf.dtype), bool(leaf.weak_type),
            repr(leaf.sharding), layout)


def key_of(plugins, mesh, with_valid: bool, tree, leaves) -> str:
    """The file's name: a digest of all the trace reads (module
    docstring)."""
    devices = sorted({d for leaf in leaves for d in leaf.devices()},
                     key=lambda d: d.id)
    backend = devices[0].client
    parts = (
        source_digest(), jax.__version__, jaxlib.__version__,
        backend.platform, backend.platform_version,
        tuple(d.device_kind for d in devices), _mesh_state(mesh), plugins,
        bool(with_valid), str(tree), tuple(_leaf_state(x) for x in leaves),
        tuple((name, repr(getattr(jax.config, name, None)))
              for name in _TRACE_CONFIG))
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:40]


def _read(path: str):
    """The stored bytes, None where there is no file; raises ``ValueError``
    on one that does not read back whole."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except (FileNotFoundError, NotADirectoryError):
        return None
    head = len(_MAGIC) + 32
    payload = data[head:]
    if (data[:len(_MAGIC)] != _MAGIC
            or data[len(_MAGIC):head] != hashlib.sha256(payload).digest()):
        raise ValueError(f"{path} is not a whole stored run")
    return payload


def _write(directory: str, path: str, payload: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)
    except BaseException:
        _remove(tmp)
        raise
    kept = []
    for entry in os.scandir(directory):
        try:
            kept.append((entry.stat().st_mtime, entry.path))
        except OSError:  # another process pruned it
            pass
    for _, old in sorted(kept)[:-KEPT]:
        _remove(old)


def _remove(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def export(fresh, name: str, tree, leaves):
    """``fresh``, the jitted runner, exported over the flat ``leaves`` of its
    arguments (a custom node of ``tree`` needs no serialization registry):
    its one trace and lowering."""
    def flat(*flat_args):
        return fresh(*jax.tree_util.tree_unflatten(tree, flat_args))

    flat.__name__ = flat.__qualname__ = name
    return jax.export.export(jax.jit(flat))(*leaves)


def _jit_restored(exported, name: str, committed: bool):
    """``(fn, its jax.jit)``: the exported program under a ``jax.jit`` named
    as the runner is (the profiler's module name, ``build.*``'s ``fun``),
    taking the runner's own arguments.  ``jax.jit`` COMMITS every output of a program that calls an
    exported one to its device (``pxla`` counts the call's outputs among the
    program's memory transfers), where the runner's own outputs are committed
    only if an argument is: with no argument ``committed`` they are handed
    back as the runner would have left them, free to follow the next
    computation's other arguments (and a stream that feeds its weights back
    meets the program it already has)."""
    def run(*args):
        return exported.call(*jax.tree_util.tree_leaves(args))

    run.__name__ = run.__qualname__ = name
    restored = jax.jit(run)
    if committed:
        return restored, restored
    from jax._src.array import ArrayImpl  # no public constructor takes it

    @functools.wraps(run)
    def uncommitted(*args):
        # a program's own outputs: the constructor's checks are made
        return tuple(ArrayImpl(out.aval, out.sharding, out._arrays,
                               committed=False, _skip_checks=True)
                     for out in restored(*args))

    return uncommitted, restored


class StoredRun:
    """``GradientDescent._runner``'s program behind the store: called as the
    jitted runner is.  The first call with arguments of a new signature
    resolves it (live, restore, export and store, or bypass); every later one
    is a dictionary lookup and the call.  ``make`` builds the jitted runner
    (``fresh``) and is called only where it is needed: on a miss, which
    traces it, and on a bypass; an optimizer that finds its program live or
    in the store never builds one (``make_step`` draws its key on the
    device: two programs a tuning loop's every call would launch for
    nothing).  ``config`` is the config's structure
    (``SGDConfig.structure``)."""

    __slots__ = ("_make", "_fresh", "gradient", "updater", "config", "mesh",
                 "with_valid", "_fns", "_lock")

    def __init__(self, make, gradient, updater, config, mesh,
                 with_valid: bool):
        self._make, self._fresh = make, None
        self.gradient, self.updater, self.config = gradient, updater, config
        self.mesh, self.with_valid = mesh, with_valid
        self._fns = {}
        self._lock = threading.Lock()

    @property
    def fresh(self):
        """The jitted runner as it was, built at the first asking."""
        if self._fresh is None:
            self._fresh = self._make()
        return self._fresh

    @property
    def name(self) -> str:
        return getattr(self.fresh, "__name__", "sgd_run")

    def __call__(self, *args):
        return self.resolve(*args)[0](*args)

    def resolve(self, *args):
        """``(fn, origin)`` for these arguments: the program to call with
        them and where this optimizer got it: ``"live"`` (the process ran it
        already), ``"restored"``, ``"stored"`` or ``"as_was"`` (a bypass)."""
        leaves, tree = jax.tree_util.tree_flatten(args)
        signature = (tree, *[
            (getattr(x, "shape", None), getattr(x, "dtype", None),
             getattr(x, "weak_type", None), getattr(x, "sharding", None),
             getattr(x, "committed", None))
            for x in leaves])
        found = self._fns.get(signature)
        if found is None:
            with self._lock:
                found = self._fns.get(signature)
                if found is None:
                    found = self._fns[signature] = self._resolve(tree, leaves)
        return found

    def _resolve(self, tree, leaves):
        start = time.time()
        try:
            fn, origin, reason = self._stored(tree, leaves)
        except Exception as e:  # the store must never fail a fit
            fn, origin, reason = (self.fresh, "as_was",
                                  f"error: {type(e).__name__}")
        if origin != "live":  # a live one was built by nobody here
            builds.restored(getattr(fn, "__name__", "sgd_run"),
                            _HIT.get(origin), reason, start, time.time())
        return fn, origin

    def _stored(self, tree, leaves):
        """``(fn, origin, reason)``: the program the process already runs
        (``"live"``), the restored program (``"restored"`` from the file,
        named as the file's export is; ``"stored"`` where this call wrote
        it), or the runner as it was (``"as_was"``) with the reason of the
        bypass."""
        plugins = tuple(plugin_state(p) for p in
                        (self.gradient, self.updater, self.config))
        if None in plugins:
            return self.fresh, "as_was", "a plugin from outside the package"
        if not all(isinstance(x, jax.Array) for x in leaves):
            return self.fresh, "as_was", "an argument that is no device array"
        if any(getattr(jax.config, name) for name in _DEBUG_CONFIG):
            return self.fresh, "as_was", "a debugging mode of jax.jit"
        directory = folder()
        key = key_of(plugins, self.mesh, self.with_valid, tree, leaves)
        at = (directory, key, any(x.committed for x in leaves))
        fn = _live(at)
        if fn is not None:
            return fn, "live", None
        if directory is None:
            # no store: the runner as it was, kept for the next optimizer
            _keep_live(at, self.fresh, self.fresh)
            return self.fresh, "as_was", "no compile cache directory"
        path = os.path.join(directory, key)
        try:
            payload = _read(path)
            if payload is not None:
                exported = jax.export.deserialize(bytearray(payload))
        except Exception:
            _remove(path)  # the next first fit stores it anew
            return (self.fresh, "as_was",
                    "a stored file that does not read back")
        if payload is not None:
            try:
                os.utime(path)  # the newest files are the ones kept
            except OSError:
                pass
            return self._restored(exported, at), "restored", None
        try:
            os.makedirs(directory, exist_ok=True)
        except OSError:
            pass
        if not (os.path.isdir(directory) and os.access(directory, os.W_OK)):
            return self.fresh, "as_was", "a directory that cannot be written"
        try:
            payload = bytes(
                export(self.fresh, self.name, tree, leaves).serialize())
        except Exception as e:
            return (self.fresh, "as_was",
                    f"jax.export refused: {type(e).__name__}")
        try:
            _write(directory, path, payload)
        except OSError:
            return self.fresh, "as_was", "a directory that cannot be written"
        exported = jax.export.deserialize(bytearray(payload))
        return self._restored(exported, at), "stored", None

    def _restored(self, exported, at):
        fn, jitted = _jit_restored(exported, exported.fun_name, at[2])
        _keep_live(at, fn, jitted)
        return fn
