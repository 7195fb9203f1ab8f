"""Sufficient-statistics execution of the least-squares gradient.

Reference frame: the reference's hot loop re-reads the sampled rows every
iteration — per-example BLAS ``dot``/``axpy`` under ``treeAggregate``
(SURVEY.md §3.1 inner hot loop); the stock TPU path here does the same two
fused MXU passes over the window, which `PROFILE_TPU.json` shows is the
two-HBM-read bandwidth floor (~1.64 ms/iter on the 3M-row slab).

For the *quadratic* loss that floor is not fundamental: the window gradient
is linear in the sufficient statistics

    grad_sum = G_w @ w - b_w          G_w = X_wᵀ X_w,  b_w = X_wᵀ y_w
    loss_sum = ½ (wᵀ G_w w - 2 bᵀ_w w + yyw)

so a one-time pass over the data (the ``cache()`` analogue — SURVEY.md §2
#13) can precompute *block-prefix* Grams, after which any contiguous-window
(``sampling="sliced"``) gradient costs two (d, d) prefix matvecs plus two
masked partial-block edge corrections — ~(8 MB + 2·B·d reads) of HBM
traffic per iteration instead of two full window reads, and it is the SAME
gradient (exact up to float summation order), not an approximation.  The
full-batch gradient, the LBFGS ``CostFun`` objective, and the batched
Armijo ``loss_sweep`` reduce to the same statistics, so quasi-Newton least
squares accelerates identically.

This is least-squares only by construction: logistic/hinge gradients are
nonlinear in the margins and have no fixed-size sufficient statistics.
It is also a MODERATE-d technique: the statistics are O(d²) per prefix
entry, so the very-wide-feature regime (the 2-D ``(data, model)`` mesh
hook, `parallel/model_parallel.py`) is out of scope — at d=100k one Gram
matrix alone is 40 GB.  The two accelerations are complementary, not
composable: gram for many-rows × moderate-d, feature sharding for wide d.

Memory: the prefix stack is ``(n/block_rows + 1) · d² · 4`` bytes (f32 —
differences of same-sign prefix accumulations would lose ~1% at bf16, so
the stats dtype floor is f32).  For the 3M×1000 bench slab at the default
``block_rows=8192`` that is ~1.5 GB next to the 6 GB bf16 slab.  A FULL
batch reads no prefix: its totals form (:func:`stats_build`) is ``G``,
``b``, ``yy`` alone, ``(d² + d + 1) · 4`` bytes whatever the rows (4 MB at
d = 1000), built in one read with no temporary of X's size, which is what
lets it run beside a stream's next micro-batch (PERF.md, PR 41).

Precision: the PREFIX form deliberately does NOT follow the hot-path
``matmul_dtype`` bandwidth contract (`ops/gradients.py`).  Window results
are *differences of whole-prefix accumulations*, so any matmul rounding is
amplified by (prefix magnitude / window-gradient magnitude) — near
convergence that ratio is huge, and bf16-pass matmuls (the TPU default for
both bf16 AND f32 operands) turn a 0.4% product error into an O(1)
gradient error.  Since the whole point of the path is to be compute-cheap
rather than bandwidth-bound, every internal matmul runs in the stats dtype
at ``lax.Precision.HIGHEST``; the precompute walks the data block-by-block
(``lax.map``) so the f32 upcast never materializes more than one block.

The TOTALS form (:func:`stats_build`: a full batch reads ``G``, ``b``, ``yy``
of all its rows and no prefix, so nothing is ever a difference of two large
sums) needs that care only for what it would round.  The product of two
bf16 numbers is exact in f32 (8 + 8 significant bits of 24), so over bf16
rows ONE bf16 pass with f32 sums gives the same ``X^T X`` as the six passes
of ``HIGHEST`` over the rows upcast, at a sixth of the matrix unit's time and
with no upcast of X at all: it is the configuration's own precision ("bf16
matmul operands, float32 sums").  ``y`` is f32 and is never rounded: it goes
to the matrix unit as the three bf16 parts that add up to it
(``pallas_kernels._parts_of``, as the wide kernel holds w), so ``X^T y``'s
products are exact too.  Rows of any other type keep the upcast and
``HIGHEST``.  The iterations on the totals (``batch_sums``) stay at
``HIGHEST``: they are (d, d) matvecs, microseconds.

Plumbing: the statistics enter compiled programs as ARGUMENTS, never as
closure constants — tracing GB-scale captured arrays into a jit program
embeds them in the lowered module, which chokes compilation (observed:
minutes of lowering through the remote-TPU path vs seconds with argument
buffers).  :class:`GramData` is a registered pytree bundling the dense
matrix with its statistics; pass it wherever ``X`` goes (``optimize``,
``make_run``) and the bound :class:`GramLeastSquaresGradient` pulls the
statistics out of the traced argument.  The optimizer-level
``set_sufficient_stats`` flags do this wrapping automatically; for a full
batch they hand the totals form to ONE unbound executor an optimizer, so a
new dataset of the same shape (a stream's next micro-batch) finds the
compiled build and the compiled run it left.
"""

from __future__ import annotations

import warnings
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_sgd.ops.gradients import (LeastSquaresGradient, acc_dtype,
                                   matmul_dtype)

Array = jax.Array

_HI = jax.lax.Precision.HIGHEST

#: default prefix block size — the round-3 hardware captures' sweet spot;
#: shared by the builders, the optimizers' knob defaults, and the
#: planner's reset (so "plan carries no block size" means THIS, not
#: whatever a previous dataset's plan left behind)
DEFAULT_BLOCK_ROWS = 8192


def _dot_hi(a, b, dtype):
    """Cancellation-safe matmul: both operands upcast to the stats dtype,
    full-precision MXU passes (see the module docstring)."""
    return jnp.dot(
        a.astype(dtype), b.astype(dtype),
        precision=_HI, preferred_element_type=dtype,
    )


def streamed_totals_chunking(n: int, block_rows: int,
                             batch_rows=None):
    """``(B, chunk)`` for a streamed TOTALS build: block granularity and
    host→device chunk rows.  ``batch_rows`` CAPS the chunk EXACTLY — the
    O(d²) totals carry has no prefix stack, so the block size is free to
    shrink to honor small caps (unlike the prefix builders, whose stack
    grows as B shrinks).  THE one policy, shared by
    ``NormalEquations.set_host_streaming`` and the meshed totals builder
    (``parallel/gram_parallel.py``)."""
    n = max(1, int(n))
    B = max(1, min(int(block_rows), n))
    if batch_rows:
        B = max(1, min(B, int(batch_rows)))
        chunk = max(B, (int(batch_rows) // B) * B)
    else:
        chunk = 64 * B
    return B, min(chunk, n)


def _running_sum(carry0, blocks):
    """Inclusive running sum over the leading axis via ``lax.scan`` —
    shared by the one-shot and the chunked-streaming prefix builders
    (``jnp.cumsum`` is avoided deliberately: its reduce-window lowering
    allocates multi-GB temporaries at (1200, d, d) scale)."""

    def step(carry, blk):
        c = carry + blk
        return c, c

    _, cums = jax.lax.scan(step, carry0, blocks)
    return cums


def _stats_of(X, y):
    """``(X^T X, X^T y, y^T y)`` of the rows ``X`` and their labels, at the
    module docstring's precision: bf16 rows take one pass with f32 sums and
    ``y`` as three bf16 parts; any other type the stats dtype at ``HIGHEST``.
    Two ``dot_general``s that contract the rows' axis of X as it is stored."""
    sd = jnp.promote_types(jnp.float32, X.dtype)
    rows = (((0,), (0,)), ((), ()))  # contract axis 0 of both
    if X.dtype == jnp.bfloat16:
        from tpu_sgd.ops.pallas_kernels import _parts_of

        G = jax.lax.dot_general(X, X, rows, preferred_element_type=sd)
        parts = jnp.stack(_parts_of(y, X.dtype, 3))  # (3, n)
        b = jax.lax.dot_general(
            parts, X, (((1,), (0,)), ((), ())),
            preferred_element_type=sd).sum(axis=0)
        y = y.astype(sd)
    else:
        Xs, y = X.astype(sd), y.astype(sd)
        G = jax.lax.dot_general(Xs, Xs, rows, precision=_HI,
                                preferred_element_type=sd)
        b = jax.lax.dot_general(Xs, y, rows, precision=_HI,
                                preferred_element_type=sd)
    return G, b, jnp.sum(y * y)


@jax.jit
def _stats_build(X, y):
    """``(G, b, yy) = (X^T X, X^T y, y^T y)`` of ALL the rows, each read
    where it lies (``_stats_of``; at d = 1000 the chip stores X feature-major
    and the contraction runs along the lanes: ``X.T`` is a bitcast, nothing
    of X's size is made; ``tests/test_chip_compile.py``), static shapes, no
    slice at a traced offset.  For rows that are ALREADY one array on the
    device; a host micro-batch's totals are folded from its row blocks as
    they land (``_stats_fold``).  The jitted function's NAME carries the
    scope's: the persistent compile cache's key holds the one and not the
    other (PERF.md, PR 25)."""
    with jax.named_scope("sgd.stats_build"):
        return _stats_of(X, y)


def stats_build(X, y) -> "GramData":
    """The TOTALS form of the statistics, for a full batch on one device:
    ONE read of ``(X, y)`` makes ``G``, ``b``, ``yy`` (12 MB at d = 1000)
    and no prefix stack, and the bundle that comes back holds no rows
    (``X`` None: the iterations read 4 MB each, and the caller's X may be
    dropped while they run).  The same program for every ``(X, y)`` of one
    shape and type; pass the bundle as ``X`` to an UNBOUND
    :class:`GramLeastSquaresGradient`."""
    return totals_bundle(_stats_build(X, y), X.shape, X.dtype)


def totals_bundle(totals, shape, dtype) -> "GramData":
    """``(G, b, yy)`` of a ``shape`` matrix of ``dtype`` as the totals form
    of :class:`GramData`: no rows, no prefix stack."""
    return GramData(None, None, None, None, *totals, int(shape[0]),
                    logical_shape=shape, logical_dtype=dtype)


@partial(jax.jit, static_argnums=(0, 1))
def _stats_zero(d, dtype):
    """The totals of no rows: what ``_stats_fold``'s first call adds to."""
    with jax.named_scope("sgd.stats_build"):
        return (jnp.zeros((d, d), dtype), jnp.zeros((d,), dtype),
                jnp.zeros((), dtype))


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _stats_fold(G, b, yy, y, offset, block):
    """The running totals (donated: added to in place) with the share of
    ``block`` added, the rows from ``offset`` on of a matrix whose labels
    are ``y`` (all of them, on the device: the offset is an operand, so one
    program serves every block of one shape); and a scalar that is ready
    when the share is in.  The block is read where it lies at
    ``_stats_of``'s precision, to the letter ``_stats_build``'s; the sums
    over the blocks are f32 additions in the blocks' order.  On the v5e a
    16,384 x 1000 bf16 block's share is 0.70 ms beside the 2.29 ms of its
    transfer: the compiler's copy of the block into fast memory 0.14 (both
    products read it there), ``X^T X`` 0.53 (62 TFLOP/s at this depth, a
    third of the 2M-row product's pace), ``X^T y`` 0.03; four blocks a
    program were no faster end to end (PERF.md, PR 44).  The scope is the
    build's, the name the fold's own."""
    with jax.named_scope("sgd.stats_build"):
        share = _stats_of(block, jax.lax.dynamic_slice_in_dim(
            y, offset, block.shape[0]))
        G, b, yy = G + share[0], b + share[1], yy + share[2]
        return G, b, yy, G[0, 0]


def stats_fold(totals, y, offset, block):
    """``(totals, done)``: ``totals`` (``(G, b, yy)``, given up; None where
    no block has been folded yet) with the share of ``block`` added by ONE
    device program (``_stats_fold``), and the scalar that is ready when it
    has run."""
    if totals is None:
        totals = _stats_zero(
            block.shape[1], jnp.promote_types(jnp.float32, block.dtype).name)
    *totals, done = _stats_fold(*totals, y, offset, block)
    return tuple(totals), done


@jax.tree_util.register_pytree_node_class
class GramData:
    """A dense ``(n, d)`` matrix bundled with its block-prefix Gram
    statistics, as a pytree — so the statistics ride into jit programs as
    argument buffers.  Quacks like the wrapped array where the SGD driver
    needs it (``shape``/``dtype``/``ndim``).

    ``X`` may be ``None`` — a VIRTUAL matrix: only the statistics exist on
    device (built by :meth:`GramLeastSquaresGradient.build_streamed` from
    host-resident data too large for HBM), and ``shape``/``dtype`` report
    the logical dataset.  Virtual data supports block-aligned sliced
    windows and full-batch sums (nothing that needs to read rows).

    ``PG``/``Pb``/``Pyy`` may be ``None`` too — the TOTALS form
    (:func:`stats_build`): ``G_tot``, ``b_tot``, ``yy_tot`` alone, which is
    all a full batch reads; it serves no window."""

    __slots__ = ("X", "PG", "Pb", "Pyy", "G_tot", "b_tot", "yy_tot",
                 "block_rows", "_logical_shape", "_logical_dtype")

    def __init__(self, X, PG, Pb, Pyy, G_tot, b_tot, yy_tot, block_rows,
                 logical_shape=None, logical_dtype=None):
        self.X = X
        self.PG = PG
        self.Pb = Pb
        self.Pyy = Pyy
        self.G_tot = G_tot
        self.b_tot = b_tot
        self.yy_tot = yy_tot
        self.block_rows = block_rows
        if X is None and (logical_shape is None or logical_dtype is None):
            raise ValueError(
                "virtual GramData (X=None) needs logical_shape and "
                "logical_dtype (build via "
                "GramLeastSquaresGradient.build_streamed)"
            )
        self._logical_shape = (
            tuple(logical_shape) if logical_shape is not None
            else tuple(X.shape)
        )
        self._logical_dtype = (
            jnp.dtype(logical_dtype) if logical_dtype is not None
            else X.dtype
        )

    @property
    def shape(self):
        return self._logical_shape

    @property
    def dtype(self):
        return self._logical_dtype

    @property
    def ndim(self):
        return len(self._logical_shape)

    def __getitem__(self, idx):
        raise TypeError(
            "GramData supports sliced/full-batch execution only; use "
            "sampling='sliced' (or mini_batch_fraction=1.0), or pass the "
            "plain matrix for indexed/bernoulli sampling"
        )

    def tree_flatten(self):
        return (
            (self.X, self.PG, self.Pb, self.Pyy,
             self.G_tot, self.b_tot, self.yy_tot),
            (self.block_rows, self._logical_shape,
             str(self._logical_dtype)),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        block_rows, shape, dtype_name = aux
        return cls(*children, block_rows, logical_shape=shape,
                   logical_dtype=dtype_name)

    # -- persistence (Saveable/Loader contract, like the models) -----------
    _FORMAT_VERSION = "1.0"

    def save(self, path: str) -> None:
        """Persist the STATISTICS (never the rows) as a directory of
        ``metadata.json`` + ``stats.npz`` — a streamed build over a slow
        link is worth keeping.  Loads back as a VIRTUAL bundle."""
        import json
        import os

        import numpy as np

        if self.PG is None:
            raise ValueError(
                "the totals form holds no prefix stack to persist; save "
                "GramLeastSquaresGradient.totals_only_data(G_tot, b_tot, "
                "yy_tot, ...) of it instead")
        os.makedirs(path, exist_ok=True)
        meta = {
            "class": "GramData",
            "version": self._FORMAT_VERSION,
            "block_rows": int(self.block_rows),
            "logical_shape": list(self._logical_shape),
            "logical_dtype": str(self._logical_dtype),
        }
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(meta, f)
        np.savez(
            os.path.join(path, "stats.npz"),
            PG=np.asarray(self.PG), Pb=np.asarray(self.Pb),
            Pyy=np.asarray(self.Pyy), G_tot=np.asarray(self.G_tot),
            b_tot=np.asarray(self.b_tot), yy_tot=np.asarray(self.yy_tot),
        )

    @classmethod
    def load(cls, path: str) -> "GramData":
        """Load statistics saved by :meth:`save` (virtual — no rows)."""
        import json
        import os

        import numpy as np

        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        if meta.get("class") != "GramData":
            raise ValueError(
                f"{path} holds a {meta.get('class')}, expected GramData"
            )
        if meta["version"] != cls._FORMAT_VERSION:
            raise ValueError(
                f"unsupported GramData format version {meta['version']}"
            )
        z = np.load(os.path.join(path, "stats.npz"))
        put = jax.device_put
        return cls(
            None, put(z["PG"]), put(z["Pb"]), put(z["Pyy"]),
            put(z["G_tot"]), put(z["b_tot"]), put(z["yy_tot"]),
            int(meta["block_rows"]),
            logical_shape=tuple(meta["logical_shape"]),
            logical_dtype=meta["logical_dtype"],
        )


@jax.jit
def _chunk_prefix(cG, cb, cyy, Gc, bc, yyc):
    """Inclusive prefix of one chunk's block stats, continued from the
    running-sum carries (streaming build helper; placement follows the
    committed inputs, so per-shard builds run on their own devices)."""
    return (_running_sum(cG, Gc), _running_sum(cb, bc),
            _running_sum(cyy, yyc))


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _write_prefix(PG, Pb, Pyy, pG, pb, pyy, kb1):
    """In-place (donated) insert of one chunk's prefix rows into the
    full stacks at block offset ``kb1``."""
    return (
        jax.lax.dynamic_update_slice_in_dim(PG, pG, kb1, 0),
        jax.lax.dynamic_update_slice_in_dim(Pb, pb, kb1, 0),
        jax.lax.dynamic_update_slice_in_dim(Pyy, pyy, kb1, 0),
    )


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _acc_totals(G, b, yy, dG, db, dyy):
    """In-place (donated) accumulate of one chunk's totals."""
    return G + dG, b + db, yy + dyy


@partial(jax.jit, donate_argnums=0)
def _scatter_acc_flat(flat, idx, vals):
    """In-place (donated) scatter-add of one compressed ``(indices,
    values)`` wire segment into the flat totals accumulator — the
    sparse sibling of :func:`_acc_totals` for the top-k merge wire
    (``parallel/gram_parallel.py``; README "Compressed wire")."""
    return flat.at[idx].add(vals.astype(flat.dtype))


@partial(jax.jit, donate_argnums=0)
def _dense_acc_flat(flat, delta):
    """In-place (donated) dense add into the flat totals accumulator —
    the compressed merge's FINAL residual flush (the error-feedback
    mass that never made a top-k cut ships exactly once here, so the
    merged totals stay exact up to f.p. reassociation)."""
    return flat + delta.astype(flat.dtype)


def _dataset_fingerprint(Xh, yh, n_rows: int) -> str:
    """Cheap dataset identity for resume checkpoints (first/last used
    row + a label head) — shared by the prefix and totals builders so a
    stale resume_dir from a different same-shaped dataset is rejected
    everywhere the same way."""
    import hashlib

    import numpy as np

    h = hashlib.sha1()
    h.update(np.ascontiguousarray(Xh[0]).tobytes())
    h.update(np.ascontiguousarray(Xh[n_rows - 1]).tobytes())
    h.update(np.ascontiguousarray(
        np.asarray(yh[:min(64, n_rows)], np.float64)).tobytes())
    return h.hexdigest()


def _atomic_json_write(path: str, obj) -> None:
    import json
    import os

    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _validate_or_write_meta(meta_path: str, meta: dict,
                            validate_keys) -> dict:
    """Load-and-compare an existing checkpoint meta (raising on a
    geometry/dataset mismatch) or write a fresh one; returns the
    on-disk meta.  Shared by both build checkpoints."""
    import json
    import os

    if os.path.exists(meta_path):
        with open(meta_path) as f:
            on_disk = json.load(f)
        want = {k: meta[k] for k in validate_keys}
        got = {k: on_disk.get(k) for k in validate_keys}
        if got != want:
            raise ValueError(
                f"resume_dir {os.path.dirname(meta_path)!r} holds a "
                f"different build ({got} != {want}); point resume_dir "
                "at a fresh directory or delete the stale one"
            )
        return on_disk
    _atomic_json_write(meta_path, meta)
    return meta


class _TotalsBuildCheckpoint:
    """Resumability for streamed TOTALS builds (normal solver, meshed
    quasi-Newton): the whole mid-pass state is the O(d²) carry, so each
    checkpoint is ONE tiny atomic npz (carry + high-water row +
    geometry + dataset fingerprint) — negligible next to the host feed
    the resume exists to protect."""

    def __init__(self, path, *, n, d, B, chunk, sd_name, fingerprint="",
                 wire="none"):
        import os

        self.path = path
        self.meta = {
            "class": "TotalsBuildCheckpoint",
            "n": int(n), "d": int(d), "B": int(B), "chunk": int(chunk),
            "stats_dtype": sd_name, "fingerprint": fingerprint,
            # the EFFECTIVE wire dtype: chunks accumulated under one wire
            # must never silently mix with a resumed pass under another
            "wire": wire,
        }
        os.makedirs(path, exist_ok=True)
        self._state_path = os.path.join(path, "totals.npz")
        self._meta_path = os.path.join(path, "meta.json")
        _validate_or_write_meta(self._meta_path, self.meta,
                                tuple(self.meta))

    def restore(self):
        """``(rows_done, (G, b, yy) | None)`` from the last checkpoint."""
        import os

        import numpy as np

        if not os.path.exists(self._state_path):
            return 0, None
        z = np.load(self._state_path)
        return int(z["rows_done"]), (z["G"], z["b"], z["yy"])

    def save(self, rows_done, G, b, yy):
        import os

        import numpy as np

        tmp = self._state_path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, rows_done=np.asarray(rows_done),
                     G=np.asarray(G), b=np.asarray(b), yy=np.asarray(yy))
        os.replace(tmp, self._state_path)

    def finalize(self):
        import shutil

        shutil.rmtree(self.path, ignore_errors=True)


class _PrefixBuildCheckpoint:
    """Per-chunk persistence for the streamed prefix build (VERDICT r4
    #4): each part file holds one chunk's inclusive prefix rows (f32
    device→host readback), written atomically (tmp+rename); ``meta.json``
    records the build geometry and the high-water row mark.  A restart
    validates the geometry, replays the persisted parts into the fresh
    device stack, and continues from the high-water block — the carry is
    the last persisted prefix row, so the resumed build is BITWISE
    identical to an uninterrupted one."""

    def __init__(self, path, *, n_used, d, B, sd_name, chunk,
                 fingerprint="", wire="none"):
        import os

        self.path = path
        self.meta = {
            "class": "PrefixBuildCheckpoint",
            "n_used": int(n_used), "d": int(d), "B": int(B),
            "stats_dtype": sd_name, "chunk": int(chunk),
            "fingerprint": fingerprint,
            # effective wire dtype: a resumed pass under a DIFFERENT wire
            # would silently mix f32-wire and bf16-wire chunk statistics
            "wire": wire,
            "high_water_rows": 0,
        }
        os.makedirs(path, exist_ok=True)
        self._meta_path = os.path.join(path, "meta.json")
        # geometry AND dataset identity AND wire: a stale resume_dir from
        # a different same-shaped dataset (or another wire dtype) would
        # otherwise silently mix two builds' statistics
        on_disk = _validate_or_write_meta(
            self._meta_path, self.meta,
            ("class", "n_used", "d", "B", "stats_dtype", "fingerprint",
             "wire"))
        if on_disk is not self.meta:
            self.meta["high_water_rows"] = int(
                on_disk.get("high_water_rows", 0))

    def _part_path(self, start_block: int) -> str:
        import os

        return os.path.join(self.path, f"part_{start_block:08d}.npz")

    def restore(self):
        """``(resume_row, parts)``: the row offset to continue from plus
        the persisted ``(start_block, (pG, pb, pyy))`` chunks in order.
        Part files past the recorded high-water mark (a crash between
        part write and meta write) are replayed too — they are valid
        completed chunks."""
        import glob
        import os

        import numpy as np

        parts = []
        resume_row = 0
        for fp in sorted(glob.glob(os.path.join(self.path, "part_*.npz"))):
            start_block = int(os.path.basename(fp)[5:-4])
            if start_block * self.meta["B"] != resume_row:
                break  # a gap: earlier part missing — stop replay here
            z = np.load(fp)
            parts.append((start_block, (z["pG"], z["pb"], z["pyy"])))
            resume_row += z["pG"].shape[0] * self.meta["B"]
        return resume_row, parts

    def save_part(self, start_block: int, pG, pb, pyy,
                  high_water_rows: int) -> None:
        import os

        import numpy as np

        fp = self._part_path(start_block)
        tmp = fp + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, pG=np.asarray(pG), pb=np.asarray(pb),
                     pyy=np.asarray(pyy))
        os.replace(tmp, fp)  # atomic: a part either exists whole or not
        self.meta["high_water_rows"] = int(high_water_rows)
        _atomic_json_write(self._meta_path, self.meta)

    def finalize(self) -> None:
        """Drop the part files once the build completed (the caller holds
        the finished stacks; `GramData.save` is the durable format)."""
        import shutil

        shutil.rmtree(self.path, ignore_errors=True)


def _donate_chunks_ok() -> bool:
    """Whether the per-chunk kernels should DONATE their chunk buffers
    (the prefetcher's staging buffer is consumed exactly once, so
    donation hands its HBM back for the next in-flight chunk).  CPU has
    no donation — requesting it there only emits a warning per call."""
    return jax.default_backend() != "cpu"


@lru_cache(maxsize=16)
def _streamed_totals_fn(B, sd_name, donate=False):
    """Jitted per-chunk TOTALS kernel, memoized per (block size, stats
    dtype) so the per-shard mesh builder compiles once, not once per
    device per build (every compile stalls the feed).  ``donate=True``
    (the pipelined ingest path off-CPU) donates the chunk buffers — see
    :func:`_donate_chunks_ok`."""
    fn = partial(
        GramLeastSquaresGradient._total_stats,
        B=B, stats_dtype=jnp.dtype(sd_name),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


@lru_cache(maxsize=16)
def _streamed_stats_fn(B, sd_name, donate=False):
    """Jitted per-chunk block-stats kernel, memoized per (block size,
    stats dtype) so the per-shard mesh builder compiles once, not once
    per shard.  ``donate`` as in :func:`_streamed_totals_fn`."""
    fn = partial(
        GramLeastSquaresGradient._block_stats,
        B=B, stats_dtype=jnp.dtype(sd_name),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


class GramLeastSquaresGradient(LeastSquaresGradient):
    """``LeastSquaresGradient`` bound to precomputed block-prefix Grams.

    Build with :meth:`build`; pass anywhere a ``Gradient`` goes
    (``GradientDescent``, ``LBFGS``), giving the optimizer ``.data`` (the
    :class:`GramData` bundle) as the feature matrix.  Accelerates:

    * ``window_sums`` (sliced mini-batch sampling) — prefix difference +
      edge corrections;
    * ``batch_sums`` with no mask (full-batch GD, LBFGS CostFun) — total
      statistics;
    * ``loss_sweep`` with no mask (the batched line-search ladder) — one
      (T, d) × (d, d) quadratic-form matmul.

    The plain bound array also works in eager calls (identity-checked);
    for anything traced/jitted pass ``.data`` — the optimizer
    ``set_sufficient_stats`` flags do this automatically.  Bernoulli-
    masked and indexed sampling, ``valid`` masks, feature-axis sharding,
    and any ``X`` that is neither the ``GramData`` bundle nor (by
    identity) the bound dataset all fall back to the stock exact
    implementation — a same-shape different matrix can never silently
    train against stale statistics.
    """

    def __init__(self, data: Optional[GramData] = None,
                 aligned: bool = False):
        # data=None gives an UNBOUND executor: it accelerates GramData
        # arguments (the DP-mesh path hands each shard its local bundle)
        # and treats every plain array as unbound stock input.
        # aligned=True floors window starts to block boundaries even when
        # rows ARE resident — skipping the edge corrections (71% of the
        # exact iteration, PROFILE_TPU.json) at the cost of a floored
        # window: a different, equally sized run of rows where the start
        # is not a block boundary (sound on shuffled rows).
        # Virtual data (X=None) is always aligned.
        self.data = data
        self.aligned = bool(aligned)
        self._X_shape = tuple(data.shape) if data is not None else None
        self._X_dtype = data.dtype if data is not None else None
        self.block_rows = data.block_rows if data is not None else None
        self._warned = False

    # -- construction ------------------------------------------------------
    @classmethod
    def build(cls, X, y, block_rows: int = DEFAULT_BLOCK_ROWS,
              stats_dtype=None,
              aligned: bool = False) -> "GramLeastSquaresGradient":
        """One pass over ``(X, y)`` → a bound gradient (stats in
        ``.data``).

        ``block_rows`` trades prefix memory (``n/B · d² · 4`` bytes)
        against per-iteration edge-read traffic (``2 · B · d`` elements).
        ``stats_dtype`` defaults to the wider of f32 and the data dtype —
        f64 data (``jax_enable_x64``) keeps f64 statistics instead of
        silently degrading to f32 relative to the stock f64 path.
        """
        X = jnp.asarray(X)
        if not jnp.issubdtype(X.dtype, jnp.inexact):
            X = X.astype(jnp.float32)  # match optimize()'s coercion
        y = jnp.asarray(y)
        if not jnp.issubdtype(y.dtype, jnp.inexact):
            y = y.astype(jnp.float32)
        if X.ndim != 2 or X.shape[0] == 0:
            raise ValueError(f"need a non-empty (n, d) matrix, got {X.shape}")
        sd = cls._resolve_stats_dtype(X.dtype, stats_dtype)
        n = X.shape[0]
        B = max(1, min(int(block_rows), n))
        stats = jax.jit(
            partial(cls._precompute, B=B, stats_dtype=sd)
        )(X, y)
        return cls(GramData(X, *stats, B), aligned=aligned)

    @staticmethod
    def _resolve_stats_dtype(data_dtype, stats_dtype):
        """Shared default/validation: the wider of f32 and the data dtype
        (f64 data keeps f64 statistics), never below f32 (prefix
        differencing would amplify the rounding — module docstring)."""
        if stats_dtype is None:
            stats_dtype = jnp.promote_types(jnp.float32, data_dtype)
        sd = jnp.dtype(stats_dtype)
        if not jnp.issubdtype(sd, jnp.floating):
            # an int/bool stats dtype would silently truncate every
            # element in the _dot_hi upcast — garbage statistics, no error
            raise ValueError(
                f"stats_dtype must be a floating dtype, got {sd}; "
                "use float32 or wider"
            )
        if jnp.finfo(sd).bits < 32:
            raise ValueError(
                "stats_dtype below f32 loses ~1% on prefix differences; "
                "use float32 or wider"
            )
        return sd

    @staticmethod
    def _block_stats(X, y, *, B, stats_dtype):
        """Stacked per-block ``(G, b, yy)`` for the full blocks of
        ``(X, y)`` — ``lax.map`` = sequential scan, so only one block's
        f32 upcast is live at a time."""
        sd = stats_dtype
        nbf = X.shape[0] // B

        def one(k):
            Xb = jax.lax.dynamic_slice_in_dim(X, k * B, B, 0)
            yb = jax.lax.dynamic_slice_in_dim(y, k * B, B, 0)
            G = _dot_hi(Xb.T, Xb, sd)
            b = _dot_hi(yb, Xb, sd)
            yy = _dot_hi(yb, yb, sd)
            return G, b, yy

        return jax.lax.map(one, jnp.arange(nbf))

    @staticmethod
    def _prefix(blocks, sd):
        """Per-block inclusive prefix with a leading zero entry (the
        memory note on ``jnp.cumsum`` avoidance lives on
        :func:`_running_sum`; observed: 20.4 GB requested on a 15.75 GB
        chip for the 10M×1000 prefix before the rewrite)."""
        zero = jnp.zeros((1,) + blocks.shape[1:], sd)
        # graftlint: disable=shape-trap -- build-time precompute: one compile per (block count, d, dtype) plan, never per-iteration
        blocks2 = jnp.concatenate([zero, blocks.astype(sd)])
        return _running_sum(jnp.zeros(blocks.shape[1:], sd), blocks2)

    @staticmethod
    def _total_stats(X, y, *, B, stats_dtype, valid=None):
        """TOTAL statistics ``(G, b, yy)`` of ``(X, y)`` by blockwise
        accumulation — one block's stats-dtype upcast live at a time with
        an O(d²) carry (no prefix stack: the quasi-Newton CostFun reads
        only totals, so meshed/combined builds skip the window machinery
        entirely).  ``valid`` masks padded rows exactly (zeroing one
        matmul operand's rows: Σ m·x xᵀ).  The ``n % B`` tail is a
        static-shape extra block, so totals are EXACT."""
        sd = stats_dtype
        n = X.shape[0]
        nbf = n // B

        def masked(Xb, yb, vb):
            if vb is None:
                return Xb.astype(sd), yb.astype(sd)
            m = vb.astype(sd)
            return Xb.astype(sd) * m[:, None], yb.astype(sd) * m

        def step(carry, k):
            G, b, yy = carry
            Xb = jax.lax.dynamic_slice_in_dim(X, k * B, B, 0)
            yb = jax.lax.dynamic_slice_in_dim(y, k * B, B, 0)
            vb = (None if valid is None else
                  jax.lax.dynamic_slice_in_dim(valid, k * B, B, 0))
            Xm, ym = masked(Xb, yb, vb)
            return (
                G + _dot_hi(Xm.T, Xb, sd),
                b + _dot_hi(ym, Xb, sd),
                yy + _dot_hi(ym, yb, sd),
            ), None

        d = X.shape[1]
        init = (jnp.zeros((d, d), sd), jnp.zeros((d,), sd),
                jnp.zeros((), sd))
        if nbf > 0:
            (G, b, yy), _ = jax.lax.scan(step, init, jnp.arange(nbf))
        else:  # fewer rows than one block (a streamed tail chunk): the
            # static-shape tail below covers everything — scan would
            # still TRACE its body and reject the oversized slice
            G, b, yy = init
        Xt = X[nbf * B:]  # static-shape tail
        yt = y[nbf * B:]
        vt = None if valid is None else valid[nbf * B:]
        Xm, ym = masked(Xt, yt, vt)
        return (G + _dot_hi(Xm.T, Xt, sd), b + _dot_hi(ym, Xt, sd),
                yy + _dot_hi(ym, yt, sd))

    @staticmethod
    def totals_only_data(G_tot, b_tot, yy_tot, n: int, d: int,
                         data_dtype) -> "GramData":
        """A VIRTUAL :class:`GramData` carrying ONLY totals (a trivial
        one-block prefix stack) — sufficient for the quasi-Newton
        CostFun's full-batch sums and line-search sweeps, which never
        read windows.  Window-based execution (GD sliced sampling) sees
        every window as the full batch and must not use this."""
        sd = G_tot.dtype
        zero_G = jnp.zeros_like(G_tot)
        zero_b = jnp.zeros_like(b_tot)
        zero_yy = jnp.zeros_like(yy_tot)
        return GramData(
            None,
            jnp.stack([zero_G, G_tot]),
            jnp.stack([zero_b, b_tot]),
            jnp.stack([zero_yy, yy_tot]),
            G_tot, b_tot, yy_tot,
            int(n),
            logical_shape=(int(n), int(d)),
            logical_dtype=data_dtype,
        )

    @classmethod
    def _precompute(cls, X, y, *, B, stats_dtype):
        sd = stats_dtype
        nbf = X.shape[0] // B
        G_blocks, b_blocks, yy_blocks = cls._block_stats(
            X, y, B=B, stats_dtype=sd
        )
        PG = cls._prefix(G_blocks, sd)
        Pb = cls._prefix(b_blocks, sd)
        Pyy = cls._prefix(yy_blocks, sd)
        Xt = X[nbf * B:]  # static-shape tail (n % B rows)
        yt = y[nbf * B:]
        G_tot = PG[-1] + _dot_hi(Xt.T, Xt, sd)
        b_tot = Pb[-1] + _dot_hi(yt, Xt, sd)
        yy_tot = Pyy[-1] + _dot_hi(yt, yt, sd)
        return PG, Pb, Pyy, G_tot, b_tot, yy_tot

    @classmethod
    def build_streamed(cls, X, y, block_rows: int = DEFAULT_BLOCK_ROWS,
                       batch_rows: Optional[int] = None,
                       stats_dtype=None,
                       resume_dir: Optional[str] = None,
                       wire_dtype=None,
                       prefetch_depth: int = 2,
                       pipeline: bool = True,
                       ) -> "GramLeastSquaresGradient":
        """Statistics for a HOST-resident dataset too large for HBM.

        Streams ``(X, y)`` through the device batch-by-batch, accumulating
        block statistics; the returned gradient is bound to a VIRTUAL
        ``GramData`` (``X=None``) — after this one pass, block-aligned
        sliced windows and full-batch sums run entirely from the on-device
        statistics with ZERO per-iteration host transfer.  This is the
        sufficient-statistics answer to the beyond-HBM config-4 north
        star: the 10M×1000 prefix stack is ~4.9 GB at the default block
        size, vs a 20 GB bf16 slab that cannot be resident.

        The trailing ``n % block_rows`` rows are dropped (windows are
        block-aligned anyway; document-level deviation, <0.1% of rows).
        ``batch_rows`` (default 64 blocks) is the host→device transfer
        granularity.  ``resume_dir`` (opt-in) makes the pass RESUMABLE:
        each chunk's prefix rows persist to atomic part files so a build
        killed mid-stream (a wedged host link) restarts from its
        high-water block, bitwise identical (see ``_streamed_prefix``).

        Ingest pipeline (``tpu_sgd/io``; README "Ingestion pipeline"):
        ``pipeline=True`` (default) streams FIXED-SHAPE chunks with
        chunk ``k+1``'s host assembly + ``device_put`` overlapping chunk
        ``k``'s kernel — f32-wire results are BITWISE identical to the
        legacy sync loop (``pipeline=False``).  ``wire_dtype="bfloat16"``
        (opt-in) halves the bytes on the wire; the kernels still
        accumulate in the f32+ stats dtype, so only the input values are
        bf16-rounded.  ``prefetch_depth`` chunks may be staged ahead
        (2 = double buffer; its staging footprint rides INSIDE the
        ``batch_rows`` budget the planner sizes).
        """
        import numpy as np

        Xh = np.asarray(X)
        yh = np.asarray(y)
        if Xh.ndim != 2 or Xh.shape[0] == 0:
            raise ValueError(
                f"need a non-empty (n, d) matrix, got {Xh.shape}"
            )
        n, d = Xh.shape
        B = max(1, min(int(block_rows), n))
        nbf = n // B
        data_dtype = (Xh.dtype if jnp.issubdtype(Xh.dtype, jnp.inexact)
                      else jnp.float32)
        sd = cls._resolve_stats_dtype(data_dtype, stats_dtype)
        chunk_blocks = max(1, int(batch_rows) // B) if batch_rows else 64
        chunk = chunk_blocks * B
        PG, Pb, Pyy = cls._streamed_prefix(
            Xh, yh, B, sd, chunk, resume_dir=resume_dir,
            wire_dtype=wire_dtype, prefetch_depth=prefetch_depth,
            pipeline=pipeline)
        jax.block_until_ready((PG, Pb, Pyy))
        data = GramData(
            None, PG, Pb, Pyy, PG[-1], Pb[-1], Pyy[-1], B,
            logical_shape=(nbf * B, d),
            logical_dtype=data_dtype,
        )
        return cls(data)

    @classmethod
    def _streamed_prefix(cls, Xh, yh, B, sd, chunk, device=None,
                         resume_dir=None, wire_dtype=None,
                         prefetch_depth=2, pipeline=True):
        """Chunked host->device streaming prefix build on ``device``
        (default placement when None) — shared by :meth:`build_streamed`
        and the per-shard mesh builder (``parallel/gram_parallel.py``).

        Truly streaming assembly: the prefix stack is ONE clean device
        allocation, updated in place chunk-by-chunk (donated through
        ``_write_prefix``), with a running-sum carry threading the chunks.
        An earlier bulk-assembly version (stack all block stats, concat,
        prefix in one program) peaked at ~3x the prefix size and died
        RESOURCE_EXHAUSTED at 10Mx1000 on a fragmented 16 GB chip; this
        form peaks at prefix + one chunk (~5.5 GB there).

        ``pipeline=True`` (default) routes the feed through the shared
        ingest layer (``tpu_sgd/io``): FIXED-shape chunks from the chunk
        planner (the tail padded with whole zero BLOCKS in host numpy, so
        the stats kernel and prefix scan compile exactly one body program
        — zero blocks contribute exact zeros and the running sum repeats
        its carry through them, keeping the result BITWISE equal to the
        ``pipeline=False`` legacy sync loop on an f32 wire), with chunk
        ``k+1``'s assembly + ``device_put`` prefetched on a worker thread
        while chunk ``k``'s kernel runs (``prefetch_depth=2`` = double
        buffer).  ``wire_dtype`` opts into the narrow wire format
        (``tpu_sgd/io/wire.py``).  Off the CPU backend the chunk buffers
        are DONATED into the kernel, so the staging footprint stays at
        ~``prefetch_depth`` chunks.

        ``resume_dir`` (opt-in): after each chunk, persist that chunk's
        prefix rows to an atomic part file (plus a meta record), so a
        build killed mid-pass — this environment's host link has wedged
        for hours at a time — restarts from the high-water block instead
        of from zero, BITWISE identical (the resumed carry is the last
        persisted f32 prefix row; the per-chunk math is deterministic).
        The analogue of RDD lineage replay resuming from persisted
        partitions (SURVEY.md §5.3).  Costs one device→host readback of
        each chunk's prefix rows — enable it when the feed is flaky, not
        by default.  Part files hold VALID prefix rows only (pad rows
        never persist), so checkpoints interoperate across both modes.
        """
        import numpy as np

        from tpu_sgd.io import (Prefetcher, pad_rows, plan_chunks,
                                resolve_wire_dtype, wire_cast)

        n_used = (Xh.shape[0] // B) * B
        nbf = n_used // B
        d = Xh.shape[1]
        sd_np = np.dtype(jnp.dtype(sd).name)
        # effective wire (legacy sync feed transfers at the data dtype)
        wd = resolve_wire_dtype(wire_dtype, Xh.dtype) if pipeline else None
        wire_name = "none" if wd is None else str(np.dtype(wd))

        def put(a):
            return jax.device_put(a, device)

        # Stack + carries are created ON the target device (jnp.zeros'
        # device kwarg): a default-placement jnp.zeros would stage each
        # shard's ~GB stack through device 0 first, shrinking its headroom
        # in exactly the beyond-HBM regime this path serves.
        zeros_fn = partial(jnp.zeros, device=device)

        PG = zeros_fn((nbf + 1, d, d), sd)
        Pb = zeros_fn((nbf + 1, d), sd)
        Pyy = zeros_fn((nbf + 1,), sd)
        cG = zeros_fn((d, d), sd)
        cb = zeros_fn((d,), sd)
        cyy = zeros_fn((), sd)
        s = 0
        ckpt = None
        if resume_dir is not None:
            ckpt = _PrefixBuildCheckpoint(
                resume_dir, n_used=n_used, d=d, B=B,
                sd_name=jnp.dtype(sd).name, chunk=chunk,
                fingerprint=_dataset_fingerprint(Xh, yh, n_used),
                wire=wire_name,
            )
            s, parts = ckpt.restore()
            for start_block, (pGh, pbh, pyyh) in parts:
                pG, pb, pyy = put(pGh), put(pbh), put(pyyh)
                PG, Pb, Pyy = _write_prefix(
                    PG, Pb, Pyy, pG, pb, pyy,
                    jnp.asarray(start_block + 1, jnp.int32))
                cG, cb, cyy = pG[-1], pb[-1], pyy[-1]
        if not pipeline:
            stats_fn = _streamed_stats_fn(B, jnp.dtype(sd).name, False)
            while s < n_used:
                e = min(s + chunk, n_used)
                if (e - s) % B:  # last partial chunk: whole blocks only
                    e = s + ((e - s) // B) * B
                Xc = put(Xh[s:e])
                # y rides at the RESOLVED stats dtype (>= f32): f64 data
                # under jax_enable_x64 keeps f64 b/yy statistics, matching
                # the resident build()'s _resolve_stats_dtype contract.
                yc = put(np.asarray(yh[s:e], sd_np))
                Gc, bc, yyc = stats_fn(Xc, yc)
                pG, pb, pyy = _chunk_prefix(cG, cb, cyy, Gc, bc, yyc)
                cG, cb, cyy = pG[-1], pb[-1], pyy[-1]
                PG, Pb, Pyy = _write_prefix(
                    PG, Pb, Pyy, pG, pb, pyy,
                    jnp.asarray(s // B + 1, jnp.int32))
                if ckpt is not None:
                    ckpt.save_part(s // B, pG, pb, pyy, high_water_rows=e)
                s = e
            if ckpt is not None:
                ckpt.finalize()
            return PG, Pb, Pyy

        stats_fn = _streamed_stats_fn(B, jnp.dtype(sd).name,
                                      _donate_chunks_ok())
        plan = plan_chunks(n_used, chunk, offset=s, round_to=B)
        cb_blocks = plan.chunk_rows // B

        def produce(c):
            # Host-side assembly on the prefetch worker: slice, wire
            # cast, fixed-shape pad (all host numpy — the device only
            # ever sees ONE chunk shape), then the async device_put.
            Xc = wire_cast(Xh[c.start:c.stop], wd)
            if c.pad:
                Xc = pad_rows(Xc, c.rows)
            yc = pad_rows(np.asarray(yh[c.start:c.stop], sd_np), c.rows)
            return c, put(Xc), put(yc)

        pf = Prefetcher(produce, plan, depth=prefetch_depth)
        try:
            for c, Xc, yc in pf:
                Gc, bc, yyc = stats_fn(Xc, yc)
                pG, pb, pyy = _chunk_prefix(cG, cb, cyy, Gc, bc, yyc)
                # pad blocks contribute exact zeros, so the padded tail
                # rows repeat the carry: pG[-1] IS the last valid row
                cG, cb, cyy = pG[-1], pb[-1], pyy[-1]
                vb = c.valid // B
                if vb != cb_blocks:  # padded tail: write valid rows only
                    pG, pb, pyy = pG[:vb], pb[:vb], pyy[:vb]
                PG, Pb, Pyy = _write_prefix(
                    PG, Pb, Pyy, pG, pb, pyy,
                    jnp.asarray(c.start // B + 1, jnp.int32))
                if ckpt is not None:
                    ckpt.save_part(c.start // B, pG, pb, pyy,
                                   high_water_rows=c.stop)
        finally:
            pf.close()
        if ckpt is not None:
            ckpt.finalize()
        return PG, Pb, Pyy

    @classmethod
    def _streamed_totals(cls, Xh, yh, B, sd, chunk, device=None,
                         resume_dir=None, checkpoint_every: int = 4,
                         finalize: bool = True, wire_dtype=None,
                         prefetch_depth=2, pipeline=True):
        """Chunked host→device streaming TOTALS accumulation on
        ``device`` — like :meth:`_streamed_prefix` but with an O(d²)
        carry instead of a prefix stack (the quasi-Newton CostFun reads
        only totals), and EXACT: every row contributes (padded zero rows
        add exact zeros, never a drop).

        ``pipeline``/``wire_dtype``/``prefetch_depth`` as in
        :meth:`_streamed_prefix`: fixed-shape chunks (tail zero-padded in
        host numpy to whole blocks — one compiled kernel), double-
        buffered prefetch, opt-in narrow wire.  Totals are exact either
        way; when ``n`` is not a multiple of ``B`` the final partial
        block's matmul runs at the padded ``(B, d)`` shape instead of the
        legacy ragged one, so pipelined-vs-sync agreement there is
        reassociation-level, not bitwise (whole-block datasets ARE
        bitwise; asserted in ``tests/test_io.py``).

        ``resume_dir`` (opt-in): persist the tiny carry after each chunk
        so a build killed mid-pass resumes from its high-water row,
        bitwise — the cheap sibling of the prefix builder's checkpoint
        (the state is one (d, d) matrix, not a GB-scale stack)."""
        import numpy as np

        from tpu_sgd.io import (Prefetcher, pad_rows, plan_chunks,
                                resolve_wire_dtype, wire_cast)

        n, d = Xh.shape
        zeros_fn = partial(jnp.zeros, device=device)
        G = zeros_fn((d, d), sd)
        b = zeros_fn((d,), sd)
        yy = zeros_fn((), sd)
        # effective wire (legacy sync feed transfers at the data dtype)
        wd = resolve_wire_dtype(wire_dtype, Xh.dtype) if pipeline else None
        s = 0
        ckpt = None
        if resume_dir is not None:
            ckpt = _TotalsBuildCheckpoint(
                resume_dir, n=n, d=d, B=B, chunk=chunk,
                sd_name=jnp.dtype(sd).name,
                fingerprint=_dataset_fingerprint(Xh, yh, n),
                wire="none" if wd is None else str(np.dtype(wd)),
            )
            s, carry = ckpt.restore()
            if carry is not None:
                G = jax.device_put(carry[0], device)
                b = jax.device_put(carry[1], device)
                yy = jax.device_put(carry[2], device)
        chunks_since_save = 0
        if not pipeline:
            tot_fn = _streamed_totals_fn(B, jnp.dtype(sd).name, False)
            while s < n:
                e = min(s + chunk, n)
                Xc = jax.device_put(Xh[s:e], device)
                yc = jax.device_put(np.asarray(yh[s:e]), device)
                dG, db, dyy = tot_fn(Xc, yc)
                G, b, yy = _acc_totals(G, b, yy, dG, db, dyy)
                chunks_since_save += 1
                # every-N saves keep the async overlap (each save blocks
                # on a device->host readback); a crash re-streams at most
                # N chunks
                if (ckpt is not None
                        and (chunks_since_save >= checkpoint_every
                             or e >= n)):
                    ckpt.save(e, G, b, yy)
                    chunks_since_save = 0
                s = e
            if ckpt is not None and finalize:
                ckpt.finalize()
            return G, b, yy

        tot_fn = _streamed_totals_fn(B, jnp.dtype(sd).name,
                                     _donate_chunks_ok())
        # resume offsets land on chunk boundaries (saves happen at chunk
        # ends), which the planner requires only to be block-aligned; the
        # final save is at row n itself — an already-complete restore
        # must not be asked to block-align it
        plan = plan_chunks(n, chunk, offset=s, round_to=B) if s < n else ()

        def produce(c):
            Xc = wire_cast(Xh[c.start:c.stop], wd)
            if c.pad:
                Xc = pad_rows(Xc, c.rows)
            yc = pad_rows(np.asarray(yh[c.start:c.stop]), c.rows)
            return c, jax.device_put(Xc, device), jax.device_put(yc, device)

        pf = Prefetcher(produce, plan, depth=prefetch_depth)
        try:
            for c, Xc, yc in pf:
                dG, db, dyy = tot_fn(Xc, yc)
                G, b, yy = _acc_totals(G, b, yy, dG, db, dyy)
                chunks_since_save += 1
                if (ckpt is not None
                        and (chunks_since_save >= checkpoint_every
                             or c.stop >= n)):
                    ckpt.save(c.stop, G, b, yy)
                    chunks_since_save = 0
        finally:
            pf.close()
        if ckpt is not None and finalize:
            ckpt.finalize()
        return G, b, yy

    # -- binding check -----------------------------------------------------
    def _stats_for(self, X, mask_or_valid, margin_axis_name):
        """``(dense_X, stats)`` — stats is the GramData to read from, or
        None when this call must run the stock path."""
        if isinstance(X, GramData):
            if mask_or_valid is not None or margin_axis_name is not None:
                if X.X is None:
                    raise NotImplementedError(
                        "virtual (stats-only) GramData supports sliced "
                        "windows and full-batch sums only — no masks, "
                        "valid padding, or feature sharding"
                    )
                return X.X, None  # masked/feature-sharded: stock is correct
            return X.X, X
        if mask_or_valid is not None or margin_axis_name is not None:
            return X, None
        # Plain arrays bind by IDENTITY only: a same-shape different matrix
        # (a validation split, a regenerated batch) must never silently
        # train against stale statistics, and a tracer (someone jitting
        # around a plain X instead of passing ``.data``) can't be
        # value-checked — both fall back to the stock exact path.  The
        # optimizer flags wrap X into GramData before tracing, so the
        # accelerated path is the traced one in normal use.
        if self.data is None:
            return X, None  # unbound executor: plain arrays are stock input
        if X is self.data.X:
            return X, self.data
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"GramLeastSquaresGradient is bound to a "
                f"{self._X_shape} {self._X_dtype} matrix but was called "
                f"with a different (or traced) {tuple(jnp.shape(X))} "
                f"{getattr(X, 'dtype', '?')} array; running the exact "
                "unaccelerated path (pass gradient.data as X — the "
                "optimizer set_sufficient_stats flags do — or rebuild)",
                RuntimeWarning,
                stacklevel=4,
            )
        return X, None

    # -- accelerated entry points -----------------------------------------
    def one_read(self, X, y, weights, mask=None, margin_axis_name=None,
                 window=None):
        # the step runs from the statistics; where it falls back to the
        # stock sums they lay their row operands out themselves
        return None

    def batch_sums(self, X, y, weights, mask=None, margin_axis_name=None):
        Xd, st = self._stats_for(X, mask, margin_axis_name)
        if st is None:
            return super().batch_sums(
                Xd, y, weights, mask, margin_axis_name=margin_axis_name
            )
        # X (GramData or bound array) carries the logical shape/dtype even
        # when the rows are virtual (st.X is None)
        cd = acc_dtype(matmul_dtype(X))
        sd = st.G_tot.dtype
        with jax.named_scope("sgd.stats_sums"):
            w = weights.astype(sd)
            Gw = _dot_hi(st.G_tot, w, sd)
            b = st.b_tot
            g_sum = (Gw - b).astype(cd)
            # cancellation-safe loss dots (see _window_sums_aligned)
            loss_sum = (0.5 * (_dot_hi(w, Gw, sd) - 2.0 * _dot_hi(w, b, sd)
                               + st.yy_tot)).astype(cd)
        return g_sum, loss_sum, jnp.asarray(X.shape[0], cd)

    def loss_sweep(self, X, y, W, mask=None):
        Xd, st = self._stats_for(X, mask, None)
        if st is None:
            return super().loss_sweep(Xd, y, W, mask)
        cd = acc_dtype(matmul_dtype(X))
        sd = st.G_tot.dtype
        Wc = W.astype(sd)  # (T, d)
        GW = _dot_hi(Wc, st.G_tot, sd)  # (T, d) — G is symmetric
        quad = jnp.sum(GW * Wc, axis=1)
        lin = _dot_hi(Wc, st.b_tot, sd)
        losses = 0.5 * (quad - 2.0 * lin + st.yy_tot)
        return losses.astype(cd), jnp.asarray(X.shape[0], cd)

    def window_sums(
        self,
        X: Array,
        y: Array,
        weights: Array,
        start: Array,
        m: int,
        valid: Optional[Array] = None,
        margin_axis_name: Optional[str] = None,
    ) -> Tuple[Array, Array, Array]:
        Xd, st = self._stats_for(X, valid, margin_axis_name)
        if st is None:
            return super().window_sums(
                Xd, y, weights, start, m, valid,
                margin_axis_name=margin_axis_name,
            )
        if st.PG is None:
            raise NotImplementedError(
                "the totals form of the statistics (stats_build) serves "
                "full-batch sums only; windows need the prefix form (build)")
        cd = acc_dtype(matmul_dtype(X))
        if st.X is None or self.aligned:
            return self._window_sums_aligned(st, weights, start, m, cd)
        n = Xd.shape[0]
        # Same effective clamp as the stock path's whole-window
        # dynamic_slice.
        start = jnp.clip(start, 0, max(n - m, 0))
        end = start + m
        Gw_s, b_s, yy_s = self._cum(st, Xd, y, weights, start, cd)
        Gw_e, b_e, yy_e = self._cum(st, Xd, y, weights, end, cd)
        Gw, b, yy = Gw_e - Gw_s, b_e - b_s, yy_e - yy_s
        g_sum = Gw - b
        wc = weights.astype(cd)
        loss_sum = 0.5 * (_dot_hi(wc, g_sum, cd) - _dot_hi(wc, b, cd) + yy)
        return g_sum, loss_sum, jnp.asarray(m, cd)

    def _window_sums_aligned(self, st, weights, start, m, cd):
        """Block-aligned window on virtual (stats-only) data: the start
        floors to a block boundary and the window length rounds to whole
        blocks — a different, equally sized run of rows where the start
        is no block boundary (harmless on i.i.d. data).  Prefix difference
        only: ZERO row access, so a beyond-HBM dataset iterates entirely
        from its on-device statistics."""
        B = st.block_rows
        n = st.shape[0]
        nbf = n // B
        mb = max(1, min(nbf, round(m / B)))
        start = jnp.clip(start, 0, max(n - m, 0))
        k1 = jnp.clip(start // B, 0, nbf - mb)
        k2 = k1 + mb
        sd = st.PG.dtype
        PG1 = jax.lax.dynamic_slice_in_dim(st.PG, k1, 1, 0)[0]
        PG2 = jax.lax.dynamic_slice_in_dim(st.PG, k2, 1, 0)[0]
        Pb1 = jax.lax.dynamic_slice_in_dim(st.Pb, k1, 1, 0)[0]
        Pb2 = jax.lax.dynamic_slice_in_dim(st.Pb, k2, 1, 0)[0]
        yy = (jax.lax.dynamic_slice_in_dim(st.Pyy, k2, 1, 0)[0]
              - jax.lax.dynamic_slice_in_dim(st.Pyy, k1, 1, 0)[0])
        PG, Pb, w = PG2 - PG1, Pb2 - Pb1, weights.astype(sd)
        g_sum = _dot_hi(PG, w, sd) - Pb
        # HIGHEST-precision dots: near convergence the loss is the near-zero
        # difference of ~||y||^2-magnitude terms, and a default-precision
        # (bf16-pass) dot's relative error dwarfs it (module docstring)
        loss_sum = 0.5 * (_dot_hi(w, g_sum, sd) - _dot_hi(w, Pb, sd) + yy)
        count = jnp.asarray(mb * B, cd)
        return g_sum.astype(cd), loss_sum.astype(cd), count

    # -- internals ---------------------------------------------------------
    def _cum(self, st, X, y, weights, r, cd):
        """Statistics of rows ``[0, r)`` applied to ``weights``:
        ``(G_[0,r) @ w, b_[0,r), yy_[0,r))`` — prefix entry ``r // B`` plus
        a masked partial-block edge."""
        B = st.block_rows
        k = r // B
        PGk = jax.lax.dynamic_slice_in_dim(st.PG, k, 1, 0)[0]
        Pbk = jax.lax.dynamic_slice_in_dim(st.Pb, k, 1, 0)[0]
        Pyyk = jax.lax.dynamic_slice_in_dim(st.Pyy, k, 1, 0)[0]
        Gw_full = _dot_hi(PGk, weights, PGk.dtype)
        e_gw, e_b, e_yy = self._edge(st, X, y, weights, r, k, cd)
        return (
            Gw_full.astype(cd) + e_gw,
            Pbk.astype(cd) + e_b,
            Pyyk.astype(cd) + e_yy,
        )

    def _edge(self, st, X, y, weights, r, k, cd):
        """Contribution of the partial block ``[k·B, r)`` (``r − k·B < B``
        rows), via masked matvecs on one B-row slice — never a (d, d)
        intermediate.  The slice start backs off to ``n − B`` near the tail
        so ``dynamic_slice`` never clamps behind our back; the mask is
        expressed in slice-local coordinates to stay exact either way."""
        B = st.block_rows
        n = X.shape[0]
        sd = st.PG.dtype
        s = jnp.minimum(k * B, max(n - B, 0))
        Xb = jax.lax.dynamic_slice_in_dim(X, s, B, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, s, B, 0)
        j = jnp.arange(B)
        msk = ((j >= k * B - s) & (j < r - s)).astype(sd)
        margins = _dot_hi(Xb, weights, sd)  # (B,)
        e_gw = _dot_hi(margins * msk, Xb, sd)
        ybm = yb.astype(sd) * msk
        e_b = _dot_hi(ybm, Xb, sd)
        e_yy = _dot_hi(yb, ybm, sd)
        return e_gw.astype(cd), e_b.astype(cd), e_yy.astype(cd)
