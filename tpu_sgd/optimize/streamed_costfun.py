"""Host-streamed full-batch cost evaluation for the quasi-Newton optimizers.

Reference parity: the reference's LBFGS ``CostFun`` evaluates the FULL-batch
(loss, gradient) with ONE ``treeAggregate`` over an RDD of ANY size, for ANY
``Gradient`` ([U] mllib/optimization/LBFGS.scala, SURVEY.md §2 #18, §3.5) —
dataset scale and loss family are orthogonal there.  This module is the
TPU-native analogue for host-resident datasets larger than device HBM: each
evaluation streams the rows through the device in fixed-size chunks,
accumulating ``(grad_sum, loss_sum, count)`` in device-resident accumulators
(donated buffers, so accumulation allocates nothing per chunk), with the
next chunk's host→device transfer overlapping the current chunk's compute —
the executors-read-partitions-while-the-driver-schedules overlap of
SURVEY.md §3.1 without per-task scheduling cost.

Works for ANY gradient implementing ``batch_sums`` (least squares, logistic,
hinge, multinomial's flattened matrix weights): unlike the
sufficient-statistics schedule (least squares only — ``ops/gram.py``),
nothing here assumes the loss has fixed-size statistics.  This is the
literal chunked treeAggregate.

Mesh composition: under a 1-D data mesh each chunk is ``device_put``
row-sharded across the cores and the per-chunk partial sums ``psum`` over
ICI before accumulating into replicated accumulators — the multi-executor
treeAggregate shape.  On a multi-host job each process streams ITS OWN
local row slice and per-chunk global arrays assemble via
``make_array_from_process_local_data`` (no cross-host rows; the chunk
grid is agreed by allgather so every process runs the same psum'd
programs); single-process meshes stream every shard from this host.

Cost model: every evaluation re-reads the whole dataset through the host
feed (an LBFGS iteration is ~2 cost evaluations + 1 sweep), so this is the
schedule of LAST RESORT — ``plan_quasi_newton`` picks it only when the data
exceeds HBM and no statistics substitution exists (non-least-squares
losses).  The reference pays the same shape of cost: its CostFun re-reads
every partition per evaluation, from executor memory when cached and from
disk/recomputation when not.
"""

from __future__ import annotations

import math
from functools import lru_cache as _lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.plan import default_stream_batch_rows

Array = jax.Array

def mesh_spans_processes(mesh) -> bool:
    """True when ``mesh`` contains devices of OTHER processes — the
    multihost regime where chunk arrays must assemble from per-process
    local slices and the chunk grid is agreed by collectives.  A mesh of
    only this process's devices streams single-host even inside a
    multi-process job (gating on ``process_count() > 1`` alone would
    run a job-wide allgather nobody else joins)."""
    import jax

    pid = jax.process_index()
    return any(d.process_index != pid for d in mesh.devices.flat)


@_lru_cache(maxsize=64)
def _replicated_zeros_fn(shape, dtype_name, sharding):
    """Cached jitted maker of replicated global zero accumulators."""
    return jax.jit(partial(jnp.zeros, shape, jnp.dtype(dtype_name)),
                   out_shardings=sharding)


class StreamedCostFun:
    """Chunked full-batch ``(loss, grad)`` / loss-sweep evaluator over
    host-resident rows.

    Returns RAW SUMS (``grad_sum``, ``loss_sum``, ``count``) — callers
    normalize and add their regularization terms, exactly like the
    in-memory ``Gradient.batch_sums`` contract the quasi-Newton loops
    already consume.

    One instance binds ``(gradient, X, y, chunking, mesh)`` and compiles
    its accumulate kernels once; every ``cost_sums``/``sweep_sums``/
    ``loss_sums`` call then streams the fixed chunk grid through them.
    """

    def __init__(self, gradient, X, y, batch_rows: Optional[int] = None,
                 mesh=None, device=None):
        self.gradient = gradient
        Xh = np.asarray(X)
        yh = np.asarray(y)
        multihost = mesh is not None and mesh_spans_processes(mesh)
        if Xh.ndim != 2 or (Xh.shape[0] == 0 and not multihost):
            # a multihost process MAY hold zero local rows (uneven
            # splits): it still must join every collective, feeding
            # all-invalid chunks
            raise ValueError(f"need a non-empty (n, d) matrix, got {Xh.shape}")
        if not jnp.issubdtype(Xh.dtype, jnp.inexact):
            Xh = Xh.astype(np.float32)  # match optimize()'s coercion
        if not jnp.issubdtype(yh.dtype, jnp.inexact):
            yh = yh.astype(np.float32)
        self.X = Xh
        self.y = yh
        n, d = Xh.shape
        self.n = n
        if batch_rows is None:
            batch_rows = default_stream_batch_rows(d, Xh.dtype.itemsize)
        cap = int(min(max(1, int(batch_rows)), n))
        self.mesh = mesh
        if mesh is None:
            self.device = device if device is not None else jax.devices()[0]
            self._row_sharding = self.device
            self._vec_sharding = self.device
            self._rep_sharding = self.device
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from tpu_sgd.parallel.mesh import DATA_AXIS

            k = mesh.shape[DATA_AXIS]
            cap += (-cap) % k  # equal shard rows; padding rows are invalid
            self.device = None
            self._row_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            self._vec_sharding = NamedSharding(mesh, P(DATA_AXIS))
            self._rep_sharding = NamedSharding(mesh, P())
        self._multihost = multihost
        if self._multihost:
            # Multi-host: (X, y) are THIS process's local rows (the
            # executor-reads-its-own-splits contract, SURVEY.md §3.4).
            # Every process must run the SAME number of psum'd chunk
            # programs, so the chunk grid is agreed via allgather on the
            # LARGEST local slice; processes that exhaust their rows feed
            # all-invalid padding chunks (masked, exact sums).
            from jax.experimental import multihost_utils

            from tpu_sgd.parallel.mesh import DATA_AXIS

            k = mesh.shape[DATA_AXIS]
            k_local = dict(mesh.local_mesh.shape).get(DATA_AXIS, 1)
            # derive the chunk size from batch_rows ALONE — the
            # single-process `min(batch_rows, n)` clamp uses the LOCAL
            # row count, which differs across processes and would
            # desync the global chunk shapes
            cap_global = max(1, int(batch_rows))
            cap_global += (-cap_global) % k
            cap_local = max(1, cap_global * k_local // k)
            cap_local += (-cap_local) % max(1, k_local)
            counts = np.asarray(multihost_utils.process_allgather(
                np.asarray(n)))
            self.cap = cap_local  # per-process rows per chunk
            self.n_chunks = math.ceil(int(counts.max()) / cap_local)
        else:
            self.cap = cap
            self.n_chunks = math.ceil(n / cap)
        self._valid_full = None  # cached all-true mask for full chunks
        # zero-padded partial-chunk host buffers, keyed by row span: X/y
        # are immutable for the instance's lifetime, so the tail's
        # alloc+memcpy (and an exhausted multihost process's all-invalid
        # chunk) is paid once, not per evaluation (~3/LBFGS iteration)
        self._pad_cache = {}
        self._shape_cache = {}  # (mode, w shape/dtype) -> output aval tuple
        self._acc_cost = self._make_acc(mode="cost")
        self._acc_loss = self._make_acc(mode="loss")
        self._acc_sweep = (
            self._make_acc(mode="sweep")
            if hasattr(gradient, "loss_sweep") else None
        )

    # -- kernels -----------------------------------------------------------
    def _make_acc(self, mode: str):
        """Jitted chunk accumulator ``(w, Xc, yc, valid, *accs) -> accs``.
        ``mode``: 'cost' accumulates (grad, loss, count); 'loss' only
        (loss, count) — XLA dead-code-eliminates the gradient matmul;
        'sweep' accumulates the (T,) trial losses + count."""
        g = self.gradient
        mesh = self.mesh

        def psum_if_meshed(vals):
            if mesh is None:
                return vals
            from tpu_sgd.parallel.mesh import DATA_AXIS

            return jax.lax.psum(vals, DATA_AXIS)

        if mode == "cost":
            def body(w, Xc, yc, valid, ag, al, ac):
                gs, ls, c = g.batch_sums(Xc, yc, w, mask=valid)
                gs, ls, c = psum_if_meshed((gs, ls, c))
                return ag + gs, al + ls, ac + c
            n_acc = 3
        elif mode == "loss":
            def body(w, Xc, yc, valid, al, ac):
                _, ls, c = g.batch_sums(Xc, yc, w, mask=valid)
                ls, c = psum_if_meshed((ls, c))
                return al + ls, ac + c
            n_acc = 2
        else:  # sweep: w is the (T, d_flat) trial stack
            def body(w, Xc, yc, valid, al, ac):
                ls, c = g.loss_sweep(Xc, yc, w, mask=valid)
                ls, c = psum_if_meshed((ls, c))
                return al + ls, ac + c
            n_acc = 2

        donate = tuple(range(4, 4 + n_acc))
        if mesh is None:
            return jax.jit(body, donate_argnums=donate)
        from jax.sharding import PartitionSpec as P

        from tpu_sgd.parallel.mesh import DATA_AXIS, shard_map_fn

        in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS),
                    P(DATA_AXIS)) + (P(),) * n_acc
        out_specs = (P(),) * n_acc
        return jax.jit(shard_map_fn(self.mesh, body, in_specs, out_specs),
                       donate_argnums=donate)

    # -- chunk feed --------------------------------------------------------
    def _chunk(self, i: int):
        """``(Xc, yc, valid)`` device buffers for chunk ``i`` — the tail
        chunk is zero-padded to the fixed ``cap`` so ONE compiled program
        serves the whole grid (the valid mask keeps sums exact).  On a
        multi-host job, ``cap`` is the PER-PROCESS chunk rows and the
        global array assembles from each process's local slice
        (``make_array_from_process_local_data`` — no cross-host rows)."""
        if self._multihost:
            return self._chunk_multihost(i)
        s = i * self.cap
        e = min(s + self.cap, self.n)
        Xb, yb = self.X[s:e], self.y[s:e]
        if e - s < self.cap:
            hit = self._pad_cache.get((s, e))
            if hit is None:
                Xp = np.zeros((self.cap, self.X.shape[1]), self.X.dtype)
                Xp[: e - s] = Xb
                yp = np.zeros((self.cap,), self.y.dtype)
                yp[: e - s] = yb
                valid = np.zeros((self.cap,), bool)
                valid[: e - s] = True
                hit = (Xp, yp,
                       jax.device_put(valid, self._vec_sharding))
                self._pad_cache[(s, e)] = hit
            Xb, yb, vd = hit
        else:
            if self._valid_full is None:
                self._valid_full = jax.device_put(
                    np.ones((self.cap,), bool), self._vec_sharding)
            vd = self._valid_full
        return (
            jax.device_put(Xb, self._row_sharding),
            jax.device_put(yb, self._vec_sharding),
            vd,
        )

    def _chunk_multihost(self, i: int):
        s = min(i * self.cap, self.n)
        e = min(s + self.cap, self.n)
        if e - s == self.cap:  # full chunk: zero-copy slices, cached mask
            Xp, yp = self.X[s:e], self.y[s:e]
            if self._valid_full is None:
                self._valid_full = jax.make_array_from_process_local_data(
                    self._vec_sharding, np.ones((self.cap,), bool))
            vd = self._valid_full
        else:  # partial or exhausted: zero-pad, mask the real rows
            # cached per span — every exhausted chunk shares (s, e) with
            # s == e, so a zero-row process builds its all-invalid chunk
            # once, not n_chunks times per evaluation
            hit = self._pad_cache.get((s, e))
            if hit is None:
                Xp = np.zeros((self.cap, self.X.shape[1]), self.X.dtype)
                yp = np.zeros((self.cap,), self.y.dtype)
                valid = np.zeros((self.cap,), bool)
                if e > s:
                    Xp[: e - s] = self.X[s:e]
                    yp[: e - s] = self.y[s:e]
                    valid[: e - s] = True
                hit = (Xp, yp, jax.make_array_from_process_local_data(
                    self._vec_sharding, valid))
                self._pad_cache[(s, e)] = hit
            Xp, yp, vd = hit
        return (
            jax.make_array_from_process_local_data(self._row_sharding, Xp),
            jax.make_array_from_process_local_data(self._vec_sharding, yp),
            vd,
        )

    def _stream(self, w, kernel, accs):
        """Drive the chunk grid through ``kernel``: the device step for
        chunk ``i`` is dispatched (async) BEFORE chunk ``i+1`` is
        assembled and transferred, so host feed and device compute
        overlap; only the caller's final read blocks."""
        if self._multihost:
            # device_put cannot target non-addressable devices; the
            # replicated weights assemble from identical per-process data
            w = jax.make_array_from_process_local_data(
                self._rep_sharding, np.asarray(w))
        else:
            w = jax.device_put(w, self._rep_sharding)
        nxt = self._chunk(0)
        for i in range(self.n_chunks):
            cur = nxt
            accs = kernel(w, *cur, *accs)
            if i + 1 < self.n_chunks:
                nxt = self._chunk(i + 1)
        return accs

    def _zeros(self, shapes):
        if self._multihost:
            # a compiled SPMD program may produce global replicated
            # arrays where a host-side placement cannot; the jitted
            # makers are cached per (shape, dtype, sharding) — the
            # DONATED buffers must be fresh, the compiled fn need not be
            return tuple(
                _replicated_zeros_fn(s.shape, jnp.dtype(s.dtype).name,
                                     self._rep_sharding)()
                for s in shapes
            )
        return tuple(
            jnp.zeros(s.shape, s.dtype, device=self._rep_sharding)
            for s in shapes
        )

    def _probe_shapes(self, mode, fn, w):
        """Accumulator output avals for ``fn`` at this weight shape —
        memoized: re-tracing the gradient via eval_shape on every hot
        evaluation (3+/LBFGS iteration) would be pure waste."""
        key = (mode, tuple(jnp.shape(w)), str(jnp.result_type(w)))
        hit = self._shape_cache.get(key)
        if hit is None:
            sds = jax.ShapeDtypeStruct
            Xc = sds((self.cap, self.X.shape[1]), self.X.dtype)
            yc = sds((self.cap,), self.y.dtype)
            valid = sds((self.cap,), jnp.bool_)
            hit = jax.eval_shape(fn, w, Xc, yc, valid)
            self._shape_cache[key] = hit
        return hit

    # -- public sums -------------------------------------------------------
    def cost_sums(self, w):
        """Full-batch ``(grad_sum, loss_sum, count)`` of ``w``."""
        g = self.gradient
        shapes = self._probe_shapes(
            "cost", lambda w_, X_, y_, v_: g.batch_sums(X_, y_, w_, mask=v_), w)
        return self._stream(w, self._acc_cost, self._zeros(shapes))

    def loss_sums(self, w):
        """Full-batch ``(loss_sum, count)`` — the gradient matmul is
        compiled out (line-search trials of non-sweep gradients)."""
        g = self.gradient
        shapes = self._probe_shapes(
            "loss", lambda w_, X_, y_, v_: g.batch_sums(X_, y_, w_, mask=v_)[1:], w)
        return self._stream(w, self._acc_loss, self._zeros(shapes))

    def sweep_sums(self, W):
        """Full-batch ``(loss_sums (T,), count)`` of a trial-weight stack
        — the whole backtracking ladder reads each chunk once."""
        if self._acc_sweep is None:
            raise NotImplementedError(
                f"{type(self.gradient).__name__} has no loss_sweep rule"
            )
        g = self.gradient
        shapes = self._probe_shapes(
            "sweep", lambda w_, X_, y_, v_: g.loss_sweep(X_, y_, w_, mask=v_), W)
        return self._stream(W, self._acc_sweep, self._zeros(shapes))
