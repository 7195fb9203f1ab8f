"""Data-parallel sufficient-statistics (Gram) execution.

Composes `tpu_sgd/ops/gram.py` with the 1-D data mesh: each shard owns the
block-prefix Gram statistics of its LOCAL rows (built in one shard_map
pass over the already-sharded dataset — the same one-time ``cache()``
moment as ``shard_dataset``), and the unchanged ``make_run`` body then
executes per-shard window gradients from those statistics with the usual
``lax.psum`` combine over ICI.  Sampling semantics are identical to the
stock DP sliced path (per-shard window starts from the axis-folded key),
so the trajectory matches the stock mesh run the way the single-device
gram path matches the single-device run.

Config-4 frame (SURVEY.md, `BASELINE.json:10`): the north star names
"8-way data-parallel all-reduce" — this module is what makes the ~20×
sufficient-stats schedule (BASELINE.md round 3) available in exactly that
shape.

Restriction: the row count must divide the data axis (no padding).  The
gram fast path normalizes windows by the full window length, while padded
datasets carry a ``valid`` mask whose realized counts differ — rather
than silently change normalization, non-divisible inputs fall back to the
stock mesh path (the optimizer handles this automatically).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gram import (DEFAULT_BLOCK_ROWS, GramData,
                              GramLeastSquaresGradient)
from tpu_sgd.ops.updaters import Updater
from tpu_sgd.parallel.mesh import (DATA_AXIS, as_data_mesh,
                                   shard_map_fn)

#: leading shard axis + per-element rank of each GramData stats leaf
_STATS_SPECS = (
    P(DATA_AXIS, None, None, None),  # PG      (k, nbf+1, d, d)
    P(DATA_AXIS, None, None),        # Pb      (k, nbf+1, d)
    P(DATA_AXIS, None),              # Pyy     (k, nbf+1)
    P(DATA_AXIS, None, None),        # G_tot   (k, d, d)
    P(DATA_AXIS, None),              # b_tot   (k, d)
    P(DATA_AXIS,),                   # yy_tot  (k,)
)


def build_sharded_gram_stats(mesh, Xd, yd, block_rows: int = DEFAULT_BLOCK_ROWS):
    """Per-shard block-prefix statistics for an already-sharded dataset.

    ``Xd``/``yd`` come from ``shard_dataset`` with no padding (``valid is
    None``).  Returns ``(stats_tuple, block_rows_local)`` where each stats
    leaf carries a leading shard axis, sharded over 'data' — ready to pass
    straight into :func:`dp_gram_run_fn`.
    """
    k = mesh.shape[DATA_AXIS]
    n_local = Xd.shape[0] // k
    B = max(1, min(int(block_rows), n_local))
    # f64 data keeps f64 statistics, matching the single-device build()
    # default (prefix-difference cancellation would amplify a silent f32
    # downgrade relative to the stock f64 mesh path).
    sd = GramLeastSquaresGradient._resolve_stats_dtype(Xd.dtype, None)
    fn = _stats_builder(mesh, B, jnp.dtype(sd).name)
    return fn(Xd, yd), B


@functools.lru_cache(maxsize=8)
def _stats_builder(mesh, B, stats_dtype_name):
    """Jitted per-shard stats builder, memoized per (mesh, block size,
    stats dtype) so repeated builds on fresh same-shape datasets retrace
    nothing (the jit itself caches per input shape/dtype)."""
    sd = jnp.dtype(stats_dtype_name)

    def body(Xl, yl):
        stats = GramLeastSquaresGradient._precompute(
            Xl, yl, B=B, stats_dtype=sd
        )
        return tuple(s[None] for s in stats)

    return jax.jit(shard_map_fn(
        mesh, body, (P(DATA_AXIS, None), P(DATA_AXIS)), _STATS_SPECS
    ))


def dp_gram_run_fn(
    updater: Updater,
    config: SGDConfig,
    mesh,
    block_rows: int,
    aligned: bool = False,
):
    """Jitted shard_map'ed full-loop runner over per-shard Gram stats.

    Same ``make_run`` body as ``dp_run_fn``, driven by an unbound
    :class:`GramLeastSquaresGradient` executor (least-squares semantics);
    each shard reconstructs its local ``GramData`` from the stacked stats
    leaves, so the accelerated window path runs per shard and only the
    (grad, loss, count) psums ride the ICI.  ``aligned`` floors per-shard
    window starts to block boundaries (edge corrections skipped — the
    documented sampling deviation; see ``set_gram_options``)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(GramLeastSquaresGradient(aligned=aligned), updater,
                   config, axis_name=DATA_AXIS)

    def body(w, Xl, yl, hyper, PG, Pb, Pyy, Gt, bt, yyt):
        gd = GramData(Xl, PG[0], Pb[0], Pyy[0], Gt[0], bt[0], yyt[0],
                      block_rows)
        return run(w, gd, yl, hyper)

    in_specs = (P(), P(DATA_AXIS, None), P(DATA_AXIS), P()) + _STATS_SPECS
    out_specs = (P(), P(), P())
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def build_streamed_sharded_gram_stats(mesh, Xh, yh, block_rows: int = DEFAULT_BLOCK_ROWS,
                                      batch_rows=None, resume_dir=None,
                                      wire_dtype=None, prefetch_depth=2,
                                      pipeline=True):
    """Per-shard VIRTUAL statistics from HOST-resident rows — the
    beyond-HBM statistics build composed with the data mesh (config 4's
    literal "8-way data-parallel" shape at full 10M×1000 scale,
    BASELINE.json:10; the treeAggregate-over-partitions analogue,
    SURVEY.md §3.5).

    Each shard's host row slice streams chunk-by-chunk to ITS OWN device
    (``GramLeastSquaresGradient._streamed_prefix`` with per-device
    placement), so no device ever holds more than one chunk of rows plus
    its own prefix stack; the per-shard stacks are then assembled into
    globally-sharded stats arrays via
    ``jax.make_array_from_single_device_arrays`` — zero cross-device row
    movement, zero host-side concatenation.

    Rows are split evenly: shard ``i`` owns host rows
    ``[i*n_local, i*n_local + nbf*B)`` where ``n_local = n // k`` — the
    ``n % k`` remainder plus each shard's ``n_local % B`` tail are dropped
    (the same block-truncation deviation as the single-device
    ``build_streamed``, <0.1% of rows at scale).  Single-process only
    (every mesh device must be addressable); on a multi-host pod each
    process would run this over its local shard slice.

    ``resume_dir`` (opt-in): per-shard resumable builds — shard ``i``
    checkpoints under ``resume_dir/shard_i`` (see
    ``GramLeastSquaresGradient._streamed_prefix``), so a mid-pass kill
    resumes every shard from its own high-water block.

    ``wire_dtype``/``prefetch_depth``/``pipeline`` route each shard's
    feed through the shared ingest layer (``tpu_sgd/io``; README
    "Ingestion pipeline"): fixed-shape chunks with the next chunk's
    host assembly + ``device_put`` overlapping the current chunk's
    kernel, and an opt-in bf16 wire halving the bytes on the hop.

    Returns ``(stats_leaves, B, n_used_local)``.
    """
    import numpy as np

    from jax.sharding import NamedSharding

    mesh = as_data_mesh(mesh)  # trivial extra axes flatten; real ones raise
    k = mesh.shape[DATA_AXIS]
    n, d = Xh.shape
    n_local = n // k
    if n_local < 1:
        raise ValueError(f"{n} rows cannot shard {k} ways")
    B = max(1, min(int(block_rows), n_local))
    nbf = n_local // B
    n_used = nbf * B
    data_dtype = (Xh.dtype if jnp.issubdtype(Xh.dtype, jnp.inexact)
                  else jnp.float32)
    sd = GramLeastSquaresGradient._resolve_stats_dtype(data_dtype, None)
    chunk_blocks = max(1, int(batch_rows) // B) if batch_rows else 64
    chunk = chunk_blocks * B

    devices = list(mesh.devices.reshape(-1))
    per_dev = []
    import os

    for i, dev in enumerate(devices):
        s = i * n_local
        PG, Pb, Pyy = GramLeastSquaresGradient._streamed_prefix(
            Xh[s:s + n_used], np.asarray(yh[s:s + n_used]), B, sd, chunk,
            device=dev,
            resume_dir=(None if resume_dir is None
                        else os.path.join(resume_dir, f"shard_{i}")),
            wire_dtype=wire_dtype, prefetch_depth=prefetch_depth,
            pipeline=pipeline,
        )
        per_dev.append((PG, Pb, Pyy, PG[-1], Pb[-1], Pyy[-1]))
    jax.block_until_ready(per_dev)

    shapes = ((nbf + 1, d, d), (nbf + 1, d), (nbf + 1,),
              (d, d), (d,), ())
    leaves = []
    for leaf_i, (shape, spec) in enumerate(zip(shapes, _STATS_SPECS)):
        bufs = [
            jax.device_put(per_dev[i][leaf_i][None], devices[i])
            for i in range(k)
        ]
        leaves.append(jax.make_array_from_single_device_arrays(
            (k,) + shape, NamedSharding(mesh, spec), bufs
        ))
    return tuple(leaves), B, n_used


def dp_virtual_gram_run_fn(
    updater: Updater,
    config: SGDConfig,
    mesh,
    block_rows: int,
    n_local: int,
    d: int,
    data_dtype_name: str,
):
    """Jitted shard_map'ed full-loop runner over per-shard VIRTUAL stats
    (no rows on device at all): each shard reconstructs a rows-free
    ``GramData`` carrying its logical ``(n_local, d)`` shape, so windows
    run block-aligned from the prefix stacks and only the (grad, loss,
    count) psums ride the ICI.  Signature:
    ``fn(w0, yd, hyper, *stats_leaves) -> (w, losses, n_rec)`` (``yd`` is the
    tiny label vector, sharded for shape parity — the virtual window path
    never reads it)."""
    from tpu_sgd.optimize.gradient_descent import make_run

    run = make_run(GramLeastSquaresGradient(), updater, config,
                   axis_name=DATA_AXIS)

    def body(w, yl, hyper, PG, Pb, Pyy, Gt, bt, yyt):
        gd = GramData(None, PG[0], Pb[0], Pyy[0], Gt[0], bt[0], yyt[0],
                      block_rows, logical_shape=(n_local, d),
                      logical_dtype=data_dtype_name)
        return run(w, gd, yl, hyper)

    in_specs = (P(), P(DATA_AXIS), P()) + _STATS_SPECS
    out_specs = (P(), P(), P())
    return jax.jit(shard_map_fn(mesh, body, in_specs, out_specs))


def _validate_data_mesh(mesh):
    """``(mesh, k)``: the 1-D data view (the canonical 2-D mesh with a
    TRIVIAL model axis flattens; a real model axis raises)."""
    mesh = as_data_mesh(mesh)
    return mesh, mesh.shape[DATA_AXIS]


def build_sharded_total_stats(mesh, Xd, yd,
                              block_rows: int = DEFAULT_BLOCK_ROWS):
    """Replicated EXACT total statistics ``(G, b, yy)`` of a dataset via
    per-shard blockwise accumulation + one ``psum`` — the quasi-Newton
    meshed sufficient-statistics substitution.

    The quasi-Newton CostFun reads ONLY totals (full-batch sums and the
    line-search sweep — never windows), so the meshed build needs no
    prefix stacks: each shard scans its rows block-by-block with an O(d²)
    carry (``GramLeastSquaresGradient._total_stats``) and one psum makes
    the totals replicated.  Non-divisible row counts pad with a valid
    mask and stay EXACT (masked-operand matmuls).  Returns a VIRTUAL
    totals-only :class:`GramData` (windows degenerate to the full batch
    — quasi-Newton only; GD sliced sampling must not use it).
    """
    from tpu_sgd.parallel.data_parallel import shard_dataset

    import numpy as np

    mesh, k = _validate_data_mesh(mesh)
    # Host inputs stay numpy until shard_dataset places each shard on its
    # own device — jnp.asarray here would stage the whole (possibly
    # beyond-one-HBM) matrix through the default device first.
    if not isinstance(Xd, jax.Array):
        Xd = np.asarray(Xd)
    if not jnp.issubdtype(Xd.dtype, jnp.inexact):
        Xd = Xd.astype(np.float32 if isinstance(Xd, np.ndarray)
                       else jnp.float32)
    if not isinstance(yd, jax.Array):
        yd = np.asarray(yd)
    if not jnp.issubdtype(yd.dtype, jnp.inexact):
        yd = yd.astype(np.float32 if isinstance(yd, np.ndarray)
                       else jnp.float32)
    n, d = Xd.shape
    Xs, ys, valid = shard_dataset(mesh, Xd, yd)
    if valid is None:
        valid = jax.device_put(
            jnp.ones((Xs.shape[0],), bool),
            jax.sharding.NamedSharding(mesh, P(DATA_AXIS)),
        )
    n_local = Xs.shape[0] // k
    B = max(1, min(int(block_rows), n_local))
    sd = GramLeastSquaresGradient._resolve_stats_dtype(Xd.dtype, None)

    def body(Xl, yl, vl):
        G, b, yy = GramLeastSquaresGradient._total_stats(
            Xl, yl, B=B, stats_dtype=sd, valid=vl
        )
        return jax.lax.psum((G, b, yy), DATA_AXIS)

    fn = jax.jit(shard_map_fn(
        mesh, body,
        (P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        (P(), P(), P()),
    ))
    G, b, yy = fn(Xs, ys, valid)
    return GramLeastSquaresGradient.totals_only_data(
        G, b, yy, n, d, Xd.dtype
    )


def build_streamed_total_stats(mesh, Xh, yh,
                               block_rows: int = DEFAULT_BLOCK_ROWS,
                               batch_rows=None, resume_dir=None,
                               wire_dtype=None, prefetch_depth=2,
                               pipeline=True, wire_compress=None):
    """Replicated EXACT total statistics of HOST-resident rows — the
    quasi-Newton beyond-HBM build composed with the data mesh.

    Shard ``i`` streams its contiguous host row slice chunk-by-chunk to
    ITS OWN device with an O(d²) totals carry
    (``GramLeastSquaresGradient._streamed_totals``) — no prefix stacks,
    no dropped rows (the ``n % k`` remainder rides with the last shard),
    peak per-device footprint one chunk + (d, d).  The k tiny (d, d)
    totals then combine on the first device.  Single-process build (every
    mesh device addressable); a multi-host pod runs this per process over
    its local slice.  Returns a VIRTUAL totals-only :class:`GramData`
    (quasi-Newton only — see :func:`build_sharded_total_stats`).

    ``wire_compress="topk:<frac>"`` (README "Compressed wire"): the
    per-shard totals MERGE ships top-k ``(indices, values)`` segments
    through a persistent error-feedback accumulator instead of k-1
    dense ``(d, d)`` adds — each shard's delta folds into the SAME
    jitted donated accumulate (``ops/gram._scatter_acc_flat``), the
    top-k selection runs in host numpy (the shape-trap rule), and the
    accumulated residual flushes ONCE, dense, at the end, so the merged
    totals carry every shard's full mass (exact up to f.p.
    reassociation vs the dense merge — the EF accumulator reorders the
    adds).  Wire bytes: ``(k-1) · 2·frac + 1`` dense-equivalents
    instead of ``k-1`` — the win grows with the shard count.
    """
    import numpy as np

    mesh, k = _validate_data_mesh(mesh)
    Xh = np.asarray(Xh)
    yh = np.asarray(yh)
    n, d = Xh.shape
    if n < k:
        raise ValueError(f"{n} rows cannot shard {k} ways")
    data_dtype = (Xh.dtype if jnp.issubdtype(Xh.dtype, jnp.inexact)
                  else jnp.float32)
    sd = GramLeastSquaresGradient._resolve_stats_dtype(data_dtype, None)
    n_local = n // k
    from tpu_sgd.ops.gram import streamed_totals_chunking

    B, chunk = streamed_totals_chunking(n_local, block_rows, batch_rows)

    import os

    devices = list(mesh.devices.reshape(-1))
    totals = []
    for i, dev in enumerate(devices):
        s = i * n_local
        e = (i + 1) * n_local if i + 1 < k else n  # remainder to the last
        totals.append(GramLeastSquaresGradient._streamed_totals(
            Xh[s:e], yh[s:e], B, sd, chunk, device=dev,
            resume_dir=(None if resume_dir is None
                        else os.path.join(resume_dir, f"shard_{i}")),
            finalize=False,  # a later shard's crash must not force the
            # completed shards to re-stream — clean up only when ALL done
            wire_dtype=wire_dtype, prefetch_depth=prefetch_depth,
            pipeline=pipeline,
        ))
    jax.block_until_ready(totals)
    if resume_dir is not None:
        import shutil

        shutil.rmtree(resume_dir, ignore_errors=True)
    dev0 = devices[0]
    from tpu_sgd.io.sparse_wire import ErrorFeedback, parse_wire_compress
    from tpu_sgd.obs.counters import record_wire
    from tpu_sgd.ops.gram import _acc_totals

    frac = parse_wire_compress(wire_compress)
    if frac is not None and k > 1:
        # Compressed merge wire: flat [G.ravel(), b, yy] accumulator on
        # the first device; shards 1..k-1 ship top-k (indices, values)
        # segments selected HOST-side through ONE persistent
        # error-feedback accumulator, folded in by the jitted donated
        # scatter-accumulate; the EF residual flushes dense, once.
        from functools import partial as _partial

        from tpu_sgd.ops.gram import _dense_acc_flat, _scatter_acc_flat

        dd = d * d
        sd_np = np.dtype(jnp.dtype(sd).name)

        def _flat_host(t):
            Gi, bi, yyi = t
            return np.concatenate([
                np.asarray(Gi).reshape(-1), np.asarray(bi),
                np.asarray(yyi).reshape(1),
            ]).astype(sd_np)

        flat = jax.device_put(_flat_host(totals[0]), dev0)
        ef = ErrorFeedback(dd + d + 1, frac, dtype=sd_np)
        for t in totals[1:]:
            # shard-merge boundary fetch: the shard's (d, d) totals come
            # back to host ONCE so the top-k selection can run in numpy
            # (graftlint shape-trap rule) — this read IS the wire being
            # compressed
            idx, vals = ef.compress(_flat_host(t))
            flat = _scatter_acc_flat(
                flat, jax.device_put(idx, dev0),
                jax.device_put(vals, dev0))
        res = ef.residual()
        record_wire("dense-f32", logical_nbytes=int(res.nbytes),
                    physical_nbytes=int(res.nbytes))
        flat = _dense_acc_flat(flat, jax.device_put(res, dev0))
        split = jax.jit(_partial(_split_flat_totals, d=d))
        G, b, yy = split(flat)
    else:
        G, b, yy = (jax.device_put(t, dev0) for t in totals[0])
        for Gi, bi, yyi in totals[1:]:
            # ONE jitted donated accumulate per shard
            # (ops/gram._acc_totals) instead of three eager per-shard
            # adds, each of which compiled and launched its own one-op
            # program
            record_wire(
                "dense-f32",
                logical_nbytes=int(Gi.nbytes + bi.nbytes + yyi.nbytes),
                physical_nbytes=int(Gi.nbytes + bi.nbytes + yyi.nbytes))
            G, b, yy = _acc_totals(
                G, b, yy,
                jax.device_put(Gi, dev0),
                jax.device_put(bi, dev0),
                jax.device_put(yyi, dev0),
            )
    return GramLeastSquaresGradient.totals_only_data(
        G, b, yy, n, d, data_dtype
    )


def _split_flat_totals(flat, *, d: int):
    """Traced split of the flat merge accumulator back into ``(G, b,
    yy)`` (jitted once per build by the compressed merge — the reshape
    needs a static ``d``)."""
    dd = d * d
    return (flat[:dd].reshape(d, d), flat[dd:dd + d], flat[dd + d])
