"""Mini-batch gradient descent: the TPU-native ``GradientDescent``.

Reference parity: [U] mllib/optimization/GradientDescent.scala (SURVEY.md §2
#2, §3.1).  The reference's per-iteration pattern —

    broadcast(weights) -> sample(frac, 42+i) -> treeAggregate(seqOp/combOp)
    -> grad /= miniBatchSize -> updater.compute -> convergence check

— is re-designed TPU-first rather than translated (SURVEY.md §7 design
stance):

  * The whole optimization runs as ONE compiled XLA program: a
    ``lax.while_loop`` whose body is the fused batched gradient step.  Spark
    pays per-iteration driver hops (broadcast setup, job scheduling, task
    serialization — SURVEY.md §3.1 "outer hot loop"); here there are zero
    host round-trips until the final result fetch.
  * ``sample(false, frac, 42 + i)`` becomes a per-example Bernoulli mask from
    ``fold_in(key, i)`` — distributional parity, normalized by the *realized*
    mini-batch count exactly as the reference divides by ``miniBatchSize``
    (SURVEY.md §7 hard parts, sampling-semantics parity).
  * ``treeAggregate`` + Torrent broadcast become ``lax.psum`` over the mesh
    axis (hardware ICI all-reduce) + deterministic replicated updates
    (SURVEY.md §3.5, §5.8).  Pass ``axis_name`` to get the sharded body;
    ``None`` gives the single-device body from the same code.
  * The loss-history contract is preserved: ``loss[t] = lossSum/miniBatchSize
    + regVal(prev iteration's weights)`` and the convergence rule is
    ``||w_t - w_{t-1}|| < tol * max(||w_t||, 1)`` checked from the second
    update on (SURVEY.md §5.5, §3.1).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_sgd.config import SGDConfig
from tpu_sgd.obs.builds import root
from tpu_sgd.obs.spans import NO_SPAN, span
from tpu_sgd.obs.timeseries import observe_scalar
from tpu_sgd.ops.gradients import (Gradient, LeastSquaresGradient,
                                   RowCount, RowDraw, matmul_dtype,
                                   rows_valid, step_sums, window_rows)
from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS
from tpu_sgd.ops.sparse import is_sparse
from tpu_sgd.ops.updaters import SimpleUpdater, Updater
from tpu_sgd.optimize.optimizer import Dataset, Optimizer
from tpu_sgd.optimize.run_store import StoredRun

Array = jax.Array


def _raise_if_nonfinite(losses, first_iteration: int = 1) -> None:
    """Shared numerics check (``set_check_numerics``), one message for all
    optimizer paths.  ``first_iteration`` is the 1-based iteration number
    of ``losses[0]`` — the stepwise driver checks one loss at a time and
    must report the TRUE diverging iteration, not 'iteration 1'."""
    import numpy as np

    arr = np.asarray(losses)
    bad = np.nonzero(~np.isfinite(arr))[0]
    if bad.size:
        raise FloatingPointError(
            f"non-finite loss at iteration {int(bad[0]) + first_iteration} "
            f"(loss={arr[bad[0]]}); reduce step_size or check the data"
        )


def _coerce_w0(gradient, initial_weights, n_features):
    """ONE coerce-and-validate for initial weights, shared by every
    driver branch (resident / host-streamed / GramData / meshed
    streamed-gram): float32 master weights (mixed-precision mode: bf16
    data halves HBM traffic, f32 weights keep convergence) and the
    clear length error instead of an opaque XLA shape failure.  A device
    array of an inexact type comes back as it is (``jnp.asarray`` hands
    it on: no copy, no program); a host array is copied once."""
    w0 = jnp.asarray(initial_weights)
    if not jnp.issubdtype(w0.dtype, jnp.inexact):
        w0 = w0.astype(jnp.float32)
    expect_dim = gradient.weight_dim(n_features)
    if w0.shape[-1] != expect_dim:
        raise ValueError(
            f"initial_weights has length {w0.shape[-1]} but this "
            f"gradient needs {expect_dim} for {n_features}-feature data"
        )
    return w0


#: How a dense host array goes to the device (``_stage_dense``): in row
#: blocks of this many bytes, this many of them in flight, cut at whole
#: multiples of ``_STAGE_ROWS`` rows (a window of rows is a window of lanes
#: where the chip stores X feature-major: the writes stay tile-aligned).
#: Set from the sweep on the v5e in PERF.md section 6 (PR 29's step 0,
#: 2,145,000 x 1000 bf16): the runtime makes one block ready on one thread
#: at 10.6 GB/s, under the wire's 14.3, so the wire is kept busy only by
#: several blocks in the runtime at once; 16 x 32 MiB reads 0.31 s against
#: 0.69 s for the one piece and 0.60 s for 2 x 256 MiB, and holds 0.52 GB
#: beside the dataset.  A block is far under 4 GiB, above which a host array
#: is copied 24 times slower.  On ONE wire the wire bounds the copy (PR 37,
#: ``train.h2d``'s ``stall_ms``), whatever form the blocks cross in: 14.5 to
#: 14.7 GB/s in the span as strided rows, as words and flat (PR 49, by
#: hand).  Over FOUR chips (PR 42, 10,000,000 x 1000 bf16, a thread a
#: device) it is the HOST: strided 2-byte rows land at 24 to 25 GB/s, for 2
#: devices as for 4, because the runtime re-tiles every block into
#: ``{0,1:T(8,128)(2,1)}`` on its own threads (``XlaLinearize``, cut into
#: ``Transpose::ExecuteChunk`` pieces on a pool: 6.4 ms of CPU a block for
#: one device, 18.5 with four devices' blocks in it at once, 13.7 of the
#: host's 30 CPUs: PR 46) and holds the issuing threads up wherever they
#: next enter it.  What the blocks' FORM gives there (PR 49, step 0, by
#: hand, the whole loop with its writes; ``_wire_form``): a C-ordered
#: array's rows as flat 1-D runs, which the runtime copies and does not
#: re-tile, 36.8 to 37.1 GB/s; a Fortran-ordered array's 2-byte items as
#: 32-bit words (the same blocks, the re-tiling 4 bytes wide: half the work
#: an item), 31.0 to 33.1.  A Fortran-ordered shard's OWN flat runs are
#: single columns (5 MB at 2,500,000 rows), and a buffer costs the runtime
#: 0.19 to 0.21 ms whatever its size, one thread after the other: 1,000
#: runs a shard in groups of 16 land at 21.5 to 22.0 GB/s, under the
#: strided rows' 24.7 (as 2-D slabs of 16 columns the re-tiling is back:
#: 24.6 to 25.3), so the blocks stay blocks and a block is ONE buffer.
_STAGE_BLOCK_BYTES = 32 << 20
_STAGE_IN_FLIGHT = 16
#: ``StagedAhead``'s capacity form, whose blocks land under a running fit:
#: whatever small thing crosses the wire meanwhile, the fit's count and
#: losses on their way BACK among them, waits behind every block in flight
#: (16 x 32.8 MB: 40 ms of an idle chip wherever a fit ended while the
#: worker was issuing, PERF.md, PR 52), and MLlib's order publishes the
#: model before the next fit starts.  4 in flight keep the one wire as busy
#: (a pass of four micro-batches 0.968 to 0.974 s on eight seeds, where 16
#: read 1.02 to 1.07 by the draw of the sizes); 2 starve it (1.40 s).
_AHEAD_IN_FLIGHT = 4
_STAGE_ROWS = 1024


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stage_dest(shape, dtype):
    """The array ``_stage_dense``'s blocks are written into, on the default
    device (the caller sets it: a program with no operand runs there)."""
    with jax.named_scope("sgd.stage"):
        return jnp.zeros(shape, dtype)


@functools.partial(jax.jit, donate_argnums=0)
def _stage_block(dest, block, offset):
    """``dest`` (donated: written in place) with ``block``'s rows at rows
    ``offset:``, and a scalar that is ready when the write is.  ``block`` is
    the row block in the form it crossed in (``_wire_form``): the rows flat,
    one 1-D run; ``(rows / 2, d)`` 32-bit words that hold rows ``2k`` and
    ``2k + 1`` of a column in their low and high halves; or the ``(rows, d)``
    rows themselves.  The chip makes the rows of it here, in its own layout.
    The offset is an operand, so one program serves every full block and one
    more the remainder (a program a device under a mesh: it runs where
    ``dest`` and ``block`` lie)."""
    with jax.named_scope("sgd.stage"):
        if block.dtype != dest.dtype:  # words: the halves are the rows
            # a mask and a shift: ``bitcast_convert_type`` of the words had
            # the chip's compiler lay out a block-sized array of shift
            # counts that carries no scope (PERF.md, PR 49)
            block = jnp.stack([jax.lax.bitcast_convert_type(
                half.astype(jnp.uint16), dest.dtype)
                for half in (block & 0xFFFF, block >> 16)], axis=1)
        block = block.reshape((-1,) + dest.shape[1:])
        dest = jax.lax.dynamic_update_slice_in_dim(dest, block, offset,
                                                   axis=0)
        return dest, dest[offset, 0]


def _block_rows(X, n=None, capacity: int = 0) -> int:
    """The rows of one block of a dense numpy array's hand-off into a
    destination of ``n`` of its rows (all of them: one destination); 0 where
    a destination goes in one piece (no matrix, or one block holds it all).
    Into an array of ``capacity`` rows (``StagedAhead``'s capacity form):
    the most ``_STAGE_ROWS`` times a POWER OF TWO that the block's bytes
    hold (16,384 at d = 1000 in bf16 either way), so that the blocks divide
    every ``row_capacity`` above them; a capacity below them is one block."""
    n = X.shape[0] if n is None else n
    if X.ndim != 2 or not n:
        return 0
    row_bytes = X.nbytes // X.shape[0]
    units = max(1, _STAGE_BLOCK_BYTES // row_bytes // _STAGE_ROWS)
    if capacity:
        return min(_STAGE_ROWS << (units.bit_length() - 1), capacity)
    if n * row_bytes <= _STAGE_BLOCK_BYTES:
        return 0
    rows = _STAGE_ROWS * units
    return rows if rows < n else 0


def _wire_form(X):
    """``piece(a, b)``: rows ``a:b`` of the dense host array as they are
    handed to the runtime, a view in every case, in the form that leaves the
    runtime least to re-tile on the host's CPUs (PERF.md, PR 49; the chip
    does the rest in ``_stage_block``).  Read off the array's strides and
    item size alone:

    - C-ordered: the rows are ONE contiguous run, handed over flat (1-D);
      the runtime copies it and re-tiles nothing;
    - Fortran-ordered with 2-byte items: the contiguous runs are single
      columns (a shard's rows of one column: too many buffers to pay for
      one by one), so the block stays ``(rows, d)`` and its items go as
      32-bit WORDS, rows ``2k`` and ``2k + 1`` of a column in one: what the
      runtime re-tiles is then 4 bytes wide, half the work an item.  A block
      that starts or ends on an odd row goes as its rows;
    - anything else (a strided view, a wider array's rows, Fortran order at
      another item size) as its rows, strided: the runtime re-tiles them."""
    import numpy as np

    if X.flags.c_contiguous:
        return lambda a, b: X[a:b].reshape(-1)
    if (X.flags.f_contiguous and X.itemsize == 2 and not X.shape[0] % 2
            and not X.ctypes.data % 4):
        words = X.T.view(np.uint32).T  # (n / 2, d), strides (4, 2 n)
        return lambda a, b: X[a:b] if (a | b) % 2 else words[a // 2:b // 2]
    return lambda a, b: X[a:b]


def _stage_dense(X, h2d=NO_SPAN, mesh=None):
    """Dense features on the device as ONE ``(N, d)`` array, and what the
    ``train.h2d`` span says of the copy: ``(X, blocks, block_bytes)``.

    A device array comes back as it is (0 blocks), and so does a
    ``StagedAhead`` (its blocks went ahead of the fit and were folded into
    the totals it is trained from).  A numpy array of at most one block goes
    in one ``jnp.asarray``.  A larger one goes in row blocks (views: nothing
    is copied on the host), each in the form that leaves the runtime least
    to re-tile (``_wire_form``: flat, 32-bit words, or the strided rows),
    issued back to back so that the next blocks are made ready while one is
    on the wire; each is written into the destination in place by a program
    that makes the chip's layout of it (``_stage_block``) and deleted, and
    the host waits for the oldest write before it issues a block beyond
    ``_STAGE_IN_FLIGHT``, so the device holds the dataset plus the blocks in
    flight, never the dataset twice.  The values are ``jnp.asarray``'s bit
    for bit whatever the form, so the fit is the single copy's.

    Under ``mesh`` (1-D, over rows) a numpy array of any size has a
    destination a DEVICE: shard ``s`` holds rows ``[s n/S, (s + 1) n/S)`` of
    ``X`` in order, zero rows behind the last where the rows do not divide
    (``ceil(n / S)`` rows a shard), and the ``S`` destinations are handed
    back as the one array sharded by rows over ``mesh``: no program, no
    copy, laid out as ``shard_dataset`` returns it.  The same loop, run by a
    thread a device so that all of them receive at once: a block goes to
    the device that owns its rows, the blocks in flight counted a device; a
    shard that one block holds goes in one piece.  No device ever holds more
    than its shard and the blocks in flight to it.

    ``h2d`` is told ``shards``, the destinations the array went to (0 for a
    device array), and ``flat``, the blocks that crossed flat or as words (0
    where they went as strided rows, in one piece, or not at all).  Where it
    is ``live`` it is given the hand-off's stall
    counter too: ``stalls``, the times the host stood in that flow-control
    wait, and ``stall_ms``, how long in all, over the devices (0 and 0 for
    one piece or a device array).  An array that goes in blocks then says
    where its issuing threads' time went, each sum over the devices as
    ``stall_ms`` is, each on the issuing thread's own clock: ``put_ms`` (the
    block's ``jnp.asarray`` / ``jax.device_put``: the block's buffer
    and its hand-over to the runtime, whose threads copy it into the
    transfer's buffers, re-tiled where its form asks for it, and send it
    behind the call), ``write_ms`` (the dispatch of
    ``_stage_block`` and, once a device, of the destination's fill),
    ``free_ms`` (``block.delete()``) and ``own_ms`` (what is left of a
    thread's time in ``send``: the slice, the deque, the loop: the
    interpreter alone), so that ``put + write + free + own + stall`` IS the
    threads' time in ``send``.  ``free_ms`` and ``own_ms`` are calls of
    microseconds: a millisecond in them is a thread waiting to be let back
    into the interpreter.  Otherwise the loop reads no clock."""
    import numpy as np

    timed = h2d.live
    now = time.perf_counter if timed else _no_clock
    h2d.set(flat=0)  # until blocks have crossed flat or as words
    if timed:
        h2d.set(stalls=0, stall_ms=0.0)
    if not isinstance(X, np.ndarray):
        h2d.set(shards=0)
        return (X if isinstance(X, StagedAhead) else jnp.asarray(X)), 0, 0
    devices = [None] if mesh is None else list(mesh.devices.flat)
    h2d.set(shards=len(devices))
    n, local = X.shape[0], -(-X.shape[0] // len(devices))
    rows = _block_rows(X, local)
    if not rows:  # one block holds a destination's rows: one piece each
        if mesh is None:
            return jnp.asarray(X), 1, X.nbytes
        dests = [jax.device_put(_rows_padded(X, s * local, local), device)
                 for s, device in enumerate(devices)]
        return (_sharded_by_rows(mesh, dests), len(dests),
                dests[0].nbytes)

    piece_of = _wire_form(X)
    dtype = jax.dtypes.canonicalize_dtype(X.dtype)  # ``jnp.asarray``'s

    def send(s):
        """Destination ``s`` written from its rows of ``X``: the array, its
        blocks and how many of them went flat or as words, how often its
        flow control stood, and the thread's seconds in the wait, in the
        puts, in the writes' dispatch, in the deletes and beside them."""
        device, first = devices[s], s * local
        end = min(first + local, n)  # behind it the fill's zero rows
        dest, writes = None, collections.deque()
        blocks, flat, stalls = 0, 0, 0
        stall_s = put_s = write_s = free_s = 0.0
        entered = now()
        for lo in range(first, end, rows):
            if len(writes) == _STAGE_IN_FLIGHT:
                # flow control, not a fetch: bounds what a device holds
                t = now()
                writes.popleft().block_until_ready()
                if timed:
                    stall_s += now() - t
                    stalls += 1
            piece = piece_of(lo, min(lo + rows, end))
            flat += piece.ndim == 1 or piece.dtype != X.dtype
            t0 = now()
            block = (jnp.asarray(piece) if device is None
                     else jax.device_put(piece, device))
            t1 = now()
            if dest is None:
                with (contextlib.nullcontext() if device is None
                      else jax.default_device(device)):
                    dest = _stage_dest((local,) + X.shape[1:], dtype)
            dest, written = _stage_block(dest, block, lo - first)
            t2 = now()
            block.delete()
            if timed:
                put_s += t1 - t0
                write_s += t2 - t1
                free_s += now() - t2
            writes.append(written)
            blocks += 1
        own_s = now() - entered - stall_s - put_s - write_s - free_s
        return (dest, blocks, flat, stalls,
                (stall_s, put_s, write_s, free_s, own_s))

    if mesh is None:
        sent = [send(0)]
    else:
        # all the devices receive at once: one thread issues a block in
        # 1.6 ms, 1.4 wires' worth, and its pace differs by a tenth from one
        # process to the next
        with ThreadPoolExecutor(len(devices)) as pool:
            sent = list(pool.map(send, range(len(devices))))
    dests, blocks, flat, stalls, spent = zip(*sent)
    h2d.set(flat=int(sum(flat)))
    if timed:
        stall, put, write, free, own = (
            round(sum(part) * 1e3, 4) for part in zip(*spent))
        h2d.set(stalls=sum(stalls), stall_ms=stall, put_ms=put,
                write_ms=write, free_ms=free, own_ms=own)
    block_bytes = rows * (X.nbytes // n)
    if mesh is None:
        return dests[0], blocks[0], block_bytes
    # every shard holds rows of X: the zero rows are fewer than the shards,
    # and a shard in blocks has more rows than that
    return _sharded_by_rows(mesh, list(dests)), sum(blocks), block_bytes


def _no_clock() -> float:
    """``_stage_dense``'s clock where no span is live: no clock is read."""
    return 0.0


def _rows_padded(X, lo, m):
    """Rows ``lo:lo + m`` of the host array, zero rows behind them where it
    ends before (a piece of at most one block: ``pad_to_multiple``'s rows
    for one shard), in the array's own order (a Fortran-ordered array's
    rows are copied a column at a time, each one run)."""
    import numpy as np

    piece = X[lo:lo + m]
    if piece.shape[0] == m:
        return piece
    out = np.zeros((m,) + X.shape[1:], X.dtype,
                   order="F" if X.ndim > 1 and X.flags.f_contiguous else "C")
    out[:piece.shape[0]] = piece
    return out


def row_capacity(X, held: int = 0) -> int:
    """The rows of the device array that a stream's micro-batch ``X`` is
    trained in where its row count is an operand and not a shape
    (``StagedAhead``'s capacity form): ``_STAGE_ROWS`` times the power of
    two that holds its rows, and no less than ``held``, the capacity the
    stream has in hand.  A function of the SCALE of the sizes seen, never of
    the sizes: every program of the fit is keyed by it, so a stream of any
    number of distinct sizes within a factor of two compiles one set of
    programs, the same set in every process (2,097,152 rows for every
    micro-batch of 1,048,577 to 2,097,152)."""
    capacity = _STAGE_ROWS
    while capacity < X.shape[0]:
        capacity *= 2
    return max(capacity, held)


def _sharded_by_rows(mesh, dests):
    """The devices' ``(n / S, ...)`` arrays, in the mesh's order, as the one
    array sharded by rows over ``mesh``: the buffers as they lie."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_sgd.parallel.mesh import DATA_AXIS

    shape = (len(dests) * dests[0].shape[0],) + dests[0].shape[1:]
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P(DATA_AXIS)), dests)


@functools.partial(jax.jit, static_argnames="scope", donate_argnames="into",
                   keep_unused=True)
def _stage_join(*blocks, scope="sgd.stage", into=None):
    """``StagedAhead``'s row blocks as the one ``(N, d)`` array: one write a
    block and no fill (12.6 ms of the v5e's for 128 blocks of 32.8 MB).  The
    chip's compiler leaves the scope on the last of the writes alone, so the
    others read ``(unscoped)`` in a trace; written out as a chain of
    ``dynamic_update_slice`` into zeros they all keep it, and the chain
    starts with a fill of the whole array (6.7 ms more: PERF.md, PR 40).
    The capacity form joins under ``sgd.whole``, over as many blocks as the
    CAPACITY holds whatever the micro-batch's own count (the rest are one
    block of zeros, named again and again), so ONE program serves every
    size up to the capacity.  ``into`` is an array of the result's shape
    that is given up to hold it (donated: the result is written over it in
    place; nothing of it is read), so that a stream never frees one array
    of its capacity to find room for the next while blocks are landing
    beside them; None: the result is a new array."""
    with jax.named_scope(scope):
        return jnp.concatenate(blocks, axis=0)


class StagedAhead:
    """A dense host array on its way to the device AHEAD of the fit that
    will train it, under a fit that is running (``StreamingLinearAlgorithm
    .train_on``), in one of three forms; ``dtype`` and ``nbytes``
    are the host array's in all, ``rows`` its rows, ``count`` its row blocks
    (``_stage_dense``'s: ``_block_rows``), ``shape`` the shape the fit is
    planned for and keyed by: the host array's, but in the capacity form.

    The CAPACITY form (``capacity`` rows, ``y`` the labels that travel with
    the rows; PERF.md, PR 52): the rows form below for a fit that takes the
    row count as an OPERAND, so that no program is compiled for it.  The
    blocks are the array's own rows, the last one filled to a whole block on
    the host (``_rows_padded``: under a block of zero rows crosses the
    wire), and the labels cross in front of them, in pieces of 16 blocks'
    rows filled likewise.  ``whole()`` makes
    them ``X``, ``(capacity, d)``, and ``y``, ``(capacity,)``, in one
    program each (``_stage_join`` under ``sgd.whole``) that takes as many
    blocks as the capacity holds, the ones the micro-batch does not fill
    being ONE block of zeros made on the device: the rows past ``rows`` are
    zeros, and the fit is handed ``valid``, ``RowCount(rows)``, which
    crossed in front of them.
    ``issue=False`` holds the host array until ``whole()`` is called: a
    micro-batch that
    comes to its fit IN TURN (a stream's first, before any plan;
    ``train_on_batch``) has to be wrapped before ``run_warm`` plans, so that
    the plan is the capacity's, and is copied inside its fit's
    ``train.h2d`` like every in-turn copy: the same blocks, the same
    programs.

    The ROWS form (no ``y``).  The device runs one program at a time, so
    ``_stage_dense``'s in-place writes (the programs that also make the
    chip's layout of a block that crossed flat or as words) would queue
    behind the running ``sgd_run`` and its flow control stall the host after
    ``_STAGE_IN_FLIGHT`` blocks; here each row block is a device array of
    its own, handed over as its strided rows (the runtime re-tiles them on
    the host: one wire, which that keeps busy): transfers that need no
    device program and land while the chip computes.  ``whole()`` makes
    them the one ``(N, d)`` array once the chip
    is free, in ONE program (``_stage_join``: 13.9 ms of the host's time on
    the v5e, where the 128 in-place writes and their destination's fill take
    68: PERF.md, PR 40); the values are ``_stage_dense``'s bit for bit.  The
    device then holds the array and, until the program has run, its blocks:
    the batch twice for those milliseconds, which is why the caller drops
    the batch it trained first.

    The TOTALS form (``y`` given: a fit that runs from ``X^T X``, ``X^T y``,
    ``y^T y`` reads no row; PERF.md, PR 44).  The rows are never one array:
    each block's share is added to the running ``(G, b, yy)`` by a device
    program of its own (``ops.gram.stats_fold``: 0.70 ms of the v5e's for a
    block whose transfer takes 2.29) while the next blocks are on the wire,
    and the block is deleted.  ``totals`` is then the bundle the fit runs
    from (12 MB at d = 1000), ``y`` the labels on the device, ``folded`` the
    blocks folded; the device holds 0.55 GB in all where the rows form held
    a micro-batch twice over, 8.92 (PERF.md, PR 44).
    The host waits for the oldest block's fold before it issues a block
    beyond ``_STAGE_IN_FLIGHT``, counted in ``alive`` ACROSS the arrays that
    share it: what a stalled device can collect is those blocks (0.52 GB)
    and a bundle an array, whatever the stream does.  The same blocks, the
    same programs in the same order for every array of one shape, so the
    totals are the same bit for bit whichever thread issues them and
    whenever; ``fold`` makes them of a rows form's blocks where they lie."""

    def __init__(self, X, y=None, alive=None, capacity: int = 0,
                 issue: bool = True):
        rows = _block_rows(X) or X.shape[0]
        self.shape, self.dtype, self.nbytes = X.shape, X.dtype, X.nbytes
        self.blocks, self.totals, self.y, self.folded = None, None, None, 0
        self.rows, self.capacity, self.X = X.shape[0], capacity, None
        self.valid = None  # the capacity form's row count, on the device
        #: the capacity form's: ``behind(self, done)`` is called by the fit
        #: that trains it once its program is in the device's queue
        #: (``queued``)
        self.behind = None
        #: what crosses the wire of X (the capacity form: with its last
        #: block's fill)
        self._host, self.wire_bytes = None, X.nbytes
        if capacity:
            rows = _block_rows(X, capacity=capacity)
            self.shape = (capacity,) + X.shape[1:]
            self.count = -(-X.shape[0] // rows)
            self.wire_bytes = self.count * rows * (X.nbytes // X.shape[0])
            self._host = (X, y, rows)
            if issue:
                self._issue()
            return
        starts = range(0, X.shape[0], rows)
        self.count = len(starts)
        if y is not None:
            self._fold((jnp.asarray(X[a:a + rows]) for a in starts), y,
                       collections.deque() if alive is None else alive)
            return
        self.blocks = []
        for a in starts:
            if len(self.blocks) >= _STAGE_IN_FLIGHT:
                # flow control: what the runtime re-tiles at once
                self.blocks[-_STAGE_IN_FLIGHT].block_until_ready()
            self.blocks.append(jnp.asarray(X[a:a + rows]))

    def _issue(self):
        """The capacity form's blocks issued in order: the row count first
        (``valid``, the fit's operand: a scalar issued as the fit starts
        would wait behind every block in flight on the one wire, 40 ms of
        an idle chip a micro-batch: PERF.md, PR 52), then the labels, in
        pieces of ``_STAGE_IN_FLIGHT`` blocks' rows (a buffer costs the
        runtime the same whatever its size, and one a block beside the
        rows' own slowed a micro-batch's hand-off by a third on the chip:
        PERF.md, PR 52), then the rows.  The last block's fill is a copy of
        up to a block on the host (13 to 30 ms for strided 2-byte rows), made
        on a thread of its own while the blocks in front of it are issued."""
        import numpy as np

        (X, y, rows), self._host = self._host, None
        y = np.asarray(y)
        if not np.issubdtype(y.dtype, np.inexact):
            y = y.astype(np.float32)
        self.valid = RowCount(jnp.asarray(self.rows, jnp.int32))
        pieces = min(self.capacity, rows * _STAGE_IN_FLIGHT)
        self.labels = [jnp.asarray(_rows_padded(y, a, pieces))
                       for a in range(0, y.shape[0], pieces)]
        starts = range(0, X.shape[0], rows)
        with ThreadPoolExecutor(1) as pool:
            last = pool.submit(_rows_padded, X, starts[-1], rows)
            self.blocks = []
            for a in starts[:-1]:
                if len(self.blocks) >= _AHEAD_IN_FLIGHT:
                    # flow control: what the runtime re-tiles at once
                    self.blocks[-_AHEAD_IN_FLIGHT].block_until_ready()
                self.blocks.append(jnp.asarray(X[a:a + rows]))
            self.blocks.append(jnp.asarray(last.result()))

    def host(self):
        """``(X, y)`` of a capacity form that holds its host array still (a
        micro-batch wrapped for its fit in turn), for a fit whose OWN plan,
        made after the wrapping, chose another schedule than the stock one
        (a stream's first micro-batch under ``set_schedule`` of a schedule's
        name, or where the planner finds the rows too many to keep)."""
        if self._host is None:
            raise RuntimeError(
                "a micro-batch staged at a row capacity ahead of its fit "
                "can be trained by the stock resident schedule alone, and "
                "this optimizer's schedule was changed since it was staged")
        return self._host[:2]

    def lands_in(self, into) -> bool:
        """Whether ``whole(into)`` writes this one's rows over the array of
        ``into`` (a capacity form) in place and makes no array of its own: a
        capacity form that is not whole yet, and ``into`` holds an array of
        this one's shape and type."""
        return bool(
            self.capacity and self.X is None and into.X is not None
            and into.X.shape == self.shape
            and into.X.dtype == jax.dtypes.canonicalize_dtype(self.dtype))

    def queued(self, done):
        """The fit's word that the program which trains this capacity form
        is in the device's queue and the host has not begun to wait for it:
        what ``behind`` dispatches before ``done()`` holds (the fit's answer
        without a wait: whether the program has run) runs the moment the fit
        ends, under the host's turn-around
        (``StreamingLinearAlgorithm._fold_ahead`` queues the next
        micro-batch's join there).  Nothing where no one asked."""
        if self.behind is not None:
            self.behind(self, done)

    def whole(self, into=None):
        """The one device array; the blocks are given up.  The capacity
        form: itself, with ``X`` and ``y`` made (once).  ``into`` is a
        capacity form that has been trained, or one whose fit is RUNNING
        (``queued``): its ``X`` is given up before anything is made, and
        where it is of this one's shape and type (``lands_in``) this one's
        rows are written over it in place (``_stage_join``'s ``into``), so
        the stream's array of the capacity stays where it lies from one
        micro-batch to the next.  Under a running fit the device's queue
        orders the two: the join runs once every program dispatched before
        it has read the array, and the runtime holds the buffer until then
        (``tests/test_streaming.py`` pins that the fit's result is the one
        it has without the join behind it)."""
        spent = None
        if into is not None:
            over = self.lands_in(into)
            spent, into.X = into.X, None
            if not over:
                spent = None  # another capacity's: gone, not written over
        if self.capacity:
            if self.X is None:
                if self._host is not None:
                    self._issue()
                made = []
                for blocks, dest in ((self.blocks, spent),
                                     (self.labels, None)):
                    slots = self.capacity // blocks[0].shape[0]
                    if slots > 1:
                        fill = slots - len(blocks)
                        zeros = fill and _stage_dest(blocks[0].shape,
                                                     blocks[0].dtype)
                        made.append(_stage_join(*blocks, *[zeros] * fill,
                                                scope="sgd.whole",
                                                into=dest))
                        for block in blocks:
                            block.delete()
                    else:
                        made.append(blocks[0])
                self.blocks = self.labels = None
                self.X, self.y = made
            return self
        del spent
        blocks, self.blocks = self.blocks, None
        if len(blocks) == 1:
            return blocks[0]
        out = _stage_join(*blocks)
        for block in blocks:
            block.delete()
        return out

    def fold(self, y):
        """The rows form made the totals form: its blocks, already on their
        way, folded where they lie and given up."""
        blocks, self.blocks = self.blocks, None
        self._fold(iter(blocks), y, collections.deque())
        return self

    def _fold(self, blocks, y, alive):
        """``totals`` and ``y`` from ``blocks``, an iterator that ISSUES a
        block when it is asked for the next: asked only once fewer than
        ``_STAGE_IN_FLIGHT`` blocks are alive."""
        from tpu_sgd.ops.gram import stats_fold, totals_bundle

        y = jnp.asarray(y)
        if not jnp.issubdtype(y.dtype, jnp.inexact):
            y = y.astype(jnp.float32)
        totals, at = None, 0
        for block in blocks:
            totals, done = stats_fold(totals, y, at, block)
            at, dtype = at + block.shape[0], block.dtype
            block.delete()
            alive.append(done)
            if len(alive) >= _STAGE_IN_FLIGHT:
                # flow control, not a fetch: bounds what a device holds
                alive.popleft().block_until_ready()
        self.y, self.folded = y, self.count
        self.totals = totals_bundle(totals, self.shape, dtype)


def _sample_key(key, i, axis_name, shard_index=None):
    """THE per-iteration (and per-shard, like Spark's per-partition
    sampler) sample-key recipe, deterministic in (seed, iteration, shard
    index).  One definition shared by the Bernoulli mask and the
    indexed/sliced streams so an edit to the fold order cannot silently
    desync them.

    ``shard_index`` is the OUT-OF-MESH spelling of the shard fold: a
    replica worker (``tpu_sgd/replica``) runs its shard's local sums as
    a standalone program — no ``shard_map``, so no ``axis_index`` — and
    folds its static shard index exactly where the meshed program folds
    the axis index, which is what makes the τ=0 replica trajectory
    bitwise-equal to the synchronous data-parallel path (the fold order
    is identical, so the per-shard sample keys are identical bits)."""
    k = jax.random.fold_in(key, i)
    if axis_name is not None:
        k = jax.random.fold_in(k, jax.lax.axis_index(axis_name))
    elif shard_index is not None:
        k = jax.random.fold_in(k, shard_index)
    return k


def _make_mask(gradient, cfg: SGDConfig, key, i, X, y, weights, valid,
               axis_name, model_axis_name, shard_index=None):
    """Per-iteration Bernoulli mini-batch mask (None = take everything):
    the array, or where the gradient's kernel draws its rows itself
    (``step_sums``' ``mask_in_kernel``) the ``RowDraw`` that stands for the same
    array: the same rows, every step, every shard."""
    if cfg.mini_batch_fraction >= 1.0:
        return valid
    with jax.named_scope("sgd.sample"):
        k = _sample_key(key, i, axis_name, shard_index)
    draw = RowDraw(k, valid, cfg.mini_batch_fraction)
    if step_sums(gradient, cfg, X, y, weights, valid,
                 model_axis_name).mask_in_kernel:
        return draw
    return draw.mask(X.shape[0])


def prepare_rows(gradient, cfg: SGDConfig, X, y, weights, valid=None,
                 model_axis_name=None):
    """The step kernel's loop-invariant row operands, laid out once a fit
    (``Gradient.row_operands`` of what ``step_sums`` says every step hands
    on as it stands) under the scope ``sgd.prepare``; None where the step
    takes ``y`` as it is.  For the caller of a loop over ``make_step``'s
    step to call in FRONT of the loop and hand every step as ``rows``:
    inside it the compiler may leave the relayout where the source put it,
    every iteration (PERF.md, PR 33)."""
    plan = step_sums(gradient, cfg, X, y, weights, valid, model_axis_name)
    if plan.kernel is None:
        return None
    with jax.named_scope("sgd.prepare"):
        return gradient.row_operands(X, y, weights, plan.mask,
                                     model_axis_name, plan.window)


def _make_local_sums(gradient, cfg, key, axis_name, model_axis_name,
                     shard_index=None):
    """THE per-iteration LOCAL ``(grad_sum, loss_sum, count)`` recipe —
    sampling (bernoulli / indexed / sliced) + the fused batch sums,
    pre-psum.  One definition shared by :func:`make_step` (dense
    all-reduce), :func:`make_compressed_step` (top-k + error-feedback
    all-reduce), and the async replica workers
    (``tpu_sgd/replica/worker.py``, via ``shard_index`` — see
    :func:`_sample_key`) so the sampled sequence can never drift between
    the wires."""
    indexed = cfg.sampling == "indexed" and cfg.mini_batch_fraction < 1.0
    sliced = cfg.sampling == "sliced" and cfg.mini_batch_fraction < 1.0

    def local_sums(weights, X, y, i, valid, rows=None):
        # ``rows`` (prepare_rows) goes on only where a caller made it, so a
        # gradient that prepares none is called as it always was
        made = {} if rows is None else {"rows": rows}
        if sliced or indexed:
            m = window_rows(cfg, X.shape[0])
        if sliced:
            # HBM-optimal path: a contiguous row window at a random offset,
            # read in place instead of a random gather: once, by the
            # one-read kernel at the window's block offset, where
            # ``window_sums`` selects it (a TPU, X stored feature-major);
            # twice, by two matvecs, elsewhere.  Assumes exchangeable row
            # order (see SGDConfig.sampling docs).
            with jax.named_scope("sgd.sample"):
                k = _sample_key(key, i, axis_name, shard_index)
                start = jax.random.randint(
                    k, (), 0, max(1, X.shape[0] - m + 1))
            return gradient.window_sums(
                X, y, weights, start, m, valid=valid,
                margin_axis_name=model_axis_name, **made,
            )
        if indexed:
            # a fixed-size batch gathered with replacement.  On a resident
            # X slower than the masked scan at both layouts (all of X
            # copied first at d = 1000; 7.709 against 5.831 ms by rows:
            # ``SGDConfig.sampling``)
            with jax.named_scope("sgd.sample"):
                k = _sample_key(key, i, axis_name, shard_index)
                idx = jax.random.randint(k, (m,), 0, X.shape[0])
                Xb, yb = X[idx], y[idx]
                mask = None if valid is None else valid[idx]
        else:
            Xb, yb = X, y
            mask = _make_mask(gradient, cfg, key, i, X, y, weights, valid,
                              axis_name, model_axis_name, shard_index)
        return gradient.batch_sums(
            Xb, yb, weights, mask, margin_axis_name=model_axis_name, **made
        )

    return local_sums


#: what JAX raises where a traced value is asked for a concrete one
_NEEDS_CONCRETE = (jax.errors.ConcretizationTypeError,
                   jax.errors.TracerArrayConversionError,
                   jax.errors.TracerIntegerConversionError)


def _update(updater, weights, gradient, step_size, i, reg_param):
    """``updater.compute`` as every compiled program calls it: with
    ``step_size`` and ``reg_param`` the program's operands, traced scalars.
    An updater that needs either as a concrete number (``float(...)``, an
    ``if`` on it, a numpy call) fails here, at trace, by the contract's
    name (``ops/updaters.py``), never by training at a stale value."""
    try:
        return updater.compute(weights, gradient, step_size, i, reg_param)
    except _NEEDS_CONCRETE as e:
        raise TypeError(
            f"{type(updater).__name__}.compute asked a traced value for a "
            "concrete one.  The Updater contract (tpu_sgd/ops/updaters.py): "
            "step_size and reg_param, like iter_num, may be TRACED scalars "
            "-- they are operands of the compiled program, so that one "
            "program serves every step size and regulariser -- and compute "
            "must stay jax.numpy arithmetic on them (jnp.where, not `if`; "
            "no float(), no numpy).") from e


def make_step(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    axis_name: Optional[str] = None,
    model_axis_name: Optional[str] = None,
):
    """Build one SGD iteration as a pure function.

    ``step(weights, X, y, i, reg_val, hyper, valid) ->
    (new_weights, loss_i, new_reg_val, count)`` — the unit the streaming mode
    and the fused driver both build on.  ``loss_i`` already includes the
    previous iteration's ``reg_val`` per the reference's loss-history contract.
    ``hyper`` is the step size and the regulariser (``config.Hyper``, two
    scalars): OPERANDS of the step and of every program built from it, never
    read from ``config`` here, so one compiled program serves every pair
    (``SGDConfig`` says which fields are the program's structure).  This is
    the step's one signature.  An eighth argument, ``rows``, is what
    :func:`prepare_rows` made of the same ``X``, ``y`` and ``valid`` in front
    of the caller's loop (None: the step lays its kernel's row operands out
    itself, every call).

    ``axis_name`` shards the example axis (data parallelism — the reference's
    only strategy); ``model_axis_name`` additionally shards the FEATURE axis
    (the optional wide-weights hook, SURVEY.md §2 ledger TP row): each core
    holds a block of ``w`` and the matching column block of ``X``, partial
    margins are all-reduced over the model axis, and the updater runs on the
    local block with its scalar reg value all-reduced.
    """
    cfg = config
    key = jax.random.PRNGKey(cfg.seed)
    local_sums = _make_local_sums(gradient, cfg, key, axis_name,
                                  model_axis_name)

    def step(weights, X, y, i, reg_val, hyper, valid=None, rows=None):
        g, l, c = local_sums(weights, X, y, i, valid, rows)
        if axis_name is not None:
            with jax.named_scope("sgd.allreduce"):
                g, l, c = jax.lax.psum((g, l, c), axis_name)
        with jax.named_scope("sgd.update"):
            has_batch = c > 0
            safe_c = jnp.maximum(c, 1.0)
            loss_i = l / safe_c + reg_val
            new_w, new_reg = _update(
                updater, weights, g / safe_c, hyper.step_size, i,
                hyper.reg_param)
            if model_axis_name is not None:
                # reg value is a sum over features -> combine the local
                # blocks
                new_reg = jax.lax.psum(new_reg, model_axis_name)
            # Reference behavior on an empty sampled batch: warn, skip the
            # update.
            new_w = jnp.where(has_batch, new_w, weights)
            new_reg = jnp.where(has_batch, new_reg, reg_val)
        return new_w, loss_i, new_reg, c

    return step


def make_run(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    axis_name: Optional[str] = None,
    model_axis_name: Optional[str] = None,
):
    """Build the full optimization loop as one traceable function.

    ``run(initial_weights, X, y, hyper, valid) -> (weights, loss_history,
    n_recorded)`` where ``loss_history`` has static length
    ``config.num_iterations`` padded with NaN beyond ``n_recorded`` (the
    while_loop may exit early on the convergence tolerance) and ``hyper`` is
    :func:`make_step`'s (the step size and the regulariser, operands).  Runs
    unchanged inside ``shard_map`` when ``axis_name`` (and optionally
    ``model_axis_name``) is given.
    """
    cfg = config
    check_conv = cfg.convergence_tol > 0.0
    step = make_step(gradient, updater, cfg, axis_name, model_axis_name)

    def _global_norms(new_w, w):
        diff_sq = jnp.sum((new_w - w) ** 2)
        w_sq = jnp.sum(new_w**2)
        if model_axis_name is not None:
            diff_sq, w_sq = jax.lax.psum((diff_sq, w_sq), model_axis_name)
        return jnp.sqrt(diff_sq), jnp.sqrt(w_sq)

    # ``sgd_run``, not ``run``: the jitted function's name is part of the
    # persistent compile cache's key and the scopes above are not (JAX
    # leaves metadata out of it), so under the old name a cache warmed
    # before the scopes existed hands back an executable without them —
    # and the profiler names operations from the executable it runs.
    def sgd_run(initial_weights, X, y, hyper, valid=None):
        w0 = initial_weights
        # Initial regVal from a zero-gradient probe update, exactly as the
        # reference initializes it before the loop (SURVEY.md §5.5).
        _, reg_val0 = _update(
            updater, w0, jnp.zeros_like(w0), 0.0, jnp.asarray(1, jnp.int32),
            hyper.reg_param)
        if model_axis_name is not None:
            # the reg value sums over FEATURES, and each model shard holds
            # only its block of w0 — combine like make_step's new_reg, or
            # a warm-started 2-D run records a block-local iteration-1 loss
            reg_val0 = jax.lax.psum(reg_val0, model_axis_name)
        losses0 = jnp.full((cfg.num_iterations,), jnp.nan, jnp.float32)
        # a row count in ``valid``'s place stays one where the step's kernel
        # bounds its grid by it, and is made its mask here, once, elsewhere
        valid = rows_valid(gradient, cfg, X, y, w0, valid, model_axis_name)
        # once a fit: what the step's kernel reads of the labels (and of a
        # padded shard's ``valid``) in the layout it reads them in, so that
        # the loop's body holds the kernel and no relayout of an operand
        # that never changes (tests/test_chip_compile.py pins the body)
        rows = prepare_rows(gradient, cfg, X, y, w0, valid, model_axis_name)

        def cond(carry):
            i, _, _, _, _, converged = carry
            with jax.named_scope("sgd.converge"):
                return (i <= cfg.num_iterations) & jnp.logical_not(converged)

        def body(carry):
            i, w, reg_val, losses, n_rec, _ = carry
            new_w, loss_i, new_reg, c = step(w, X, y, i, reg_val, hyper,
                                             valid, rows)
            has_batch = c > 0
            losses = jnp.where(
                has_batch, losses.at[n_rec].set(loss_i.astype(jnp.float32)), losses
            )
            n_rec = n_rec + has_batch.astype(n_rec.dtype)
            if check_conv:
                with jax.named_scope("sgd.converge"):
                    diff, w_norm = _global_norms(new_w, w)
                    conv = (
                        has_batch
                        & (i > 1)
                        & (diff
                           < cfg.convergence_tol * jnp.maximum(w_norm, 1.0))
                    )
            else:
                conv = jnp.asarray(False)
            return (i + 1, new_w, new_reg, losses, n_rec, conv)

        carry = (
            jnp.asarray(1, jnp.int32),
            w0,
            reg_val0,
            losses0,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(False),
        )
        _, w, _, losses, n_rec, _ = jax.lax.while_loop(cond, body, carry)
        return w, losses, n_rec

    return sgd_run


def pack_step_ys(prev_w, new_w, loss_i, new_rv, count, f32: bool = False):
    """THE per-step scan-ys tuple every fused driver emits — ``(new_w,
    loss, reg_val, count, ||w_t - w_{t-1}||, ||w_t||)``, exactly what
    :func:`_replay_fused_steps` consumes.  One definition shared by the
    four scan bodies (:func:`make_superstep`,
    :func:`make_shared_batch_superstep`,
    :func:`make_resident_window_superstep`, and the resident driver's
    while-loop body) so the norms-ride-the-ys convergence contract
    cannot drift between drivers.  ``f32`` casts the scalar leaves for
    the resident ring buffer's fixed-dtype carry."""
    dn = jnp.linalg.norm(new_w - prev_w)
    wn = jnp.linalg.norm(new_w)
    if f32:
        f = jnp.float32
        return (new_w, loss_i.astype(f), new_rv.astype(f),
                count.astype(f), dn.astype(f), wn.astype(f))
    return (new_w, loss_i, new_rv, count, dn, wn)


#: fused ``(||w_t - w_{t-1}||, ||w_t||)`` for the OBSERVED stepwise
#: drivers (this module's K=1 loop and the host-streamed loop in
#: ``optimize/streamed.py``): one compiled program and ONE host fetch
#: where the eager spelling paid three one-op dispatches and two
#: separate device->host syncs per iteration (graftlint host-sync
#: finding; bitwise-equal to the eager norms on CPU — the reduce
#: lowers identically fused or not)
step_norms = jax.jit(lambda new_w, w: jnp.stack(
    (jnp.linalg.norm(new_w - w), jnp.linalg.norm(new_w))))


def observe_step(
    i, prev_w, new_w, loss_i, new_reg, count, losses, reg_val, cfg, *,
    listener=None, wall_dt=0.0, check_numerics=False,
    save_cb=None, save_every=0,
):
    """One OBSERVED iteration's host bookkeeping — THE single definition
    of the per-step record/convergence/checkpoint recipe the stepwise
    drivers share (the fused twin is :func:`_replay_fused_steps`, which
    replays the same recipe from scan ys).

    Consumers: the dense host-streamed K=1 loop
    (``optimize/streamed.py``), the sparse host-streamed K=1 loop
    (``optimize/streamed_sparse.py``), and the async replica parameter
    store's push-apply (``tpu_sgd/replica/store.py``) — extracted after
    the PR 9 review flagged the first two as duplicated and the replica
    driver would have made a third copy.

    Takes the step's DEVICE results plus the host-side running state;
    fetches each scalar exactly once (the observed-driver contract: the
    per-iteration host hop IS the bookkeeping), appends to ``losses``
    in place, and fires ``save_cb(i, w_np, reg_val)`` on the legacy
    cadence (``i % save_every == 0``, on convergence, and at the final
    iteration).  An empty sampled batch (``count == 0``) records
    nothing and returns ``prev_w`` unchanged, exactly as the loops it
    replaced did.

    Returns ``(w, reg_val, converged)`` — ``w`` is ``new_w`` when the
    step recorded, else ``prev_w``.
    """
    import numpy as np

    from tpu_sgd.utils.events import IterationEvent

    c_host = int(count)  # count gates the whole bookkeeping branch (fetched ONCE)
    converged = False
    if c_host <= 0:
        return prev_w, reg_val, converged
    loss_f = float(loss_i)  # per-iteration loss history is the contract
    if check_numerics and not np.isfinite(loss_f):
        _raise_if_nonfinite([loss_f], first_iteration=i)
    losses.append(loss_f)
    reg_val = float(new_reg)  # feeds the next step's host-side argument
    # ONE fused program + ONE fetch for both norms (the host-sync
    # finding the PR 7 sweep fixed; step_norms is the shared program)
    delta, w_norm = (
        float(v)
        for v in np.asarray(step_norms(new_w, prev_w))
    )
    # the live loss/variance series (obs.timeseries): these are the
    # host floats the bookkeeping already fetched — the near-free
    # AdaBatch sensor, ZERO added syncs; disabled = one global load
    observe_scalar("train.loss", loss_f)
    observe_scalar("train.weight_delta", delta)
    if listener is not None:
        listener.on_iteration(IterationEvent(
            iteration=i,
            loss=loss_f,
            weight_delta_norm=delta,
            mini_batch_size=c_host,
            wall_time_s=wall_dt,
        ))
    if cfg.convergence_tol > 0 and i > 1:
        converged = delta < cfg.convergence_tol * max(w_norm, 1.0)
    if save_cb is not None and (
            (save_every and i % save_every == 0)
            or converged or i == cfg.num_iterations):
        save_cb(i, np.asarray(new_w), reg_val)
    return new_w, reg_val, converged


def observed_loop_tail(
    i, w, new_w, loss_i, new_reg, count, losses, reg_val, cfg, *,
    listener=None, wall_dt=0.0, save_cb=None, save_every=0,
    stop_signal=None,
):
    """One observed iteration's ENTIRE host tail: the shared
    :func:`observe_step` bookkeeping plus the cooperative-preemption
    check (persist the CURRENT iteration through ``save_cb``, then
    unwind :class:`~tpu_sgd.reliability.supervisor.TrainingPreempted`).

    This is the K=1 observed-loop duplication the PR 9 review flagged
    between ``optimize/streamed.py`` and ``optimize/streamed_sparse.py``
    — the same statements, now with one home next to ``observe_step``
    (both drivers' bitwise pins stay green: extraction moved code, not
    math).  The caller owns the per-step barrier and the wall-clock
    timing (they live inside its ``train.step`` span)."""
    import numpy as np

    w, reg_val, converged = observe_step(
        i, w, new_w, loss_i, new_reg, count, losses, reg_val, cfg,
        listener=listener, wall_dt=wall_dt,
        save_cb=save_cb, save_every=save_every,
    )
    if not converged and stop_signal is not None and stop_signal():
        # cooperative preemption (TrainingSupervisor): persist the
        # CURRENT iteration — not just the last cadence save — then
        # unwind cleanly; the save is atomic, so a SIGKILL racing this
        # still leaves the previous checkpoint intact
        from tpu_sgd.reliability.supervisor import TrainingPreempted

        if save_cb is not None:
            save_cb(i, np.asarray(w), reg_val)
        raise TrainingPreempted(i)
    return w, reg_val, converged


def make_superstep(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    axis_name: Optional[str] = None,
    model_axis_name: Optional[str] = None,
):
    """Fuse K consecutive SGD iterations over PER-STEP batches into ONE
    compiled program (``lax.scan`` over the superchunk's leading axis).

    ``superstep(weights, reg_val, hyper, i0, Xs, ys, valids) ->
    (carry_weights, ys_out)`` (``hyper``: :func:`make_step`'s operands,
    here as in every superstep behind ``reg_val``):
    ``Xs``/``ys``/``valids`` stack K
    per-iteration batches on axis 0 — the host-assembled *superchunk*
    (``tpu_sgd.io.stack_superchunk``) that replaces K ``device_put`` +
    dispatch round-trips with one of each.  The scan body is EXACTLY
    ``make_step``: iteration ``i0 + t`` consumes batch ``t`` with the
    same per-step math and the same deterministic sample sequence as
    the per-iteration loop.  ``ys_out`` is the per-step ``(weights,
    loss, reg_val, count, delta_norm, weight_norm)`` history:
    everything the host loop used to read back one iteration at a time
    (loss history, convergence norms, checkpoint state) now arrives as
    one stacked fetch.

    Trajectory contract (measured, tests/test_superstep.py): everything
    SAME-PROGRAM is bitwise — a fused run replayed, resumed from a
    checkpoint, or fed through a different prefetch depth reproduces
    its weights exactly.  Against the per-iteration loop the math is
    identical but XLA lowers the batch dot through a different emitter
    inside a scanned program than as a standalone dispatch (measured 1
    ulp/step on the CPU harness — even a scan over a ``(1, m, d)``
    superchunk differs from the unscanned program), so fused-vs-legacy
    trajectories agree to reassociation noise, with the loss-history
    LENGTH, sampled sequence, and detected convergence iteration
    exactly equal — the same cross-program caveat
    ``optimize/streamed.py`` documents for the partial-residency
    ``resident_step``.

    The device program never branches on convergence or run length: a
    tail superstep (K ∤ remaining iterations) rides all-False
    ``valids`` rows, which ``make_step``'s empty-batch rule turns into
    no-op updates, and the host truncates overshoot from the ys
    (:func:`_replay_fused_steps`).  One shape -> exactly one fused-body
    program per build (``assert_compile_count``-guarded in
    tests/test_superstep.py).
    """
    step = make_step(gradient, updater, config, axis_name, model_axis_name)

    def superstep(weights, reg_val, hyper, i0, Xs, ys, valids):
        idx = i0 + jnp.arange(Xs.shape[0], dtype=jnp.int32)

        def body(carry, xs):
            w, rv = carry
            i, Xb, yb, vb = xs
            new_w, loss_i, new_rv, c = step(w, Xb, yb, i, rv, hyper, vb)
            # per-step norms ride the ys so the host-side convergence
            # check stays EXACTLY the legacy per-iteration rule
            return (new_w, new_rv), pack_step_ys(w, new_w, loss_i,
                                                 new_rv, c)

        (w, _), out = jax.lax.scan(body, (weights, reg_val),
                                   (idx, Xs, ys, valids))
        return w, out

    return superstep


def make_shared_batch_superstep(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    k: int,
    axis_name: Optional[str] = None,
    model_axis_name: Optional[str] = None,
):
    """The shared-batch variant of :func:`make_superstep`: K fused
    iterations over ONE ``(X, y)`` — the resident/stepwise driver
    (per-iteration sampling happens inside ``make_step``, on device)
    and the streamed full-batch feed (every iteration's "sample" IS the
    whole transferred batch, so it moves once and the scan reuses it).

    Same return contract and the same one-program guarantee as
    :func:`make_superstep`.  Steps past ``num_iterations`` in a tail
    superstep run real updates here (there is no per-step valids row to
    blank them); the caller discards the carry and takes the true last
    iteration's weights from the ys — ≤ K-1 wasted updates once per
    run.
    """
    step = make_step(gradient, updater, config, axis_name, model_axis_name)
    K = int(k)

    def superstep(weights, reg_val, hyper, i0, X, y, valid=None):
        idx = i0 + jnp.arange(K, dtype=jnp.int32)

        def body(carry, i):
            w, rv = carry
            new_w, loss_i, new_rv, c = step(w, X, y, i, rv, hyper, valid)
            return (new_w, new_rv), pack_step_ys(w, new_w, loss_i,
                                                 new_rv, c)

        (w, _), out = jax.lax.scan(body, (weights, reg_val), idx)
        return w, out

    return superstep


def make_resident_window_superstep(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    window_rows: int,
):
    """The partial-residency variant of :func:`make_superstep`: each
    fused step's window comes EITHER from the device-resident slab
    (sliced on device at a host-drawn start — zero transfer) OR from
    the transferred superchunk batch, selected per step by a flag the
    host packs alongside the superchunk.

    ``superstep(weights, reg_val, hyper, i0, Xres, yres, starts, flags,
    Xs, ys, valids) -> (carry_weights, ys_out)`` with the same ys contract
    as :func:`make_superstep`.  ``starts``/``flags`` are ``(K,)``
    per-step window starts and residency flags; resident steps ride
    zero rows in ``Xs`` (the fixed superchunk shape is the price of
    one compiled program — fusing trades those windows' transfer-byte
    savings for the K-fold dispatch cut, which wins wherever the
    per-dispatch tax dominates; the fully-resident
    slab feed avoids even that via the resident driver).  Both window
    sources feed bit-identical rows through the SAME scan body, so
    same-program contracts stay bitwise across mixed
    resident/transferred windows — this is what lifts the old
    "superstep fusion applies ... without partial residency" warning.
    """
    step = make_step(gradient, updater, config)
    m = int(window_rows)

    def superstep(weights, reg_val, hyper, i0, Xres, yres, starts, flags,
                  Xs, ys, valids):
        idx = i0 + jnp.arange(Xs.shape[0], dtype=jnp.int32)

        def body(carry, xs):
            w, rv = carry
            i, s0, res, Xb, yb, vb = xs
            Xw, yw = jax.lax.cond(
                res,
                lambda: (jax.lax.dynamic_slice_in_dim(Xres, s0, m, 0),
                         jax.lax.dynamic_slice_in_dim(yres, s0, m, 0)),
                lambda: (Xb, yb))
            new_w, loss_i, new_rv, c = step(w, Xw, yw, i, rv, hyper, vb)
            return (new_w, new_rv), pack_step_ys(w, new_w, loss_i,
                                                 new_rv, c)

        (w, _), out = jax.lax.scan(body, (weights, reg_val),
                                   (idx, starts, flags, Xs, ys, valids))
        return w, out

    return superstep


def make_compressed_step(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    axis_name: Optional[str] = None,
):
    """One SGD iteration over the COMPRESSED gradient wire: top-k +
    error feedback (``wire_compress="topk:<frac>"``; README "Compressed
    wire", SparCML arXiv:1802.08021).

    ``step(weights, ef, X, y, i, reg_val, hyper, valid) -> (new_w, new_ef,
    loss_i, new_reg_val, count)`` (``hyper``: :func:`make_step`'s
    operands, in the same place).  Sampling and the local batch sums
    are EXACTLY :func:`make_step`'s (one shared ``_make_local_sums``);
    what changes is the combine: each shard folds its normalized
    gradient contribution into a persistent per-shard error-feedback
    accumulator, extracts the top-k ``(values, indices)`` segment with
    ``jax.lax.top_k`` (``k`` is STATIC — shape-stable inside the traced
    program; the host-numpy-top-k rule is for HOST wires), and only
    those segments cross the link (``lax.all_gather`` of ``2·k``
    entries per shard instead of a dense ``(d,)`` psum) before a
    scatter-add rebuilds the applied update on every shard.  The
    dropped mass stays in ``ef`` and ships on later iterations — the
    EF-SGD update rule, convergent at matched final loss where plain
    top-k is not.

    ``ef`` is OPTIMIZER STATE (ADVICE.md "Error feedback is optimizer
    state, not a transport detail"): the caller carries it across
    iterations (the superstep scan carries it in
    :func:`make_compressed_superstep`), checkpoints it
    (``CheckpointManager.save(extras={"ef": ...})``), and restores it
    on resume — a compressed run resumed mid-stream is bitwise equal
    to its uninterrupted twin only if the accumulator travels too.
    Loss and count still combine densely (two scalars); an empty
    sampled batch leaves weights AND accumulator untouched (the
    reference's skip-the-update rule — extracted mass must not vanish
    on a skipped step).  Single-device (``axis_name=None``) the same
    rule applies without the gather: the update is the top-k of the
    accumulated gradient — the sparsified-update twin used for
    matched-loss A/B runs.
    """
    from tpu_sgd.io.sparse_wire import topk_nnz

    cfg = config
    key = jax.random.PRNGKey(cfg.seed)
    frac = float(topk_frac)
    local_sums = _make_local_sums(gradient, cfg, key, axis_name, None)

    def step(weights, ef, X, y, i, reg_val, hyper, valid=None):
        g, l, c = local_sums(weights, X, y, i, valid)
        if axis_name is not None:
            l, c = jax.lax.psum((l, c), axis_name)
        has_batch = c > 0
        safe_c = jnp.maximum(c, 1.0)
        loss_i = l / safe_c + reg_val
        dim = g.shape[-1]
        k = topk_nnz(dim, frac)  # static at trace time: one program
        acc = ef + (g / safe_c).astype(ef.dtype)
        _, idx = jax.lax.top_k(jnp.abs(acc), k)
        vals = jnp.take(acc, idx)
        new_ef = acc.at[idx].set(0.0)
        if axis_name is not None:
            # the compressed all-reduce: (values, indices) segments ride
            # the link, each shard scatter-adds every shard's segment
            vals_all = jax.lax.all_gather(vals, axis_name)
            idx_all = jax.lax.all_gather(idx, axis_name)
            ghat = jnp.zeros((dim,), acc.dtype).at[
                idx_all.reshape(-1)].add(vals_all.reshape(-1))
        else:
            ghat = jnp.zeros((dim,), acc.dtype).at[idx].add(vals)
        new_w, new_reg = _update(
            updater, weights, ghat.astype(weights.dtype), hyper.step_size,
            i, hyper.reg_param)
        # empty sampled batch: skip the update AND keep the accumulator
        # (the extracted mass must not vanish on a skipped step)
        new_w = jnp.where(has_batch, new_w, weights)
        new_reg = jnp.where(has_batch, new_reg, reg_val)
        new_ef = jnp.where(has_batch, new_ef, ef)
        return new_w, new_ef, loss_i, new_reg, c

    return step


def make_compressed_superstep(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    axis_name: Optional[str] = None,
):
    """:func:`make_superstep` over the compressed wire: the
    error-feedback accumulator rides the scan CARRY (state, like the
    weights) and the per-step post-update accumulators ride the ys as a
    seventh leaf — checkpoints taken mid-superstep need iteration-exact
    EF state just as they need iteration-exact weights.

    ``superstep(weights, ef, reg_val, hyper, i0, Xs, ys, valids) ->
    (carry_weights, carry_ef, ys_out)`` with ``ys_out = (*pack_step_ys,
    efs)``.  Same one-program / tail-padding contract as
    :func:`make_superstep` (a padded no-op step passes ``ef`` through
    unchanged)."""
    step = make_compressed_step(gradient, updater, config, topk_frac,
                                axis_name)

    def superstep(weights, ef, reg_val, hyper, i0, Xs, ys, valids):
        idx = i0 + jnp.arange(Xs.shape[0], dtype=jnp.int32)

        def body(carry, xs):
            w, e, rv = carry
            i, Xb, yb, vb = xs
            new_w, new_e, loss_i, new_rv, c = step(w, e, Xb, yb, i, rv,
                                                   hyper, vb)
            return (new_w, new_e, new_rv), pack_step_ys(
                w, new_w, loss_i, new_rv, c) + (new_e,)

        (w, e, _), out = jax.lax.scan(body, (weights, ef, reg_val),
                                      (idx, Xs, ys, valids))
        return w, e, out

    return superstep


def make_compressed_shared_superstep(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    topk_frac: float,
    k: int,
    axis_name: Optional[str] = None,
):
    """The shared-batch variant of :func:`make_compressed_superstep`
    (one transferred ``(X, y)``, K fused compressed steps; same
    overshoot-truncation contract as
    :func:`make_shared_batch_superstep`)."""
    step = make_compressed_step(gradient, updater, config, topk_frac,
                                axis_name)
    K = int(k)

    def superstep(weights, ef, reg_val, hyper, i0, X, y, valid=None):
        idx = i0 + jnp.arange(K, dtype=jnp.int32)

        def body(carry, i):
            w, e, rv = carry
            new_w, new_e, loss_i, new_rv, c = step(w, e, X, y, i, rv,
                                                   hyper, valid)
            return (new_w, new_e, new_rv), pack_step_ys(
                w, new_w, loss_i, new_rv, c) + (new_e,)

        (w, e, _), out = jax.lax.scan(body, (weights, ef, reg_val), idx)
        return w, e, out

    return superstep


def _replay_fused_steps(
    ys_host, i0, steps, losses, reg_val, cfg, *,
    listener=None, wall_dt=0.0, check_numerics=False,
    save_cb=None, save_every=0,
):
    """Replay one superstep's scan ys with EXACTLY the per-iteration
    loop's host bookkeeping — THE one definition of fused-mode
    loss-history / convergence / checkpoint semantics, shared by the
    host-streamed and stepwise drivers so they cannot drift.

    ``ys_host`` is the numpy-fetched per-step ``(weights, loss, reg,
    count, delta_norm, weight_norm)`` stack; ``steps`` bounds the
    replay to the REAL iterations (a tail superstep's padded no-op
    steps, and shared-batch overshoot past ``num_iterations``, are
    never read).  Convergence is detected per STEP from the ys — the
    true converged iteration, never the superstep boundary — with the
    identical host float comparison the legacy loops make
    (``delta < tol * max(||w||, 1)`` from the second update on), and
    empty sampled batches (``count == 0``) skip the record exactly as
    before.  ``save_cb(i, w_np, reg_val)`` fires on the legacy cadence
    (``i % save_every == 0``, on convergence, and at the final
    iteration) with the EXACT iteration-``i`` state from the ys, so
    fused checkpoints are indistinguishable from per-iteration ones and
    resume stays bitwise.

    Returns ``(t_last, reg_val, converged)``; the caller truncates the
    device program's overshoot by taking ``ys weights[t_last]`` as the
    final state when the run ends mid-superstep.
    """
    import numpy as np

    from tpu_sgd.utils.events import IterationEvent

    ws, ls, rs, cs, dns, wns = ys_host
    converged = False
    t_last = 0
    for t in range(steps):
        i = i0 + t
        t_last = t
        if int(cs[t]) > 0:
            loss_f = float(ls[t])
            if check_numerics and not np.isfinite(loss_f):
                _raise_if_nonfinite([loss_f], first_iteration=i)
            losses.append(loss_f)
            reg_val = float(rs[t])
            # live loss/variance series from the replayed ys — the
            # values are ALREADY host numpy (one bulk fetch per
            # superstep), so the zero-added-syncs pin holds
            observe_scalar("train.loss", loss_f)
            observe_scalar("train.weight_delta", float(dns[t]))
            if listener is not None:
                listener.on_iteration(IterationEvent(
                    iteration=i,
                    loss=loss_f,
                    weight_delta_norm=float(dns[t]),
                    mini_batch_size=int(cs[t]),
                    wall_time_s=wall_dt,
                ))
            if cfg.convergence_tol > 0 and i > 1:
                converged = float(dns[t]) < cfg.convergence_tol * max(
                    float(wns[t]), 1.0)
            if save_cb is not None and (
                    (save_every and i % save_every == 0)
                    or converged or i == cfg.num_iterations):
                save_cb(i, ws[t], reg_val)
        if converged:
            break
    return t_last, reg_val, converged


#: memo-key contract (graftlint memo-key rule): every compiled runner
#: cached in ``_run_cache`` must key on the roots below — the rule
#: decomposes each store site's key and the stored program's factory
#: reads and flags a program-affecting value the key misses (the
#: incomplete-memo-key class the PR 6 review caught by hand)
GRAFTLINT_MEMO = {
    "GradientDescent._run_cache": (
        "gradient", "updater", "config", "mesh", "with_valid",
        "k", "cadence", "sparse_shape",
        # gram-runner keys carry the data geometry and the gram/ingest
        # knobs the compiled prefix programs bake in
        "X", "y", "gram_aligned", "gram_batch_rows", "gram_block_rows",
        "ingest_pipeline", "ingest_prefetch_depth", "ingest_wire_dtype",
        # ``_select``'s view of (X, y) as ``_place`` laid them on the mesh:
        # ``with_valid`` and the sharded statistics' block rows come from it
        "placed",
    ),
}

#: ``GradientDescent._step_kernel``'s answer where the step is no one-read
#: kernel: ``(labels_prepared, row_tile, feature_blocks, mask_in_kernel,
#: by_rows, class_rows, ahead)``, then :func:`_rows_as_read`'s two
_NO_KERNEL = (0, 0, 1, 0, 0, 0, 0)


def _rows_as_read(X):
    """``train.run``'s ``(row_item_bytes, operand)`` where the step is no
    one-read kernel (whose record says both): the bytes of one feature as
    the step's products read it from HBM, X's own item, and the name of
    the type their operands are in (``ops/gradients.matmul_dtype``: the
    contract every path computes under; BCOO rows compute at the
    accumulation type)."""
    operand = matmul_dtype(X)
    if is_sparse(X):
        operand = jnp.promote_types(operand, jnp.float32)
    return jnp.dtype(X.dtype).itemsize, jnp.dtype(operand).name


def _stays_integer(X) -> bool:
    """Whether dense rows of ``X``'s type are trained as the integers they
    are, in the bytes they are (``matmul_dtype``: 8-bit ones, whose
    products' operands are the bf16 values they exactly are), and not cast
    to float32 after the copy as ``bool`` and wider integers are."""
    return (not jnp.issubdtype(X.dtype, jnp.inexact)
            and matmul_dtype(X) != jnp.float32)


class GradientDescent(Optimizer):
    """Drop-in mini-batch SGD optimizer (``TpuGradientDescent``).

    Fluent setters mirror the reference's builder API (SURVEY.md §5.6):
    ``set_step_size``, ``set_num_iterations``, ``set_reg_param``,
    ``set_mini_batch_fraction``, ``set_convergence_tol``.  Passing a
    ``jax.sharding.Mesh`` via ``set_mesh`` switches the same loop to the
    data-parallel shard_map body with ICI all-reduce.
    """

    planned_by = "plan_for"

    def __init__(
        self,
        gradient: Gradient = None,
        updater: Updater = None,
        config: SGDConfig = None,
    ):
        self.gradient = gradient if gradient is not None else LeastSquaresGradient()
        self.updater = updater if updater is not None else SimpleUpdater()
        self.config = config if config is not None else SGDConfig()
        self.mesh = None
        self.listener = None
        self.host_streaming = False
        self.streaming_resident_rows = 0
        self.check_numerics = False
        self.checkpoint_manager = None
        self.checkpoint_every = 10
        self.sufficient_stats = False
        self.streamed_stats = False
        self.gram_block_rows = DEFAULT_BLOCK_ROWS
        self.gram_batch_rows = None
        self.gram_aligned = False
        #: ingest-pipeline knobs (tpu_sgd/io; set_ingest_options): wire
        #: dtype for the host→device hop (None = data dtype), prefetch
        #: lookahead (2 = double buffer, 0 = synchronous), and the
        #: pipelined-build master switch (False = legacy sync loops)
        self.ingest_wire_dtype = None
        self.ingest_prefetch_depth = 2
        self.ingest_pipeline = True
        #: compressed gradient/update wire (tpu_sgd/io/sparse_wire;
        #: README "Compressed wire"): "topk:<frac>" ships top-k
        #: (values, indices) segments with error-feedback state on the
        #: update-shaped wires; None = dense wire.  The planner may
        #: choose it (plan.choose_wire_compress); user wins
        self.ingest_wire_compress = None
        #: reliability knobs (tpu_sgd/reliability): a RetryPolicy for
        #: transient host-feed faults (set_ingest_options(retry=...))
        #: and the cooperative preemption probe (set_stop_signal — the
        #: TrainingSupervisor installs it)
        self.ingest_retry_policy = None
        self._stop_signal = None
        #: fused-step count (set_superstep): K consecutive iterations
        #: run as ONE compiled lax.scan program on the host-dispatched
        #: paths (host-streamed + stepwise); 1 = the legacy
        #: one-dispatch-per-iteration drivers.  The planner picks K for
        #: host_streamed schedules (plan.choose_superstep)
        self.superstep = 1
        #: device-residency cadence (set_residency): C >= 2 moves the
        #: WHOLE run loop into one compiled lax.while_loop over fused
        #: supersteps on the device-resident-data paths, with host
        #: callbacks every C supersteps (optimize/resident_driver.py);
        #: 0 = the per-superstep host driver.  The planner picks C for
        #: host_streamed schedules (plan.choose_residency)
        self.resident_cadence = 0
        #: gram-knob fields the USER set via set_gram_options /
        #: set_streamed_stats — the planner preserves these and resets
        #: only plan-owned fields (Plan.apply)
        self._user_gram_opts = frozenset()
        self.last_plan = None
        self._plan_key = None
        self._gram_entry = None
        #: the totals form's ONE unbound executor (``_maybe_gram``)
        self._totals_gradient = None
        self._gram_dp_entry = None
        self._streamed_gram_entry = None
        self._streamed_gram_dp_entry = None
        self._loss_history = None
        self._run_cache = {}
        #: ``_hyper``'s last answer and what it was made from
        self._hyper_made = None

    # -- fluent config (returns self, like the reference's setters) --------
    def set_gradient(self, g: Gradient):
        self.gradient = g
        return self

    def set_updater(self, u: Updater):
        self.updater = u
        return self

    def set_step_size(self, s: float):
        self.config = self.config.replace(step_size=float(s))
        return self

    def set_num_iterations(self, n: int):
        if n < 1:
            raise ValueError(f"num_iterations must be positive, got {n}")
        self.config = self.config.replace(num_iterations=int(n))
        return self

    def set_reg_param(self, r: float):
        self.config = self.config.replace(reg_param=float(r))
        return self

    def set_mini_batch_fraction(self, f: float):
        if not 0.0 < f <= 1.0:
            raise ValueError("mini_batch_fraction must be in (0, 1]")
        self.config = self.config.replace(mini_batch_fraction=float(f))
        return self

    def set_convergence_tol(self, t: float):
        if not 0.0 <= t <= 1.0:
            raise ValueError("convergence_tol must be in [0, 1]")
        self.config = self.config.replace(convergence_tol=float(t))
        return self

    def set_seed(self, s: int):
        self.config = self.config.replace(seed=int(s))
        return self

    def set_sampling(self, mode: str):
        """'bernoulli' (reference parity), 'indexed' (a fixed-size gather
        with replacement: sound host-streamed, on a resident X slower than
        the masked scan) or 'sliced' (contiguous-window fast path —
        HBM-optimal; assumes exchangeable row order); see
        ``SGDConfig.sampling``."""
        self.config = self.config.replace(sampling=mode)
        return self

    def set_mesh(self, mesh):
        self.mesh = mesh
        return self

    def set_listener(self, listener):
        """Attach an ``SGDListener`` (tpu_sgd.utils.events).

        Switches ``optimize`` to the step-wise traced path: one jitted step
        per iteration with host-visible loss/timing events — the analogue of
        Spark's per-job listener bus (SURVEY.md §5.1) — instead of the single
        fused while_loop program.
        """
        self.listener = listener
        return self

    def set_check_numerics(self, flag: bool = True):
        """Raise ``FloatingPointError`` when the loss goes non-finite
        (diverging step size, bad data) — the JAX-side analogue of the
        reference's JVM sanitizer story (SURVEY.md §5.2: functional purity
        plus explicit NaN checks; no TSAN equivalent is needed)."""
        self.check_numerics = bool(flag)
        return self

    def set_host_streaming(self, flag: bool = True, resident_rows: int = 0):
        """Keep the dataset in host RAM and stream per-iteration sampled
        batches to the device with double-buffered prefetch — for datasets
        larger than HBM (SURVEY.md §7, config 4 at full 40 GB scale).
        Composes with ``set_mesh`` on a 1-D data mesh: each batch is
        row-sharded across cores and gradients all-reduce over ICI.

        ``resident_rows``: partial residency (sliced sampling, single
        device) — rows ``[0, resident_rows)`` are placed on the device once
        and windows inside that prefix are sliced on-device, cutting
        per-epoch host->device traffic by ~``resident_rows/n`` with an
        unchanged window sequence (see ``optimize_host_streamed``).

        The per-iteration feed runs through the shared ingest pipeline
        (``tpu_sgd/io``): iteration ``i+1``'s batch assembles and
        transfers on a worker thread while ``i`` computes, and
        ``set_ingest_options(wire_dtype="bfloat16")`` halves the bytes on
        the wire — see README "Ingestion pipeline" for the knobs and the
        bf16 safety notes."""
        self._clear_planned_schedule()
        self.host_streaming = bool(flag)
        self.streaming_resident_rows = int(resident_rows)
        self._mark_manual_schedule()
        return self

    def _clear_planned_schedule(self):
        """A manual schedule setter taking the wheel AFTER an auto-planned
        run: the previous plan's sibling flags are the PLANNER's, not the
        user's — reset them so the schedule-exclusion guards never blame
        the user for a flag a plan set (user-set flags always come with
        ``last_plan is None``)."""
        if self.last_plan is not None:
            self.host_streaming = False
            self.streaming_resident_rows = 0
            self.sufficient_stats = False
            self.streamed_stats = False
            # ...and the plan's SIZING knobs: a block size / chunk cap
            # sized for the planned dataset must not leak into a manual
            # schedule on a different one (user-set knobs survive)
            from tpu_sgd.plan import reset_plan_owned_gram_knobs

            reset_plan_owned_gram_knobs(self)

    def _mark_manual_schedule(self):
        """A user-called schedule setter invalidates any auto-plan: the
        planner's 'manual flags win' contract keys on ``last_plan is
        None`` (tpu_sgd/models/glm.py), so clear it (and the repeat-run
        plan cache key) whenever the user takes the wheel."""
        self.last_plan = None
        self._plan_key = None

    def set_sufficient_stats(self, flag: bool = True):
        """Execute least-squares via precomputed block-prefix Gram
        statistics (``ops/gram.py``): window/full-batch gradients become
        two (d, d) matvecs plus masked edge blocks instead of two full
        passes over the sampled rows — exact, and far below the two-read
        HBM bandwidth floor the stock path sits at (PROFILE_TPU.json).

        Applies when the gradient is exactly ``LeastSquaresGradient``, the
        data is dense and device-resident (no mesh, no host streaming), and
        sampling is ``sliced`` or full-batch; any other combination runs
        unchanged.  Under sliced sampling the one-time build pass is cached
        per ``(X, y)`` array identity — and RETAINED after ``optimize``
        returns (repeated calls on the same arrays must not rebuild), which
        pins the dataset plus the ~GB-scale prefix stack in HBM until a
        different dataset is passed, the optimizer is dropped, or
        :meth:`release_sufficient_stats` is called.  A FULL batch reads the
        totals alone (``stats_in_totals``): one read builds them anew for
        every fit, 12 MB at d = 1000, and nothing of the dataset is kept."""
        self._clear_planned_schedule()
        self.sufficient_stats = bool(flag)
        self._mark_manual_schedule()
        return self

    def set_gram_options(self, block_rows: int = None, aligned: bool = None,
                         batch_rows: int = None):
        """Tuning knobs for the sufficient-statistics schedules.

        ``block_rows`` trades prefix-stack memory (``n/B · d² · 4`` bytes)
        against per-iteration edge-read traffic (see ``ops/gram.py``).
        ``aligned=True`` floors window starts to block boundaries, skipping
        the edge corrections (~71% of the exact iteration) at the cost of
        a floored window (a different, equally sized run of rows where the
        start is no block boundary) — fine on shuffled rows, not on
        sorted/grouped data.
        ``batch_rows`` caps the streamed build's host→device chunk (the
        chunk is co-resident with the growing prefix stack, so a tight
        device budget needs a smaller chunk than the 64-block default).
        The execution planner (``tpu_sgd/plan.py``) sets ``block_rows``/
        ``batch_rows`` automatically; ``aligned`` stays opt-in."""
        from tpu_sgd.plan import apply_user_gram_knobs

        apply_user_gram_knobs(self, block_rows=block_rows, aligned=aligned,
                              batch_rows=batch_rows)
        return self

    def set_ingest_options(self, wire_dtype=None, prefetch_depth=None,
                           pipeline=None, retry=None, wire_compress=None):
        """Tuning knobs for the host→device ingest pipeline
        (``tpu_sgd/io``; README "Ingestion pipeline") — they apply to
        every streaming schedule: ``set_host_streaming``,
        ``set_streamed_stats`` (single-device and meshed), and the
        planner's streamed choices.

        ``wire_dtype="bfloat16"`` casts each transferred chunk on host
        and moves half the bytes; the device side still accumulates in
        f32+ (see ``tpu_sgd/io/wire.py`` for when that is safe).
        ``prefetch_depth`` caps the chunks materialized at once,
        INCLUDING the one being consumed (2 = double buffer — the 2×
        staging footprint the planner budgets ``batch_rows`` for;
        depths above 2 grow that footprint proportionally, so shrink
        ``batch_rows`` to match on a tight device); ``0``/``1`` and
        ``pipeline=False`` fall back to the synchronous legacy feed
        (bitwise A/B, one chunk live at a time; ``pipeline=False`` also
        disables the wire cast).

        ``retry`` (the reliability knob; README "Reliability"): a
        ``tpu_sgd.reliability.RetryPolicy`` that re-runs a failed
        host-side batch assembly/transfer with seeded backoff before
        the error propagates — transient ``device_put``/disk faults
        heal in place on the ``set_host_streaming`` feed.  Retries do
        not change WHAT is sampled (the sample is deterministic in
        ``(seed, i)``), so a healed run stays bitwise identical.  For
        whole-run crash-resume and preemption safety wrap the run in a
        ``tpu_sgd.reliability.TrainingSupervisor``.

        ``wire_compress="topk:<frac>"`` (README "Compressed wire"): the
        COMPRESSED gradient/update wire — top-k ``(values, indices)``
        segments with error-feedback accumulation on the wires that
        move update-shaped data: the per-step gradient all-reduce of
        the ``set_host_streaming`` feed (meshed: segments replace the
        dense psum; single-device: the same EF top-k update rule, the
        matched-loss A/B twin) and the per-shard totals merge of the
        streamed statistics builds.  The EF accumulator is optimizer
        state — checkpointed and scan-carried, see ADVICE.md "Error
        feedback is optimizer state, not a transport detail".  Pass
        ``False`` to clear a previously set spec."""
        from tpu_sgd.plan import apply_user_ingest_options

        apply_user_ingest_options(self, wire_dtype=wire_dtype,
                                  prefetch_depth=prefetch_depth,
                                  pipeline=pipeline, retry=retry,
                                  wire_compress=wire_compress)
        return self

    def set_superstep(self, k: int):
        """Fuse ``k`` consecutive SGD iterations into ONE compiled
        program (``lax.scan`` of the per-iteration step) on the paths
        that pay a host round-trip per iteration — the host-streamed
        feed (``set_host_streaming``; the prefetcher assembles a
        ``k``-batch *superchunk* so ``device_put`` fires once per
        superstep too) and the observed stepwise driver
        (listener/checkpoint attached).  Per-step math and the sampled
        sequence are unchanged: loss history and convergence detection
        stay per-iteration exact (the scan returns per-step ys),
        checkpoints land on the same iterations, and every
        same-program contract is bitwise — fused runs replay, resume,
        and prefetch-A/B to identical weights.  Versus the ``k=1``
        legacy loop, trajectories agree to reassociation noise (~1
        ulp/step: XLA lowers the batch dot differently inside a
        scanned program — see ``make_superstep``'s trajectory
        contract).  What changes: dispatch + transfer count drops
        ~``k``×
        (BENCH_SUPERSTEP.json), listener events arrive in bursts of
        ``k`` with averaged per-iteration wall times, and cooperative
        preemption (``set_stop_signal``) is polled at superstep
        boundaries — worst-case preemption latency grows to ``k``
        iterations (see ADVICE.md; keep ``k`` at or below the
        checkpoint cadence).  ``k=1`` restores the legacy drivers.
        Single-device only: meshed and partial-residency feeds keep the
        per-iteration driver (a warning says so).  The fused
        single-program paths (no listener/checkpoint/streaming) already
        run zero host dispatches and ignore it."""
        if int(k) < 1:
            raise ValueError(f"superstep must be >= 1, got {k}")
        self.superstep = int(k)
        self._user_gram_opts = self._user_gram_opts | {"superstep"}
        self._plan_key = None
        return self

    def set_residency(self, cadence: int = 8):
        """Move the WHOLE run loop on device: a single compiled
        ``lax.while_loop`` over fused superstep scans drives the run
        from start to converged-or-budget-exhausted in ONE program
        dispatch, with the host involved only every ``cadence``
        supersteps — an ordered ``io_callback`` surfaces a bounded
        ring buffer of per-step history that replays through the exact
        superstep bookkeeping (loss history, listener events,
        convergence at the true iteration, checkpoint cadence; see
        ``optimize/resident_driver.py`` and README "Device-resident
        training").  Applies where the per-iteration data already
        lives on device: the observed stepwise driver and the
        host-streamed full-batch / fully-resident-slab feeds; the
        host-sampled streamed feeds keep the superstep driver (the
        host hop there IS the data feed).  Requires ``set_superstep(K
        >= 2)`` (or a planner-chosen K) — residency fuses the
        superstep executor, it does not replace it.  Stop signals are
        polled once per cadence window, so worst-case preemption
        latency grows to ``cadence * K`` iterations (ADVICE.md); keep
        the window at or below the checkpoint cadence.  ``cadence=0``
        restores the per-superstep host driver; a window of ONE
        superstep is the superstep driver already, so ``cadence=1``
        is rejected.  ``plan.choose_residency`` picks the cadence
        automatically for planned host-streamed schedules."""
        c = int(cadence)
        if c == 1:
            raise ValueError(
                "residency cadence 1 is the per-superstep driver "
                "(set_superstep); use cadence >= 2 or 0 to disable")
        if c < 0:
            raise ValueError(f"cadence must be >= 0, got {cadence}")
        self.resident_cadence = c
        self._user_gram_opts = self._user_gram_opts | {"residency"}
        self._plan_key = None
        return self

    def set_stop_signal(self, stop_signal):
        """Install a zero-arg callable polled once per iteration on the
        observed (listener/checkpoint) and host-streamed paths: when it
        returns True the current state is checkpointed (if a manager is
        attached) and the run unwinds with ``TrainingPreempted`` — the
        cooperative half of preemption-safe training.  Pass ``None`` to
        clear.  Installed automatically by
        ``tpu_sgd.reliability.TrainingSupervisor``; the fused
        single-program paths (no per-iteration host hop) cannot poll
        and simply run to completion."""
        self._stop_signal = stop_signal
        return self

    def set_streamed_stats(self, flag: bool = True, block_rows: int = None):
        """Beyond-HBM least squares via streamed statistics: ONE host-
        streaming pass builds the block-prefix Gram stack on device
        (``GramLeastSquaresGradient.build_streamed``), after which
        iterations run entirely from the statistics — zero per-iteration
        host transfer (measured 0.026 ms/iter on the true 10M×1000,
        BASELINE.md round 3).  Windows are ALIGNED (block-floored) by
        construction and the trailing ``n % block_rows`` rows are dropped —
        a sampling deviation that is harmless on shuffled rows but not on
        sorted/grouped data; use ``set_host_streaming`` for exact-window
        streaming.  Applies to exactly ``LeastSquaresGradient`` on dense
        single-device data with sliced or full-batch sampling; the build is
        identity-cached per ``(X, y)`` like ``set_sufficient_stats``.

        The one-time build pass streams through the shared ingest
        pipeline (``tpu_sgd/io``): double-buffered fixed-shape chunks
        (f32 wire bitwise-identical to the legacy sync feed), with an
        opt-in bf16 wire via ``set_ingest_options`` — see README
        "Ingestion pipeline"."""
        self._clear_planned_schedule()
        self.streamed_stats = bool(flag)
        if block_rows is not None:
            self.gram_block_rows = int(block_rows)
            self._user_gram_opts = self._user_gram_opts | {"block_rows"}
        self._mark_manual_schedule()
        return self

    def release_sufficient_stats(self):
        """Drop the cached sufficient-statistics bundles (single-device,
        DP-mesh, and streamed-virtual) and the compiled runners keyed on
        them, so the bound dataset plus the prefix stacks can be freed from
        HBM.  Call after a one-shot ``optimize`` when the statistics are no
        longer needed; the next run rebuilds from scratch.
        (The DP-mesh runner takes its stats as call arguments, so clearing
        the entry alone frees them; only the single-device gram gradients
        appear in run-cache keys.)"""
        for entry in (self._gram_entry, self._streamed_gram_entry):
            if entry is not None:
                self._purge_run_cache_for(entry[2])
        if self._totals_gradient is not None:
            # the totals form keeps no statistics, only its executor's runner
            self._purge_run_cache_for(self._totals_gradient)
        self._gram_entry = None
        self._totals_gradient = None
        self._gram_dp_entry = None
        self._streamed_gram_entry = None
        self._streamed_gram_dp_entry = None
        return self

    def _purge_run_cache_for(self, obj):
        """Drop compiled runners whose cache key contains ``obj`` (by
        identity) so a superseded gram gradient's GB-scale prefix stack is
        not pinned by a closure."""
        self._run_cache = {
            k: v for k, v in self._run_cache.items()
            if not any(part is obj for part in k)
        }

    def set_checkpoint(self, manager, every: int = 10):
        """Attach a ``CheckpointManager``; optimizer state is saved every
        ``every`` iterations and ``optimize`` resumes from the latest
        checkpoint when one exists (SURVEY.md §5.4)."""
        self.checkpoint_manager = manager
        self.checkpoint_every = int(every)
        return self

    # -- optimization ------------------------------------------------------
    @property
    def loss_history(self):
        """Stochastic loss history of the last ``optimize`` call (np array)."""
        return self._loss_history

    def optimize(self, data: Dataset, initial_weights: Array) -> Array:
        w, losses = self.optimize_with_history(data, initial_weights)
        return w

    def optimize_with_history(self, data: Dataset, initial_weights: Array):
        return self._fit(data, initial_weights)

    def _fit(self, data: Dataset, initial_weights: Array,
             integers_f32: bool = False):
        """``optimize_with_history``, which trains 8-bit integer rows as
        the integers they are (``matmul_dtype``: bf16 operands, exact).
        ``integers_f32``, said with the call by a caller whose own contract
        is float32 for every integer input: those rows too are cast to
        float32 after the copy, as ``bool`` and wider integers always are."""
        import numpy as np

        # classes: K for a (K-1, d) matrix of weights, 2 for a vector
        with span("train.run", iterations=self.config.num_iterations,
                  rows=data[0].rows if isinstance(data[0], StagedAhead)
                  else np.shape(data[0])[0],
                  classes=getattr(self.gradient, "num_classes", 2)
                  ) as run_span, root("train.run", run_span):
            return self._optimize(data, initial_weights, run_span,
                                  integers_f32)

    def _optimize(self, data: Dataset, initial_weights: Array, run_span,
                  integers_f32: bool = False):
        """``_fit`` under its ``train.run`` span, whose ``path`` is set
        where the route is decided."""
        import numpy as np

        X, y = data
        from tpu_sgd.ops.gram import GramData, GramLeastSquaresGradient

        if (isinstance(X, StagedAhead) and X.capacity
                and not self.trains_at_capacity()):
            X, y = X.host()  # planned otherwise since: its rows as they are
        if isinstance(X, GramData):
            # Statistics-first input (build/build_streamed): the rows may
            # be virtual (beyond-HBM datasets), so coerce only y/w0 and
            # route straight to the resident single-device path.
            if not isinstance(self.gradient, GramLeastSquaresGradient):
                raise ValueError(
                    "GramData input needs a GramLeastSquaresGradient "
                    "(use GramLeastSquaresGradient.build/build_streamed "
                    "and pass it as the gradient)"
                )
            if self.mesh is not None or self.host_streaming:
                raise NotImplementedError(
                    "GramData input supports the single-device resident "
                    "path (stats are already on device); drop set_mesh/"
                    "set_host_streaming"
                )
            cfg = self.config
            if cfg.mini_batch_fraction < 1.0 and cfg.sampling != "sliced":
                raise NotImplementedError(
                    "GramData input supports sliced sampling or full "
                    f"batch (got sampling={cfg.sampling!r})"
                )
            if cfg.mini_batch_fraction < 1.0 and X.PG is None:
                raise NotImplementedError(
                    "the totals form of the statistics (stats_build) "
                    "serves full-batch fits; sliced windows need the "
                    "prefix form (GramLeastSquaresGradient.build)"
                )
            if (cfg.mini_batch_fraction < 1.0 and X.X is None
                    and X.PG.shape[0] <= 2):
                import warnings

                # a single-block virtual stack (e.g. a persisted
                # totals-only bundle from the quasi-Newton/normal paths)
                # cannot express sub-batch windows: every "window" IS
                # the full batch — the run silently stops being SGD
                warnings.warn(
                    "these virtual statistics hold a single block, so "
                    f"sliced windows at frac={cfg.mini_batch_fraction} "
                    "degenerate to FULL-BATCH iterations; rebuild with "
                    "a smaller block_rows for true mini-batch sampling",
                    RuntimeWarning, stacklevel=4,
                )
            y = jnp.asarray(y)
            if not jnp.issubdtype(y.dtype, jnp.inexact):
                y = y.astype(jnp.float32)
            w0 = _coerce_w0(self.gradient, initial_weights, X.shape[1])
            return self._optimize_routed(X, y, w0, False, run_span)
        sparse_X = is_sparse(X)
        if sparse_X:
            # BCOO feature path (VERDICT r1 missing #2; [U] SparseVector
            # training, SURVEY.md §2 #10): same fused step, gather/segment
            # lowering.  Everything that needs a dense row layout raises.
            if self.host_streaming:
                # host-streamed SPARSE feed (optimize/streamed_sparse.py;
                # README "Compressed wire"): the dataset stays host-
                # resident as CSR entry arrays and each sampled batch
                # ships as fixed-nse BCOO components — never densified
                # anywhere on the path
                from tpu_sgd.optimize.streamed_sparse import (
                    optimize_host_streamed_sparse,
                )

                if self.mesh is not None:
                    raise NotImplementedError(
                        "host-streamed sparse training is single-device "
                        "(shard the resident BCOO path with set_mesh "
                        "instead)"
                    )
                if self.ingest_wire_dtype is not None:
                    import warnings

                    warnings.warn(
                        "wire_dtype applies to dense row chunks; the "
                        "sparse feed ships BCOO components at the data "
                        "dtype (its compression is the sparsity itself)",
                        RuntimeWarning, stacklevel=3,
                    )
                run_span.set(path="streamed")
                w0 = _coerce_w0(self.gradient, initial_weights,
                                X.shape[1])
                w, hist = optimize_host_streamed_sparse(
                    self.gradient, self.updater, self.config, X,
                    np.asarray(y), w0,
                    listener=self.listener,
                    checkpoint_manager=self.checkpoint_manager,
                    checkpoint_every=self.checkpoint_every,
                    prefetch_depth=(self.ingest_prefetch_depth
                                    if self.ingest_pipeline else 0),
                    retry_policy=self.ingest_retry_policy,
                    stop_signal=self._stop_signal,
                    superstep_k=self.superstep,
                    resident_cadence=self.resident_cadence,
                    wire_compress=(self.ingest_wire_compress
                                   if self.ingest_pipeline else None),
                )
                self._loss_history = hist
                if self.check_numerics:
                    _raise_if_nonfinite(hist)
                return w, hist
            if self.mesh is not None and self._mesh_kind() == "dp_mp":
                raise NotImplementedError(
                    "feature-axis ('model') sharding needs dense column "
                    "blocks; sparse (BCOO) features support 1-D 'data' "
                    "meshes"
                )
            if (self.config.sampling != "bernoulli"
                    and self.config.mini_batch_fraction < 1.0):
                raise NotImplementedError(
                    "sparse features support bernoulli sampling only "
                    f"(got sampling={self.config.sampling!r})"
                )
        if self.streamed_stats:
            # Beyond-HBM sufficient statistics (set_streamed_stats): build
            # once from the host rows, then iterate from the on-device
            # statistics.  Routed BEFORE host_streaming/device conversion —
            # the rows never live on the device at all.  With a 1-D data
            # mesh the build streams each shard's rows to its own device
            # and the run is the shard_map'ed virtual-stats loop; single-
            # device re-enters through the GramData branch above.
            self._check_streamed_stats_applies(sparse_X)
            if self.mesh is not None:
                run_span.set(path="gram")
                return self._optimize_streamed_stats_mesh(
                    X, y, initial_weights
                )
            gram = self._route_streamed_stats(X, y)
            orig, self.gradient = self.gradient, gram
            try:
                n_logical = gram.data.shape[0]
                return self._optimize(
                    (gram.data, np.asarray(y)[:n_logical]), initial_weights,
                    run_span, integers_f32
                )
            finally:
                self.gradient = orig
        if self.host_streaming:
            # Route BEFORE any device conversion: the whole point is that X
            # never lives on the device in full.
            from tpu_sgd.optimize.streamed import optimize_host_streamed

            if self.mesh is not None and self._mesh_kind() == "dp_mp":
                raise NotImplementedError(
                    "host streaming supports 1-D data meshes; feature-axis "
                    "('model') sharding needs the resident path"
                )
            run_span.set(path="streamed")
            Xh = np.asarray(X)
            if integers_f32 and _stays_integer(Xh):
                # no cast follows the copy of a chunk: on the host, then
                Xh = Xh.astype(np.float32)
            # same weight validation/coercion as the resident paths — a
            # wrong-length w0 must raise the clear ValueError here, not
            # an opaque XLA dot-shape error inside the streamed step
            w0 = _coerce_w0(self.gradient, initial_weights, Xh.shape[1])
            if Xh.shape[0] == 0:
                self._loss_history = np.zeros((0,), np.float32)
                return w0, self._loss_history
            w, hist = optimize_host_streamed(
                self.gradient, self.updater, self.config, Xh, np.asarray(y),
                w0, mesh=self.mesh, listener=self.listener,
                checkpoint_manager=self.checkpoint_manager,
                checkpoint_every=self.checkpoint_every,
                resident_rows=self.streaming_resident_rows,
                # pipeline=False is the LEGACY feed: no wire cast, no
                # lookahead — the bitwise A/B contract (the gram
                # builders make the same reduction)
                wire_dtype=(self.ingest_wire_dtype
                            if self.ingest_pipeline else None),
                prefetch_depth=(self.ingest_prefetch_depth
                                if self.ingest_pipeline else 0),
                retry_policy=self.ingest_retry_policy,
                stop_signal=self._stop_signal,
                superstep_k=self.superstep,
                resident_cadence=self.resident_cadence,
                wire_compress=(self.ingest_wire_compress
                               if self.ingest_pipeline else None),
            )
            self._loss_history = hist
            if self.check_numerics:
                _raise_if_nonfinite(hist)
            return w, hist
        # host time in the calls: a large X's blocks are issued in here,
        # the last of them (any other copy whole) may still drain after it
        valid = queued = None
        with span("train.h2d", bytes=sum(
                a.nbytes for a in (X, y)
                if isinstance(a, np.ndarray)
                or getattr(a, "_host", None) is not None)) as h2d:
            if isinstance(X, StagedAhead) and X.capacity:
                # a stream's micro-batch at a row capacity: its blocks were
                # issued ahead of this fit, or are now (in turn: the same
                # blocks, the same programs); its row count is an operand
                h2d.set(blocks=X.count if X.X is None else 0,
                        block_bytes=X.wire_bytes // X.count, shards=1,
                        flat=0)
                at = X if X.X is not None else X.whole()
                X, y, valid, queued = at.X, at.y, at.valid, at.queued
            elif self._hands_off_sharded(X):
                # each row block to the device that owns its rows; y and
                # the mask of padded rows lie sharded beside them
                from tpu_sgd.parallel.data_parallel import shard_dataset

                X, y, valid = shard_dataset(self.mesh, X, y, h2d)
            elif not sparse_X:
                X, blocks, block_bytes = _stage_dense(X, h2d)
                h2d.set(blocks=blocks, block_bytes=block_bytes)
            if not sparse_X:
                if (not jnp.issubdtype(X.dtype, jnp.inexact)
                        and (integers_f32 or not _stays_integer(X))):
                    # bool and wider integer features (one-hot etc.), and
                    # every integer type where the call says so; else
                    # 8-bit integer rows stay the bytes they are
                    X = X.astype(jnp.float32)
            y = jnp.asarray(y)
            if not jnp.issubdtype(y.dtype, jnp.inexact):
                y = y.astype(jnp.float32)
            w0 = _coerce_w0(self.gradient, initial_weights, X.shape[1])
        n = X.shape[0]
        if n == 0:
            self._loss_history = np.zeros((0,), np.float32)
            return w0, self._loss_history
        if n * self.config.mini_batch_fraction < 1:
            import warnings

            warnings.warn(
                "The miniBatchFraction is too small", RuntimeWarning, stacklevel=3
            )
        return self._optimize_routed(X, y, w0, sparse_X, run_span, valid,
                                     queued)

    def _hands_off_sharded(self, X) -> bool:
        """Whether ``train.h2d`` sends ``X`` straight to the mesh's devices,
        a shard each (``shard_dataset``'s host branch): a dense host matrix
        under a 1-D data mesh of this process's devices.  Anything else is
        staged as without a mesh and laid out by ``_place``."""
        import numpy as np

        return (isinstance(X, np.ndarray) and X.ndim == 2 and X.shape[0] > 0
                and self.mesh is not None and self._mesh_kind() == "dp"
                and jax.process_count() == 1)

    def _optimize_routed(self, X, y, w0, sparse_X, run_span, valid=None,
                         queued=None):
        """Resident-data path routing (single-device / mesh / sparse /
        stepwise), after input coercion.  The fused fit's leaves tile it:
        ``train.place`` (a 1-D mesh alone), ``train.stats`` where the
        sufficient-stats substitution builds (``_maybe_gram``), then
        ``train.select`` — the route, the compiled runner's lookup and
        what ``train.run`` says of the step — up to the start of
        ``train.dispatch``, and ``train.fetch`` to the return.  ``queued``
        (a stream's micro-batch at a row capacity alone:
        ``StagedAhead.queued``; None for every other fit) is called between
        the two, once the fused program is in the device's queue, with the
        program's own answer to "has it run" (no wait)."""
        import numpy as np

        if self.listener is not None or self.checkpoint_manager is not None:
            with self._substituted(X, y, sparse_X) as X:
                if (self.sufficient_stats and self.mesh is not None
                        and not sparse_X):
                    import warnings

                    warnings.warn(
                        "sufficient_stats is not applied on the meshed "
                        "listener/checkpoint path (the observed "
                        "per-iteration stepper uses the stock DP step); "
                        "detach the listener or run single-device to "
                        "combine them",
                        RuntimeWarning,
                        stacklevel=4,
                    )
                run_span.set(path="stepwise")
                return self._optimize_stepwise(X, y, w0, valid)
        placed = None
        if (not sparse_X and self.mesh is not None
                and self._mesh_kind() == "dp"):
            placed = self._place(X, y, valid)
        with self._substituted(X, y, sparse_X) as X, \
                span("train.select") as select_span:
            fn, args, built = self._select(X, y, w0, sparse_X, placed,
                                           run_span, select_span, valid)
        with span("train.dispatch", built=int(built)):
            w, losses, n_rec = fn(*args)
            # the copies to the host ride behind the program, so the fit
            # ends in ONE wait: the count's read wakes the host once and
            # the loss history has landed beside it (in series they were
            # two round trips after the chip was done).  w is returned on
            # the device as before; its copy is started too, so that the
            # caller's first read of it finds it landed and is no third
            # round trip (the fit itself never reads it)
            n_rec.copy_to_host_async()
            losses.copy_to_host_async()
            w.copy_to_host_async()
        if queued is not None:
            queued(n_rec.is_ready)
        with span("train.fetch") as sp:
            recorded = int(n_rec)
            self._loss_history = np.asarray(losses)[:recorded]
            sp.set(recorded=recorded, waits=1)
            if self.check_numerics:
                _raise_if_nonfinite(self._loss_history)
        return w, self._loss_history

    @contextlib.contextmanager
    def _substituted(self, X, y, sparse_X):
        """The sufficient-stats substitution for the length of the block,
        where it applies (``_maybe_gram``): yields the X to train on.  The
        stats ride as the X argument (GramData pytree) so they enter the
        jit program as buffers, not closure constants; a runner built in
        the block closes over the substituted gradient."""
        from tpu_sgd.ops.gram import GramData

        gram = None if isinstance(X, GramData) \
            else self._maybe_gram(X, y, sparse_X)
        if gram is None:
            if isinstance(X, StagedAhead):
                raise RuntimeError(
                    "a micro-batch folded into its totals ahead of its fit "
                    "holds no rows, and this optimizer no longer trains "
                    "from totals (its schedule, gradient or fraction was "
                    "changed between the fold and the fit)")
            yield X
            return
        orig, (self.gradient, data) = self.gradient, gram
        try:
            yield data
        finally:
            self.gradient = orig

    def _select(self, X, y, w0, sparse_X, placed, run_span, select_span,
                valid=None):
        """``train.select``'s work for a fused fit: ``(fn, args, built)`` —
        each route names its compiled runner and its arguments, ONE call in
        ``train.dispatch`` runs them; ``built`` where the runner is a new
        ``_run_cache`` entry, which traces, lowers and compiles inside that
        call.  ``_runner``'s program is resolved HERE for its arguments
        (``StoredRun.resolve``: this optimizer's own from the second fit on,
        else one the process already runs, else the store's) and
        ``train.select`` says where it came from (``runner``: ``live``,
        ``restored``, ``stored``, ``as_was``).  Sets ``train.run``'s
        attributes (and ``by_rows``, ``class_rows`` and ``ahead`` on
        ``train.select`` too) where the spans keep them."""
        from tpu_sgd.ops.gram import GramData

        cached = len(self._run_cache)
        runner = False  # ``_runner``'s program (make_run): the step's kernel
        if sparse_X and self.mesh is not None:
            # Distributed sparse: equal-nse BCOO blocks per shard, same
            # make_run body, psum over ICI (the treeAggregate-over-sparse-
            # partitions analogue — see parallel/sparse_parallel.py).
            from tpu_sgd.parallel.sparse_parallel import (
                shard_bcoo,
                sparse_dp_run_fn,
            )

            data, idx, yd, valid, rows_local, d = shard_bcoo(self.mesh, X, y)
            with_valid = valid is not None
            key = ("sparse_run", self.gradient, self.updater,
                   self.config.structure(),
                   self.mesh, rows_local, d, with_valid)
            fn = self._run_cache.get(key)
            if fn is None:
                fn = sparse_dp_run_fn(self.gradient, self.updater,
                                      self.config.structure(), self.mesh,
                                      rows_local, d, with_valid)
                self._run_cache[key] = fn
            path, args = "sparse_mesh", (w0, data, idx, yd, self._hyper())
            if with_valid:
                args += (valid,)
        elif self.mesh is not None and placed is None:
            # a 2-D data x model mesh: the one dense mesh nothing places
            from tpu_sgd.parallel.model_parallel import dp_mp_optimize

            if self.gradient.weight_dim(X.shape[1]) != X.shape[1]:
                raise NotImplementedError(
                    "feature-axis ('model') sharding supports vector-weight "
                    "gradients only; matrix-weight gradients (multinomial) "
                    "need a 1-D 'data' mesh"
                )
            path, fn = "mesh", dp_mp_optimize
            args = (self.gradient, self.updater, self.config, self.mesh,
                    w0, X, y)
        elif self.mesh is not None:
            Xd, yd, valid = placed
            stats = self._maybe_gram_dp(X, y, Xd, yd, valid)
            if stats is not None:
                stats_leaves, block_rows = stats
                key = ("gram_dp_run", self.updater,
                       self.config.structure(), self.mesh, block_rows,
                       self.gram_aligned)
                fn = self._run_cache.get(key)
                if fn is None:
                    from tpu_sgd.parallel.gram_parallel import (
                        dp_gram_run_fn,
                    )

                    fn = dp_gram_run_fn(self.updater,
                                        self.config.structure(),
                                        self.mesh, block_rows,
                                        aligned=self.gram_aligned)
                    self._run_cache[key] = fn
                args = (w0, Xd, yd, self._hyper(), *stats_leaves)
            else:
                fn, runner = self._runner(with_valid=valid is not None), True
                args = (w0, Xd, yd, self._hyper())
                if valid is not None:
                    args += (valid,)
            path = "mesh"
        else:
            # ``valid`` on one device: a stream's row count (``RowCount``)
            fn, runner = self._runner(with_valid=valid is not None), True
            path = "gram" if isinstance(X, GramData) else "fused"
            args = (w0, X, y, self._hyper())
            if valid is not None:
                args += (valid,)
        call = fn  # what ``train.dispatch`` calls with ``args``
        if runner:
            call, origin = fn.resolve(*args)
            select_span.set(runner=origin)
        if run_span.live:
            # (labels_prepared, row_tile, feature_blocks, mask_in_kernel,
            # by_rows, class_rows, ahead, row_item_bytes, operand):
            # evaluated only where a span carries them
            kernel = (self._step_kernel(*args) if runner
                      else _NO_KERNEL + _rows_as_read(X))
            # stats: 1 where the fit runs from the totals of its rows
            stats = int(isinstance(X, GramData) and X.PG is None)
            run_span.set(
                path=path,
                shards=1 if self.mesh is None else self.mesh.devices.size,
                labels_prepared=kernel[0], row_tile=kernel[1],
                feature_blocks=kernel[2], mask_in_kernel=kernel[3],
                by_rows=kernel[4], class_rows=kernel[5], ahead=kernel[6],
                row_item_bytes=kernel[7], operand=kernel[8], stats=stats)
            select_span.set(by_rows=kernel[4], class_rows=kernel[5],
                            ahead=kernel[6], stats=stats)
        return call, args, len(self._run_cache) > cached

    def _step_kernel(self, w0, X, y, hyper=None, valid=None):  # as ``args``
        """``train.run``'s ``(labels_prepared, row_tile, feature_blocks,
        mask_in_kernel, by_rows, class_rows, ahead, row_item_bytes,
        operand)`` for the fit
        ``_runner``'s program is about to make of these arguments (a
        shard's operands under a mesh), on a TPU, read off
        ``ops.gradients.step_sums``' record of the step's kernel:
        ``labels_prepared`` 1 where there is one (the fit then lays the
        labels out once, before its loop), ``row_tile`` the rows a grid
        step of it takes and ``feature_blocks`` the blocks its body cuts the
        width into, ``mask_in_kernel`` 1 where it draws every step's
        Bernoulli mask itself (0 where the step is handed an array or draws
        nothing), ``by_rows`` 1 where it is the by-rows form (row blocks of
        an X the chip stores by rows), ``class_rows`` the padded class rows
        its two products are issued with for a matrix of weights (16 for
        ten classes, 1,008 for a thousand; 0 a vector), ``ahead`` 1 where
        the class body issues a lane chunk's margins ahead of the chunk
        before's rule (past 128 class rows: the matrix unit bounds the
        step; 0 in turn), ``row_item_bytes`` the bytes of one feature as
        the step reads it from HBM (1 for int8 rows, which the kernel
        widens in VMEM; 2 and 4 elsewhere) and ``operand`` the name of the
        type both products' operands are in.  ``_NO_KERNEL`` and
        :func:`_rows_as_read`'s two
        where the step takes ``y`` as it is and is no kernel (two reads;
        statistics; a CPU, whose program drops the row nothing reads)."""
        kernel = None
        if jax.default_backend() == "tpu":
            if self.mesh is not None:
                shards = self.mesh.devices.size

                def shard(a):
                    return None if a is None else jax.ShapeDtypeStruct(
                        (a.shape[0] // shards,) + tuple(a.shape[1:]),
                        a.dtype)

                X, y, valid = shard(X), shard(y), shard(valid)
            plan = step_sums(self.gradient, self.config, X, y, w0, valid)
            kernel = plan.kernel
        if kernel is None:
            return _NO_KERNEL + _rows_as_read(X)
        return (1, kernel.tile, kernel.feature_blocks, int(plan.mask_in_kernel),
                int(kernel.by_rows), kernel.class_rows, int(kernel.ahead),
                kernel.item_bytes, kernel.operand)

    def _place(self, X, y, valid=None):
        """``shard_dataset`` for this fit's mesh under the ``train.place``
        span: ``in_place`` 1 where the dataset already lay sharded for the
        mesh (a cached one; a host array since ``train.h2d`` sends every
        block to the device that owns it) and is trained where it lies;
        ``bytes`` is what the placement moved to lay it out (between
        devices), 0 in place.  ``valid`` is the hand-off's mask of the rows
        it padded, the mask of what it laid out."""
        from tpu_sgd.parallel.data_parallel import shard_dataset

        with span("train.place", shards=self.mesh.devices.size) as sp:
            Xd, yd, padded = shard_dataset(self.mesh, X, y)
            in_place = Xd is X and yd is y
            sp.set(in_place=int(in_place),
                   bytes=0 if in_place else X.nbytes + y.nbytes)
        return Xd, yd, valid if in_place else padded

    def _check_streamed_stats_applies(self, sparse_X):
        """Shared guards for ``set_streamed_stats`` (single-device and
        meshed)."""
        from tpu_sgd.ops.gradients import LeastSquaresGradient as _LS

        if sparse_X:
            raise NotImplementedError(
                "streamed statistics need dense rows; BCOO features are "
                "~1000x smaller and stay device-resident instead"
            )
        if self.mesh is not None and self._mesh_kind() == "dp_mp":
            raise NotImplementedError(
                "streamed statistics compose with a 1-D 'data' mesh; "
                "feature-axis ('model') sharding needs resident column "
                "blocks"
            )
        if self.host_streaming:
            raise ValueError(
                "set_streamed_stats and set_host_streaming are alternative "
                "beyond-HBM schedules; enable exactly one"
            )
        if type(self.gradient) is not _LS:
            raise NotImplementedError(
                "streamed statistics exist for least squares only (the "
                f"quadratic loss); got {type(self.gradient).__name__} — "
                "use set_host_streaming"
            )
        cfg = self.config
        if cfg.mini_batch_fraction < 1.0 and cfg.sampling != "sliced":
            raise NotImplementedError(
                "streamed statistics support sliced sampling or full "
                f"batch (got sampling={cfg.sampling!r}); use "
                "set_host_streaming for bernoulli/indexed parity"
            )

    def _optimize_streamed_stats_mesh(self, X, y, initial_weights):
        """Meshed ``set_streamed_stats``: per-shard virtual statistics
        built by streaming each shard's HOST rows to its own device, then
        the shard_map'ed virtual-stats loop (zero rows on device —
        config 4's 8-way DP shape at beyond-HBM scale;
        ``parallel/gram_parallel.py``)."""
        import numpy as np

        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_sgd.parallel.gram_parallel import (
            build_streamed_sharded_gram_stats,
            dp_virtual_gram_run_fn,
        )
        from tpu_sgd.parallel.mesh import DATA_AXIS

        if self.listener is not None or self.checkpoint_manager is not None:
            import warnings

            warnings.warn(
                "listener/checkpoint callbacks are not applied on the "
                "meshed streamed-statistics path (the shard_map'ed "
                "virtual loop has no per-iteration host hop); detach "
                "them or run single-device to combine",
                RuntimeWarning,
                stacklevel=4,
            )
        Xh = np.asarray(X)
        d = Xh.shape[1]
        entry = getattr(self, "_streamed_gram_dp_entry", None)
        opts = (self.gram_block_rows, self.gram_batch_rows,
                self._ingest_opts())
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[2] is self.mesh and entry[4] == opts):
            stats, B, n_used, yd = entry[3]
        else:
            stats, B, n_used = build_streamed_sharded_gram_stats(
                self.mesh, Xh, np.asarray(y),
                block_rows=self.gram_block_rows,
                batch_rows=self.gram_batch_rows,
                wire_dtype=self.ingest_wire_dtype,
                prefetch_depth=self.ingest_prefetch_depth,
                pipeline=self.ingest_pipeline,
            )
            k = self.mesh.shape[DATA_AXIS]
            n_local_host = Xh.shape[0] // k
            yh = np.asarray(y, np.float32)
            # labels ride along for shape parity only (the virtual window
            # path never reads them); cached with the stats so repeat
            # calls skip the concat + sharded transfer
            yd = jax.device_put(
                np.concatenate([
                    yh[i * n_local_host:i * n_local_host + n_used]
                    for i in range(k)
                ]),
                NamedSharding(self.mesh, P(DATA_AXIS)),
            )
            self._streamed_gram_dp_entry = (
                X, y, self.mesh, (stats, B, n_used, yd), opts,
            )
        w0 = _coerce_w0(self.gradient, initial_weights, d)
        dtype_name = str(np.dtype(Xh.dtype)
                         if np.issubdtype(Xh.dtype, np.inexact)
                         else np.dtype(np.float32))
        key = ("virtual_gram_dp_run", self.updater,
               self.config.structure(), self.mesh, B, n_used, d, dtype_name)
        fn = self._run_cache.get(key)
        if fn is None:
            fn = dp_virtual_gram_run_fn(self.updater,
                                        self.config.structure(),
                                        self.mesh, B, n_used, d, dtype_name)
            self._run_cache[key] = fn
        w, losses, n_rec = fn(w0, yd, self._hyper(), *stats)
        n_rec = int(n_rec)
        self._loss_history = np.asarray(losses)[:n_rec]
        if self.check_numerics:
            _raise_if_nonfinite(self._loss_history)
        return w, self._loss_history

    def _ingest_opts(self):
        """The ingest-pipeline knobs as a cache-key tuple — a wire/depth
        change must invalidate the identity-cached streamed builds (the
        statistics DEPEND on the wire dtype)."""
        return (self.ingest_wire_dtype, self.ingest_prefetch_depth,
                self.ingest_pipeline)

    def _route_streamed_stats(self, X, y):
        """Identity-cached single-device build for ``set_streamed_stats``
        (guards already checked)."""
        from tpu_sgd.ops.gram import GramLeastSquaresGradient

        entry = self._streamed_gram_entry
        opts = (self.gram_block_rows, self.gram_batch_rows,
                self._ingest_opts())
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[3] == opts):
            return entry[2]
        if entry is not None:
            self._purge_run_cache_for(entry[2])
        import numpy as np

        g = GramLeastSquaresGradient.build_streamed(
            np.asarray(X), np.asarray(y),
            block_rows=self.gram_block_rows,
            batch_rows=self.gram_batch_rows,
            wire_dtype=self.ingest_wire_dtype,
            prefetch_depth=self.ingest_prefetch_depth,
            pipeline=self.ingest_pipeline,
        )
        self._streamed_gram_entry = (X, y, g, opts)
        return g

    def trains_at_capacity(self) -> bool:
        """Whether a dense matrix may reach this optimizer's fit in an array
        of MORE rows than its own, its row count an operand
        (``StagedAhead``'s capacity form, ``RowCount``): on the stock
        resident schedule of one device alone, fused, under a gradient with
        no statistics that a plan could choose to run from (every one but
        exactly ``LeastSquaresGradient``, the planner's ``gram_able``,
        whose totals are keyed by the matrix's own shape).
        The other schedules size their state, their chunks or their
        statistics from the rows; the observed per-iteration path counts
        them on the host."""
        return (self.mesh is None and not self.host_streaming
                and not self.sufficient_stats and not self.streamed_stats
                and type(self.gradient) is not LeastSquaresGradient
                and self.listener is None
                and self.checkpoint_manager is None)

    def rows_read(self, staged) -> int:
        """The rows one step of the fit of ``staged`` (a ``StagedAhead`` in
        its capacity form) reads: its real rows rounded up to the row tile
        where the step's kernel bounds its grid by them (a TPU, a vector of
        weights over rows stored feature-major), else all of the
        capacity's, masked."""
        struct = jax.ShapeDtypeStruct
        X = struct(staged.shape, jax.dtypes.canonicalize_dtype(staged.dtype))
        y = struct(staged.shape[:1], jnp.float32)
        w = struct((self.gradient.weight_dim(staged.shape[1]),), jnp.float32)
        valid = RowCount(struct((), jnp.int32))
        if jax.default_backend() == "tpu" and isinstance(
                rows_valid(self.gradient, self.config, X, y, w, valid),
                RowCount):
            tile = self.gradient.one_read(X, y, w, valid).tile
            return min(staged.capacity, -(-staged.rows // tile) * tile)
        return staged.capacity

    def stats_in_totals(self) -> bool:
        """Whether the sufficient-stats substitution (``_maybe_gram``)
        takes the TOTALS form under this configuration: a full batch reads
        ``G``, ``b``, ``yy`` of all its rows and no window, so the build is
        one read that makes 12 MB at d = 1000 and the run holds no rows
        (``ops.gram.stats_build``); sliced windows take the prefix form."""
        return self.config.mini_batch_fraction >= 1.0

    def fits_from_totals(self) -> bool:
        """Whether a dense matrix on this optimizer's one device is trained
        from the totals of its rows: ``_maybe_gram``'s substitution in its
        totals form.  What lets a stream fold a host micro-batch's row
        blocks into ``(G, b, yy)`` as they land and keep no row
        (``StagedAhead``)."""
        return (self.sufficient_stats and self.mesh is None
                and not self.host_streaming and not self.streamed_stats
                and type(self.gradient) is LeastSquaresGradient
                and self.stats_in_totals())

    def _totals_executor(self):
        """The optimizer's ONE unbound executor of the totals form."""
        from tpu_sgd.ops.gram import GramLeastSquaresGradient

        if self._totals_gradient is None:
            self._totals_gradient = GramLeastSquaresGradient()
        return self._totals_gradient

    @staticmethod
    def _stats_span(X, y):
        """``train.stats``: the host's time in a statistics build (the
        launch of the totals' one program; all of a prefix build), a leaf
        between ``train.h2d`` and ``train.select``; ``bytes`` and ``rows``
        are what the build reads."""
        return span("train.stats", bytes=X.nbytes + y.nbytes,
                    rows=X.shape[0])

    def _maybe_gram(self, X, y, sparse_X):
        """The sufficient-stats substitution, when it applies (see
        ``set_sufficient_stats``): ``(gradient, X to train on)``, or None.

        A full batch (``stats_in_totals``) takes the totals form: built
        anew from every ``(X, y)`` in one read (nothing of a superseded
        dataset is kept, so nothing is purged), handed to the optimizer's
        ONE unbound executor, so that ``_runner``'s key and the build's
        program are the same for every dataset of one shape (a stream's
        micro-batches).  Sliced windows take the prefix form,
        identity-cached so that repeated ``optimize`` calls on the same
        arrays build once.  Either build runs under ``train.stats``.  A
        ``StagedAhead`` brings the totals of its rows with it (folded from
        its blocks under their copy) and nothing is built."""
        from tpu_sgd.ops.gradients import LeastSquaresGradient as _LS
        from tpu_sgd.ops.gram import GramLeastSquaresGradient, stats_build

        cfg = self.config
        if (sparse_X or self.mesh is not None or self.host_streaming
                or (cfg.mini_batch_fraction < 1.0
                    and cfg.sampling != "sliced")):
            return None
        if isinstance(X, StagedAhead):
            # a stream's micro-batch whose totals were folded from its row
            # blocks as they landed: nothing is built in this fit
            return (self._totals_executor(), X.totals) \
                if self.fits_from_totals() else None
        if (isinstance(self.gradient, GramLeastSquaresGradient)
                and self.gradient.data is not None
                and self.gradient.data.X is X):
            # user-built gram gradient on exactly this matrix: route its
            # GramData through so the traced program accelerates
            return self.gradient, self.gradient.data
        if not self.sufficient_stats or type(self.gradient) is not _LS:
            return None
        if self.stats_in_totals():
            with self._stats_span(X, y):
                return self._totals_executor(), stats_build(X, y)
        entry = self._gram_entry
        opts = (self.gram_block_rows, self.gram_aligned)
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[3:] == opts):
            return entry[2], entry[2].data
        if entry is not None:
            # new dataset (or new gram options): drop compiled runners
            # keyed on the superseded gram gradient so its GB-scale prefix
            # stack can be freed
            self._purge_run_cache_for(entry[2])
        with self._stats_span(X, y):
            g = GramLeastSquaresGradient.build(
                X, y, block_rows=self.gram_block_rows,
                aligned=self.gram_aligned
            )
        # keep the ORIGINAL arrays in the key: build() may re-coerce
        self._gram_entry = (X, y, g) + opts
        return g, g.data

    def _maybe_gram_dp(self, X, y, Xd, yd, valid):
        """The sufficient-stats substitution over a 1-D data mesh (see
        ``parallel/gram_parallel.py``): per-shard prefix stats, identity-
        cached per ``(X, y, mesh)``.  Returns ``(stats_leaves, block_rows)``
        or None.  Padded datasets (``valid`` mask) fall back — the gram
        window normalizes by the full window length, which would differ
        from the stock path's realized valid count."""
        from tpu_sgd.ops.gradients import LeastSquaresGradient as _LS

        cfg = self.config
        if (
            not self.sufficient_stats
            or valid is not None
            or type(self.gradient) is not _LS
            or (cfg.mini_batch_fraction < 1.0 and cfg.sampling != "sliced")
        ):
            return None
        entry = getattr(self, "_gram_dp_entry", None)
        if (entry is not None and entry[0] is X and entry[1] is y
                and entry[2] is self.mesh
                and entry[4] == self.gram_block_rows):
            return entry[3]
        from tpu_sgd.parallel.gram_parallel import build_sharded_gram_stats

        stats = build_sharded_gram_stats(self.mesh, Xd, yd,
                                         block_rows=self.gram_block_rows)
        self._gram_dp_entry = (X, y, self.mesh, stats,
                               self.gram_block_rows)
        return stats

    def _optimize_stepwise(self, X, y, w0, valid=None):
        """Observed path: jitted step per iteration with host round-trips.

        Used when a listener or checkpoint manager is attached.  Supports
        single-device and 1-D data-parallel meshes; preserves the exact loss
        history / convergence semantics of the fused path (same make_step).
        """
        import time as _time

        import numpy as np

        from tpu_sgd.utils.events import IterationEvent, RunEvent

        cfg = self.config
        if self.mesh is not None and self._mesh_kind() == "dp_mp":
            raise NotImplementedError(
                "listener/checkpoint mode supports single-device and 1-D "
                "data meshes"
            )
        sparse_shape = None
        if self.mesh is not None:
            if is_sparse(X):
                from tpu_sgd.parallel.sparse_parallel import shard_bcoo

                data, idx, y, valid, rows_local, d_feat = shard_bcoo(
                    self.mesh, X, y
                )
                X = (data, idx)  # component tuple; the stepper rebuilds
                sparse_shape = (rows_local, d_feat)
            else:
                X, y, valid = self._place(X, y, valid)
        step = self._stepper(with_valid=valid is not None,
                             sparse_shape=sparse_shape)
        hyper = self._hyper()

        # regVal probe init (same as the fused path)
        _, reg_val = self.updater.compute(
            w0, jnp.zeros_like(w0), 0.0, jnp.asarray(1, jnp.int32), cfg.reg_param
        )
        reg_val = float(reg_val)
        losses = []
        start_iter = 1
        config_key = repr((type(self.gradient).__name__,
                           type(self.updater).__name__, cfg))
        mgr = self.checkpoint_manager
        if mgr is not None:
            state = mgr.restore()
            if state is not None:
                if state["config_key"] and state["config_key"] != config_key:
                    import warnings

                    warnings.warn(
                        "checkpoint config differs from current config; "
                        "resuming anyway",
                        RuntimeWarning,
                        stacklevel=4,
                    )
                w0 = jnp.asarray(state["weights"])
                reg_val = state["reg_val"]
                losses = list(np.asarray(state["loss_history"], np.float32))
                start_iter = state["iteration"] + 1
        if self.listener is not None:
            self.listener.on_run_start(cfg)

        fused_k = int(self.superstep or 1)
        if fused_k > 1 and sparse_shape is not None:
            import warnings

            warnings.warn(
                "set_superstep applies to dense data on the meshed "
                "observed path; the sparse meshed stepper stays "
                "per-iteration",
                RuntimeWarning, stacklevel=5,
            )
            fused_k = 1
        resident_c = int(self.resident_cadence or 0)
        if resident_c >= 2 and fused_k > 1 and self.mesh is not None:
            import warnings

            warnings.warn(
                "set_residency is single-device (io_callback cadence "
                "hooks do not ride shard_map); the meshed observed "
                "path runs the fused superstep driver",
                RuntimeWarning, stacklevel=5,
            )
            resident_c = 0
        if resident_c >= 2 and fused_k <= 1:
            import warnings

            warnings.warn(
                "set_residency rides the fused superstep executor; "
                "call set_superstep(K >= 2) (or let the planner pick "
                "K) to engage the device-resident driver",
                RuntimeWarning, stacklevel=5,
            )
            resident_c = 0

        w = w0
        t_run = _time.perf_counter()
        converged_early = False
        if fused_k > 1 and resident_c >= 2:
            # Device-resident route: the WHOLE run is one lax.while_loop
            # program over fused superstep scans — one dispatch for a
            # converged-or-budget-exhausted run, host hops only at the
            # cadence io_callback (optimize/resident_driver.py).  The
            # ring ys replay through the same _replay_fused_steps, so
            # history, events, convergence, and checkpoint bytes are
            # exactly the superstep driver's (bitwise-pinned in
            # tests/test_resident.py).
            from tpu_sgd.optimize.resident_driver import (
                ResidentBookkeeper,
            )

            loop = self._resident_loop(fused_k, resident_c)

            def _save_res(ii, w_np, rv_):
                mgr.save(ii, np.asarray(w_np), rv_, np.asarray(losses),
                         config_key)

            hooks = ResidentBookkeeper(
                cfg, fused_k, resident_c, losses=losses,
                reg_val=reg_val, start_iter=start_iter,
                listener=self.listener,
                save_cb=(_save_res if mgr is not None else None),
                save_every=self.checkpoint_every,
                stop_signal=self._stop_signal,
                retry_policy=self.ingest_retry_policy,
                check_numerics=self.check_numerics)
            if start_iter <= cfg.num_iterations:
                w_np, converged_early = loop.run(
                    jnp.asarray(w0), reg_val, start_iter, (hyper, X, y),
                    hooks)
                w = jnp.asarray(w_np)
                reg_val = hooks.reg_val
        elif fused_k > 1:
            # Fused stepwise: K iterations per compiled lax.scan
            # dispatch, per-step loss/norm/weights returned as scan ys
            # and replayed host-side with the EXACT legacy bookkeeping
            # (_replay_fused_steps) — listener events, convergence at
            # the true iteration, checkpoints on the same cadence with
            # identical state.  X/y stay resident, so the only
            # per-superstep host work is the one dispatch.  On a 1-D
            # data mesh the same fused scan runs under shard_map with
            # the ICI gradient all-reduce (dp_shared_superstep_fn).
            fused = self._superstepper(fused_k,
                                       with_valid=valid is not None)

            def _save(ii, w_np, rv):
                mgr.save(ii, np.asarray(w_np), rv, np.asarray(losses),
                         config_key)

            i0 = start_iter
            while i0 <= cfg.num_iterations and not converged_early:
                steps = min(fused_k, cfg.num_iterations - i0 + 1)
                t0 = _time.perf_counter()
                # span times dispatch -> ys-on-host; the fetch below is
                # this driver's own boundary, so tracing adds zero
                # syncs/dispatches on the warmed path (the acceptance
                # pin in tests/test_obs.py)
                with span("train.superstep", i0=i0, steps=steps):
                    if valid is not None:
                        w_dev, ys = fused(
                            w, jnp.asarray(reg_val, jnp.float32), hyper,
                            jnp.asarray(i0, jnp.int32), X, y, valid,
                        )
                    else:
                        w_dev, ys = fused(
                            w, jnp.asarray(reg_val, jnp.float32), hyper,
                            jnp.asarray(i0, jnp.int32), X, y,
                        )
                    ys_host = tuple(np.asarray(a) for a in ys)  # blocks
                dt = _time.perf_counter() - t0
                t_last, reg_val, converged_early = _replay_fused_steps(
                    ys_host, i0, steps, losses, reg_val, cfg,
                    listener=self.listener, wall_dt=dt / steps,
                    check_numerics=self.check_numerics,
                    save_cb=(_save if mgr is not None else None),
                    save_every=self.checkpoint_every,
                )
                if converged_early or steps < fused_k:
                    # the run ends mid-superstep: truncate the
                    # program's overshoot — the true last iteration's
                    # state rides the ys
                    w = jnp.asarray(ys_host[0][t_last])
                else:
                    w = w_dev
                if (not converged_early and self._stop_signal is not None
                        and self._stop_signal()):
                    # cooperative preemption at the superstep BOUNDARY
                    # (the fused program cannot poll mid-scan):
                    # checkpoint the exact boundary iteration, then
                    # unwind — a resume replays from precisely here, so
                    # interrupted+resumed runs stay bitwise
                    from tpu_sgd.reliability.supervisor import (
                        TrainingPreempted,
                    )

                    boundary = i0 + steps - 1
                    if mgr is not None:
                        # graftlint: disable=host-sync -- preemption save: fires once at unwind, not per trip
                        mgr.save(boundary, np.asarray(w), reg_val,
                                 np.asarray(losses), config_key)
                    raise TrainingPreempted(boundary)
                i0 += steps
        i = start_iter
        while fused_k == 1 and i <= cfg.num_iterations:
            t0 = _time.perf_counter()
            # span around an ALREADY-contractual per-iteration barrier
            # (the observed driver's host hop IS its bookkeeping
            # contract); the span itself adds no sync
            with span("train.step", i=i):
                if valid is not None:
                    new_w, loss_i, new_reg, c = step(
                        w, X, y, jnp.asarray(i, jnp.int32),
                        jnp.asarray(reg_val), hyper, valid
                    )
                else:
                    new_w, loss_i, new_reg, c = step(
                        w, X, y, jnp.asarray(i, jnp.int32),
                        jnp.asarray(reg_val), hyper
                    )
                # the observed stepwise driver's host hop IS the
                # contract: per-iteration listener scalars and
                # convergence need the step's results on host every
                # trip — barrier once, then fetch each scalar exactly
                # once
                # graftlint: disable=host-sync -- observed driver: one barrier per step precedes the scalar reads below
                new_w = jax.block_until_ready(new_w)
            dt = _time.perf_counter() - t0
            c = int(c)  # graftlint: disable=host-sync -- observed driver: count gates the whole bookkeeping branch
            if c > 0:
                loss_f = float(loss_i)  # graftlint: disable=host-sync -- observed driver: per-iteration loss history is the contract
                if self.check_numerics and not np.isfinite(loss_f):
                    _raise_if_nonfinite([loss_f], first_iteration=i)
                losses.append(loss_f)
                # ONE fused program + ONE fetch for both norms (was two
                # eager norms with separate syncs — host-sync finding)
                delta, w_norm = (
                    float(v)
                    for v in np.asarray(step_norms(new_w, w))  # graftlint: disable=host-sync -- observed driver: the single per-step norm fetch, post-barrier
                )
                reg_val = float(new_reg)  # graftlint: disable=host-sync -- observed driver: reg_val feeds the next step's host-side argument
                if self.listener is not None:
                    self.listener.on_iteration(
                        IterationEvent(
                            iteration=i,
                            loss=loss_f,
                            weight_delta_norm=delta,
                            mini_batch_size=c,
                            wall_time_s=dt,
                        )
                    )
                if cfg.convergence_tol > 0 and i > 1:
                    if delta < cfg.convergence_tol * max(w_norm, 1.0):
                        converged_early = True
                w = new_w
                if mgr is not None and (
                    i % self.checkpoint_every == 0
                    or converged_early
                    or i == cfg.num_iterations
                ):
                    # graftlint: disable=host-sync -- checkpoint save: cadence-gated (every checkpoint_every iterations), the documented host hop
                    mgr.save(i, np.asarray(w), reg_val, np.asarray(losses),
                             config_key)
            if converged_early:
                break
            if self._stop_signal is not None and self._stop_signal():
                # cooperative preemption (set_stop_signal): checkpoint
                # the CURRENT iteration, then unwind cleanly — the
                # supervised resume replays from exactly here
                from tpu_sgd.reliability.supervisor import TrainingPreempted

                if mgr is not None:
                    # graftlint: disable=host-sync -- preemption save: fires once at unwind, not per trip
                    mgr.save(i, np.asarray(w), reg_val, np.asarray(losses),
                             config_key)
                raise TrainingPreempted(i)
            i += 1

        if self.listener is not None:
            self.listener.on_run_end(
                RunEvent(
                    event="run_completed",
                    num_iterations=len(losses),
                    final_loss=losses[-1] if losses else None,
                    converged_early=converged_early,
                    wall_time_s=_time.perf_counter() - t_run,
                )
            )
        import numpy as _np

        self._loss_history = _np.asarray(losses, _np.float32)
        return w, self._loss_history

    def _superstepper(self, k: int, with_valid: bool = False):
        """Memoized jitted fused K-step function for the stepwise
        driver (``set_superstep``) — built ONCE per (plugin pair,
        config, K, mesh) like ``_stepper``, so every superstep of a run
        (including the tail) reuses the one compiled scan program.
        Single device runs the plain scan; a 1-D data mesh runs the
        same scan under shard_map (``dp_shared_superstep_fn``)."""
        key = ("superstep", self.gradient, self.updater,
               self.config.structure(), int(k), self.mesh, with_valid)
        fn = self._run_cache.get(key)
        if fn is None:
            if self.mesh is None:
                fn = jax.jit(make_shared_batch_superstep(
                    self.gradient, self.updater, self.config.structure(),
                    int(k)))
            else:
                from tpu_sgd.parallel.data_parallel import (
                    dp_shared_superstep_fn,
                )

                fn = dp_shared_superstep_fn(
                    self.gradient, self.updater, self.config.structure(),
                    int(k), self.mesh, with_valid)
            self._run_cache[key] = fn
        return fn

    def _resident_loop(self, k: int, cadence: int):
        """Memoized device-resident whole-run program
        (``set_residency``; ``optimize/resident_driver.py``) — one
        compiled while_loop per (plugin pair, config, K, C); repeated
        runs and resumes re-dispatch the same program."""
        key = ("resident", self.gradient, self.updater,
               self.config.structure(), int(k), int(cadence))
        loop = self._run_cache.get(key)
        if loop is None:
            from tpu_sgd.optimize.resident_driver import ResidentLoop

            step = make_step(self.gradient, self.updater,
                             self.config.structure())
            # ``hyper`` rides in front of the loop's data: an operand
            loop = ResidentLoop(
                lambda w, i, rv, hyper, X, y: step(w, X, y, i, rv, hyper),
                self.config.structure(), int(k), int(cadence))
            self._run_cache[key] = loop
        return loop

    def _stepper(self, with_valid: bool, sparse_shape=None):
        """Memoized jitted single-step function (mesh-aware; pass
        ``sparse_shape=(rows_local, d)`` when X arrives as sharded BCOO
        component tuples)."""
        # Key on the objects themselves (identity hash, strong ref): an
        # id()-based key could alias a new gradient/mesh to a stale compiled
        # fn after GC id reuse.
        key = ("step", self.gradient, self.updater,
               self.config.structure(), self.mesh, with_valid, sparse_shape)
        fn = self._run_cache.get(key)
        if fn is None:
            if self.mesh is None:
                fn = jax.jit(make_step(self.gradient, self.updater,
                                       self.config.structure()))
            elif sparse_shape is not None:
                from tpu_sgd.parallel.sparse_parallel import sparse_dp_step_fn

                fn = sparse_dp_step_fn(
                    self.gradient, self.updater, self.config.structure(),
                    self.mesh, sparse_shape[0], sparse_shape[1], with_valid,
                )
            else:
                from tpu_sgd.parallel.data_parallel import dp_step_fn

                fn = dp_step_fn(self.gradient, self.updater,
                                self.config.structure(), self.mesh,
                                with_valid)
            self._run_cache[key] = fn
        return fn

    def _hyper(self):
        """This fit's step size and regulariser as the compiled programs'
        operands (``config.Hyper`` of two weakly typed scalars on the
        device, replicated over the mesh where there is one): made anew
        only where a value or the mesh changed, so a steady fit sends
        nothing."""
        cfg = self.config
        made = (cfg.step_size, cfg.reg_param, self.mesh)
        if self._hyper_made is None or self._hyper_made[0] != made:
            to = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                to = NamedSharding(self.mesh, PartitionSpec())
            self._hyper_made = (made, jax.device_put(cfg.hyper(), to))
        return self._hyper_made[1]

    def _mesh_kind(self) -> str:
        from tpu_sgd.parallel.mesh import has_model_axis

        return "dp_mp" if has_model_axis(self.mesh) else "dp"

    def _runner(self, with_valid: bool):
        """Memoized jitted runner.

        Rebuilt only when the plugin pair, the config's STRUCTURE
        (``SGDConfig.structure``: a new step size or regulariser is a new
        operand of the same program) or the mesh changes — repeated
        ``optimize`` calls (the streaming mode's per-micro-batch pattern,
        SURVEY.md §3.3) hit XLA's compile cache instead of retracing;
        measured ~3000x faster on repeat calls.  Its first call on this
        object goes through the store of exported runners beside the
        compile cache (``optimize/run_store.py``): a runner another
        optimizer of the process already runs under the same key is taken
        LIVE, one the store holds is restored, not traced.
        """
        key = ("run", self.gradient, self.updater,
               self.config.structure(), self.mesh, with_valid)
        fn = self._run_cache.get(key)
        if fn is None:
            fn = StoredRun(
                functools.partial(_make_runner, self.gradient, self.updater,
                                  self.config.structure(), self.mesh,
                                  with_valid),
                self.gradient, self.updater, self.config.structure(),
                self.mesh, with_valid)
            self._run_cache[key] = fn
        return fn


def _make_runner(gradient, updater, config, mesh, with_valid: bool):
    """``_runner``'s jitted program, traced by nobody yet: what ``StoredRun``
    builds where it finds the program neither live nor stored."""
    if mesh is None:
        return jax.jit(make_run(gradient, updater, config))
    from tpu_sgd.parallel.data_parallel import dp_run_fn

    return dp_run_fn(gradient, updater, config, mesh, with_valid)


def run_mini_batch_sgd(
    data: Dataset,
    gradient: Gradient,
    updater: Updater,
    step_size: float,
    num_iterations: int,
    reg_param: float,
    mini_batch_fraction: float,
    initial_weights: Array,
    convergence_tol: float = 0.001,
    seed: int = 42,
    mesh=None,
    sampling: str = None,
    sufficient_stats: bool = False,
) -> Tuple[Array, "jnp.ndarray"]:
    """Functional entry point, signature-parity with the reference's
    ``object GradientDescent.runMiniBatchSGD`` (SURVEY.md §2 #2).
    ``mesh``, ``sampling`` and ``sufficient_stats`` are the TPU-side
    extensions; note ``sufficient_stats`` engages on sub-unit
    mini-batch fractions only with ``sampling="sliced"`` (see
    ``GradientDescent.set_sufficient_stats``).

    Returns ``(weights, loss_history)``.
    """
    opt = GradientDescent(
        gradient,
        updater,
        SGDConfig(
            step_size=step_size,
            num_iterations=num_iterations,
            reg_param=reg_param,
            mini_batch_fraction=mini_batch_fraction,
            convergence_tol=convergence_tol,
            seed=seed,
        ),
    )
    if mesh is not None:
        opt.set_mesh(mesh)
    if sampling is not None:
        opt.set_sampling(sampling)
    if sufficient_stats:
        opt.set_sufficient_stats(True)
    return opt.optimize_with_history(data, initial_weights)
