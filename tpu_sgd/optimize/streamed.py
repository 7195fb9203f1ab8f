"""Host-streamed SGD for datasets larger than device HBM.

SURVEY.md §7 (phase 6, hard parts): config 4's full 10M x 1000 f32 dataset is
40 GB — it cannot be device-resident on a 16 GB chip.  The TPU-idiomatic
answer is to keep the dataset in host RAM, sample each iteration's
mini-batch host-side (the per-iteration seeded sample, same determinism
contract: ``default_rng(seed + i)``), and overlap iteration ``i``'s device
compute with iteration ``i+1``'s host-side batch assembly + transfer: the
sample sequence is deterministic in ``(seed, i)``, so the shared ingest
prefetcher (``tpu_sgd/io``) assembles and ``device_put``s iteration
``i+1``'s batch on a worker thread while iteration ``i``'s dispatched step
computes — only the final ``block_until_ready`` waits on the device — the
analogue of the reference's executors reading partitions while the driver
schedules the next job (SURVEY.md §3.1), without the per-iteration
scheduling cost.  An opt-in bf16 wire format (``wire_dtype``) halves the
transferred bytes on the feed-bound paths.

The device-side step is the SAME ``make_step`` the resident paths use
(frac=1.0 over the transferred batch; normalization by the realized batch
size is preserved because the host sampler marks exactly the sampled rows
valid).  All three sampling modes (bernoulli / indexed / sliced) are
honored host-side.  Bernoulli and indexed match the resident path's
distribution; sliced draws ONE global contiguous window that is then
sharded, whereas the resident mesh path draws an independent window per
shard — both are single-window-per-sampler designs, but the streamed batch
is globally contiguous where the resident mesh batch is a union of 8 local
windows.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.updaters import Updater


def sliced_window_rows(n: int, frac: float) -> int:
    """Rows per sliced-sampling window — THE definition shared by the
    sampler and by external consumers (bench's residency math), so they
    cannot silently desync on rounding."""
    return max(1, round(frac * n))


def resident_window_probability(n: int, frac: float, resident: int) -> float:
    """Probability a sliced window lies in the resident prefix: the sampler
    draws ``start ~ integers(0, n-m+1)`` and the window is resident iff
    ``start + m <= resident`` — shared with bench's recorded
    ``expected_transfer_fraction`` so the artifact cannot desync from the
    sampler's actual accept set."""
    m = sliced_window_rows(n, frac)
    return min(1.0, max(0.0, (resident - m + 1) / max(n - m + 1, 1)))


#: whole-run resident-loop memo for the streamed path — the stepwise
#: driver memoizes its loops per-optimizer (``_run_cache``), but this is
#: a free function, so the memo lives here: ``TrainingSupervisor``
#: resume attempts and repeated runs with an unchanged ``(gradient,
#: updater, config, K, C, feed)`` reuse the ONE compiled while-loop
#: program instead of re-tracing the largest program in the codebase
#: per call.  Bounded FIFO so a long-lived process cycling configs
#: doesn't pin dead programs (and their gradient objects) forever.
_RESIDENT_LOOPS: OrderedDict = OrderedDict()
_RESIDENT_LOOPS_MAX = 8

#: memo-key contract (checked by graftlint's memo-key rule): the cache
#: key must be built from exactly these roots, and every program-
#: affecting value the stored loop derives from must be covered by them
GRAFTLINT_MEMO = {
    # the loop key's locals (K, C, comp_frac, m_fixed,
    # shared_full_batch) decompose to these roots: the optimizer
    # plugins, the config, the superstep / cadence / wire knobs, and
    # the feed geometry through X
    "_RESIDENT_LOOPS": ("gradient", "updater", "config", "superstep_k",
                        "resident_cadence", "wire_compress", "X"),
}


def optimize_host_streamed(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    X: np.ndarray,
    y: np.ndarray,
    initial_weights,
    device=None,
    mesh=None,
    listener=None,
    checkpoint_manager=None,
    checkpoint_every: int = 10,
    resident_rows: int = 0,
    wire_dtype=None,
    prefetch_depth: int = 2,
    retry_policy=None,
    stop_signal=None,
    superstep_k: int = 1,
    resident_cadence: int = 0,
    wire_compress=None,
) -> Tuple[jax.Array, np.ndarray]:
    """Run mini-batch SGD with the dataset resident on the HOST.

    Returns ``(weights, loss_history)`` with the same semantics as the
    resident path: per-iteration sample of ``mini_batch_fraction`` honoring
    ``config.sampling`` (host-side, seeded ``seed + i``), loss history
    including the previous iteration's reg value, convergence tolerance
    early exit.

    ``mesh``: a 1-D data mesh combines the two scaling axes — each streamed
    batch is ``device_put`` row-sharded across cores and the step runs under
    ``shard_map`` with the ICI gradient all-reduce, so datasets beyond one
    chip's HBM still use every core (SURVEY.md §7 phase 6).

    ``resident_rows``: partial residency for datasets only somewhat beyond
    HBM (the 10M x 1000 bf16 north star is 20 GB vs a 16 GB chip): rows
    ``[0, resident_rows)`` are placed on the device ONCE, and any sliced
    window falling inside that prefix is sliced on-device — zero
    host->device traffic for a ``resident_rows/n`` fraction of iterations,
    cutting per-epoch feed bytes by the same factor while drawing the
    identical window sequence (the sampler's RNG stream is unchanged).
    Sliced sampling, single device (``mesh=None``) only.

    Ingest pipeline (``tpu_sgd/io``; README "Ingestion pipeline"): the
    window/index sequence is deterministic in ``(seed, i)``, so iteration
    ``i+1``'s whole host-side assembly — the sliced window copy, the
    INDEXED row gather, the bernoulli mask + gather, padding, wire cast,
    and the ``device_put`` dispatch — runs on a prefetch worker thread
    while iteration ``i`` computes on the device (``prefetch_depth=2`` =
    double buffer; ``0`` = the legacy inline assembly, bitwise the same
    trajectory).  ``wire_dtype="bfloat16"`` (opt-in) halves the bytes of
    every transferred batch; the step then consumes bf16 rows, which is
    exactly the north-star host dtype (see the wire-safety notes in
    ``tpu_sgd/io/wire.py``).

    Reliability (``tpu_sgd/reliability``): ``retry_policy`` re-runs a
    failed host-side sample/transfer with seeded backoff (transient
    ``device_put`` faults heal in place).  ``stop_signal`` is a zero-arg
    callable polled once per iteration — the ``TrainingSupervisor``'s
    cooperative preemption hook: when it returns True the CURRENT state
    is checkpointed and ``TrainingPreempted`` unwinds cleanly; a later
    run with the same checkpoint manager resumes and, because every
    iteration is deterministic in ``(seed, i)``, finishes with
    bitwise-identical final weights (f32 wire).  The iteration body and
    the transfer pass the ``optimize.streamed.step`` /
    ``io.device_put`` failpoints.

    Superstep fusion (``superstep_k=K > 1``; README "Fused stepping"):
    K consecutive iterations run as ONE compiled ``lax.scan`` program,
    and the prefetch worker assembles a K-batch *superchunk*
    (``tpu_sgd.io.stack_superchunk``, the ``io.superstep`` failpoint)
    so ``device_put`` and program dispatch each fire once per K
    iterations instead of once per iteration — the per-iteration host
    dispatch tax drops ~K× (BENCH_SUPERSTEP.json).  Per-step math is
    the SAME ``make_step`` over the SAME deterministic sample sequence;
    per-step loss/norm/weights return as scan ys and replay host-side
    with the legacy bookkeeping, so the loss-history length, the
    detected convergence iteration, and the checkpoint cadence are
    exactly the K=1 loop's, and every same-program contract stays
    bitwise (fused runs replay, RESUME, and prefetch-A/B to identical
    weights, all three sampling modes).  Versus the K=1 loop the
    trajectories agree to reassociation noise — XLA lowers the batch
    dot differently inside the scanned program (~1 ulp/step; the same
    cross-program caveat as ``resident_step`` above — see
    ``make_superstep``).  ``stop_signal`` is polled at superstep
    BOUNDARIES
    (worst-case preemption latency: K iterations; the boundary
    iteration is checkpointed exactly).  Full-batch feeds
    (``mini_batch_fraction >= 1``) transfer the batch ONCE and scan
    over it.  A mesh shards the superchunk row-wise under the shared
    ``superchunk_specs`` layout (``dp_superstep_fn``), and
    ``resident_rows`` rides the same scan body with a per-step
    resident/transferred flag — both fuse since PR 6.
    ``resident_cadence >= 2`` additionally moves the WHOLE run loop on
    device for the full-batch and fully-resident-slab feeds (README
    "Device-resident training"); host-sampled feeds keep the superstep
    driver (warned — the host hop is the data feed).

    Compressed gradient wire (``wire_compress="topk:<frac>"``; README
    "Compressed wire"): the per-step gradient combine ships top-k
    ``(values, indices)`` segments with per-shard error-feedback state
    instead of a dense all-reduce (``make_compressed_step``).  The EF
    accumulator is optimizer state: it rides the superstep scan carry,
    is checkpointed (``extras={"ef": ...}``) at every save — cadence,
    convergence, and preemption — and restores on resume, so an
    interrupted+resumed compressed run is bitwise equal to its
    uninterrupted twin.  Composes with ``superstep_k`` AND with the
    whole-run resident driver (``resident_cadence >= 2`` on the
    full-batch or fully-resident-slab feed): the EF accumulator rides
    the while-loop carry with its per-step history on a ring leaf, so
    a compressed resident run is ONE dispatch per run like the dense
    one (tests/test_composition.py).  Only PARTIAL residency falls
    back to the dense wire with a warning (the mixed
    resident/transferred window step carries no EF state — the grid's
    recorded fallback cell).
    """
    import time as _time

    from tpu_sgd.io import (Prefetcher, parse_wire_compress,
                            resolve_wire_dtype, wire_cast)
    from tpu_sgd.io.integrity import seal, verify
    from tpu_sgd.obs.counters import record_wire
    from tpu_sgd.obs.spans import span
    from tpu_sgd.optimize.gradient_descent import (make_compressed_step,
                                                   make_step,
                                                   observed_loop_tail)
    from tpu_sgd.reliability.failpoints import corruptpoint, failpoint
    from tpu_sgd.utils.events import RunEvent

    cfg = config
    n = X.shape[0]
    w = jnp.asarray(initial_weights)
    if not jnp.issubdtype(w.dtype, jnp.inexact):
        w = w.astype(jnp.float32)
    if n == 0:
        return w, np.zeros((0,), np.float32)
    wd = resolve_wire_dtype(wire_dtype, X.dtype)
    comp_frac = parse_wire_compress(wire_compress)
    # frac applied host-side; the device step consumes the whole batch.
    step_cfg = cfg.replace(mini_batch_fraction=1.0)
    frac = cfg.mini_batch_fraction
    m_fixed = sliced_window_rows(n, frac)
    R = 0
    if resident_rows:
        if mesh is not None:
            raise NotImplementedError(
                "resident_rows composes with a single device; a mesh "
                "shards the resident slab with its own layout — use the "
                "fully-resident mesh path or plain streaming"
            )
        if cfg.sampling != "sliced" or frac >= 1.0:
            raise NotImplementedError(
                "resident_rows requires sampling='sliced' with "
                "mini_batch_fraction < 1 (contiguous windows are what can "
                "be sliced on-device)"
            )
        R = min(int(resident_rows), n)
        if R < m_fixed:
            raise ValueError(
                f"resident_rows={resident_rows} is smaller than one "
                f"window ({m_fixed} rows); no window can ever hit the "
                "resident prefix — raise it or use plain streaming"
            )
    K = max(1, int(superstep_k))
    C = max(0, int(resident_cadence))
    # fully-resident slab: R == n means EVERY sliced window lands in the
    # resident prefix — the feed is device-resident-sample and the
    # whole-run resident driver can take it (zero steady-state transfer)
    fully_resident = bool(R) and R >= n
    if C >= 2 and K <= 1:
        import warnings

        warnings.warn(
            "device residency rides the fused superstep executor; pass "
            "superstep_k >= 2 (or let the planner pick K) to engage it",
            RuntimeWarning, stacklevel=3,
        )
        C = 0
    if C >= 2 and (mesh is not None
                   or not (frac >= 1.0 or fully_resident)):
        import warnings

        warnings.warn(
            "device residency applies to the single-device full-batch "
            "and fully-resident-slab feeds (a host-sampled feed's host "
            "hop IS the data feed); running the fused superstep driver "
            "— the recorded composition-grid cell for this feed "
            "(tests/test_composition.py, feed=host-sampled x resident)",
            RuntimeWarning, stacklevel=3,
        )
        C = 0
    if comp_frac is not None and R and not (fully_resident and C >= 2):
        import warnings

        # a PARTIALLY-resident window feed mixes on-device and
        # transferred windows through steps that carry no EF state
        # (make_resident_window_superstep / resident_step) — the dense
        # wire runs instead, per the recorded composition-grid cell
        # (tests/test_composition.py, feed=slab-partial x compressed).
        # A FULLY-resident slab with resident_cadence >= 2 composes:
        # the EF accumulator rides the while-loop carry (the lifted
        # PR 9 DEVIATION — see resident_driver.ResidentLoop).
        warnings.warn(
            "wire_compress with a partially-resident window feed runs "
            "the dense gradient wire (the resident-window step has no "
            "EF carry; composition grid cell feed=slab-partial x "
            "compressed) — a fully resident slab with "
            "resident_cadence >= 2 carries EF in the while-loop ring",
            RuntimeWarning, stacklevel=3,
        )
        comp_frac = None
    if mesh is None:
        if device is None:
            device = jax.devices()[0]
        w_sharding = device
        base_step = make_step(gradient, updater, step_cfg)
        if comp_frac is not None:
            step = jax.jit(make_compressed_step(
                gradient, updater, step_cfg, comp_frac))
        else:
            step = jax.jit(base_step)
        row_sharding = mask_sharding = device
        super_row_sharding = super_mask_sharding = device
        ef_sharding = device
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_sgd.parallel.data_parallel import (dp_compressed_step_fn,
                                                    dp_step_fn)
        from tpu_sgd.parallel.mesh import DATA_AXIS, superchunk_specs

        if comp_frac is not None:
            step = dp_compressed_step_fn(
                gradient, updater, step_cfg, comp_frac, mesh,
                with_valid=True)
        else:
            step = dp_step_fn(gradient, updater, step_cfg, mesh,
                              with_valid=True)
        w_sharding = NamedSharding(mesh, P())
        row_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
        mask_sharding = NamedSharding(mesh, P(DATA_AXIS))
        spec_xs, spec_ys, _ = superchunk_specs()
        super_row_sharding = NamedSharding(mesh, spec_xs)
        super_mask_sharding = NamedSharding(mesh, spec_ys)
        ef_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    w = jax.device_put(w, w_sharding)
    # the step size and the regulariser: operands of every step program
    # below (``make_step``), placed once beside the weights
    hyper = jax.device_put(cfg.hyper(), w_sharding)

    _, reg_val = updater.compute(
        w, jnp.zeros_like(w), 0.0, jnp.asarray(1, jnp.int32), cfg.reg_param
    )

    # Fixed row cap so the device step compiles once.  Bernoulli batches are
    # variable-size: cap at the binomial mean + 6 sigma + slack (overflow is
    # astronomically rare; a uniformly random subset is kept on overflow —
    # shuffle before truncation — so the estimate stays unbiased).  Indexed
    # and sliced batches are fixed-size by construction.
    if frac >= 1.0:
        cap = n
    elif cfg.sampling == "bernoulli":
        sigma = np.sqrt(n * frac * (1.0 - frac))
        cap = int(min(n, np.ceil(n * frac + 6.0 * sigma + 8)))
    else:  # indexed / sliced: same batch size as the device-resident path
        cap = m_fixed
    if mesh is not None:
        n_shards = mesh.shape[DATA_AXIS]
        cap += (-cap) % n_shards  # even shards; padding rows are invalid

    if R:
        # One-time placement of the resident prefix; windows inside it are
        # sliced on-device by the SAME step math (identical window sequence
        # and mask/count ops; the two compiled programs may fuse
        # differently, so trajectories agree to reassociation noise).  The
        # slab rides at the WIRE dtype so the resident and transferred
        # windows feed the same compiled step.
        Xres = jax.device_put(wire_cast(X[:R], wd), device)
        yres = jax.device_put(y[:R], device)
        ones_mask = jnp.ones((m_fixed,), bool)

        @jax.jit
        def resident_step(w, Xr, yr, start, i, reg_val, hyper):
            Xb = jax.lax.dynamic_slice_in_dim(Xr, start, m_fixed, 0)
            yb = jax.lax.dynamic_slice_in_dim(yr, start, m_fixed, 0)
            return base_step(w, Xb, yb, i, reg_val, hyper, ones_mask)

        # Prewarm BOTH compiled programs (dummy on-device inputs, no host
        # transfer): the window sequence decides per iteration which
        # program runs, so without this the OTHER program's first compile
        # would land mid-run at an RNG-dependent iteration — a multi-second
        # wall spike that corrupts steady-state timing.  The fused K > 1
        # drivers run ONE program for both window kinds and compile it on
        # their own first dispatch — no prewarm to do.
        if K == 1:
            i0 = jnp.asarray(1, jnp.int32)
            r0 = jnp.zeros((), jnp.float32)
            jax.block_until_ready(resident_step(
                w, Xres, yres, jnp.asarray(0, jnp.int32), i0, r0, hyper
            ))
            Xb0 = jnp.zeros((m_fixed,) + X.shape[1:], Xres.dtype)
            yb0 = jnp.zeros((m_fixed,), yres.dtype)
            v0 = jnp.ones((m_fixed,), bool)
            jax.block_until_ready(step(w, Xb0, yb0, i0, r0, hyper, v0))
            del Xb0, yb0, v0

    _gather = lambda A, idx: A[idx]
    if X.flags.c_contiguous:  # native gather requires contiguous rows
        try:  # multi-threaded row gather; X[idx] fallback
            from tpu_sgd.utils.native import gather_rows as _native_gather

            _native_gather(X[:1], np.zeros((1,), np.int64))  # probe once
            _gather = _native_gather
        except Exception:
            pass

    # frac >= 1: the "sample" is the whole dataset every iteration — the
    # host-side assembly is IDENTICAL across iterations and must be paid
    # once, not re-gathered per step (a full (n, d) memcpy that roughly
    # doubles the host feed cost the overlap exists to hide)
    _full_batch = [None]

    _wire_fmt = "bf16" if wd is not None else "dense-f32"

    def _put_batch(Xb, yb, valid):
        """The host→device hop of one assembled batch — THE transfer
        fault-injection site (``io.device_put``); retries, when
        configured, wrap the whole sample via the prefetcher.

        The chunk is a checksummed FRAME (tpu_sgd/io/integrity.py):
        sealed over the assembled host bytes, passed through the
        ``io.chunk`` corrupting failpoint (the modeled wire/DMA damage
        window), and verified at this consume boundary — the last host
        instant before the bytes become a device buffer.  A mismatch
        raises typed IntegrityError inside the prefetcher's retry
        scope, and the deterministic (seed, i) reassembly heals it
        BITWISE."""
        failpoint("io.device_put")
        ck = seal(Xb, yb, valid)
        Xb, yb, valid = corruptpoint("io.chunk", (Xb, yb, valid))
        verify("io.chunk", ck, Xb, yb, valid)
        record_wire(
            _wire_fmt,
            logical_nbytes=int(Xb.size * 4 + yb.nbytes + valid.nbytes),
            physical_nbytes=int(Xb.nbytes + yb.nbytes + valid.nbytes))
        return ("batch", (
            jax.device_put(Xb, row_sharding),
            jax.device_put(yb, mask_sharding),
            jax.device_put(valid, mask_sharding),
        ))

    def sample_host(i: int):
        """Per-iteration HOST-side sample honoring ``config.sampling`` —
        bernoulli (RDD.sample parity), indexed (fixed-size gather with
        replacement), or sliced (contiguous window) — deterministic in
        ``default_rng(seed + i)`` and padded to the fixed cap.  Pure
        host assembly (gather, pad, wire cast); the transfer belongs to
        the caller, so the SAME assembly feeds both the per-iteration
        feed (one ``_put_batch`` per batch) and the superstep feed (K
        batches stacked into one superchunk, one put).

        Returns a tagged pair: ``("resident", start)`` for an on-device
        window of the resident prefix, or ``("host", (Xb, yb, valid))``
        with cap-row host arrays — explicit dispatch, no
        type-sniffing."""
        rng = np.random.default_rng(cfg.seed + i)
        if frac < 1.0 and cfg.sampling == "sliced":
            # Contiguous window: a plain slice (zero-copy view on an f32
            # wire), never the row gather — sequential host I/O is this
            # mode's entire point.
            start = int(rng.integers(0, max(1, n - m_fixed + 1)))
            if start + m_fixed <= R:
                # window lies in the device-resident prefix: no transfer;
                # the RNG stream is identical either way, so residency
                # changes WHERE a window is read from, never WHICH windows
                # are drawn
                return ("resident", start)
            Xb = wire_cast(X[start:start + m_fixed], wd)
            yb = y[start:start + m_fixed]
            valid = np.ones((cap,), bool)
            if cap > m_fixed:  # mesh shard padding: one tail memcpy
                valid[m_fixed:] = False
                Xp = np.zeros((cap, X.shape[1]), Xb.dtype)
                Xp[:m_fixed] = Xb
                yp = np.zeros((cap,), y.dtype)
                yp[:m_fixed] = yb
                Xb, yb = Xp, yp
            return ("host", (Xb, yb, valid))
        if frac >= 1.0:
            if _full_batch[0] is None:
                Xw = wire_cast(X, wd)
                if cap == n:
                    # no shard padding: stream the rows as they are —
                    # no host copy at all (f32 wire; the bf16 wire cast
                    # above is the one host pass, paid once and cached)
                    _full_batch[0] = (Xw, y, np.ones((cap,), bool))
                else:
                    Xp = np.zeros((cap, X.shape[1]), Xw.dtype)
                    Xp[:n] = Xw
                    yp = np.zeros((cap,), y.dtype)
                    yp[:n] = y
                    valid = np.zeros((cap,), bool)
                    valid[:n] = True
                    _full_batch[0] = (Xp, yp, valid)
            return ("host", _full_batch[0])
        if cfg.sampling == "indexed":
            idx = rng.integers(0, n, size=m_fixed)
        else:  # bernoulli
            m = rng.random(n) < frac
            idx = np.nonzero(m)[0]
            if idx.shape[0] > cap:
                idx = rng.permutation(idx)[:cap]
        valid = np.zeros((cap,), bool)
        valid[: idx.shape[0]] = True
        pad = np.zeros((cap,), np.int64)
        pad[: idx.shape[0]] = idx
        # the gather itself rides the prefetch worker (the i+1 lookahead),
        # so this host pass overlaps iteration i's device step
        return ("host", (wire_cast(_gather(X, pad), wd), y[pad], valid))

    def sample(i: int):
        """``sample_host`` plus the transfer — the per-iteration
        producer the legacy (K=1) prefetch loop consumes."""
        kind, payload = sample_host(i)
        if kind == "resident":
            return (kind, payload)
        return _put_batch(*payload)

    def _put_super(Xs, Ys, Vs):
        """The host→device hop of one assembled K-step superchunk —
        the same ``io.device_put`` failpoint/retry scope as
        ``_put_batch``, with the ``(K, rows, ...)`` shardings from
        ``superchunk_specs`` (row axis sharded on a mesh, step axis
        replicated).  Same checksummed-frame contract as
        ``_put_batch`` — one seal/verify per superchunk, so the
        integrity plane's host cost amortizes with K exactly like the
        dispatch tax the superstep exists to amortize."""
        failpoint("io.device_put")
        ck = seal(Xs, Ys, Vs)
        Xs, Ys, Vs = corruptpoint("io.chunk", (Xs, Ys, Vs))
        verify("io.chunk", ck, Xs, Ys, Vs)
        record_wire(
            _wire_fmt,
            logical_nbytes=int(Xs.size * 4 + Ys.nbytes + Vs.nbytes),
            physical_nbytes=int(Xs.nbytes + Ys.nbytes + Vs.nbytes))
        return (jax.device_put(Xs, super_row_sharding),
                jax.device_put(Ys, super_mask_sharding),
                jax.device_put(Vs, super_mask_sharding))

    def sample_super(base: int):
        """Superstep producer: assemble the K per-iteration batches for
        iterations ``[base, base+K)`` into ONE ``(K, cap, ...)``
        superchunk (host numpy; ``tpu_sgd.io.stack_superchunk`` — the
        ``io.superstep`` failpoint) and transfer it with a single
        ``device_put`` per leaf (row-sharded over a mesh when one is
        set).  A tail superstep (fewer than K real
        iterations left) pads with zero rows and all-False valid masks,
        which the fused step turns into no-op updates — the fixed (K,
        cap) shape keeps the scan program compiled exactly once.  Runs
        on the prefetch worker, inside the retry scope, like every
        other producer."""
        from tpu_sgd.io import stack_superchunk

        steps = min(K, cfg.num_iterations - base + 1)
        parts = [sample_host(base + t)[1] for t in range(steps)]
        Xs, Ys, Vs = stack_superchunk(
            [p[0] for p in parts], [p[1] for p in parts],
            [p[2] for p in parts], k=K)
        return _put_super(Xs, Ys, Vs)

    def sample_super_resident(base: int):
        """Partial-residency superstep producer: a per-step window that
        lands in the resident prefix rides as a ``(start, True)`` flag
        pair with zero rows in the superchunk (the fixed shape still
        transfers — fusing trades those windows' transfer-byte savings
        for the K-fold dispatch cut, see
        ``make_resident_window_superstep``), while non-resident windows
        assemble and transfer exactly like ``sample_super``'s.  One put
        per superstep, same failpoint/retry scope as every producer."""
        from tpu_sgd.io import stack_superchunk

        steps = min(K, cfg.num_iterations - base + 1)
        starts = np.zeros((K,), np.int32)
        flags = np.zeros((K,), bool)
        xdt = np.dtype(wd) if wd is not None else X.dtype
        zeros = None
        parts = []
        for t in range(steps):
            kind, payload = sample_host(base + t)
            if kind == "resident":
                starts[t] = payload
                flags[t] = True
                if zeros is None:
                    zeros = (np.zeros((cap, X.shape[1]), xdt),
                             np.zeros((cap,), y.dtype),
                             np.ones((cap,), bool))
                parts.append(zeros)
            else:
                parts.append(payload)
        Xs, Ys, Vs = stack_superchunk(
            [p[0] for p in parts], [p[1] for p in parts],
            [p[2] for p in parts], k=K)
        Xd, Yd, Vd = _put_super(Xs, Ys, Vs)
        return (jax.device_put(starts, device),
                jax.device_put(flags, device), Xd, Yd, Vd)

    if listener is not None:
        listener.on_run_start(cfg)
    losses = []
    start_iter = 1
    config_key = repr((type(gradient).__name__, type(updater).__name__, cfg))
    ef_resume = None
    if checkpoint_manager is not None:
        state = checkpoint_manager.restore()
        if state is not None:
            if state["config_key"] and state["config_key"] != config_key:
                import warnings

                warnings.warn(
                    "checkpoint config differs from current config; resuming "
                    "anyway",
                    RuntimeWarning,
                    stacklevel=3,
                )
            w = jax.device_put(jnp.asarray(state["weights"]), w_sharding)
            reg_val = state["reg_val"]
            losses = list(np.asarray(state["loss_history"], np.float32))
            start_iter = state["iteration"] + 1
            ef_resume = state.get("extras", {}).get("ef")
    ef = None
    if comp_frac is not None:
        # error feedback is OPTIMIZER STATE (ADVICE.md): a fresh run
        # starts the accumulator at zero; a resumed compressed run MUST
        # restore the checkpointed accumulator or it stops being
        # bitwise vs its uninterrupted twin
        dim = int(w.shape[-1])
        if mesh is None:
            ef0 = np.zeros((dim,), np.float32)
        else:
            ef0 = np.zeros((mesh.shape[DATA_AXIS], dim), np.float32)
        if ef_resume is not None:
            ef0 = np.asarray(ef_resume, np.float32).reshape(ef0.shape)
        elif start_iter > 1:
            import warnings

            warnings.warn(
                "resuming a compressed run from a checkpoint without EF "
                "state (written by an uncompressed run?); the "
                "accumulator restarts at zero — the trajectory will not "
                "be bitwise vs an uninterrupted compressed run",
                RuntimeWarning, stacklevel=3,
            )
        ef = jax.device_put(jnp.asarray(ef0), ef_sharding)
    t_run = _time.perf_counter()
    converged = False

    # iteration-exact EF for mid-superstep checkpoint saves: the
    # replay's save_cb fires at iteration ii inside the CURRENT
    # superstep, whose per-step post-update accumulators sit in the
    # ys' seventh leaf (installed before each replay); the K=1 loop
    # never installs a window, so its saves read the live accumulator
    _ef_window = {"efs": None, "i0": start_iter}

    def _save(ii, w_np, rv):
        extras = None
        if comp_frac is not None:
            efs = _ef_window["efs"]
            extras = {"ef": (efs[ii - _ef_window["i0"]]
                             if efs is not None else np.asarray(ef))}
        checkpoint_manager.save(ii, np.asarray(w_np), rv,
                                np.asarray(losses), config_key,
                                extras=extras)

    if K > 1:
        # Superstep executor: ONE compiled lax.scan program advances K
        # iterations per dispatch; the prefetcher stages whole
        # superchunks, so device_put ALSO fires once per K iterations.
        # Per-step (weights, loss, reg, count, norms) return as scan ys
        # and replay host-side with the legacy loop's exact bookkeeping
        # (_replay_fused_steps) — same loss history, same convergence
        # iteration, same checkpoint bytes.  A mesh runs the same scan
        # under shard_map; partial residency runs the mixed
        # resident/transferred-window scan; and resident_cadence >= 2
        # on a device-resident-data feed escalates to the whole-run
        # resident driver below.
        from tpu_sgd.optimize.gradient_descent import (
            _replay_fused_steps,
            make_resident_window_superstep,
            make_shared_batch_superstep,
            make_superstep,
        )
        from tpu_sgd.reliability.supervisor import TrainingPreempted

        shared_full_batch = frac >= 1.0
        window_resident = bool(R) and not shared_full_batch

        def _full_batch_transfer():
            # THE one-time full-batch device_put, inside the ingest
            # retry scope (it runs outside a prefetcher, so a transient
            # fault must heal here exactly as on the per-iteration
            # feed) — shared by the resident and superstep drivers
            def _t():
                return sample(start_iter)

            _, put = (retry_policy.call(_t)
                      if retry_policy is not None else _t())
            return put

        if C >= 2:
            # Whole-run device-resident driver
            # (optimize/resident_driver.py): the per-iteration data is
            # already on device — the one-time full-batch transfer, or
            # the fully-resident slab plus a precomputed window-start
            # sequence — so the entire converged-or-budget-exhausted
            # run is ONE program dispatch; the host hops only at the
            # cadence io_callback, whose ring ys replay through the
            # same _replay_fused_steps as the superstep loop below
            # (bitwise-pinned in tests/test_resident.py).
            from tpu_sgd.optimize.resident_driver import (
                ResidentBookkeeper,
                ResidentLoop,
            )

            if start_iter <= cfg.num_iterations:
                # compressed wire on the resident driver: the EF
                # accumulator is a CARRY LEAF of the same while-loop
                # (with_extra) and its per-step history rides the ring,
                # exactly as make_compressed_superstep carries it in
                # the scan — one driver, many carries (ADVICE.md)
                comp_step = (make_compressed_step(
                    gradient, updater, step_cfg, comp_frac)
                    if comp_frac is not None else None)
                # ``hyper`` rides in front of the loop's data: an operand
                # of the one program, like the rows
                if shared_full_batch:
                    res_data = (hyper,) + tuple(_full_batch_transfer())

                    if comp_frac is not None:
                        def _res_step(w_, e_, i_, rv_, hy, Xr, yr, vr):
                            return comp_step(w_, e_, Xr, yr, i_, rv_,
                                             hy, vr)
                    else:
                        def _res_step(w_, i_, rv_, hy, Xr, yr, vr):
                            return base_step(w_, Xr, yr, i_, rv_, hy, vr)
                else:
                    # fully-resident sliced slab: the window sequence
                    # is deterministic in (seed, i) — replay THE host
                    # sampler's draws up front (every window of a
                    # fully-resident slab returns ("resident", start),
                    # zero assembly) so the on-device run consumes the
                    # IDENTICAL windows from the one authoritative RNG
                    # rule (one tiny (N,) int32 transfer, once per run)
                    starts_np = np.empty((cfg.num_iterations,),
                                         np.int32)
                    for it in range(1, cfg.num_iterations + 1):
                        tag, start = sample_host(it)
                        assert tag == "resident", tag
                        starts_np[it - 1] = start
                    starts_d = jax.device_put(starts_np, device)
                    res_data = (hyper, Xres, yres, starts_d)

                    if comp_frac is not None:
                        def _res_step(w_, e_, i_, rv_, hy, Xr, yr, st):
                            s0 = st[i_ - 1]
                            Xb = jax.lax.dynamic_slice_in_dim(
                                Xr, s0, m_fixed, 0)
                            yb = jax.lax.dynamic_slice_in_dim(
                                yr, s0, m_fixed, 0)
                            return comp_step(w_, e_, Xb, yb, i_, rv_,
                                             hy, ones_mask)
                    else:
                        def _res_step(w_, i_, rv_, hy, Xr, yr, st):
                            s0 = st[i_ - 1]
                            Xb = jax.lax.dynamic_slice_in_dim(
                                Xr, s0, m_fixed, 0)
                            yb = jax.lax.dynamic_slice_in_dim(
                                yr, s0, m_fixed, 0)
                            return base_step(w_, Xb, yb, i_, rv_,
                                             hy, ones_mask)

                # the loop's program depends only on (step math, cfg,
                # K, C, wire) and the feed shape family — memo hit =
                # zero re-trace on resume/replay with the same
                # optimizer
                loop_key = (gradient, updater, cfg, K, C, comp_frac,
                            ("full",) if shared_full_batch
                            else ("slab", m_fixed))
                loop = _RESIDENT_LOOPS.get(loop_key)
                if loop is None:
                    loop = ResidentLoop(
                        _res_step, cfg, K, C,
                        with_extra=comp_frac is not None)
                    _RESIDENT_LOOPS[loop_key] = loop
                    while len(_RESIDENT_LOOPS) > _RESIDENT_LOOPS_MAX:
                        _RESIDENT_LOOPS.popitem(last=False)

                def _install_ef_window(i0w, exs):
                    # iteration-exact EF for checkpoint saves fired
                    # inside this window's replay (_save reads it)
                    _ef_window["efs"] = exs
                    _ef_window["i0"] = int(i0w)

                hooks = ResidentBookkeeper(
                    cfg, K, C, losses=losses, reg_val=reg_val,
                    start_iter=start_iter, listener=listener,
                    save_cb=(_save if checkpoint_manager is not None
                             else None),
                    save_every=checkpoint_every,
                    stop_signal=stop_signal,
                    retry_policy=retry_policy,
                    extras_cb=(_install_ef_window
                               if comp_frac is not None else None))
                # the iteration-body failpoint fires once per DISPATCH,
                # as on every other driver — one hit per resident run
                failpoint("optimize.streamed.step")
                if comp_frac is not None:
                    w_np, converged = loop.run(w, reg_val, start_iter,
                                               res_data, hooks,
                                               extra0=ef)
                else:
                    w_np, converged = loop.run(w, reg_val, start_iter,
                                               res_data, hooks)
                w = jax.device_put(jnp.asarray(w_np), w_sharding)
                reg_val = hooks.reg_val
            if listener is not None:
                listener.on_run_end(RunEvent(
                    event="run_completed",
                    num_iterations=len(losses),
                    final_loss=losses[-1] if losses else None,
                    converged_early=converged,
                    wall_time_s=_time.perf_counter() - t_run,
                ))
            return w, np.asarray(losses, np.float32)

        if mesh is not None:
            from tpu_sgd.parallel.data_parallel import (
                dp_compressed_shared_superstep_fn,
                dp_compressed_superstep_fn,
                dp_shared_superstep_fn,
                dp_superstep_fn,
            )

            if shared_full_batch:
                if comp_frac is not None:
                    fused = dp_compressed_shared_superstep_fn(
                        gradient, updater, step_cfg, comp_frac, K,
                        mesh, True)
                else:
                    fused = dp_shared_superstep_fn(
                        gradient, updater, step_cfg, K, mesh, True)
            elif comp_frac is not None:
                fused = dp_compressed_superstep_fn(
                    gradient, updater, step_cfg, comp_frac, mesh)
            else:
                fused = dp_superstep_fn(gradient, updater, step_cfg,
                                        mesh)
        elif shared_full_batch:
            # the full-batch "sample" is identical every iteration:
            # transfer it ONCE and let the scan reuse it — zero
            # per-iteration AND zero per-superstep transfer
            if comp_frac is not None:
                from tpu_sgd.optimize.gradient_descent import (
                    make_compressed_shared_superstep,
                )

                fused = jax.jit(make_compressed_shared_superstep(
                    gradient, updater, step_cfg, comp_frac, K))
            else:
                fused = jax.jit(make_shared_batch_superstep(
                    gradient, updater, step_cfg, K))
        elif window_resident:
            fused = jax.jit(make_resident_window_superstep(
                gradient, updater, step_cfg, m_fixed))
        elif comp_frac is not None:
            from tpu_sgd.optimize.gradient_descent import (
                make_compressed_superstep,
            )

            fused = jax.jit(make_compressed_superstep(
                gradient, updater, step_cfg, comp_frac))
        else:
            fused = jax.jit(make_superstep(gradient, updater, step_cfg))

        prefetch = None
        try:
            if shared_full_batch:
                if start_iter <= cfg.num_iterations:
                    Xd, yd, vd = _full_batch_transfer()
            else:
                producer = (sample_super_resident if window_resident
                            else sample_super)
                prefetch = Prefetcher(
                    producer,
                    range(start_iter, cfg.num_iterations + 1, K),
                    depth=prefetch_depth, retry_policy=retry_policy)
                nxt = (next(prefetch)
                       if start_iter <= cfg.num_iterations else None)
            i0 = start_iter
            while i0 <= cfg.num_iterations and not converged:
                steps = min(K, cfg.num_iterations - i0 + 1)
                t0 = _time.perf_counter()
                failpoint("optimize.streamed.step")
                # Dispatch the fused program FIRST (async), pull the
                # next superchunk while the device runs the K steps,
                # and only then block on the ys fetch.  The span times
                # dispatch -> ys-on-host; attrs are HOST ints, and the
                # ys fetch below is the driver's own documented
                # boundary, so tracing adds zero syncs (the acceptance
                # pin in tests/test_obs.py)
                with span("train.superstep", i0=i0, steps=steps):
                    if shared_full_batch:
                        if comp_frac is not None:
                            w_dev, ef, ys = fused(
                                w, ef, jnp.asarray(reg_val, jnp.float32),
                                hyper,
                                jnp.asarray(i0, jnp.int32), Xd, yd, vd)
                        else:
                            w_dev, ys = fused(
                                w, jnp.asarray(reg_val, jnp.float32),
                                hyper,
                                jnp.asarray(i0, jnp.int32), Xd, yd, vd)
                    elif window_resident:
                        w_dev, ys = fused(
                            w, jnp.asarray(reg_val, jnp.float32), hyper,
                            jnp.asarray(i0, jnp.int32), Xres, yres,
                            *nxt)
                        if i0 + K <= cfg.num_iterations:
                            nxt = next(prefetch)
                    else:
                        Xs, Ys, Vs = nxt
                        if comp_frac is not None:
                            w_dev, ef, ys = fused(
                                w, ef, jnp.asarray(reg_val, jnp.float32),
                                hyper,
                                jnp.asarray(i0, jnp.int32), Xs, Ys, Vs)
                        else:
                            w_dev, ys = fused(
                                w, jnp.asarray(reg_val, jnp.float32),
                                hyper,
                                jnp.asarray(i0, jnp.int32), Xs, Ys, Vs)
                        if i0 + K <= cfg.num_iterations:
                            nxt = next(prefetch)
                    ys_host = tuple(np.asarray(a) for a in ys)
                dt = _time.perf_counter() - t0
                efs_host = None
                if comp_frac is not None:
                    # seventh ys leaf = per-step post-update EF state
                    efs_host, ys_host = ys_host[6], ys_host[:6]
                    _ef_window["efs"] = efs_host
                    _ef_window["i0"] = i0
                t_last, reg_val, converged = _replay_fused_steps(
                    ys_host, i0, steps, losses, reg_val, cfg,
                    listener=listener, wall_dt=dt / steps,
                    save_cb=(_save if checkpoint_manager is not None
                             else None),
                    save_every=checkpoint_every,
                )
                if converged or steps < K:
                    # run ends mid-superstep: the true last iteration's
                    # weights ride the ys (per-batch tails are no-op
                    # padded, shared-batch tails overshoot — either
                    # way the carry is not the answer)
                    w = jax.device_put(jnp.asarray(ys_host[0][t_last]),
                                       w_sharding)
                else:
                    w = w_dev
                if (not converged and stop_signal is not None
                        and stop_signal()):
                    # cooperative preemption at the superstep BOUNDARY
                    # (the scan cannot poll mid-program): checkpoint
                    # the exact boundary iteration so a resumed run
                    # replays from precisely here, bitwise
                    boundary = i0 + steps - 1
                    if checkpoint_manager is not None:
                        checkpoint_manager.save(
                            # graftlint: disable=host-sync -- preemption save: fires once at the superstep boundary unwind, not per trip
                            boundary, np.asarray(w), reg_val,
                            np.asarray(losses), config_key,
                            extras=(
                                {"ef": efs_host[steps - 1]}
                                if comp_frac is not None else None))
                    raise TrainingPreempted(boundary)
                i0 += steps
        finally:
            if prefetch is not None:
                prefetch.close()
        if listener is not None:
            listener.on_run_end(
                RunEvent(
                    event="run_completed",
                    num_iterations=len(losses),
                    final_loss=losses[-1] if losses else None,
                    converged_early=converged,
                    wall_time_s=_time.perf_counter() - t_run,
                )
            )
        return w, np.asarray(losses, np.float32)
    # Lookahead prefetcher: the sample sequence is deterministic in
    # (seed, i), so sample(i+1) — gather/pad/cast/put, the whole host
    # side — runs on the worker thread while iteration i computes.
    # depth=0 degrades to the legacy inline assembly (same trajectory
    # either way; only WHERE the host work runs changes).
    prefetch = Prefetcher(sample, range(start_iter, cfg.num_iterations + 1),
                          depth=prefetch_depth, retry_policy=retry_policy)
    try:
        # a checkpoint restored at the final iteration leaves nothing to
        # sample — the loop below is skipped and the restored weights
        # return as-is
        nxt = (next(prefetch) if start_iter <= cfg.num_iterations
               else None)
        i = start_iter
        while i <= cfg.num_iterations and not converged:
            t0 = _time.perf_counter()
            # mid-iteration fault-injection site: a crash here loses the
            # iterations since the last checkpoint, which the supervised
            # resume replays deterministically (chaos-soak contract)
            failpoint("optimize.streamed.step")
            # Dispatch the device step FIRST (async), then pull the next
            # prefetched batch while the device computes — only the final
            # block_until_ready waits on the device.  The span times the
            # host region around an ALREADY-contractual barrier (this
            # driver's per-iteration hop IS the data feed); it adds no
            # sync of its own.
            with span("train.step", i=i):
                kind, payload = nxt
                if kind == "resident":
                    new_w, loss_i, new_reg, c = resident_step(
                        w, Xres, yres, jnp.asarray(payload, jnp.int32),
                        jnp.asarray(i, jnp.int32),
                        jnp.asarray(reg_val, jnp.float32), hyper,
                    )
                elif comp_frac is not None:
                    # compressed wire: the EF accumulator is carried
                    # across iterations like the weights (a skipped
                    # empty batch passes it through unchanged)
                    Xb, yb, valid = payload
                    new_w, ef, loss_i, new_reg, c = step(
                        w, ef, Xb, yb, jnp.asarray(i, jnp.int32),
                        jnp.asarray(reg_val, jnp.float32), hyper,
                        valid,
                    )
                else:
                    Xb, yb, valid = payload
                    new_w, loss_i, new_reg, c = step(
                        w, Xb, yb, jnp.asarray(i, jnp.int32),
                        jnp.asarray(reg_val, jnp.float32), hyper,
                        valid,
                    )
                if i < cfg.num_iterations:
                    nxt = next(prefetch)
                # observed streamed driver: the per-iteration host hop IS
                # the data feed and the bookkeeping contract — barrier
                # once per step, then fetch each scalar exactly once
                # graftlint: disable=host-sync -- observed driver: one barrier per step precedes the scalar reads below
                new_w = jax.block_until_ready(new_w)
            dt = _time.perf_counter() - t0
            # the shared observed-loop TAIL (one definition for this
            # driver and the sparse streamed driver — the PR 9 review's
            # flagged duplication, extracted to the observe_step home):
            # barrier above, then each scalar fetched exactly once, then
            # the cooperative-preemption check
            w, reg_val, converged = observed_loop_tail(  # graftlint: disable=host-sync -- observed driver: the per-step scalar fetches ARE the contract (one barrier above, each scalar fetched once inside the shared helper)
                i, w, new_w, loss_i, new_reg, c, losses, reg_val, cfg,
                listener=listener, wall_dt=dt,
                save_cb=(_save if checkpoint_manager is not None
                         else None),
                save_every=checkpoint_every, stop_signal=stop_signal,
            )
            i += 1
    finally:
        # convergence exits early: cancel the worker's queued lookahead —
        # nobody will consume those batches
        prefetch.close()
    if listener is not None:
        listener.on_run_end(
            RunEvent(
                event="run_completed",
                num_iterations=len(losses),
                final_loss=losses[-1] if losses else None,
                converged_early=converged,
                wall_time_s=_time.perf_counter() - t_run,
            )
        )
    return w, np.asarray(losses, np.float32)
