"""Execution planning: ``train()`` picks the measured-best schedule itself.

The reference's user never chooses data placement: ``train()`` runs, and
Spark's scheduler plus ``cache()`` own where partitions live and how the
work is staged ([U] core/.../scheduler/DAGScheduler.scala — SURVEY.md §2
#16; the north star keeps the user API unchanged, BASELINE.json:5).
Rounds 2–3 left this framework with SIX measured execution schedules but
made the user compose them from flags (``sampling`` + ``sufficient_stats``
+ ``host_streaming`` + ``streaming_resident_rows`` + block size) — only
the benchmark driver knew the ladder.  This module is the scheduler analogue: probe
``(n, d, dtype, gradient family, sampling, free HBM)``, pick the schedule
the round-3 hardware measurements say is fastest, and configure the
optimizer — so a zero-flag ``train()`` call lands on the right schedule
and an explicit ``schedule=...`` override is honored with a warning when
the estimate says it will lose.

Schedules (figures measured on a TPU v5 lite in round 3, through a
remote attachment that added a fixed tax to every launch and transfer —
not re-measured on a directly attached chip; ROADMAP.md Speed 0/6):

=====================  ====================================================
``resident_stock``     data fits in HBM; fused two-pass iterations at the
                       two-HBM-read bandwidth floor (1.64 ms/iter on the
                       3M×1000 bf16 slab)
``resident_gram``      + least squares with sliced/full-batch sampling:
                       block-prefix sufficient statistics, exact
                       trajectory, 0.036–0.123 ms/iter (19–45×); a full
                       batch on one device reads the TOTALS alone, built
                       in one read (``ops.gram.stats_build``), on terms
                       measured on a directly attached chip (PR 41)
``partial_residency``  just beyond HBM, sliced sampling, single device:
                       leading rows resident, windows inside the prefix
                       cost no transfer (~2.4× the plain streamed rate
                       here)
``host_streamed``      anything host-resident: double-buffered per-
                       iteration batch transfer (feed-bandwidth-bound);
                       on a single device the planner also picks the
                       fused-step count K (``choose_superstep``) so one
                       compiled K-step scan amortizes the per-iteration
                       dispatch tax (README "Fused stepping")
``streamed_virtual_gram``  least squares beyond HBM, sliced/full-batch:
                       ONE streaming pass builds on-device statistics,
                       then iterations touch no rows (0.026 ms/iter
                       post-build on the true 10M×1000).  Uses ALIGNED
                       (block-floored) windows — a sampling deviation
                       (harmless on shuffled rows, not on sorted/grouped
                       data) that the plan's ``reason`` states loudly.
=====================  ====================================================

The quasi-Newton optimizers (LBFGS/OWL-QN) plan a narrower menu through
:func:`plan_quasi_newton` (``QN_SCHEDULES``): stock full-batch passes,
the sufficient-statistics substitution (least squares — resident or
streamed-virtual, meshed via per-shard totals), and — round 5 — the
``host_streamed`` chunked-CostFun schedule for NON-least-squares losses
beyond HBM (``optimize/streamed_costfun.py``), closing the reference's
any-size-any-loss CostFun contract.

The cost model's constants are calibrated to the round-3 hardware captures
(deleted in PR 23, in git history; :class:`CostModel` says which have been
measured on a directly attached chip since); they steer *decision
boundaries*, not perf claims, and every number the decision used is
recorded in ``Plan.estimates`` for inspection.  Decisions are deliberately
conservative for small problems: the one-time statistics build only pays
for itself past ``build_amortize_iters`` iterations (the prefix form:
measured ~1000–1900 at 3M×1000; the totals form of a full batch: one read
and ``2 n d²`` operations, a few iterations' worth at d = 1000), and a
stock iteration that reads under ~18 MB costs less than an iteration on
the statistics, so tiny workloads keep the stock path and its bitwise
round-2 trajectories.  :meth:`CostModel.calibrate` re-measures the two
environment-sensitive rates (~2 s) for any deployment but the remotely
attached chip the defaults were captured on.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import warnings
from typing import Optional

from tpu_sgd.ops.gradients import step_sums

logger = logging.getLogger("tpu_sgd.plan")

#: the five schedules `plan` chooses among (resident_gram covers both the
#: exact and aligned variants via Plan.aligned)
SCHEDULES = (
    "resident_stock",
    "resident_gram",
    "partial_residency",
    "host_streamed",
    "streamed_virtual_gram",
)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Decision-boundary constants.  Override any of them (e.g.
    ``host_feed_gb_s`` for a pod-local host whose DMA feed is ~100–1000×
    the remote attachment's 0.03–0.16 GB/s).

    Whose they are: ``mxu_bf16_flops`` and ``totals_overhead_s`` (the
    statistics' totals form: PR 41) were measured on a DIRECTLY attached
    TPU v5 lite, and ``hbm_gb_s`` (730; from the round-3 captures) is
    within 4% of what the one-read kernel reaches there (755 GB/s).
    Everything else is still the round-3 REMOTE attachment's, which added
    a fixed tax to every launch and transfer: ``build_overhead_s`` (1.2 s:
    the prefix form's and the streamed builds'), ``mxu_f32_flops``,
    ``gram_iter_overhead_s``, ``host_feed_gb_s`` (0.15 GB/s where the
    direct wire moves 14.3), ``dispatch_overhead_s`` and the compress /
    all-reduce terms.  A schedule that rests on those (the prefix form,
    the streaming schedules' sizing) is decided on that attachment's
    terms until a PR measures them here (ROADMAP Speed 3)."""

    #: effective HBM read bandwidth (measured: 1.64 ms/iter for the 1.2 GB
    #: two-read window on the 3M×1000 bf16 slab)
    hbm_gb_s: float = 730.0
    #: f32 HIGHEST-precision matmul throughput for the statistics build
    #: (the prefix form's, and the totals form's over rows that are not
    #: bf16)
    mxu_f32_flops: float = 2.0e13
    #: fixed build cost: compile + launches of the one-time statistics pass
    #: (the PREFIX form's and the streamed builds': a block loop, a prefix
    #: scan, GB-scale stacks; the remote attachment's figure)
    build_overhead_s: float = 1.2
    #: the TOTALS form's build (``ops.gram.stats_build``: a full batch on
    #: one device) over bf16 rows: ``2 n d^2`` operations at this rate, ONE
    #: bf16 pass with f32 sums.  THIS chip's, directly attached (TPU v5
    #: lite; PERF.md, PR 41): ``X^T X`` alone took 5.587 / 22.314 / 44.692
    #: ms at 524,288 / 2,097,152 / 4,194,304 x 1000, 187.7 to 188.0
    #: TFLOP/s of the algorithm's operations, 95.4% of the published bf16
    #: peak (all of it, counting 1000 padded to 1024; its read of X hides
    #: under the products; ``X^T y`` is one read more)
    mxu_bf16_flops: float = 1.88e14
    #: what the totals' build costs beyond its read and its operations:
    #: one warm launch of one program, 1.08 to 1.27 ms at those shapes
    #: (PERF.md, PR 41)
    totals_overhead_s: float = 1.2e-3
    #: per-iteration fixed cost of the gram schedule beyond its HBM traffic
    #: (loop bookkeeping; measured residual at 0.08 ms/iter total)
    gram_iter_overhead_s: float = 5.0e-5
    #: host->device feed bandwidth for streaming schedules (measured
    #: over the round-3 remote attachment: 0.03–0.163 GB/s; pod-local PCIe
    #: is ~10–100 GB/s — override for real deployments)
    host_feed_gb_s: float = 0.15
    #: fallback device memory when the backend reports no memory stats
    hbm_bytes: float = 16.0e9
    #: fraction of free device memory the planner will commit
    hbm_safety: float = 0.80
    #: minimum fraction of iterations that must avoid transfer for partial
    #: residency to be chosen over plain streaming
    min_resident_gain: float = 0.05
    #: fixed host cost of ONE streamed-SGD iteration dispatch (batch
    #: ``device_put``s + program launch + readback bookkeeping) — the
    #: per-iteration tax the superstep executor amortizes K-fold.
    #: Fitted from BENCH_SUPERSTEP.json's slope difference between the
    #: K=1 and K=8 drivers on this harness (slope_K1 - slope_K8 scaled
    #: by 8/7 = implied_dispatch_overhead_s; recalibrated for the
    #: resident-driver round at 2.3 ms — the earlier 1.4 ms capture was
    #: a quieter ambient state, the same run-to-run band
    #: BENCH_SUPERSTEP.json's basis string warns about); like
    #: ``host_feed_gb_s`` it is environment-bound — pod-local hosts
    #: dispatch ~10× faster
    dispatch_overhead_s: float = 2.3e-3
    #: target ceiling for the residual dispatch tax under fusion:
    #: choose_superstep picks the smallest K with
    #: ``dispatch_overhead_s / K <= frac * per-iteration wall``
    superstep_dispatch_frac: float = 0.05
    #: gradient all-reduce link rate for the compressed-wire decision
    #: (choose_wire_compress).  ICI within a slice is far faster, but
    #: the rate that matters for the wires this planner can choose to
    #: compress is the slowest link the update crosses — DCN / host
    #: network class; like host_feed_gb_s it is environment-bound
    allreduce_gb_s: float = 10.0
    #: fixed per-step cost of the compress/decompress stages (host
    #: top-k selection + segment scatter-add dispatch); compression
    #: pays only when the predicted wire-byte saving dominates this
    compress_overhead_s: float = 2.0e-4
    #: top-k fraction the planner proposes when compression pays; 1%
    #: of coordinates = ~50x fewer physical bytes (value + int32 index
    #: per entry), the SparCML operating point
    wire_compress_frac: float = 0.01
    #: density (nnz / dim) at which the sharded store's SparCML
    #: pairwise segment merge switches to a dense accumulator
    #: (``io.sparse_wire.merge_sparse_segments``; arXiv:1802.08021's
    #: representation crossover): a sparse merge costs O(nnz log nnz)
    #: per pair and only re-pays while the union stays sparse — past
    #: this density the O(dim) dense scatter-add is strictly cheaper
    sparse_merge_density: float = 0.25
    #: set by :meth:`calibrate` — raw probe readings plus which probes
    #: were rejected and fell back to the persisted defaults; excluded
    #: from equality/repr (two models with the same rates ARE the same
    #: model however they were obtained)
    calibration_report: Optional[dict] = dataclasses.field(
        default=None, compare=False, repr=False)

    @classmethod
    def calibrate(cls, device=None, copy_mb: float = 256.0,
                  feed_mb: float = 64.0, **overrides):
        """Measure THIS environment's two planner-critical rates and
        return a :class:`CostModel` carrying them (~2 s; everything else
        keeps the defaults unless overridden).

        The persisted defaults are single-environment calibrations of a
        remotely attached TPU v5 lite (0.15 GB/s feed!); on a pod-local
        host every streaming decision boundary shifts ~100×, so a
        deployment that cares about the boundaries should probe once:

        * ``hbm_gb_s`` — effective on-device bandwidth from the SLOPE
          between two trip counts of one compiled read+write loop over a
          ``copy_mb`` buffer, so the per-call tax (launch + readback,
          ~65–130 ms over a remote attachment) cancels out.  The trip
          count is a TRACED argument — a constant bound lets XLA unroll
          and fold the whole loop into one fused pass (measured: a
          constant-200 loop reported ~700,000 GB/s) —
          and each timing ends with a 1-element device→host readback,
          which cannot return before the work is done even where
          ``block_until_ready`` is unreliable (experimental remote
          platforms).
        * ``host_feed_gb_s`` — the same two-point slope over two
          ``device_put`` sizes (``feed_mb`` and a quarter of it), each
          synced by readback, cancelling the per-transfer round trip.

        Either probe falls back to the persisted default (and keeps the
        other's measurement) if its slope comes out non-positive or the
        implied rate lands outside a physical-plausibility window
        (1–20,000 GB/s for HBM, 0.001–1,000 GB/s for host feed) — a
        stalled transfer or an elided program must not poison the cost
        model with a garbage rate.
        """
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np

        if device is None:
            device = jax.devices()[0]

        n_elems = max(1024, int(copy_mb * 1e6 // 4))
        x = jnp.zeros((n_elems,), jnp.float32, device=device)

        @jax.jit
        def many_passes(a, n):
            return jax.lax.fori_loop(0, n, lambda i, v: v + 1.0, a)

        def timed_passes(loops):
            t0 = time.perf_counter()
            r = many_passes(x, jnp.int32(loops))
            np.asarray(r[:1])  # readback: forces true completion
            return time.perf_counter() - t0

        def accept(raw, slope_s, window, default, label):
            """ONE rejection policy for both probes: a rate outside its
            physical-plausibility window (collapsed, elided, clamped, or
            noise-dominated measurement) falls back to the persisted
            default with a warning.  Returns ``(rate, fell_back)``."""
            fell_back = not (window[0] <= raw <= window[1])
            if fell_back:
                logger.warning(
                    "calibrate: %s probe rejected (implied %.6g GB/s, "
                    "slope %.2e s); keeping the persisted default "
                    "%.6g GB/s", label, raw, slope_s, default)
            return (default if fell_back else raw), fell_back

        lo, hi = 50, 200
        timed_passes(2)  # compile + warm (dynamic bound: one program)
        dt_lo, dt_hi = timed_passes(lo), timed_passes(hi)
        hbm_slope = dt_hi - dt_lo
        hbm_raw = ((hi - lo) * 2.0 * n_elems * 4.0 / hbm_slope / 1e9
                   if hbm_slope > 1e-5 else 0.0)
        # no real memory system exceeds ~20 TB/s
        hbm_gb_s, hbm_fell_back = accept(
            hbm_raw, hbm_slope, (1.0, 20_000.0), cls.hbm_gb_s, "HBM")

        n_feed = max(1024, int(feed_mb * 1e6 // 4))
        h_lo = np.zeros((max(1024, n_feed // 4),), np.float32)
        h_hi = np.zeros((n_feed,), np.float32)

        def timed_put(h):
            t0 = time.perf_counter()
            y = jax.device_put(h, device)
            np.asarray(y[:1])  # readback: forces arrival
            return time.perf_counter() - t0

        timed_put(h_lo)  # warm the transfer path + both buffer sizes'
        timed_put(h_hi)  # device allocations before timing either
        slope = timed_put(h_hi) - timed_put(h_lo)
        nbytes_delta = h_hi.nbytes - h_lo.nbytes
        # Trust the slope only when h_lo escaped its 1024-element clamp
        # (feed_mb >= ~0.017): a partially-clamped pair leaves a few-KB
        # byte delta whose jitter-dominated slope can land inside the
        # plausibility window as a garbage rate.
        unclamped = n_feed // 4 >= 1024
        feed_raw = (nbytes_delta / slope / 1e9
                    if slope > 1e-5 and unclamped else 0.0)
        feed_gb_s, feed_fell_back = accept(
            feed_raw, slope, (1e-3, 1_000.0), cls.host_feed_gb_s,
            "host-feed")

        report = {"hbm_raw_gb_s": hbm_raw, "hbm_slope_s": hbm_slope,
                  "hbm_fell_back": hbm_fell_back,
                  "feed_raw_gb_s": feed_raw, "feed_slope_s": slope,
                  "feed_fell_back": feed_fell_back}
        # explicit overrides win, including over the measured fields
        # (a user may probe one rate while pinning the other)
        return cls(**{"hbm_gb_s": hbm_gb_s, "host_feed_gb_s": feed_gb_s,
                      "calibration_report": report, **overrides})


DEFAULT_COST_MODEL = CostModel()


def device_budget(device=None, cost_model: CostModel = DEFAULT_COST_MODEL,
                  empty: bool = False):
    """``(free_bytes, source)`` for the target device — probed from
    ``device.memory_stats()`` when the backend reports it (TPU does),
    otherwise the cost model's fallback.  ``source`` says which.
    ``empty``: what the device would have free with nothing on it (for a
    caller that sizes ALL it will hold, part of which is there already)."""
    import jax

    if device is None:
        device = jax.devices()[0]  # a backend that cannot start raises
    stats = device.memory_stats()  # None where the backend reports none
    if stats and stats.get("bytes_limit"):
        free = stats["bytes_limit"] - (
            0 if empty else stats.get("bytes_in_use", 0))
        return max(0.0, free * cost_model.hbm_safety), "memory_stats"
    return cost_model.hbm_bytes * cost_model.hbm_safety, "fallback"


@dataclasses.dataclass(frozen=True)
class Plan:
    """A chosen execution schedule plus the estimates that chose it.

    ``apply(optimizer)`` configures a ``GradientDescent`` accordingly and
    returns it; ``describe()`` is the one-line human explanation that
    ``train()`` logs."""

    schedule: str
    reason: str
    block_rows: Optional[int] = None
    batch_rows: Optional[int] = None
    aligned: bool = False
    resident_rows: int = 0
    #: ingest-pipeline knobs for the streaming schedules (tpu_sgd/io):
    #: wire_dtype stays None — the bf16 wire is a documented opt-in, the
    #: planner never silently rounds the user's inputs; prefetch_depth=2
    #: is the double buffer whose 2× staging footprint
    #: choose_streamed_build budgets for
    wire_dtype: Optional[str] = None
    prefetch_depth: int = 2
    #: fused-step count for the host_streamed schedule (README "Fused
    #: stepping"): K iterations per compiled dispatch, the K-batch
    #: superchunk staged double-buffered like every other chunk
    #: (choose_superstep budgets 2× its footprint); 1 = the
    #: per-iteration driver
    superstep: int = 1
    #: device-residency cadence for the host_streamed full-batch feed
    #: (README "Device-resident training"): C >= 2 moves the whole run
    #: into one compiled while_loop with host callbacks every C
    #: supersteps (choose_residency — resident only when the cadence
    #: window holds at least 2 supersteps); 0 = the per-superstep
    #: host-dispatched driver
    residency: int = 0
    #: compressed gradient wire for the meshed host_streamed schedule
    #: (README "Compressed wire"): "topk:<frac>" when
    #: choose_wire_compress says the per-step all-reduce bytes dominate
    #: the compress cost, else None.  NOTE the compressed wire changes
    #: the UPDATE RULE (top-k + error feedback — convergent at matched
    #: final loss, not bitwise), so the planner proposes it only where
    #: a real multi-shard all-reduce exists; user wire_compress wins
    wire_compress: Optional[str] = None
    #: async replica-worker count for the bounded-staleness driver
    #: (``tpu_sgd/replica``; README "Async replicas"): how many
    #: ``ReplicaDriver`` workers the cost model says this workload can
    #: keep busy (``choose_replicas``; 0 = stay synchronous), stamped
    #: on every plan :func:`plan` returns (also in
    #: ``estimates["replicas"]``).  NOT a schedule the planner
    #: auto-applies — ``tau > 0`` changes the update rule (matched
    #: final loss, not matched trajectory), so going async is always
    #: the USER's call; this field is the sizing advice they read when
    #: they make it
    replicas: int = 0
    #: store-shard count for the async store's apply plane
    #: (``tpu_sgd/replica/shard.py``; ``choose_store_shards``): how
    #: many per-shard apply pipelines the cost model says pay at this
    #: width (1 = unsharded).  Sizing advice with the same contract as
    #: :attr:`replicas` — the driver only shards when the user asks
    #: (``ReplicaDriver.set_store_shards``); also in
    #: ``estimates["store_shards"]``
    store_shards: int = 1
    estimates: dict = dataclasses.field(default_factory=dict)

    def describe(self) -> str:
        return f"plan: {self.schedule} — {self.reason}"

    def apply(self, optimizer):
        """Configure ``optimizer`` (a ``GradientDescent``) for this
        schedule.  Clears the schedule flags and plan-owned gram knobs
        first, so re-planning an optimizer between datasets never leaks
        the previous choice.  Attributes are assigned DIRECTLY, not
        through the fluent setters: the setters record USER intent
        (``_user_gram_opts``, ``last_plan`` invalidation) and the planner
        must not masquerade as the user — knob fields the user set via
        ``set_gram_options`` are preserved (user flags win)."""
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}")
        apply_gram_knobs(optimizer, self)
        optimizer.host_streaming = self.schedule in (
            "partial_residency", "host_streamed")
        optimizer.streaming_resident_rows = (
            self.resident_rows if self.schedule == "partial_residency"
            else 0)
        optimizer.sufficient_stats = self.schedule == "resident_gram"
        optimizer.streamed_stats = self.schedule == "streamed_virtual_gram"
        optimizer.last_plan = self
        return optimizer

    def apply_quasi_newton(self, optimizer):
        """Configure an ``LBFGS``/``OWLQN`` optimizer per this plan — the
        quasi-Newton analogue of :meth:`apply`, kept HERE so schedule
        application has one home and callers (``models/glm.py``) cannot
        drift from it.  Same contract as :meth:`apply`: direct
        assignment, user-set knobs win, plan-owned fields always reset."""
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}")
        optimizer.sufficient_stats = self.schedule == "resident_gram"
        optimizer.streamed_stats = self.schedule == "streamed_virtual_gram"
        optimizer.host_streaming = self.schedule == "host_streamed"
        if "stream_batch_rows" not in getattr(
                optimizer, "_user_gram_opts", frozenset()):
            optimizer.stream_batch_rows = (
                self.batch_rows if self.schedule == "host_streamed"
                else None)
        apply_gram_knobs(optimizer, self)
        optimizer.last_plan = self
        return optimizer


def apply_gram_knobs(optimizer, p: "Plan") -> None:
    """Write a plan's gram build knobs onto ``optimizer``, preserving any
    field the USER set via ``set_gram_options``/``set_streamed_stats``
    (recorded in ``_user_gram_opts``).  Plan-owned fields are always
    reset — a previous dataset's block size or streamed-build chunk cap
    must not leak into this build (the gram identity caches key on them).
    Shared by :meth:`Plan.apply` (GradientDescent) and
    :meth:`Plan.apply_quasi_newton` (LBFGS/OWL-QN)."""
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS

    user = getattr(optimizer, "_user_gram_opts", frozenset())
    if "block_rows" not in user:
        optimizer.gram_block_rows = p.block_rows or DEFAULT_BLOCK_ROWS
    if "batch_rows" not in user:
        # A host_streamed plan sizes batch_rows as the STREAM chunk (a
        # global, mesh-scaled row count owned by stream_batch_rows) —
        # writing it here would hand a later manual streamed-gram build
        # an absurd chunk cap sized for the wrong schedule.
        optimizer.gram_batch_rows = (
            None if p.schedule == "host_streamed" else p.batch_rows or None)
    if "aligned" not in user and hasattr(optimizer, "gram_aligned"):
        optimizer.gram_aligned = bool(p.aligned)
    if ("wire_dtype" not in user
            and hasattr(optimizer, "ingest_wire_dtype")):
        optimizer.ingest_wire_dtype = p.wire_dtype
    if ("prefetch_depth" not in user
            and hasattr(optimizer, "ingest_prefetch_depth")):
        optimizer.ingest_prefetch_depth = int(p.prefetch_depth)
    if "superstep" not in user and hasattr(optimizer, "superstep"):
        optimizer.superstep = int(getattr(p, "superstep", 1) or 1)
    if ("residency" not in user
            and hasattr(optimizer, "resident_cadence")):
        optimizer.resident_cadence = int(getattr(p, "residency", 0) or 0)
    if ("wire_compress" not in user
            and hasattr(optimizer, "ingest_wire_compress")):
        optimizer.ingest_wire_compress = getattr(p, "wire_compress", None)


#: THE user-facing gram knob table: name -> (optimizer attribute,
#: requires-positive-int).  Shared by the setters' validate-then-apply
#: (`apply_user_gram_knobs`), `apply_gram_knobs`, and
#: `reset_plan_owned_gram_knobs`, so a new knob is wired in ONE place.
_GRAM_KNOBS = {
    "block_rows": ("gram_block_rows", True),
    "batch_rows": ("gram_batch_rows", True),
    "aligned": ("gram_aligned", False),
}


def apply_user_gram_knobs(optimizer, **knobs) -> None:
    """Validate-all-then-apply for USER-set gram knobs (the
    ``set_gram_options`` body, shared by GradientDescent and LBFGS): a
    bad LATER argument must not leave earlier knobs half-applied —
    mutated but unrecorded in ``_user_gram_opts`` with the plan cache
    intact.  Records every applied knob as user-owned and invalidates
    the repeat-run plan key (knobs are not a schedule choice, so
    ``last_plan`` survives and re-planning still runs)."""
    provided = {}
    for name, val in knobs.items():
        if val is None:
            continue
        attr, positive = _GRAM_KNOBS[name]
        if positive:
            if int(val) < 1:
                raise ValueError(f"{name} must be positive, got {val}")
            val = int(val)
        else:
            val = bool(val)
        provided[name] = (attr, val)
    for attr, val in provided.values():
        setattr(optimizer, attr, val)
    optimizer._user_gram_opts = optimizer._user_gram_opts | set(provided)
    optimizer._plan_key = None


def apply_user_ingest_options(optimizer, wire_dtype=None,
                              prefetch_depth=None, pipeline=None,
                              retry=None, wire_compress=None) -> None:
    """Validate-all-then-apply for USER-set ingest-pipeline knobs (the
    ``set_ingest_options`` body, shared by GradientDescent and LBFGS) —
    the ingest sibling of :func:`apply_user_gram_knobs`, with the same
    contract: a bad later argument leaves earlier knobs untouched, every
    applied knob is recorded user-owned in ``_user_gram_opts`` so the
    planner preserves it, and the repeat-run plan key invalidates.

    ``wire_dtype``: ``"bfloat16"`` (half the bytes on the host→device
    hop; see ``tpu_sgd/io/wire.py`` for when that is safe) or any
    floating dtype name; validated eagerly so a typo fails HERE, not
    mid-build.  ``prefetch_depth``: chunks staged ahead (0 = synchronous
    legacy feed, 2 = double buffer).  ``pipeline``: False reverts the
    streamed builds to the legacy sync loop (A/B debugging).
    ``retry``: a ``tpu_sgd.reliability.RetryPolicy`` healing transient
    host-feed faults on the host-streamed SGD path (``False`` clears a
    previously set policy); retries never change the sampled sequence,
    so results are unaffected.  ``wire_compress``: ``"topk:<frac>"``
    engages the compressed sparse gradient wire
    (``tpu_sgd/io/sparse_wire.py``; README "Compressed wire"),
    validated eagerly like ``wire_dtype``; ``False`` clears it."""
    from tpu_sgd.io import parse_wire_compress, resolve_wire_dtype

    provided = {}
    if wire_compress is not None:
        if wire_compress is False:
            provided["wire_compress"] = ("ingest_wire_compress", None)
        else:
            parse_wire_compress(wire_compress)  # validate, keep spec
            provided["wire_compress"] = ("ingest_wire_compress",
                                         str(wire_compress))
    if retry is not None:
        if retry is False:
            provided["retry"] = ("ingest_retry_policy", None)
        else:
            from tpu_sgd.reliability.retry import RetryPolicy

            if not isinstance(retry, RetryPolicy):
                raise TypeError(
                    f"retry must be a RetryPolicy or False, got "
                    f"{type(retry).__name__}"
                )
            provided["retry"] = ("ingest_retry_policy", retry)
    if wire_dtype is not None:
        resolve_wire_dtype(wire_dtype, "float32")  # validate, keep name
        provided["wire_dtype"] = ("ingest_wire_dtype", str(wire_dtype))
    if prefetch_depth is not None:
        if int(prefetch_depth) < 0:
            raise ValueError(
                f"prefetch_depth must be >= 0, got {prefetch_depth}"
            )
        provided["prefetch_depth"] = ("ingest_prefetch_depth",
                                      int(prefetch_depth))
    if pipeline is not None:
        provided["pipeline"] = ("ingest_pipeline", bool(pipeline))
    for attr, val in provided.values():
        setattr(optimizer, attr, val)
    optimizer._user_gram_opts = optimizer._user_gram_opts | set(provided)
    optimizer._plan_key = None


def reset_plan_owned_gram_knobs(optimizer) -> None:
    """The clearing counterpart of :func:`apply_gram_knobs`: restore
    every gram knob the USER did not set (``_user_gram_opts``) to its
    constructor default.  Called when a manual schedule setter takes the
    wheel after an auto-planned run — the previous plan's block size /
    chunk caps were sized for ITS dataset and budget, and a manual
    schedule on a different dataset must not inherit them (the same
    leak class as the host_streamed batch_rows fix, but via the
    manual-after-plan path)."""
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS

    user = getattr(optimizer, "_user_gram_opts", frozenset())
    if "block_rows" not in user:
        optimizer.gram_block_rows = DEFAULT_BLOCK_ROWS
    if "batch_rows" not in user:
        optimizer.gram_batch_rows = None
    if "aligned" not in user and hasattr(optimizer, "gram_aligned"):
        optimizer.gram_aligned = False
    if ("stream_batch_rows" not in user
            and hasattr(optimizer, "stream_batch_rows")):
        optimizer.stream_batch_rows = None
    if ("wire_dtype" not in user
            and hasattr(optimizer, "ingest_wire_dtype")):
        optimizer.ingest_wire_dtype = None
    if ("prefetch_depth" not in user
            and hasattr(optimizer, "ingest_prefetch_depth")):
        from tpu_sgd.io import DEFAULT_PREFETCH_DEPTH

        optimizer.ingest_prefetch_depth = DEFAULT_PREFETCH_DEPTH
    if "superstep" not in user and hasattr(optimizer, "superstep"):
        optimizer.superstep = 1
    if ("residency" not in user
            and hasattr(optimizer, "resident_cadence")):
        optimizer.resident_cadence = 0
    if ("wire_compress" not in user
            and hasattr(optimizer, "ingest_wire_compress")):
        optimizer.ingest_wire_compress = None


def _stack_bytes(n_local: int, block_rows: int, d: int) -> float:
    """Device bytes of the f32 block-prefix statistics at this block size
    (PG + Pb + Pyy + totals; see ops/gram.py memory note)."""
    nbf = max(1, n_local // block_rows)
    return (nbf + 2) * (d * d + d + 1) * 4.0


def _totals_bytes(d: int) -> float:
    """Device bytes the statistics' TOTALS form needs (``ops.gram.
    stats_build``), whatever the rows: ``G``, ``b``, ``yy`` in f32 three
    times over (a fit's own, the next build's result beside it, the
    products on them): 12 MB at d = 1000, and no stack."""
    return 3.0 * (d * d + d + 1) * 4.0


def choose_block_rows(n_local: int, d: int, stats_budget: float,
                      start: int = 4096) -> Optional[int]:
    """Smallest measured-good block size whose prefix stack fits the
    budget (doubling from the 4096 the round-3 captures liked; smaller
    blocks mean less edge traffic but a bigger stack).  None when no block
    size up to ``n_local`` fits — gram is then infeasible here."""
    B = min(max(1, start), max(1, n_local))
    while _stack_bytes(n_local, B, d) > stats_budget:
        if B >= n_local:
            return None
        B *= 2
    return B


def choose_streamed_build(n_local: int, d: int, itemsize: int,
                          budget: float, start: int = 4096):
    """``(block_rows, batch_rows)`` for a STREAMED statistics build whose
    whole device footprint fits ``budget`` — the prefix stack PLUS the
    in-flight host→device chunks that are co-resident during the build
    (``build_streamed`` defaults the chunk to 64 blocks, which at the
    large block sizes a tight stack budget forces can exceed the stack
    itself).  The stack gets ~2/3 of the budget; the chunk is capped to
    the remainder divided by TWO — the double-buffered ingest pipeline
    (``tpu_sgd/io``) stages chunk ``k+1`` while chunk ``k``'s kernel
    consumes its buffer, so two chunks are live at the peak (never above
    the builder's 64-block default).  Returns ``(None, None)`` when no
    split fits."""
    B = choose_block_rows(n_local, d, budget * 2.0 / 3.0, start=start)
    if B is None:
        return None, None
    chunk_budget = budget - _stack_bytes(n_local, B, d)
    rows = int(chunk_budget // max(1, 2 * (d * itemsize + 4)))
    if rows < B:  # cannot hold even one block alongside the stack
        return None, None
    return B, int(min(rows, 64 * B))


def choose_superstep(window_rows: int, d: int, itemsize: int,
                     iter_s: float, staging_budget: float,
                     cost_model: CostModel = DEFAULT_COST_MODEL,
                     cap: int = 64) -> int:
    """Fused-step count K for the host_streamed schedule, from the
    fixed-cost/slope fit (the GRAM_SCAN_EXPERIMENT / BENCH_SUPERSTEP
    methodology): every streamed iteration pays a fixed host dispatch
    tax ``dispatch_overhead_s`` on top of its ``iter_s`` transfer/
    compute slope, and fusing K steps into one program divides the tax
    by K.  Picks the smallest K that pushes the residual tax below
    ``superstep_dispatch_frac`` of the per-iteration wall — smallest,
    not largest, because K also multiplies the preemption latency and
    the staging footprint — then clamps to what the double-buffered
    K-batch superchunk (2× one superchunk live at the peak, the same
    2× rule ``choose_streamed_build`` applies) fits in
    ``staging_budget``, and to ``cap``.  Returns 1 when fusion cannot
    pay (tiny dispatch tax or no staging room)."""
    cm = cost_model
    batch_bytes = window_rows * (d * itemsize + 5.0)  # X + y(f32) + valid
    if math.isinf(staging_budget):
        # shared-batch feeds stage no superchunk at all (one transfer,
        # the scan reuses it): only the amortization target binds
        k_budget = int(cap)
    else:
        k_budget = int(staging_budget // max(1.0, 2.0 * batch_bytes))
    if k_budget < 2:
        return 1
    target = cm.superstep_dispatch_frac * max(iter_s, 1e-9)
    k_amortize = math.ceil(cm.dispatch_overhead_s / target)
    return int(max(1, min(cap, k_amortize, k_budget)))


def choose_wire_compress(dim: int, n_devices: int,
                         cost_model: CostModel = DEFAULT_COST_MODEL,
                         resident_cadence: int = 0) -> Optional[str]:
    """Compressed-wire decision for the per-step gradient all-reduce
    (README "Compressed wire"): compression pays ONLY when the
    predicted wire bytes dominate the compress/decompress cost.

    The per-step dense wire moves one ``(dim,)`` f32 update per shard
    (``dim * 4`` bytes at ``allreduce_gb_s``); top-k at
    ``wire_compress_frac`` shrinks that to ``2 * frac`` of the bytes
    (each surviving entry carries an int32 index beside its f32 value)
    at a fixed ``compress_overhead_s`` per step (host/device top-k
    selection + the segment scatter-add).  Returns ``"topk:<frac>"``
    when the byte-time saving exceeds the overhead, else None.

    ``resident_cadence`` lifts the old single-device gate (ISSUE 20):
    a lone device has no all-reduce wire, so the EF rule used to be
    strictly a user opt-in for A/B runs — and the resident driver
    REFUSED it anyway (the PR 9 deviation).  With EF carried in the
    resident while-loop ring, a plan may propose residency AND the
    compressed update together: under ``resident_cadence >= 2`` the
    top-k select runs in-trace inside the one fused body (no
    ``compress_overhead_s`` host hop — only one extra ``(dim,)`` pass
    at ``hbm_gb_s``), so the single-device proposal costs what that
    pass costs and buys scale-out-ready EF state: the run trains the
    exact update rule its meshed or replica twin ships, with the wire
    already matched-loss-validated.  The proposal still requires the
    kept segment to hold at least one entry (``frac * dim >= 1``) and
    the in-trace pass to fit the same ``compress_overhead_s`` budget
    the meshed rule charges.

    Deliberately conservative: the compressed wire CHANGES the update
    rule (top-k + error feedback — matched final loss, not matched
    trajectory), so the planner proposes it only where the cost model
    says the wire genuinely dominates (or, resident, where it rides
    free); borderline cases keep the dense wire and its bitwise
    contracts."""
    cm = cost_model
    if int(dim) < 2:
        return None
    frac = float(cm.wire_compress_frac)
    if int(n_devices) <= 1:
        if int(resident_cadence) < 2 or frac * dim < 1.0:
            return None
        select_s = dim * 4.0 / (cm.hbm_gb_s * 1e9)
        if select_s > cm.compress_overhead_s:
            return None
        return f"topk:{frac:g}"
    dense_s = dim * 4.0 / (cm.allreduce_gb_s * 1e9)
    saved_s = dense_s * (1.0 - 2.0 * frac)
    if saved_s <= cm.compress_overhead_s:
        return None
    return f"topk:{frac:g}"


#: fraction of a replica worker's per-push compute wall the SERIALIZED
#: store work (one apply dispatch + the update wire) may consume at the
#: chosen fleet size before the store becomes the bottleneck —
#: ``choose_replicas`` keeps the store at most half busy so push
#: arrivals queue on compute, not on each other
REPLICA_STORE_HEADROOM = 0.5


def choose_replicas(n: int, d: int, itemsize: int = 4,
                    n_devices: int = 1,
                    mini_batch_fraction: float = 1.0,
                    cost_model: CostModel = DEFAULT_COST_MODEL,
                    cap: int = 8, store_shards: int = 1) -> int:
    """Replica-worker count W for the async bounded-staleness driver
    (``tpu_sgd/replica``), from the existing cost model.

    The async fleet's structural bottleneck is the STORE: every
    accepted push costs one serialized apply — a program dispatch
    (``dispatch_overhead_s``) plus the update-shaped wire both ways
    (pulled weights + pushed contribution, ``2 * d * 4`` bytes at
    ``allreduce_gb_s``) — while the workers' shard gradients run
    concurrently (each a two-pass read of its sampled rows,
    ``2 * (n/W) * frac * d * itemsize / hbm_gb_s``).  W workers
    generate one push per per-shard compute wall, so the store's busy
    fraction is ``W * store_s / compute_s(W)`` and grows as W² (more
    pushers, each pushing sooner).  W is the LARGEST count — capped by
    ``n_devices`` and ``cap`` — that keeps the store under
    :data:`REPLICA_STORE_HEADROOM` busy; 0 when even W=2 saturates it
    (tiny workloads stay synchronous — the same "smallest that pays"
    honesty as ``choose_residency``'s crossover).

    Like :data:`Plan.replicas`, this is SIZING advice, not a schedule
    decision: ``tau > 0`` changes the update rule (matched final loss,
    not matched trajectory), so the async switch itself is always the
    user's.

    ``store_shards``: the store's apply-pipeline count
    (:func:`choose_store_shards`; ``tpu_sgd/replica/shard.py``).  A
    sharded store splits the per-push COMBINE across S pipelines, so
    only the update wire scales down by S — the one whole-vector apply
    dispatch stays serialized (the updater is not per-coordinate
    separable; ADVICE.md "Shard the apply, not the contract").  The
    pre-shard model charged the full wire to every push, silently
    understating the fleet a sharded store can feed."""
    cm = cost_model
    store_s = (cm.dispatch_overhead_s
               + 2.0 * d * 4.0
               / (max(1, int(store_shards)) * cm.allreduce_gb_s * 1e9))
    best = 0
    # an empty range when fewer than 2 devices: a single device cannot
    # place a fleet, whatever the cost model says
    for w in range(2, min(int(n_devices), int(cap)) + 1):
        rows_local = max(1.0, float(n) / w)
        compute_s = (2.0 * rows_local * mini_batch_fraction * d
                     * itemsize / (cm.hbm_gb_s * 1e9))
        if w * store_s <= REPLICA_STORE_HEADROOM * compute_s:
            best = w
    return best


def choose_store_shards(n: int, d: int, itemsize: int = 4,
                        n_devices: int = 1,
                        workers: int = 2,
                        mini_batch_fraction: float = 1.0,
                        cost_model: CostModel = DEFAULT_COST_MODEL,
                        cap: int = 8) -> int:
    """Store-shard count S for the sharded parameter store
    (``tpu_sgd/replica/shard.py``): the largest S — clamped by the
    device count and ``cap`` — whose per-shard pipeline keeps
    :data:`REPLICA_STORE_HEADROOM` headroom under a ``workers``-strong
    fleet's push arrival rate, subject to DISPATCH DOMINANCE: each
    added pipeline replicates the fixed apply-dispatch tax
    (``dispatch_overhead_s``), so splitting only pays while the
    per-shard share of the update wire (``2 * d * 4 / S`` bytes at
    ``allreduce_gb_s``) still dominates one dispatch.  Small models
    return 1 (unsharded — the wire never dominated); wide models
    return the largest S the clamps allow.  Sizing advice with the
    same contract as :func:`choose_replicas`: the driver only shards
    when the user asks (``ReplicaDriver.set_store_shards``)."""
    cm = cost_model
    w = max(2, int(workers))
    transfer_s = 2.0 * d * 4.0 / (cm.allreduce_gb_s * 1e9)
    rows_local = max(1.0, float(n) / w)
    compute_s = (2.0 * rows_local * mini_batch_fraction * d
                 * itemsize / (cm.hbm_gb_s * 1e9))
    best = 1
    for s in range(2, min(int(n_devices), int(cap)) + 1):
        if transfer_s / s < cm.dispatch_overhead_s:
            break  # dispatch dominance: the (s-1)-way split already
            # shrank the wire below one dispatch tax
        if (w * (cm.dispatch_overhead_s + transfer_s / s)
                <= REPLICA_STORE_HEADROOM * compute_s):
            best = s
    return best


def choose_residency(k: int, checkpoint_every: int = 10,
                     preempt_latency_iters: Optional[int] = None,
                     cap: int = 64) -> int:
    """Cadence C (in supersteps) for the device-resident whole-run
    driver — :func:`choose_superstep` extended past the dispatch axis:
    K fixed how many iterations one PROGRAM advances; C fixes how many
    supersteps run between HOST callbacks once the loop itself lives on
    device (``optimize/resident_driver.py``).

    The choice rule, and the resident-vs-superstep crossover it
    records: residency only pays when a cadence window holds at least
    **2 supersteps** — at C=1 the resident loop would call back to the
    host exactly as often as the superstep driver dispatches, paying
    the io_callback round trip where the superstep driver pays the
    (comparable, ``dispatch_overhead_s``-calibrated) dispatch tax, for
    no structural win; BENCH_RESIDENT.json measures the counts.  So C
    is the LARGEST window that respects the two host-side bounds, and 0
    (keep the superstep driver) when that window is smaller than 2:

    * **checkpoint cadence** — the window may not exceed
      ``checkpoint_every`` iterations, or cadence saves (replayed
      inside the window callback) would trail their legacy iterations
      by a whole window;
    * **preemption latency** — stop signals are polled once per window,
      so the window may not exceed the preemption-latency budget
      (defaults to ``checkpoint_every``, the same grace-window
      reasoning as ADVICE.md's K <= checkpoint_every rule).

    ``cap`` bounds C itself (supersteps per window) as a backstop; the
    ring buffer stages ``C*K`` steps of history, and its ROW bound
    comes from the budget above — ``C*K`` never exceeds
    ``min(checkpoint_every, preempt_latency_iters)`` iterations, the
    same staging-vs-cadence reasoning as ``choose_superstep``'s cap."""
    K = max(1, int(k))
    if K < 2:
        return 0  # residency rides the fused executor; no K, no ring
    budget_iters = min(
        max(1, int(checkpoint_every)),
        max(1, int(preempt_latency_iters))
        if preempt_latency_iters is not None else max(
            1, int(checkpoint_every)),
    )
    c = min(int(cap), budget_iters // K)
    return int(c) if c >= 2 else 0


def choose_slab_capacity(n_tenants: int, d: int, itemsize: int = 4,
                         free_hbm: Optional[float] = None,
                         working_set: Optional[int] = None,
                         hot_frac: float = 0.1,
                         cost_model: CostModel = DEFAULT_COST_MODEL,
                         cap: int = 65536) -> int:
    """Slab capacity C (resident tenant rows) for the multi-tenant
    model store (``tpu_sgd/tenant``): the smallest power of two holding
    the HOT working set, clamped to what HBM can carry.

    The decision axes, in order:

    * **working set, not tenant count** — a Zipf-shaped tenant
      population serves most traffic from a small head, and every
      resident row costs HBM whether or not it is ever gathered, so C
      targets ``working_set`` (explicit, from the operator's traffic
      knowledge) or ``hot_frac * n_tenants`` (the default 10% head)
      rather than all ``n_tenants``.  Misses are not failures — the
      store re-admits from checkpoint at disk latency — but each one
      evicts a neighbor, so an undersized slab thrashes (the opt-in
      ``SlabThrashDetector`` watches the evict/admit ratio live).
    * **power-of-two rounding (up)** — the slab's capacity is a
      compiled-program shape root (``ops/bucketed.py``'s slab-program
      keys): every distinct capacity is a fresh compile of the gather,
      multi-model, and row-set programs, so quantizing keeps a fleet
      of stores on a handful of executables.
    * **HBM clamp** — ``C * (d + 1) * itemsize`` (rows + intercepts)
      must fit the measured free budget under the cost model's
      ``hbm_safety`` fraction (``free_hbm=None`` probes
      :func:`device_budget`), leaving the rest for serving batches and
      any co-resident training run.  ``cap`` backstops the search.

    Same contract as :func:`choose_replicas`: sizing ADVICE, not a
    schedule decision — the caller constructs the store with the
    returned capacity (or their own number) explicitly."""
    m = max(1, int(n_tenants))
    target = (max(1, int(working_set)) if working_set is not None
              else max(1, int(round(hot_frac * m))))
    target = min(target, m)
    c = 1
    while c < target:
        c *= 2
    if free_hbm is None:
        free_hbm, _ = device_budget(cost_model=cost_model)
    row_bytes = (int(d) + 1) * int(itemsize)
    budget = cost_model.hbm_safety * float(free_hbm)
    while c > 1 and c * row_bytes > budget:
        c //= 2
    return int(min(c, int(cap)))


def _fmt_gb(b: float) -> str:
    return f"{b / 1e9:.2f} GB"


def plan(
    n: int,
    d: int,
    *,
    itemsize: int = 4,
    gram_able: bool = False,
    sampling: str = "bernoulli",
    mini_batch_fraction: float = 1.0,
    num_iterations: int = 100,
    n_devices: int = 1,
    free_hbm: Optional[float] = None,
    host_resident_ok: bool = True,
    cost_model: CostModel = DEFAULT_COST_MODEL,
    force: Optional[str] = None,
    checkpoint_every: int = 10,
    stock_reads: int = 2,
) -> Plan:
    """Pick an execution schedule for an ``(n, d)`` dense dataset.

    Pure decision function — probing (device memory, dtype, gradient
    class) belongs to the caller; :func:`plan_for` does it for an
    optimizer + arrays.  Arguments:

    * ``itemsize`` — bytes per element of the training matrix (2 for
      bf16, 4 for f32).
    * ``gram_able`` — the gradient is exactly least squares (fixed-size
      sufficient statistics exist) AND the data is dense.
    * ``sampling`` / ``mini_batch_fraction`` — the USER's sampling
      semantics; the planner never changes them (gram requires sliced
      windows or full batch — under bernoulli/indexed sampling it simply
      does not qualify).
    * ``n_devices`` — data-mesh size; rows shard across it.
      ``streamed_virtual_gram`` composes with the mesh (per-shard virtual
      statistics streamed to each device); ``partial_residency`` is
      single-device only and reduces to ``host_streamed`` on a mesh.
    * ``free_hbm`` — plannable device bytes; defaults to
      :func:`device_budget`.
    * ``host_resident_ok`` — False when the data is already a committed
      device array (streaming schedules are then meaningless).
    * ``force`` — schedule name to apply regardless; the planner still
      runs its estimates and WARNS when the forced choice is estimated to
      lose (e.g. gram with ``build_amortize_iters > num_iterations``).
    * ``checkpoint_every`` — the optimizer's checkpoint cadence in
      iterations; bounds the device-residency window
      (:func:`choose_residency`) so cadence saves and preemption
      latency stay within one checkpoint interval.
    * ``stock_reads`` — how often a stock iteration reads its sampled
      rows: 1 where the step is the one-read kernel (a TPU, a layout it
      takes: :func:`plan_for` asks ``ops.gradients.step_sums``), 2
      where it is two matvecs.

    Least squares on a full batch, resident on ONE device, is planned in
    the statistics' TOTALS form (``estimates["stats_form"]``): one read of
    the rows builds ``G``, ``b``, ``yy`` (``ops.gram.stats_build``), no
    prefix stack, and ``resident_gram`` is chosen where ``build_s <
    num_iterations x (stock_iter_s - gram_iter_s)``.  Sliced windows and
    meshes keep the prefix form and its terms.

    Returns a :class:`Plan`; ``plan.estimates`` records every number the
    decision used.
    """
    if force is not None and force not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {force!r}; choose one of {SCHEDULES}"
        )
    cm = cost_model
    if free_hbm is None:
        free_hbm, budget_source = device_budget(cost_model=cm)
    else:
        budget_source = "caller"
    n_local = max(1, math.ceil(n / max(1, n_devices)))
    frac = float(mini_batch_fraction)
    full_batch = frac >= 1.0
    data_bytes_local = n_local * d * itemsize + n_local * 4.0  # + y
    fits = data_bytes_local <= free_hbm
    window_sliced = full_batch or sampling == "sliced"
    gram_eligible = bool(gram_able) and window_sliced

    est = {
        "n": int(n), "d": int(d), "itemsize": int(itemsize),
        "n_devices": int(n_devices), "n_local": int(n_local),
        "data_bytes_local": data_bytes_local,
        "free_hbm": float(free_hbm), "budget_source": budget_source,
        "fits_resident": bool(fits),
        "gram_eligible": gram_eligible,
        "sampling": sampling, "mini_batch_fraction": frac,
        "num_iterations": int(num_iterations),
    }

    # per-iteration walls of the candidate schedules (seconds)
    window_rows = n_local if full_batch else max(1, round(frac * n_local))
    stock_iter_s = (float(stock_reads) * window_rows * d * itemsize
                    / (cm.hbm_gb_s * 1e9))
    est["stock_iter_s"] = stock_iter_s
    est["stock_reads"] = int(stock_reads)

    def _gram_terms(B: int, aligned: bool):
        edge_bytes = 0.0 if aligned else 2.0 * B * d * itemsize
        prefix_bytes = 2.0 * (d * d + d) * 4.0
        it = (cm.gram_iter_overhead_s
              + (edge_bytes + prefix_bytes) / (cm.hbm_gb_s * 1e9))
        build = (cm.build_overhead_s
                 + n_local * d * itemsize / (cm.hbm_gb_s * 1e9)
                 + 2.0 * n_local * d * d / cm.mxu_f32_flops)
        return it, build

    def _totals_terms():
        # an iteration reads G once.  The build is the matrix unit's (one
        # bf16 pass over bf16 rows, HIGHEST over any other): the read of
        # the rows for G hides under its products, the one for b does not
        # (27.94 ms measured at 2,097,152 x 1000 bf16 for 29.2 here)
        it = (cm.gram_iter_overhead_s
              + (d * d + d) * 4.0 / (cm.hbm_gb_s * 1e9))
        flops = cm.mxu_bf16_flops if itemsize == 2 else cm.mxu_f32_flops
        build = (cm.totals_overhead_s
                 + n_local * d * itemsize / (cm.hbm_gb_s * 1e9)
                 + 2.0 * n_local * d * d / flops)
        return it, build

    chosen: Optional[Plan] = None

    # ---- resident regime -------------------------------------------------
    if fits:
        if gram_eligible:
            # a full batch on one device reads the totals alone
            totals = full_batch and n_devices == 1
            headroom = free_hbm - data_bytes_local
            B = None if totals else choose_block_rows(n_local, d, headroom)
            if B is not None or (totals and _totals_bytes(d) <= headroom):
                gram_iter_s, build_s = (
                    _totals_terms() if totals
                    else _gram_terms(B, aligned=False))
                saving = stock_iter_s - gram_iter_s
                amortize = (math.inf if saving <= 0
                            else build_s / saving)
                est.update(stats_form="totals" if totals else "prefix",
                           gram_iter_s=gram_iter_s,
                           gram_build_s=build_s,
                           build_amortize_iters=amortize)
                if not totals:
                    est["block_rows"] = B
                if amortize <= num_iterations:
                    if totals:
                        how = ("a full batch runs from the totals of its "
                               "rows (G, b, yy: one read, a build of "
                               f"~{build_s * 1e3:.0f} ms that")
                    else:
                        how = (f"{'full-batch' if full_batch else 'sliced'} "
                               "windows run from block-prefix statistics "
                               f"(B={B}, exact mode; build")
                    chosen = Plan(
                        "resident_gram",
                        f"data ({_fmt_gb(data_bytes_local)}/device) fits "
                        f"HBM ({_fmt_gb(free_hbm)} free); least-squares "
                        f"{how} amortizes in "
                        f"~{amortize:.0f} of {num_iterations} iters)",
                        block_rows=B, estimates=est,
                    )
                elif force == "resident_gram":
                    warnings.warn(
                        "forced resident_gram is estimated a NET LOSS "
                        f"here: the statistics build (~{build_s:.2f}s) "
                        f"amortizes in ~{amortize:.0f} iterations but the "
                        f"run is only {num_iterations}",
                        RuntimeWarning, stacklevel=3,
                    )
        if chosen is None:
            why = (
                f"data ({_fmt_gb(data_bytes_local)}/device) fits HBM "
                f"({_fmt_gb(free_hbm)} free)"
            )
            if gram_eligible and "build_amortize_iters" in est:
                why += (
                    "; statistics build would amortize in "
                    f"~{est['build_amortize_iters']:.0f} iters > "
                    f"{num_iterations} run length, so stock wins"
                )
            elif gram_able and not window_sliced:
                why += (
                    f"; sufficient stats need sliced windows or full "
                    f"batch (sampling={sampling!r} honored)"
                )
            chosen = Plan("resident_stock", why, estimates=est)

    # ---- beyond-HBM regime ----------------------------------------------
    if chosen is None:
        feed = cm.host_feed_gb_s * 1e9
        streamed_iter_s = window_rows * d * itemsize / feed
        est["streamed_iter_s"] = streamed_iter_s
        if gram_eligible:
            B, batch_rows = choose_streamed_build(n_local, d, itemsize,
                                                  free_hbm)
            if B is not None:
                gram_iter_s, _ = _gram_terms(B, aligned=True)
                build_s = (cm.build_overhead_s
                           + n_local * d * itemsize / feed)
                saving = streamed_iter_s - gram_iter_s
                amortize = (math.inf if saving <= 0
                            else build_s / saving)
                est.update(block_rows=B, batch_rows=batch_rows,
                           gram_iter_s=gram_iter_s,
                           gram_build_s=build_s,
                           build_amortize_iters=amortize,
                           stack_bytes=_stack_bytes(n_local, B, d),
                           # double-buffered ingest: two chunks live
                           staging_bytes=2.0 * batch_rows
                           * (d * itemsize + 4.0))
                if amortize <= num_iterations:
                    chosen = Plan(
                        "streamed_virtual_gram",
                        f"data ({_fmt_gb(data_bytes_local)}) exceeds HBM "
                        f"({_fmt_gb(free_hbm)} free) but its statistics "
                        f"({_fmt_gb(est['stack_bytes'])}, B={B}) fit "
                        "beside the build chunk: one streaming build "
                        f"pass (~{build_s:.0f}s at {cm.host_feed_gb_s} "
                        "GB/s), then iterations touch no rows.  NOTE: "
                        "uses ALIGNED (block-floored) windows — a "
                        "sampling deviation (fine on shuffled rows, not "
                        "on sorted/grouped data); pass "
                        "schedule='host_streamed' to keep exact windows",
                        block_rows=B, batch_rows=batch_rows,
                        aligned=True, estimates=est,
                    )
                elif force == "streamed_virtual_gram":
                    warnings.warn(
                        "forced streamed_virtual_gram is estimated a NET "
                        f"LOSS here: the streaming build (~{build_s:.0f}s) "
                        f"amortizes in ~{amortize:.0f} iterations but the "
                        f"run is only {num_iterations}",
                        RuntimeWarning, stacklevel=3,
                    )
        if chosen is None and (sampling == "sliced" and not full_batch
                               and n_devices == 1):
            m = max(1, round(frac * n_local))
            R = int((free_hbm - 4.0 * n_local) // (d * itemsize))
            p_resident = min(
                1.0, max(0.0, (R - m + 1) / max(n_local - m + 1, 1))
            )
            est.update(resident_rows=max(0, R),
                       resident_window_p=p_resident)
            if R >= m and p_resident >= cm.min_resident_gain:
                chosen = Plan(
                    "partial_residency",
                    f"data ({_fmt_gb(data_bytes_local)}) exceeds HBM "
                    f"({_fmt_gb(free_hbm)} free); keeping the leading "
                    f"{R} rows resident makes ~{p_resident:.0%} of "
                    "sliced windows transfer-free",
                    resident_rows=R, estimates=est,
                )
        if chosen is None:
            # superstep fusion: single-device only (the meshed feed now
            # fuses too, but through per-superstep host staging the
            # planner does not yet model), budgeted against the free
            # HBM a streamed schedule leaves idle — a quarter of it
            # caps the double-buffered superchunk staging; the shared
            # full-batch feed stages nothing (one transfer, the scan
            # reuses it), so only the amortization target binds there
            K = 1
            if n_devices == 1:
                # the shared full-batch feed transfers ONCE and then
                # iterates at the device rate, so its dispatch-tax
                # amortization is judged against stock_iter_s, not the
                # per-iteration feed slope (which it never pays after
                # the first transfer); it also stages no superchunk
                K = choose_superstep(
                    window_rows, d, itemsize,
                    stock_iter_s if full_batch else streamed_iter_s,
                    math.inf if full_batch else free_hbm * 0.25,
                    cost_model=cm)
            est["superstep"] = K
            # device residency: the run loop itself moves on device
            # when the feed is device-resident-data (full batch) and a
            # cadence window holds >= 2 supersteps (choose_residency's
            # crossover rule) — host hops drop from one per superstep
            # to one per window, and dispatches to one per run
            Cres = 0
            if n_devices == 1 and full_batch and K > 1:
                # under residency K no longer buys dispatch savings
                # (the whole run is one dispatch regardless) — shrink
                # it into the ADVICE K <= checkpoint_every rule, halved
                # so the cadence window holds >= 2 supersteps; the
                # shrink only sticks if residency actually engages —
                # when choose_residency still says 0 (a tight
                # checkpoint cadence), the dispatch tax IS the cost
                # model again and the unshrunk amortizing K wins
                K_res = max(2, min(K, max(1, int(checkpoint_every) // 2)))
                Cres = choose_residency(K_res, checkpoint_every)
                if Cres:
                    K = K_res
                    est["superstep"] = K
            est["residency"] = Cres
            # compressed gradient wire: where a real multi-shard
            # all-reduce exists and its bytes dominate the compress
            # cost — or, single-device, where the EF select rides the
            # RESIDENT body in-trace (ISSUE 20 lifted the PR 9 mutual
            # exclusion, so a plan may propose residency and the
            # compressed update together).  Matched-loss, not
            # matched-trajectory either way, so the proposal is loud
            # in the reason string
            wc = choose_wire_compress(d, n_devices, cost_model=cm,
                                      resident_cadence=Cres)
            est["wire_compress"] = wc
            fused_note = (
                f"; K={K} fused steps per dispatch amortize the "
                f"~{cm.dispatch_overhead_s * 1e3:.1f} ms/iter host "
                "dispatch tax" if K > 1 else "")
            if Cres:
                fused_note += (
                    f"; device-resident run loop (cadence {Cres} "
                    "supersteps/host hop — one dispatch per run)")
            if wc and n_devices > 1:
                fused_note += (
                    f"; compressed gradient wire ({wc}: top-k + error "
                    "feedback — matched final loss, NOT a bitwise "
                    "trajectory; pass wire_compress=False to keep the "
                    "dense all-reduce)")
            elif wc:
                fused_note += (
                    f"; compressed gradient wire ({wc}) riding the "
                    "resident body — the EF top-k selects in-trace "
                    "inside the one while-loop dispatch (ISSUE 20), "
                    "matched final loss, NOT a bitwise trajectory; "
                    "pass wire_compress=False to keep the dense "
                    "update")
            chosen = Plan(
                "host_streamed",
                f"data ({_fmt_gb(data_bytes_local)}) exceeds HBM "
                f"({_fmt_gb(free_hbm)} free); host-resident with "
                "double-buffered per-iteration batches "
                f"(~{streamed_iter_s:.2f}s/iter at {cm.host_feed_gb_s} "
                f"GB/s feed){fused_note}",
                superstep=K, residency=Cres, wire_compress=wc,
                estimates=est,
            )

    # async replica sizing advice (tpu_sgd/replica; README "Async
    # replicas"), stamped on EVERY returned plan: not a schedule choice
    # (τ>0 changes the update rule, so going async is the user's call),
    # just what the cost model says a fleet could be if they make it
    replicas = choose_replicas(n, d, itemsize, n_devices,
                               mini_batch_fraction=frac, cost_model=cm)
    # two-pass sizing: the single-apply fleet estimate feeds the shard
    # choice, then the replica advice is re-derived against the sharded
    # store (the fix for the stale single-apply model)
    store_shards = choose_store_shards(
        n, d, itemsize, n_devices, workers=max(2, replicas),
        mini_batch_fraction=frac, cost_model=cm)
    if store_shards > 1:
        replicas = choose_replicas(n, d, itemsize, n_devices,
                                   mini_batch_fraction=frac,
                                   cost_model=cm,
                                   store_shards=store_shards)
    est["replicas"] = replicas
    est["store_shards"] = store_shards

    if not host_resident_ok and chosen.schedule in (
            "partial_residency", "host_streamed", "streamed_virtual_gram"):
        chosen = Plan(
            "resident_stock",
            "data is already device-committed; streaming schedules do "
            "not apply (" + chosen.reason + ")",
            estimates=est,
        )

    if force is not None and force != chosen.schedule:
        forced = _forced_plan(
            force, chosen, est, fits=fits, free_hbm=free_hbm,
            data_bytes_local=data_bytes_local,
            per_dev=f"/device × {n_devices}" if n_devices > 1 else "",
            stacklevel=4,
            aligned=force == "streamed_virtual_gram",
            resident_rows=est.get("resident_rows", 0),
        )
        if force == "partial_residency" and not forced.resident_rows:
            if fits:
                raise ValueError(
                    "partial_residency cannot be forced here: the data "
                    f"({_fmt_gb(data_bytes_local)}/device) already fits "
                    "HBM — run resident, or shrink free_hbm to test the "
                    "beyond-HBM ladder"
                )
            raise ValueError(
                "partial_residency cannot be forced here: it needs "
                "sliced sampling with mini_batch_fraction < 1 on a "
                "single device, and at least one window of rows must "
                f"fit the budget (sampling={sampling!r}, frac={frac}, "
                f"n_devices={n_devices})"
            )
        return dataclasses.replace(forced, replicas=replicas,
                                   store_shards=store_shards)
    return dataclasses.replace(chosen, replicas=replicas,
                               store_shards=store_shards)


def _forced_plan(force, chosen, est, *, fits, free_hbm, data_bytes_local,
                 per_dev="", stacklevel=3, **plan_fields):
    """The forced-schedule contract, shared by :func:`plan` and
    :func:`plan_quasi_newton`'s ``_force_wrap``: warn when the forced
    schedule has no feasible statistics block size or exceeds the probed
    budget, then construct the forced :class:`Plan` recording what the
    planner would have picked instead."""
    if (force in ("resident_gram", "streamed_virtual_gram")
            and est.get("block_rows") is None
            and est.get("stats_form") != "totals"):
        warnings.warn(
            f"forced {force} has NO feasible block size at this "
            f"budget ({_fmt_gb(free_hbm)} free vs O(d²) statistics); "
            "the build will run at the default block size and may "
            "exhaust device memory",
            RuntimeWarning, stacklevel=stacklevel,
        )
    if force.startswith("resident_") and not fits:
        warnings.warn(
            f"forced {force} commits {_fmt_gb(data_bytes_local)}"
            f"{per_dev} to a device with only {_fmt_gb(free_hbm)} in "
            "the probed budget — it does not fit and will likely "
            "exhaust device memory",
            RuntimeWarning, stacklevel=stacklevel,
        )
    return Plan(
        force,
        f"forced by caller (planner would pick {chosen.schedule}: "
        + chosen.reason + ")",
        block_rows=est.get("block_rows"),
        batch_rows=est.get("batch_rows"),
        estimates=est, **plan_fields,
    )


def _device_dtype(X):
    """The type ``X``'s elements have on the device.  Asked of JAX's type
    lattice: a host array of ``ml_dtypes``' bfloat16 (what the fetch of a
    bf16 device array gives) is no ``np.inexact`` and was counted at four
    bytes an element, twice its size (PERF.md, PR 41).  int/bool features
    coerce to f32 in ``optimize()``."""
    import jax.numpy as jnp

    dt = jnp.dtype(getattr(X, "dtype", jnp.float32))
    return dt if jnp.issubdtype(dt, jnp.inexact) else jnp.dtype(jnp.float32)


def _stock_reads(optimizer, n_local: int, d: int, dtype) -> int:
    """How often a stock iteration of ``optimizer`` over a device's
    ``(n_local, d)`` rows of ``dtype`` reads what it samples: once where its
    step is the one-read kernel (a TPU, and a layout, width and sampling
    the kernel takes: ``step_sums``, from shapes and types alone), twice
    where it is two matvecs."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return 2
    shape = jax.ShapeDtypeStruct
    gradient = optimizer.gradient
    step = step_sums(
        gradient, optimizer.config, shape((n_local, d), dtype),
        shape((n_local,), jnp.float32),
        shape((gradient.weight_dim(d),), jnp.float32))
    return 1 if step.kernel is not None else 2


#: default host→device chunk budget in bytes (~256 MB keeps two in-flight
#: buffers ~0.5 GB beside the model state; the planner overrides per the
#: probed HBM budget)
_DEFAULT_CHUNK_BYTES = 256e6


def default_stream_batch_rows(d: int, itemsize: int,
                              chunk_bytes: Optional[float] = None) -> int:
    """Rows per streamed chunk at a byte budget (default ~256 MB) —
    THE chunk-sizing policy, shared by ``plan_quasi_newton`` and the
    streamed evaluator (``optimize/streamed_costfun.py``) so the planner's
    estimate and the evaluator's default cannot drift."""
    if chunk_bytes is None:
        chunk_bytes = _DEFAULT_CHUNK_BYTES
    return max(1024, int(chunk_bytes // max(1, d * itemsize)))


#: schedules a quasi-Newton optimizer can be forced onto
QN_SCHEDULES = ("resident_stock", "resident_gram", "host_streamed",
                "streamed_virtual_gram")


def plan_quasi_newton(optimizer, X, y,
                      cost_model: Optional[CostModel] = None,
                      free_hbm: Optional[float] = None,
                      force: Optional[str] = None) -> Optional[Plan]:
    """Schedule decision for the quasi-Newton optimizers (LBFGS/OWL-QN):
    enable the sufficient-statistics substitution when the one-time build
    amortizes inside ``max_num_iterations``, and pick the beyond-HBM
    execution otherwise.

    Each quasi-Newton iteration is several FULL-batch passes over ``X``
    (cost+gradient at the current and accepted points, plus the batched
    line-search sweep — ~4 row reads), so the break-even comes much
    earlier than for mini-batch SGD.  The menu:

    * least squares, fits HBM: ``resident_gram`` when the build
      amortizes, else ``resident_stock``;
    * least squares, beyond HBM: ``streamed_virtual_gram`` — one
      streaming build pass, then every cost/sweep is an O(d²)
      statistics read (single device: prefix stacks, the ``n % B`` tail
      dropped; meshed: per-shard O(d²) totals carries, EXACT);
    * any other loss, beyond HBM: ``host_streamed`` — the chunked
      treeAggregate CostFun (``optimize/streamed_costfun.py``), the
      literal analogue of the reference's any-size-any-loss CostFun
      ([U] mllib/optimization/LBFGS.scala, SURVEY.md §2 #18).

    Meshed optimizers (1-D data mesh) divide the HBM budget by the
    shard count exactly as the GD planner does; the statistics builds
    run per shard and combine to replicated totals.  ``force`` accepts
    any of ``QN_SCHEDULES``."""
    import numpy as np

    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS, GramData
    from tpu_sgd.ops.sparse import is_sparse

    if (getattr(optimizer, "planned_by", None) != "plan_quasi_newton"
            or is_sparse(X) or isinstance(X, GramData)):
        return None
    if force is not None and force not in QN_SCHEDULES:
        raise ValueError(
            f"schedule {force!r} does not exist behind a quasi-Newton "
            f"optimizer; choose one of {QN_SCHEDULES}"
        )
    n_devices = 1
    if optimizer.mesh is not None:
        from tpu_sgd.parallel.mesh import DATA_AXIS, MODEL_AXIS

        mesh_shape = optimizer.mesh.shape
        if DATA_AXIS not in mesh_shape or mesh_shape.get(MODEL_AXIS, 1) > 1:
            return None  # model-sharded: leave the user's config alone
        n_devices = int(mesh_shape[DATA_AXIS])
    shape = np.shape(X)
    if len(shape) != 2 or shape[0] == 0:
        return None
    n, d = (int(shape[0]), int(shape[1]))
    itemsize = _device_dtype(X).itemsize
    cm = cost_model or DEFAULT_COST_MODEL
    if free_hbm is None:
        free_hbm, budget_source = device_budget(cost_model=cm)
    else:
        budget_source = "caller"
    iters = int(optimizer.max_num_iterations)
    gram_able = type(optimizer.gradient) is LeastSquaresGradient
    n_local = max(1, math.ceil(n / n_devices))
    data_bytes_local = n_local * d * itemsize + n_local * 4.0
    fits = data_bytes_local <= free_hbm
    est = {
        "n": n, "d": d, "itemsize": int(itemsize),
        "n_devices": int(n_devices), "n_local": int(n_local),
        "data_bytes_local": data_bytes_local,
        "free_hbm": float(free_hbm), "budget_source": budget_source,
        "fits_resident": bool(fits), "gram_able": bool(gram_able),
        "max_num_iterations": iters,
    }
    per_dev = f"/device × {n_devices}" if n_devices > 1 else ""

    def _force_wrap(chosen):
        if force is None or force == chosen.schedule:
            return chosen
        return _forced_plan(
            force, chosen, est, fits=fits, free_hbm=free_hbm,
            data_bytes_local=data_bytes_local, per_dev=per_dev,
            stacklevel=5,
        )

    # ---- non-least-squares losses ---------------------------------------
    if not gram_able:
        if force in ("resident_gram", "streamed_virtual_gram"):
            raise ValueError(
                f"schedule {force!r} cannot apply: no fixed-size "
                "sufficient statistics exist for "
                f"{type(optimizer.gradient).__name__} (least squares "
                "only); choose resident_stock or host_streamed"
            )
        if fits:
            chosen = Plan(
                "resident_stock",
                f"data ({_fmt_gb(data_bytes_local)}{per_dev}) fits; "
                "stock full-batch passes (no fixed-size statistics "
                f"exist for {type(optimizer.gradient).__name__})",
                estimates=est,
            )
        else:
            # chunk sized so two in-flight buffers use <= half the
            # budget (the policy function is the evaluator's own too)
            # per-DEVICE budget: the evaluator shards each chunk
            # n_devices ways, so the global chunk scales with the mesh
            batch_rows = default_stream_batch_rows(
                d, itemsize, chunk_bytes=free_hbm * 0.25 * n_devices)
            est["batch_rows"] = batch_rows
            chosen = Plan(
                "host_streamed",
                f"data ({_fmt_gb(data_bytes_local)}{per_dev}) exceeds "
                f"HBM ({_fmt_gb(free_hbm)} free) and "
                f"{type(optimizer.gradient).__name__} has no fixed-size "
                "statistics: every full-batch cost/sweep streams the "
                "rows through the device in "
                f"{batch_rows}-row chunks (the chunked treeAggregate "
                "CostFun — feed-bound, ~3 dataset reads per iteration)",
                batch_rows=batch_rows, estimates=est,
            )
        return _force_wrap(chosen)

    # ---- least squares, beyond HBM --------------------------------------
    if not fits:
        B, batch_rows = choose_streamed_build(n_local, d, itemsize,
                                              free_hbm)
        if B is None and n_devices > 1:
            # the meshed build carries O(d²) totals, not prefix stacks —
            # feasible whenever one chunk fits beside the (d, d) carry
            rows = int((free_hbm - 3 * d * d * 4.0)
                       // max(1, 2 * (d * itemsize + 4)))
            if rows >= 1:
                B, batch_rows = min(DEFAULT_BLOCK_ROWS, rows), rows
        if B is not None:
            est.update(block_rows=B, batch_rows=batch_rows,
                       stack_bytes=(_stack_bytes(n_local, B, d)
                                    if n_devices == 1 else 3 * d * d * 4.0))
            tail_note = (
                f"exact totals; the n_local % {B} tail rows are dropped"
                if n_devices == 1 else
                "EXACT totals — the meshed build keeps every row"
            )
            chosen = Plan(
                "streamed_virtual_gram",
                f"data ({_fmt_gb(data_bytes_local)}{per_dev}) exceeds "
                f"HBM ({_fmt_gb(free_hbm)} free) but its statistics "
                f"({_fmt_gb(est['stack_bytes'])}, B={B}) fit beside the "
                "build chunk: one streaming build pass"
                f"{' per shard' if n_devices > 1 else ''}, then every "
                "full-batch cost/sweep is an O(d²) statistics read "
                f"({tail_note})",
                block_rows=B, batch_rows=batch_rows, estimates=est,
            )
        else:
            chosen = Plan(
                "resident_stock",
                f"data ({_fmt_gb(data_bytes_local)}{per_dev}) exceeds "
                f"HBM ({_fmt_gb(free_hbm)} free) and so does its O(d²) "
                "statistics stack; no schedule fits this device",
                estimates=est,
            )
        return _force_wrap(chosen)

    # ---- least squares, resident ----------------------------------------
    if n_devices == 1:
        B = choose_block_rows(n_local, d, free_hbm - data_bytes_local)
    else:
        # the meshed substitution carries O(d²) TOTALS per shard, not a
        # prefix stack (build_sharded_total_stats) — feasible whenever
        # the tiny carry fits the headroom
        from tpu_sgd.ops.gram import DEFAULT_BLOCK_ROWS as _DEF_B

        carry_bytes = 3 * d * d * 4.0
        B = (min(_DEF_B, n_local)
             if carry_bytes <= free_hbm - data_bytes_local else None)
    chosen = None
    if B is not None:
        # ~4 full row reads per iteration vs O(d^2) stats matvecs (the
        # 25-trial sweep's (T,d)x(d,d) matmul reads G once per chunk)
        stock_iter_s = 4.0 * n_local * d * itemsize / (cm.hbm_gb_s * 1e9)
        gram_iter_s = (cm.gram_iter_overhead_s
                       + 8.0 * d * d * 4.0 / (cm.hbm_gb_s * 1e9))
        build_s = (cm.build_overhead_s
                   + n_local * d * itemsize / (cm.hbm_gb_s * 1e9)
                   + 2.0 * n_local * d * d / cm.mxu_f32_flops)
        saving = stock_iter_s - gram_iter_s
        amortize = math.inf if saving <= 0 else build_s / saving
        est.update(block_rows=B, stock_iter_s=stock_iter_s,
                   gram_iter_s=gram_iter_s, gram_build_s=build_s,
                   build_amortize_iters=amortize)
        if amortize <= iters:
            chosen = Plan(
                "resident_gram",
                f"quasi-Newton least squares on a resident "
                f"({_fmt_gb(data_bytes_local)}{per_dev}) dataset: "
                f"full-batch cost/sweep from statistics (B={B}; build "
                f"amortizes in ~{amortize:.0f} of {iters} iterations"
                + ("; per-shard totals combine over the mesh"
                   if n_devices > 1 else "") + ")",
                block_rows=B, estimates=est,
            )
        elif force == "resident_gram":
            warnings.warn(
                "forced resident_gram is estimated a NET LOSS here: the "
                f"statistics build (~{build_s:.2f}s) amortizes in "
                f"~{amortize:.0f} iterations but max_num_iterations is "
                f"{iters}",
                RuntimeWarning, stacklevel=3,
            )
    if chosen is None:
        why = (f"data ({_fmt_gb(data_bytes_local)}{per_dev}) fits; "
               "stock full-batch passes")
        if "build_amortize_iters" in est:
            why += (
                f" (statistics build would amortize in "
                f"~{est['build_amortize_iters']:.0f} iters > {iters})"
            )
        chosen = Plan("resident_stock", why, estimates=est)
    return _force_wrap(chosen)


def plan_for(optimizer, X, y, cost_model: Optional[CostModel] = None,
             force: Optional[str] = None) -> Optional[Plan]:
    """Probe ``(optimizer, X, y)`` and :func:`plan` for it.

    Returns None (no planning) when the input is sparse (BCOO trains
    resident by construction) or the optimizer is not a
    ``GradientDescent``.  The caller applies/logs the returned plan."""
    import numpy as np

    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.sparse import is_sparse

    if getattr(optimizer, "planned_by", None) != "plan_for" or is_sparse(X):
        return None
    from tpu_sgd.ops.gram import GramData

    if isinstance(X, GramData):
        return None  # statistics-first input: the schedule is the input
    shape = np.shape(X)
    if len(shape) != 2 or shape[0] == 0:
        return None
    n, d = shape
    dtype = _device_dtype(X)
    cfg = optimizer.config
    mesh = optimizer.mesh
    n_devices = 1
    if mesh is not None:
        from tpu_sgd.parallel.mesh import DATA_AXIS, MODEL_AXIS

        if DATA_AXIS not in mesh.shape:
            return None  # model-only mesh: resident by construction
        if mesh.shape.get(MODEL_AXIS, 1) > 1:
            # 2-D (data x model) mesh: every streaming schedule needs a
            # 1-D data mesh, so there is nothing to plan — leave the
            # advanced-mesh configuration exactly as the user set it
            return None
        n_devices = int(mesh.shape[DATA_AXIS])  # rows shard over 'data'
    import jax

    host_resident_ok = not isinstance(X, jax.Array)
    return plan(
        int(n), int(d),
        itemsize=dtype.itemsize,
        gram_able=type(optimizer.gradient) is LeastSquaresGradient,
        sampling=cfg.sampling,
        mini_batch_fraction=cfg.mini_batch_fraction,
        num_iterations=cfg.num_iterations,
        n_devices=n_devices,
        host_resident_ok=host_resident_ok,
        cost_model=cost_model or DEFAULT_COST_MODEL,
        force=force,
        checkpoint_every=int(getattr(optimizer, "checkpoint_every", 10)),
        stock_reads=_stock_reads(optimizer, math.ceil(n / n_devices),
                                 int(d), dtype),
    )
