"""Streaming (online) SGD over micro-batches.

Reference parity: [U] mllib/regression/StreamingLinearRegressionWithSGD.scala
and StreamingLinearAlgorithm.scala (SURVEY.md §2 #15, §3.3), plus
[U] mllib/classification/StreamingLogisticRegressionWithSGD.scala.  The
reference implements online learning by re-running the batch optimizer per
micro-batch, warm-started with the latest weights — there is no separate
online-SGD code path.  The TPU build reuses the batch step the same way
(config 5, BASELINE.json:11): a "DStream" is any iterator of ``(X, y)``
micro-batches, and ``train_on`` folds the model through it.

The fold holds one micro-batch AHEAD (PERF.md, PR 40): a micro-batch does
not depend on the weights, so while batch k trains a worker thread takes
batch k+1 from the stream and issues the host-to-device copy of its dense
rows in blocks (``gradient_descent.StagedAhead``), in one of two forms.
Where the fit runs from the TOTALS of its rows (least squares on the
planner's statistics schedule, a full batch: ``X^T X``, ``X^T y``,
``y^T y``) each block's share is folded into them by a device program of
its own while the next blocks are on the wire and the block is deleted: the
rows are never one array, nothing is built after the copy, the device holds
16 blocks and two 12 MB bundles (0.55 GB where the rows held 8.92), and the
worker goes on to batch k+2's blocks as soon as batch k+1's are issued, so
the wire never stands between two micro-batches (PERF.md, PR 44: a pass of
three 4.19 GB micro-batches 0.900 s where the wire's floor is 0.880 and the
rows form took 0.985).  Every other stream (logistic, a fraction under 1,
the stock schedule, ``set_schedule("off")``, a shape the plan in hand is not
for) goes as ROWS: blocks that need no device program, which land under the
running fit and are made the one array when the chip is free; the fold
waits only for what is left of the copy.

A stream's micro-batches are of UNEQUAL sizes (a DStream's RDD holds what
arrived in the batch interval), and a program compiled for a row count
would be compiled anew for every one of them.  So where no fit of the
stream can run from statistics (every gradient but least squares, whose
served path is the totals form above) a micro-batch whose size is not the
one before it's goes in the rows form at a row CAPACITY and not at its own
count (PERF.md, PR 52; ``StagedAhead``'s capacity form; a size that REPEATS
keeps an array of its own rows: the program compiled for it is found
again, so a stream of equal micro-batches trains as it did before): the
rows land in a device array of
``gradient_descent.row_capacity`` rows (a power of two of 1,024-row units
over the largest micro-batch so far: a function of the sizes' scale, never
of the sizes), the labels beside them, what lies past the real rows is
zeros made on the device, and the fit takes the real count as an OPERAND
(``ops.gradients.RowCount``): on a TPU the step's kernel bounds its grid by
it, so the padding is neither read nor counted; the plan, the repeat-run
key and every program are keyed by the capacity, so a stream probes, plans
and compiles once.  A micro-batch over the capacity in hand raises it (new
programs; the ``stream.regrow`` span counts it) and is trained whole.
Unequal sizes also mean that a large micro-batch's copy does not fit under
the fit of a small one before it, so where the device holds three arrays of
the capacity the fold holds TWO such micro-batches ahead (the second lands
under the fit before that one, which a small copy left the wire idle in),
and the stream's array of the capacity is made once: each micro-batch's
rows are written over the one trained before it, in place, so the device
never frees an array of that size to find room for the next while blocks
are landing beside it.  That write needs nothing the fit before it
produces, only the array once the fit has read it, which the device's queue
orders by itself: where the worker's take is done while fit k runs (the
fold waits for it under the fit, never past the fit's end), micro-batch
k+1's join is dispatched BEHIND fit k, before the host waits for the fit,
runs the moment the fit ends, and the host's turn-around (the fetch, the
publish with its listeners, the next fit's select and dispatch) passes
under it (PERF.md, PR 60).  Fit k+1 is still dispatched only once the
listeners of k have returned: they may set the weights it starts from.

Spans (``obs.spans``):
``stream.run`` is all of ``train_on``; on its thread ``stream.wait``
(``stream.take`` inside it: the worker's answer; then in the rows form
``stream.whole``: the blocks made whole, ``ahead`` 0; a micro-batch made
whole behind the fit before it has its ``stream.whole``, ``ahead`` 1, inside
THAT fit, between ``train.dispatch`` and ``train.fetch``, and its own
``stream.wait`` holds neither),
``stream.batch`` (``index``, ``rows``: the real ones, ``ahead``: 1 where
the worker was
issuing this batch's copy before the previous batch's fit returned,
``totals``: 1 where the fit ran from a bundle made ahead of it; at a
capacity also ``capacity`` and ``rows_read``, what one step of its fit
reads) around the
fit's own spans and ``stream.publish`` (the stream position, the history's
tail, the checkpoint, the listeners); on the worker's ``stream.stage``
(``bytes``: what crossed the wire of X, ``blocks``, ``folded`` of a batch
that went ahead, and ``rows``, ``capacity``, ``rows_read`` as above).

Driver recovery (SURVEY.md §5.4c): the reference rides DStream
checkpointing — a restarted driver resumes from the latest model and
stream position.  The analogue here is ``set_checkpoint`` (persist the
latest model + batch index every K micro-batches through the shared
``CheckpointManager``) and ``resume_from`` (reconstruct the algorithm
mid-stream from the newest checkpoint); with a replayable stream the
resumed run reproduces the uninterrupted run's weights and loss history
exactly, because each micro-batch update is deterministic in
``(warm-start weights, batch)``.
"""

from __future__ import annotations

import collections
import concurrent.futures
import itertools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Iterator, Optional, Tuple

import jax
import numpy as np

from tpu_sgd import plan as plan_mod
from tpu_sgd.models.classification import LogisticRegressionWithSGD
from tpu_sgd.models.glm import (GeneralizedLinearAlgorithm,
                                GeneralizedLinearModel, as_features,
                                off_stock)
from tpu_sgd.models.regression import LinearRegressionWithSGD
from tpu_sgd.obs.builds import root
from tpu_sgd.obs.spans import span
from tpu_sgd.optimize.gradient_descent import StagedAhead, row_capacity

Batch = Tuple[np.ndarray, np.ndarray]


def _shape_and_type(X):
    """What a plan is keyed by (``GeneralizedLinearAlgorithm._apply_plan``'s
    ``_plan_key`` starts with it)."""
    return np.shape(X), str(X.dtype)


def _take(batches, began: threading.Event, stage: bool, training: list,
          totals=None, alive=None, at=None):
    """On ``train_on``'s worker thread: the stream's next micro-batch
    ``(X, y)``, None at its end.  Where ``stage`` allows it, dense host rows
    that fit the device's free memory come back as a :class:`StagedAhead`,
    their copy issued in blocks; ``began`` is set before the first block is
    issued.  BCOO features, device arrays, an empty batch and one too large
    to lie beside the batch in training are handed on as they are (the fit
    copies what it must, in turn).

    ``totals`` is the ``(shape, dtype name)`` of the micro-batches that are
    SURE to be trained from the totals of their rows
    (``StreamingLinearAlgorithm._totals_key``; None: none is).  Such a one
    comes back in the TOTALS form, with its labels on the device: each
    block's share of ``(G, b, yy)`` is folded in under the next blocks'
    transfer and the block deleted, so nothing of it is ever whole, and its
    first block is issued as soon as fewer than 16 blocks are alive in
    ``alive``, the deque that counts them across the micro-batches: the wire
    does not stand between two micro-batches (PERF.md, PR 44).

    Any other goes in the ROWS form, blocks that need no device program
    (``at``, the stream whose micro-batches go at a row capacity, None where
    none does: in an array of ``at._capacity_of`` rows, the labels beside
    them, the real count an operand of the fit; else in one of its own
    rows), and
    ``training`` holds the features the fold is about to train (taken out of
    it here, so that no reference outlives the wait; of a micro-batch at a
    capacity its LABELS, made whole behind the rows, which the next one's
    are written over): the first block is
    issued only once they are whole on the device, when the blocks they were
    made of are gone (a join queued behind a running fit is whole when that
    fit has ended and the join has run: a take submitted with it waits as
    long as one submitted after the fit would have).  Transfers do not wait
    for the chip, so a late join
    (its last blocks still on the wire, a stalled device) would else have a
    third micro-batch land beside its blocks and its result (12.6 GB read
    in one traced run; PERF.md, PR 40).

    ``stream.stage`` says ``bytes`` and ``blocks`` of a batch that went
    ahead, ``folded``: the blocks whose share was folded under the copy
    (0 in the rows form), and ``rows``; at a capacity also ``capacity`` and
    ``rows_read`` (``GradientDescent.rows_read``), and ``bytes`` is what
    crossed the wire of X, the last block's fill included."""
    with span("stream.stage") as sp:
        batch = next(batches, None)
        if batch is None:
            return None
        X, y = batch
        X = as_features(X)
        if (stage and isinstance(X, np.ndarray) and X.ndim == 2
                and X.shape[0]):
            capacity = 0 if at is None else at._capacity_of(X)
            if not capacity and X.nbytes > plan_mod.device_budget()[0]:
                return X, y
            folds = not capacity and _shape_and_type(X) == totals
            before = training.pop()
            if not folds:
                jax.block_until_ready(before)
            del before
            began.set()
            X = StagedAhead(X, y if folds or capacity else None, alive,
                            capacity)
            if folds:
                y = X.y
            if sp.live:
                sp.set(bytes=X.wire_bytes, blocks=X.count, folded=X.folded,
                       rows=X.rows,
                       **(at._capacity_says(X) if capacity else {}))
        return X, y


class StreamingLinearAlgorithm:
    """Fold a GLM through a stream of micro-batches with warm restarts."""

    def __init__(self, algorithm: GeneralizedLinearAlgorithm):
        self.algorithm = algorithm
        self.model: Optional[GeneralizedLinearModel] = None
        self._batch_count = 0
        self.loss_history: list = []
        self.checkpoint_manager = None
        self.checkpoint_every = 1
        self.checkpoint_history_tail = None
        self._resume_skip = 0
        self._model_update_listeners: list = []
        #: the row capacity the stream's micro-batches are trained at (0:
        #: none yet); the worker and the fold both raise it
        self._capacity = 0
        #: the rows of the micro-batch before (``_capacity_of``)
        self._rows_before = 0
        #: the micro-batches the worker holds ahead of the one in training
        #: where they go at the capacity (``_capacity_of`` says when 2)
        self._ahead = 1
        self._capacity_lock = threading.Lock()

    @property
    def capacity(self) -> int:
        """The row capacity this stream's micro-batches are trained at
        (``tpu_sgd.row_capacity`` over the largest so far); 0 while none has
        been, or where they keep arrays of their own rows."""
        return self._capacity

    def latest_model(self) -> GeneralizedLinearModel:
        if self.model is None:
            raise RuntimeError(
                "Model must be initialized (set_initial_weights) or trained "
                "before use"
            )
        return self.model

    def set_initial_weights(self, weights, intercept: float = 0.0):
        """``weights`` as float32 on the host; float32 weights that lie on
        the device already stay there (the first fit takes them as the later
        ones take the model's)."""
        if not (isinstance(weights, jax.Array)
                and weights.dtype == np.float32):
            weights = np.asarray(weights, np.float32)
        self.model = self.algorithm.create_model(weights, intercept)
        return self

    def set_checkpoint(self, manager_or_directory, every: int = 1,
                       history_tail: int = None):
        """Persist (latest model, batch index, cumulative loss history)
        every ``every`` micro-batches — the DStream-checkpointing analogue
        (SURVEY.md §5.4c): kill the driver mid-stream and
        :meth:`resume_from` restarts from the newest checkpoint.  Accepts
        a ``CheckpointManager`` or a directory path.

        ``history_tail`` bounds the persisted loss history to its last N
        entries.  The default (None, full history) keeps resume BITWISE
        identical to the uninterrupted run — but re-serializes the whole
        unbounded history every checkpoint, which is O(N²) cumulative
        I/O over a long-lived stream; an UNBOUNDED stream with frequent
        checkpoints should set a tail (the resumed run's history then
        starts at the tail, weights still exact)."""
        import os

        from tpu_sgd.utils.checkpoint import CheckpointManager

        if isinstance(manager_or_directory, (str, os.PathLike)):
            manager_or_directory = CheckpointManager(
                str(manager_or_directory))
        self.checkpoint_manager = manager_or_directory
        self.checkpoint_every = max(1, int(every))
        if history_tail is not None and int(history_tail) < 1:
            raise ValueError(
                f"history_tail must be positive, got {history_tail}"
            )
        self.checkpoint_history_tail = (
            None if history_tail is None else int(history_tail))
        return self

    @classmethod
    def resume_from(cls, directory: str, every: int = 1, **init_kwargs):
        """Reconstruct a streaming algorithm mid-stream from the newest
        checkpoint in ``directory`` (written by :meth:`set_checkpoint`):
        latest model, batch index, and loss history are restored, and
        checkpointing continues into the same directory.  Construct with
        the SAME hyper-parameters as the interrupted run
        (``init_kwargs``) — they are not stored in the checkpoint.

        With a stream replayed from the beginning, the next
        :meth:`train_on` skips the already-consumed micro-batches and the
        run reproduces the uninterrupted weights/history exactly; a LIVE
        stream that only yields new batches should be consumed with
        ``train_on(stream, skip=0)``."""
        from tpu_sgd.utils.checkpoint import CheckpointManager

        import warnings

        self = cls(**init_kwargs)
        manager = CheckpointManager(directory)
        ck = manager.restore()
        if ck is None:
            raise FileNotFoundError(
                f"no checkpoint to resume from in {directory!r}"
            )
        if "intercept" not in ck["extras"]:
            raise ValueError(
                f"{directory!r} holds a non-streaming checkpoint "
                f"(config_key={ck['config_key']!r}); streaming resume "
                "needs one written by set_checkpoint"
            )
        expect_key = f"stream:{type(self.algorithm).__name__}"
        if ck["config_key"] != expect_key:
            warnings.warn(
                f"resuming a checkpoint written by {ck['config_key']!r} "
                f"with {expect_key!r} — construct the same streaming "
                "family/hyper-parameters as the interrupted run",
                RuntimeWarning,
                stacklevel=2,
            )
        self.set_checkpoint(manager, every=every)
        self.model = self.algorithm.create_model(
            ck["weights"], float(ck["extras"]["intercept"])
        )
        self._batch_count = int(ck["iteration"])
        self.loss_history = [float(v) for v in ck["loss_history"]]
        self._resume_skip = self._batch_count
        return self

    def add_model_update_listener(self, callback):
        """Register ``callback(model, batch_index)`` to fire after every
        micro-batch that updates the model — AFTER the checkpoint write
        for that batch (if any), so a listener that consumes the durable
        artifact (e.g. ``tpu_sgd.serve.ModelRegistry.on_model_update``)
        sees the published version.  Listener exceptions propagate to the
        training loop: a broken publisher should fail loudly, not train
        silently unpublished."""
        if not callable(callback):
            raise TypeError(f"callback must be callable, got {callback!r}")
        self._model_update_listeners.append(callback)
        return self

    def remove_model_update_listener(self, callback):
        self._model_update_listeners.remove(callback)
        return self

    def on_model_update(self):
        """Fire the registered model-update listeners with the current
        model and stream position."""
        for cb in self._model_update_listeners:
            cb(self.model, self._batch_count)

    def _maybe_checkpoint(self):
        if (self.checkpoint_manager is not None
                and self.model is not None
                and self._batch_count % self.checkpoint_every == 0):
            m = self.model
            self.checkpoint_manager.save(
                self._batch_count,  # = batches consumed (stream position)
                np.asarray(m.weights),
                0.0,
                np.asarray(
                    self.loss_history if self.checkpoint_history_tail
                    is None
                    else self.loss_history[-self.checkpoint_history_tail:],
                    np.float64,
                ),
                config_key=f"stream:{type(self.algorithm).__name__}",
                extras={
                    "intercept": np.asarray(m.intercept, np.float64),
                },
            )

    def train_on_batch(self, X, y) -> GeneralizedLinearModel:
        """One micro-batch update (the body of the reference's foreachRDD);
        accepts dense or sparse (BCOO) feature batches, on the host or on
        the device (a device array stays there).  EVERY batch —
        including an empty one, whose update is skipped like the
        reference skips empty RDDs — advances ``_batch_count``, so the
        count is the STREAM POSITION and a resumed replay's skip stays
        aligned with the consumed prefix.  A dense host micro-batch goes at
        a row capacity where the stream's do (``_in_capacity``), so that
        micro-batches of unequal sizes share their programs in turn as they
        do ahead."""
        self._publish(self._fit(self._in_capacity(as_features(X), y), y))
        return self.model

    def _by_capacity(self) -> bool:
        """Whether this stream's dense host micro-batches may be trained in
        arrays of a row CAPACITY, their real row count an operand of the
        fit, so that no program and no plan is made for a row count: where
        the optimizer takes one (``GradientDescent.trains_at_capacity``:
        the stock resident schedule on one device, and no statistics a plan
        could choose to run from) and the harness hands it the matrix as it
        is (no intercept column, no scaling).  Observed, never set; which
        micro-batches then do is ``_capacity_of``'s, from their sizes."""
        alg, opt = self.algorithm, self.algorithm.optimizer
        takes = getattr(opt, "trains_at_capacity", None)
        return (takes is not None and takes()
                and not alg.add_intercept and not alg.use_feature_scaling)

    def _capacity_of(self, X, beside: int = 1) -> int:
        """The row capacity ``X`` is trained at; 0 where it keeps an array
        of its own rows.  It does where its size REPEATS the size of the
        micro-batch before it: a program compiled for that row count is
        found again (a stream of equal micro-batches lowers, from its second
        one on, the programs it lowered before there was a capacity, and
        pays nothing for one); a size seen for the first time would compile
        a program that no later micro-batch and no later process uses.  And
        where ``beside`` arrays of the capacity do not fit the device's free
        memory (1 for a micro-batch taken ahead: the batch in training is
        counted in use, and is gone when the blocks are made whole beside
        themselves; 2 in turn).  The capacity is ``row_capacity`` over the
        largest micro-batch so far: one that passes the capacity in hand
        RAISES it (the ``stream.regrow`` span; its fit plans and compiles
        anew) and is trained whole.  As the capacity is made or raised the
        stream also learns how many micro-batches go ahead at it
        (``_ahead``): two where the device, empty, holds three arrays of it,
        else one.  Asked once a micro-batch, in stream
        order, by the worker or by the fold for one that comes in turn (a
        micro-batch the worker hands on as it came, too large to go ahead,
        is asked about again: it repeats itself, and the answer is 0 as
        before)."""
        with self._capacity_lock:
            repeats = X.shape[0] == self._rows_before
            self._rows_before = X.shape[0]
            if repeats:
                return 0
            capacity = row_capacity(X, self._capacity)
            nbytes = capacity * (X.nbytes // X.shape[0])
            if beside * nbytes > plan_mod.device_budget()[0]:
                return 0
            if capacity > self._capacity:
                if self._capacity:
                    with span("stream.regrow", rows=X.shape[0],
                              capacity=capacity, was=self._capacity):
                        pass  # counted: a point, in both tracers
                self._capacity = capacity
                # TWO go ahead where the device holds three arrays of the
                # capacity: the one in training and the rows of two
                # micro-batches landing beside it (what is free as each is
                # taken decides, above, whether it goes at all)
                self._ahead = 2 if 3 * nbytes <= plan_mod.device_budget(
                    empty=True)[0] else 1
            return capacity

    def _capacity_says(self, X) -> dict:
        """What ``stream.stage`` and ``stream.batch`` say of a micro-batch
        at a capacity beside its real ``rows``."""
        return dict(capacity=X.capacity,
                    rows_read=self.algorithm.optimizer.rows_read(X))

    def _in_capacity(self, X, y):
        """A dense host micro-batch that comes to its fit in turn, at the
        stream's row capacity where it has one (``_by_capacity``): the
        capacity form, its blocks issued inside the fit (``train.h2d``) as
        the worker would have issued them; anything else as it is."""
        if (isinstance(X, np.ndarray) and X.ndim == 2 and X.shape[0]
                and self._by_capacity()):
            capacity = self._capacity_of(X, beside=2)
            if capacity:
                return StagedAhead(X, y, capacity=capacity, issue=False)
        return X

    def _fit(self, X, y) -> bool:
        """The batch optimizer from the latest model over one micro-batch;
        False for an empty one (no update).  A dense host micro-batch that
        is trained from its totals (``_totals_key``) takes the path one
        taken ahead takes, on this thread: its row blocks folded into
        ``(G, b, yy)`` as they land, by the same programs in the same
        order, so the fold ahead and the fold in turn differ in when,
        never in what."""
        if X.shape[0] == 0:
            return False
        if (isinstance(X, np.ndarray) and X.ndim == 2
                and self._totals_key(X) is not None):
            X = StagedAhead(X, y)
            y = X.y
        self.model = self.algorithm.run_warm((X, y), self.model)
        return True

    def _publish(self, updated: bool) -> None:
        """The stream position, and for a batch that updated the model the
        history's tail, the checkpoint and the listeners, in that order."""
        with span("stream.publish"):
            self._batch_count += 1
            hist = getattr(self.algorithm.optimizer, "loss_history", None)
            if updated and hist is not None and len(hist):
                self.loss_history.append(float(hist[-1]))
            self._maybe_checkpoint()
            if updated:
                self.on_model_update()

    def train_on(self, stream: Iterable[Batch],
                 skip: Optional[int] = None) -> GeneralizedLinearModel:
        """Consume an entire stream (parity with ``trainOn(DStream)``).

        ``skip``: leading micro-batches to drop before training — defaults
        to the number already consumed when this instance was resumed via
        :meth:`resume_from` (so a stream replayed from the beginning
        continues where the interrupted run stopped); pass ``0`` for a
        live stream that only yields new batches.  The resume skip is
        consumed by the first ``train_on`` call.

        The fold is ``train_on_batch`` over the batches in order, and it
        holds ONE micro-batch ahead: a micro-batch does not depend on the
        weights, so while batch k trains a worker thread takes batch k+1
        from the stream and issues the host-to-device copy of its dense
        rows (``_take``), which lands under the running fit; the fold then
        waits only for what of the copy is left.  In the rows form the
        device holds the batch in training and the one ahead, never a
        third; in the totals form (a least-squares stream on the statistics
        schedule) no batch at all: 16 row blocks on their way and a 12 MB
        bundle a batch.  Where the stream goes at a row capacity
        (``_by_capacity``: a logistic stream; ``_capacity_of``: every
        micro-batch whose size is not the one before it's) what the device
        holds is ONE array of the CAPACITY's rows, which each micro-batch's
        rows are written over in turn, so that micro-batches of unequal
        sizes share one plan and one set of programs, and beside it the row
        blocks of the one ahead; of TWO ahead where three arrays of the
        capacity fit the device (``_capacity_of``), since a large
        micro-batch's copy does not fit under the fit of a small one before
        it.  There the write itself goes ahead too: where the worker's
        take of micro-batch k+1 is done while fit k runs, its rows'
        join is queued on the device BEHIND fit k, before the host waits
        for the fit (``_fold_ahead``), and runs as the fit ends, so the
        host's turn-around between two micro-batches (the fetch, the
        publish, the next fit's dispatch) passes under it and not under an
        idle chip; a take that is not done when the fit has ended (the
        model is published without waiting for it), a regrown capacity and
        every other form go in turn, as observed, with no setting.  Fit k+1
        itself is dispatched only after k's checkpoint and listeners, from
        the model that stands then (a listener may set it).  The weights,
        the listeners' calls and the
        checkpoints are the in-turn fold's, bit for bit (a batch taken
        ahead and not yet trained has not advanced ``_batch_count``; its
        totals are folded by the programs, in the order, that
        ``train_on_batch`` folds them with; a join is the same program over
        the same operands whenever it is dispatched)."""
        if skip is None:
            skip = self._resume_skip
        self._resume_skip = 0
        batches = itertools.islice(stream, skip, None)
        pool = ThreadPoolExecutor(1, thread_name_prefix="tpu-sgd-stream")
        try:
            with span("stream.run") as run_span, \
                    root("stream.run", run_span):
                self._fold_ahead(pool, batches)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        return self.model

    def _stages_ahead(self) -> bool:
        """Whether a micro-batch may go to the device beside the one in
        training: on one device alone, on the stock resident schedule or
        on the statistics schedule in its TOTALS form (a full batch:
        ``GradientDescent.stats_in_totals``), and once a plan (or
        ``set_schedule("off")``) has said that this is the schedule.  The
        totals' device state is 12 MB at d = 1000; they are folded from the
        micro-batch's row blocks as they land where the plan in hand is for
        its shape (``_totals_key``: no rows are kept), and else built from
        the rows where they lie, with no temporary of their size
        (``ops.gram.stats_build``; PERF.md, PRs 41 and 44).  The planner's other
        schedules size their own device state (a prefix stack, streamed
        chunks) from the memory that was free when they planned: the
        PREFIX build over a 4.19 GB micro-batch ran out of memory on the
        chip with a second one staged beside it (PERF.md, PR 40).  Until a
        stream's first fit has planned, its micro-batches are copied
        inside their fits, in turn."""
        opt = self.algorithm.optimizer
        if getattr(opt, "mesh", None) is not None:
            return False
        if off_stock(opt):
            # of the other schedules the statistics' totals form alone
            totals = getattr(opt, "stats_in_totals", None)
            if (opt.host_streaming or opt.streamed_stats
                    or totals is None or not totals()):
                return False
        return (self.algorithm.schedule == "off"
                or getattr(opt, "last_plan", None) is not None)

    def _totals_key(self, training=None):
        """``(shape, dtype name)`` of the dense host micro-batches that are
        SURE to be trained from the totals of their rows, so that their row
        blocks may be folded into ``(G, b, yy)`` as they land and no row
        kept (``StagedAhead``'s totals form); None where none is.  They are
        where ``_stages_ahead`` holds on the statistics schedule
        (``GradientDescent.fits_from_totals``), the plan in hand is for
        THIS shape and type (``_plan_key``: a micro-batch of another shape
        is planned anew by its fit, and once its rows are folded a stock
        fit could not be run from them), the harness hands the optimizer
        the matrix as it is (no intercept column, no scaling), and
        ``training``, the features whose fit comes first (None or empty:
        none does), are of that shape and type too, so that their fit
        leaves the plan as it is."""
        alg, opt = self.algorithm, self.algorithm.optimizer
        key = getattr(opt, "_plan_key", None)
        fits = getattr(opt, "fits_from_totals", None)
        if (key is None or getattr(opt, "last_plan", None) is None
                or fits is None or not fits() or not self._stages_ahead()
                or alg.add_intercept or alg.use_feature_scaling):
            return None
        if (training is not None and np.shape(training)[0]
                and _shape_and_type(training) != key[:2]):
            return None  # an empty micro-batch has no fit
        return key[:2]

    def _fold_ahead(self, pool, batches) -> None:
        """``train_on``'s loop under its ``stream.run`` span, whose leaves
        tile it: in ``stream.wait`` ``stream.take`` (the worker's answer,
        the oldest of the one or two takes it has been given: in
        the totals form
        every block issued and every fold dispatched, the fit queues behind
        the last of them on the device) and in the rows form then
        ``stream.whole`` (the blocks
        made whole; ``ahead`` 0), then in ``stream.batch`` the fit's own and
        ``stream.publish``; ``stream.stage`` is the worker's.
        ``stream.batch`` says ``totals`` 1 where its fit ran from a bundle
        made ahead of it.

        At a capacity micro-batch k+1 is made whole BEHIND the running fit
        of micro-batch k where the fold can (``whole_behind``; PERF.md, PR
        60): its ``stream.whole`` (``ahead`` 1) then lies inside fit k,
        between ``train.dispatch`` and ``train.fetch``, the join runs the
        moment the fit ends, the host's turn-around passes under it, and
        the ``stream.wait`` in front of fit k+1 holds no leaf.  Fit k+1
        itself is never dispatched ahead: a listener of k may set the
        weights it starts from."""
        alive = collections.deque()  # the worker's blocks not yet folded
        pending = collections.deque()  # the worker's takes, in stream order
        made = []  # the micro-batch made whole behind the running fit

        def take(training=None, ahead=1):
            """Takes submitted until ``ahead`` are the worker's; each waits
            for ``training`` to be whole before its first block."""
            while len(pending) < ahead:
                began = threading.Event()
                pending.append((began, pool.submit(
                    _take, batches, began, self._stages_ahead(), [training],
                    self._totals_key(training), alive,
                    self if self._by_capacity() else None)))

        def whole_behind(training, fit_done):
            """``StagedAhead.behind`` of the capacity form in training: its
            fit's program is in the device's queue and the host has not
            begun to wait for it.  Once the worker's oldest take is DONE
            while the fit still runs, and is a capacity form that ``whole``
            writes over ``training``'s array in place (``lands_in``: no
            second array of the capacity), its join is dispatched, behind
            the fit, and the next takes are submitted as they are in turn.
            A take still on its way is waited for under the running fit (a
            ``stream.take`` leaf inside the fit: where the wire is the
            slower the worker answers late in it) and no longer than the
            fit: once ``fit_done()`` holds the chip has nothing to run
            until the take is there, nothing is left to hide, and the model
            is published first, in turn.  Anything else (a take that
            raised, the stream's end, host rows, the rows or the totals
            form, another capacity's) is left to its turn too: what the
            fold observes decides, nothing is set."""
            answer = pending[0][1]  # a fit at a capacity has its takes out
            if not answer.done():
                with span("stream.take", behind=1):
                    # the interval moves nothing: a fit that ends inside it
                    # ends on a chip that has to wait for this take anyway
                    while not (answer.done() or fit_done()):
                        concurrent.futures.wait([answer], timeout=1e-3)
            if not answer.done() or answer.exception() is not None:
                return
            taken = answer.result()
            if taken is None or not (isinstance(taken[0], StagedAhead)
                                     and taken[0].lands_in(training)):
                return
            X, y = taken
            pending.popleft()
            with span("stream.whole", blocks=X.count, ahead=1):
                X.whole(training)
            take(X.y, self._ahead)
            made.append((X, y))

        take()
        under = 0  # 1: this batch's copy began under its predecessor's fit
        spent = None  # the capacity form trained last: its array is the next's
        while True:
            with span("stream.wait"):
                if made:
                    taken = made.pop()  # whole already (``whole_behind``)
                    X, y = taken
                else:
                    with span("stream.take"):  # a leaf: the worker's answer
                        taken = pending.popleft()[1].result()
                    if taken is None:
                        return
                    X, y = taken
                    if isinstance(X, StagedAhead) and X.totals is None:
                        # the rows form; its blocks are folded where they
                        # lie if the plan has come to be for them since
                        # they were taken
                        if (not X.capacity
                                and self._totals_key(X) is not None):
                            X = X.fold(y)
                            y = X.y
                        else:
                            with span("stream.whole", blocks=X.count,
                                      ahead=0):
                                X = X.whole(spent)
                    else:
                        X = self._in_capacity(X, y)
                spent = None
            staged = isinstance(X, StagedAhead)
            at_capacity = staged and bool(X.capacity)
            # a micro-batch at a capacity that is copied inside its fit (a
            # stream's first, before any plan): the next is taken after it,
            # so that the two are never on the wire, and whole, at once
            in_turn = at_capacity and X.X is None
            if at_capacity and not in_turn:
                # the labels are made whole behind the rows: ready when
                # they are, and no reference to the rows, which the next
                # micro-batch's are written over (``whole``)
                take(X.y, self._ahead)
                X.behind = whole_behind
            elif not in_turn:
                take(X)
            with span("stream.batch") as turn:
                if turn.live:
                    turn.set(index=self._batch_count,
                             rows=X.rows if staged else X.shape[0],
                             ahead=under,
                             totals=int(staged and not at_capacity),
                             **(self._capacity_says(X) if at_capacity
                                else {}))
                updated = self._fit(X, y)
                if in_turn:
                    take(None, self._ahead)
                # one made whole behind this fit was in the worker's hands
                # before the fit returned
                under = int(bool(made) or pending[0][0].is_set())
                if at_capacity:
                    spent = X  # its array stays: the next is made in it
                del taken, X, y  # any other is gone before the next is whole
                self._publish(updated)

    def predict_on(self, stream: Iterable[np.ndarray]) -> Iterator[np.ndarray]:
        """Lazily map prediction over a stream of feature batches, using the
        model snapshot current at consumption time (parity with
        ``predictOn``)."""
        for X in stream:
            yield np.asarray(self.latest_model().predict(X))

    def predict_on_values(
        self, stream: Iterable[Tuple[object, np.ndarray]]
    ) -> Iterator[Tuple[object, np.ndarray]]:
        """Keyed variant (parity with ``predictOnValues``)."""
        for key, X in stream:
            yield key, np.asarray(self.latest_model().predict(X))


class StreamingLinearRegressionWithSGD(StreamingLinearAlgorithm):
    def __init__(
        self,
        step_size: float = 0.1,
        num_iterations: int = 50,
        mini_batch_fraction: float = 1.0,
        reg_param: float = 0.0,
    ):
        super().__init__(
            LinearRegressionWithSGD(
                step_size, num_iterations, reg_param, mini_batch_fraction
            )
        )


class StreamingLogisticRegressionWithSGD(StreamingLinearAlgorithm):
    """[U] mllib/classification/StreamingLogisticRegressionWithSGD.scala:
    ``LogisticRegressionWithSGD`` (the logistic gradient under the squared-L2
    updater) re-run over every micro-batch from the latest weights, with
    upstream's defaults: ``stepSize`` 0.1, ``numIterations`` 50,
    ``miniBatchFraction`` 1.0, ``regParam`` 0.0 (at which the updater's rule
    is the plain step ``w - 0.1 / sqrt(t) * g``).

    A logistic fit has no statistics to run from: every step reads the
    micro-batch's rows, so the stream ALWAYS goes by rows
    (``StreamingLinearAlgorithm._by_capacity``): a micro-batch is kept whole
    on the device while it trains, beside the next one landing in blocks
    (the next two, where the device has the room: ``_capacity_of``).
    The rows lie in the stream's array of its row CAPACITY, their real count
    an operand of the fit, so that micro-batches of unequal sizes (what a
    DStream delivers) share one plan and one set of compiled programs; a
    micro-batch of the very size of the one before it keeps an array of its
    own rows (``_capacity_of``: its program is found again)."""

    def __init__(
        self,
        step_size: float = 0.1,
        num_iterations: int = 50,
        mini_batch_fraction: float = 1.0,
        reg_param: float = 0.0,
    ):
        super().__init__(
            LogisticRegressionWithSGD(
                step_size, num_iterations, reg_param, mini_batch_fraction
            )
        )
