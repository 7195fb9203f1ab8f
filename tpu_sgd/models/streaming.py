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
waits only for what is left of the copy.  Spans (``obs.spans``):
``stream.run`` is all of ``train_on``; on its thread ``stream.wait`` (the
worker's answer, then in the rows form the blocks made whole),
``stream.batch`` (``index``, ``rows``, ``ahead``: 1 where the worker was
issuing this batch's copy before the previous batch's fit returned,
``totals``: 1 where the fit ran from a bundle made ahead of it) around the
fit's own spans and ``stream.publish`` (the stream position, the history's
tail, the checkpoint, the listeners); on the worker's ``stream.stage``
(``bytes``, ``blocks``, ``folded`` of a batch that went ahead).

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
from tpu_sgd.obs.spans import span
from tpu_sgd.optimize.gradient_descent import StagedAhead

Batch = Tuple[np.ndarray, np.ndarray]


def _shape_and_type(X):
    """What a plan is keyed by (``GeneralizedLinearAlgorithm._apply_plan``'s
    ``_plan_key`` starts with it)."""
    return np.shape(X), str(X.dtype)


def _take(batches, began: threading.Event, stage: bool, training: list,
          totals=None, alive=None):
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

    Any other goes in the ROWS form, blocks that need no device program, and
    ``training`` holds the features the fold is about to train (taken out of
    it here, so that no reference outlives the wait): the first block is
    issued only once they are whole on the device, when the blocks they were
    made of are gone.  Transfers do not wait for the chip, so a late join
    (its last blocks still on the wire, a stalled device) would else have a
    third micro-batch land beside its blocks and its result (12.6 GB read
    in one traced run; PERF.md, PR 40).

    ``stream.stage`` says ``bytes`` and ``blocks`` of a batch that went
    ahead, and ``folded``: the blocks whose share was folded under the copy
    (0 in the rows form)."""
    with span("stream.stage") as sp:
        batch = next(batches, None)
        if batch is None:
            return None
        X, y = batch
        X = as_features(X)
        if (stage and isinstance(X, np.ndarray) and X.ndim == 2
                and X.shape[0]
                and X.nbytes <= plan_mod.device_budget()[0]):
            folds = _shape_and_type(X) == totals
            before = training.pop()
            if not folds:
                jax.block_until_ready(before)
            del before
            began.set()
            X = StagedAhead(X, y if folds else None, alive)
            if folds:
                y = X.y
            sp.set(bytes=X.nbytes, blocks=X.count, folded=X.folded)
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

    def latest_model(self) -> GeneralizedLinearModel:
        if self.model is None:
            raise RuntimeError(
                "Model must be initialized (set_initial_weights) or trained "
                "before use"
            )
        return self.model

    def set_initial_weights(self, weights, intercept: float = 0.0):
        self.model = self.algorithm.create_model(
            np.asarray(weights, np.float32), intercept
        )
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
        aligned with the consumed prefix."""
        self._publish(self._fit(as_features(X), y))
        return self.model

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
        bundle a batch.  The weights, the listeners' calls and the
        checkpoints are the in-turn fold's, bit for bit (a batch taken
        ahead and not yet trained has not advanced ``_batch_count``; its
        totals are folded by the programs, in the order, that
        ``train_on_batch`` folds them with)."""
        if skip is None:
            skip = self._resume_skip
        self._resume_skip = 0
        batches = itertools.islice(stream, skip, None)
        pool = ThreadPoolExecutor(1, thread_name_prefix="tpu-sgd-stream")
        try:
            with span("stream.run"):
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
        tile it: ``stream.wait`` (the worker's answer: in the totals form
        every block issued and every fold dispatched, the fit queues behind
        the last of them on the device; in the rows form then the blocks
        made whole), then in ``stream.batch`` the fit's own and
        ``stream.publish``; ``stream.stage`` is the worker's.
        ``stream.batch`` says ``totals`` 1 where its fit ran from a bundle
        made ahead of it."""
        alive = collections.deque()  # the worker's blocks not yet folded

        def take(training=None):
            began = threading.Event()
            return began, pool.submit(
                _take, batches, began, self._stages_ahead(), [training],
                self._totals_key(training), alive)

        began, ahead = take()
        under = 0  # 1: this batch's copy began under its predecessor's fit
        while True:
            with span("stream.wait"):
                taken = ahead.result()
                if taken is None:
                    return
                X, y = taken
                if isinstance(X, StagedAhead) and X.totals is None:
                    # the rows form; its blocks are folded where they lie if
                    # the plan has come to be for them since they were taken
                    if self._totals_key(X) is not None:
                        X = X.fold(y)
                        y = X.y
                    else:
                        X = X.whole()
            began, ahead = take(X)
            with span("stream.batch") as turn:
                if turn.live:
                    turn.set(index=self._batch_count, rows=X.shape[0],
                             ahead=under,
                             totals=int(isinstance(X, StagedAhead)))
                updated = self._fit(X, y)
                under = int(began.is_set())
                del taken, X, y  # gone before the next is made whole
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
