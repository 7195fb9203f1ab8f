"""A stream of UNEQUAL micro-batches through the streaming model's entry
point: ``StreamingLogisticRegressionWithSGD(...).train_on(micro-batches)``
with the micro-batches' row ranges the generator's (``boundaries``: drawn from
the run's data, at a granularity of one row, no two runs the same) in place
of ``stream_train_on``'s fixed stride.

The stream is ONE, as a backlogged DStream is: pass after pass of the host
array's ranges from one iterator through one ``train_on`` (on a thread of its
own), so that the next micro-batch is ALWAYS waiting on the host, the next
pass's first one too while this pass's last one trains, and the program takes
it when it will.  One fit of the harness is one pass of it, and each pass is a
fresh stream's: the listener, called after the pass's last micro-batch is
published and before the next fit starts, hands the harness the pass's weights
and losses, sets the initial weights anew, and holds the fold there until the
harness asks for the next pass, so that nothing is trained outside a fit the
harness times (what the program has taken AHEAD by then stays taken).  A pass
whose first copy lay bare would follow the size the run's seed drew for it
(7% between the quartiles of six seeds on the chip: PERF.md, PR 52).

The configuration's micro-batches are trained with NO program compiled for a
row count (``row_count``: an operand): the entry holds the program to the row
capacity it states (``tpu_sgd.row_capacity``; a program without one
cannot run this configuration and fails here, at once)."""

import queue
import threading
import weakref

import jax.numpy as jnp
import numpy as np

import tpu_sgd
from bench.data.dense_synthetic_stream_uneven import boundaries
from tpu_sgd import row_capacity  # the parent of the PR that brought it: none


class _Dropped(Exception):
    """Raised in the stream's thread once the harness has dropped ``fit``."""


def prepare(config: dict, X, y, seed: int):
    """Build the streaming algorithm ONCE; ``fit() -> (the pass's last
    model's weights, every micro-batch's loss history in order)``."""
    alg = getattr(tpu_sgd, config["model"])(
        float(config["step_size"]), int(config["num_iterations"]),
        float(config["mini_batch_fraction"]), float(config["reg_param"]))
    opt = alg.algorithm.optimizer
    for kind in ("gradient", "updater"):
        if type(getattr(opt, kind)).__name__ != config[kind]:
            raise ValueError(
                f"{config['model']} trains "
                f"{type(getattr(opt, kind)).__name__}, the configuration "
                f"states {config[kind]}")
    (opt.set_sampling(config["sampling"])
     .set_convergence_tol(float(config["convergence_tol"]))
     .set_seed(seed))
    alg.algorithm.set_schedule(config["schedule"])
    ranges = boundaries(config, X)
    capacity = row_capacity(X[:max(b - a for a, b in ranges)])
    # on the device, once: every pass starts from them where they lie
    w0 = jnp.zeros((X.shape[1],), jnp.float32)
    losses, passes = [], queue.SimpleQueue()
    asked, dropped = threading.Semaphore(0), threading.Event()

    def listener(model, batch_count):
        # what a predictOn beside the stream reads: the latest weights, on
        # the host; and this micro-batch's losses from the optimizer
        weights = np.asarray(model.weights)
        losses.append(np.asarray(opt.loss_history))
        if len(losses) < len(ranges):
            return
        if alg.capacity != capacity:
            raise RuntimeError(
                f"micro-batches of up to {capacity} rows trained at a "
                f"capacity of {alg.capacity}: the configuration states "
                f"row_count {config['row_count']!r}")
        passes.put((weights, np.concatenate(losses)))
        del losses[:]
        alg.set_initial_weights(w0)  # the next pass is a fresh stream's
        asked.acquire()
        if dropped.is_set():
            raise _Dropped

    def stream():
        while True:
            for a, b in ranges:
                yield X[a:b], y[a:b]

    def run():
        try:
            asked.acquire()
            if not dropped.is_set():
                alg.add_model_update_listener(listener)
                alg.set_initial_weights(w0)
                alg.train_on(stream())
        except _Dropped:
            pass
        except BaseException as error:  # the harness's to raise, in fit()
            passes.put(error)

    thread = threading.Thread(target=run, name="bench-stream", daemon=True)
    thread.start()

    def fit():
        asked.release()
        while True:
            try:
                out = passes.get(timeout=1.0)
            except queue.Empty:
                if thread.is_alive():
                    continue
                raise RuntimeError("the stream has ended") from None
            if isinstance(out, BaseException):
                raise out
            return out

    def drop():
        dropped.set()
        asked.release()
        thread.join()

    # the harness drops ``fit`` before the reference's fit: the stream ends
    # there and gives up what it holds of the device
    weakref.finalize(fit, drop)
    return fit
