"""Streaming SGD tests (SURVEY.md §4: StreamingLinearRegressionSuite
analogue): deterministic micro-batch generator, weights move toward truth,
prediction error falls."""

import numpy as np
import pytest

from tpu_sgd.models.streaming import (
    StreamingLinearRegressionWithSGD,
    StreamingLogisticRegressionWithSGD,
)
from tpu_sgd.utils.mlutils import linear_data, logistic_data


def micro_batches(n_batches, n, d, w_true, eps=0.05, seed=0):
    """Deterministic generator — the analogue of ManualClock queued batches."""
    for i in range(n_batches):
        X, y, _ = linear_data(n, d, weights=w_true, eps=eps, seed=seed + i)
        yield X, y


def test_streaming_linear_converges_to_truth():
    d = 8
    w_true = np.linspace(-1, 1, d).astype(np.float32)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=20)
    alg.set_initial_weights(np.zeros(d, np.float32))
    errs = []
    for X, y in micro_batches(10, 500, d, w_true):
        alg.train_on_batch(X, y)
        errs.append(np.linalg.norm(np.asarray(alg.latest_model().weights) - w_true))
    assert errs[-1] < 0.1
    assert errs[-1] < errs[0]


def test_streaming_prediction_error_falls():
    d = 6
    w_true = np.ones(d, np.float32)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=20)
    alg.set_initial_weights(np.zeros(d, np.float32))
    Xt, yt, _ = linear_data(500, d, weights=w_true, eps=0.01, seed=99)
    alg.train_on_batch(*next(micro_batches(1, 500, d, w_true, seed=1)))
    early = np.mean((np.asarray(alg.latest_model().predict(Xt)) - yt) ** 2)
    alg.train_on(micro_batches(8, 500, d, w_true, seed=2))
    late = np.mean((np.asarray(alg.latest_model().predict(Xt)) - yt) ** 2)
    assert late < early


def test_streaming_train_on_full_stream():
    d = 4
    w_true = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=25)
    alg.set_initial_weights(np.zeros(d, np.float32))
    model = alg.train_on(micro_batches(12, 400, d, w_true))
    np.testing.assert_allclose(np.asarray(model.weights), w_true, atol=0.15)


def test_predict_on_uses_latest_model():
    d = 3
    alg = StreamingLinearRegressionWithSGD()
    alg.set_initial_weights(np.ones(d, np.float32))
    stream = [np.eye(d, dtype=np.float32)]
    (pred,) = list(alg.predict_on(iter(stream)))
    np.testing.assert_allclose(pred, np.ones(d), rtol=1e-5)


def test_predict_on_values_keys_preserved():
    d = 2
    alg = StreamingLinearRegressionWithSGD()
    alg.set_initial_weights(np.zeros(d, np.float32))
    out = list(alg.predict_on_values([("a", np.ones((1, d), np.float32))]))
    assert out[0][0] == "a"


def test_uninitialized_model_raises():
    alg = StreamingLinearRegressionWithSGD()
    with pytest.raises(RuntimeError, match="initialized"):
        alg.latest_model()


def test_empty_batch_skipped():
    d = 3
    alg = StreamingLinearRegressionWithSGD()
    alg.set_initial_weights(np.ones(d, np.float32))
    before = np.asarray(alg.latest_model().weights).copy()
    alg.train_on_batch(np.zeros((0, d), np.float32), np.zeros((0,), np.float32))
    np.testing.assert_array_equal(np.asarray(alg.latest_model().weights), before)


def test_streaming_logistic():
    d = 5
    w_true = np.asarray([1.0, -1.0, 2.0, -2.0, 0.5], np.float32)
    alg = StreamingLogisticRegressionWithSGD(step_size=0.5, num_iterations=20)
    alg.set_initial_weights(np.zeros(d, np.float32))
    for i in range(8):
        X, y, _ = logistic_data(600, d, weights=w_true, seed=i)
        alg.train_on_batch(X, y)
    Xt, yt, _ = logistic_data(1000, d, weights=w_true, seed=100)
    acc = np.mean(np.asarray(alg.latest_model().predict(Xt)) == yt)
    bayes = np.mean((Xt @ w_true > 0).astype(np.float32) == yt)
    assert acc > bayes - 0.03


# ---- driver recovery: checkpoint / resume (SURVEY.md §5.4c) ---------------

def _replayable_stream(d=12, batches=10, rows=500):
    w_true = np.linspace(-1, 1, d).astype(np.float32)
    out = []
    for i in range(batches):
        r = np.random.default_rng(100 + i)
        X = r.normal(size=(rows, d)).astype(np.float32)
        y = (X @ w_true + 0.05 * r.normal(size=rows)).astype(np.float32)
        out.append((X, y))
    return out, w_true


def test_streaming_checkpoint_resume_reproduces_run(tmp_path):
    """Kill the stream after batch j, resume from the checkpoint directory,
    replay: weights AND loss history must equal the uninterrupted run's
    bitwise (each micro-batch update is deterministic in (warm weights,
    batch))."""
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    stream, w_true = _replayable_stream()
    kwargs = dict(step_size=0.3, num_iterations=20)

    full = StreamingLinearRegressionWithSGD(**kwargs)
    full.set_initial_weights(np.zeros(12, np.float32))
    full.set_checkpoint(str(tmp_path / "full"), every=1)
    full.train_on(stream)

    # interrupted driver: consumes only the first 4 batches, then "dies"
    part = StreamingLinearRegressionWithSGD(**kwargs)
    part.set_initial_weights(np.zeros(12, np.float32))
    part.set_checkpoint(str(tmp_path / "resume"), every=1)
    part.train_on(stream[:4])
    del part

    # restarted driver: resume + replay the SAME stream from the start
    res = StreamingLinearRegressionWithSGD.resume_from(
        str(tmp_path / "resume"), **kwargs)
    assert res._batch_count == 4
    res.train_on(stream)
    assert res._batch_count == 10

    np.testing.assert_array_equal(
        np.asarray(res.latest_model().weights),
        np.asarray(full.latest_model().weights))
    assert res.latest_model().intercept == full.latest_model().intercept
    np.testing.assert_array_equal(np.asarray(res.loss_history),
                                  np.asarray(full.loss_history))
    assert len(res.loss_history) == 10


def test_streaming_resume_preserves_intercept(tmp_path):
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    stream, _ = _replayable_stream(batches=3)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=10)
    alg.algorithm.set_intercept(True)
    alg.set_initial_weights(np.zeros(12, np.float32), intercept=0.5)
    alg.set_checkpoint(str(tmp_path), every=1)
    alg.train_on(stream)
    want = alg.latest_model().intercept

    res = StreamingLinearRegressionWithSGD.resume_from(
        str(tmp_path), step_size=0.3, num_iterations=10)
    res.algorithm.set_intercept(True)
    assert res.latest_model().intercept == want


def test_streaming_resume_empty_dir_raises(tmp_path):
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        StreamingLinearRegressionWithSGD.resume_from(str(tmp_path / "x"))


def test_streaming_checkpoint_every_k(tmp_path):
    import glob as _glob

    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD
    from tpu_sgd.utils.checkpoint import CheckpointManager

    stream, _ = _replayable_stream(batches=6)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=5)
    alg.set_initial_weights(np.zeros(12, np.float32))
    alg.set_checkpoint(CheckpointManager(str(tmp_path), keep=10), every=2)
    alg.train_on(stream)
    files = sorted(_glob.glob(str(tmp_path / "ckpt_*.npz")))
    # every=2 over 6 batches -> checkpoints at batch 2, 4, 6
    assert [int(f[-12:-4]) for f in files] == [2, 4, 6]


def test_streaming_resume_live_stream_skip_zero(tmp_path):
    """A live stream yields only NEW batches: skip=0 must train them all
    instead of dropping the first batch_count."""
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    stream, _ = _replayable_stream(batches=6)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=5)
    alg.set_initial_weights(np.zeros(12, np.float32))
    alg.set_checkpoint(str(tmp_path), every=1)
    alg.train_on(stream[:3])

    res = StreamingLinearRegressionWithSGD.resume_from(
        str(tmp_path), step_size=0.3, num_iterations=5)
    res.train_on(stream[3:], skip=0)  # live continuation
    assert res._batch_count == 6
    # and the result matches the replayed-resume path on the same data
    res2 = StreamingLinearRegressionWithSGD.resume_from(
        str(tmp_path), step_size=0.3, num_iterations=5)
    res2.train_on(stream)  # replay: default skip drops first 3
    np.testing.assert_array_equal(
        np.asarray(res.latest_model().weights),
        np.asarray(res2.latest_model().weights))


def test_streaming_resume_empty_batches_stay_aligned(tmp_path):
    """An empty micro-batch advances the stream position (no update), so
    a resumed replay's skip cannot double-train the batch after it
    (review r4 finding)."""
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD

    stream, _ = _replayable_stream(batches=5)
    d = stream[0][0].shape[1]
    empty = (np.zeros((0, d), np.float32), np.zeros((0,), np.float32))
    stream = [stream[0], empty] + stream[1:]  # empty at position 1
    kwargs = dict(step_size=0.3, num_iterations=10)

    full = StreamingLinearRegressionWithSGD(**kwargs)
    full.set_initial_weights(np.zeros(d, np.float32))
    full.train_on(stream)

    part = StreamingLinearRegressionWithSGD(**kwargs)
    part.set_initial_weights(np.zeros(d, np.float32))
    part.set_checkpoint(str(tmp_path), every=1)
    part.train_on(stream[:3])  # consumes batch0, empty, batch1
    assert part._batch_count == 3  # stream POSITION, empties included

    res = StreamingLinearRegressionWithSGD.resume_from(str(tmp_path),
                                                       **kwargs)
    res.train_on(stream)
    np.testing.assert_array_equal(
        np.asarray(res.latest_model().weights),
        np.asarray(full.latest_model().weights))
    np.testing.assert_array_equal(np.asarray(res.loss_history),
                                  np.asarray(full.loss_history))


def test_streaming_resume_rejects_non_streaming_checkpoint(tmp_path):
    from tpu_sgd.models.streaming import StreamingLinearRegressionWithSGD
    from tpu_sgd.utils.checkpoint import CheckpointManager

    CheckpointManager(str(tmp_path)).save(
        5, np.zeros(4, np.float32), 0.0, np.zeros(5), config_key="sgd:cfg")
    with pytest.raises(ValueError, match="non-streaming checkpoint"):
        StreamingLinearRegressionWithSGD.resume_from(str(tmp_path))


def test_streaming_resume_family_mismatch_warns(tmp_path):
    import warnings as _warnings

    from tpu_sgd.models.streaming import (
        StreamingLinearRegressionWithSGD,
        StreamingLogisticRegressionWithSGD,
    )

    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=5)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.set_checkpoint(str(tmp_path), every=1)
    X = np.random.default_rng(0).normal(size=(64, 6)).astype(np.float32)
    y = (X @ np.ones(6, np.float32)).astype(np.float32)
    alg.train_on_batch(X, y)

    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        StreamingLogisticRegressionWithSGD.resume_from(str(tmp_path))
    assert any("construct the same streaming" in str(r.message)
               for r in rec)


def test_checkpoint_history_tail_bounds_persisted_history(tmp_path, rng):
    """history_tail caps per-checkpoint serialization for unbounded
    streams (full-history default stays bitwise; the tail trades the
    resumed history's head for O(N) instead of O(N^2) cumulative I/O)."""
    from tpu_sgd.utils.checkpoint import CheckpointManager

    alg = (StreamingLinearRegressionWithSGD(step_size=0.3,
                                            num_iterations=5)
           .set_initial_weights(np.zeros(4, np.float32))
           .set_checkpoint(str(tmp_path / "ck"), every=1, history_tail=3))
    w = rng.uniform(-1, 1, 4).astype(np.float32)
    for i in range(6):
        X = rng.normal(size=(64, 4)).astype(np.float32)
        y = (X @ w).astype(np.float32)
        alg.train_on_batch(X, y)
    assert len(alg.loss_history) == 6  # in-memory history stays full
    st = CheckpointManager(str(tmp_path / "ck")).restore()
    assert st["iteration"] == 6
    assert len(st["loss_history"]) == 3  # persisted history bounded
    with pytest.raises(ValueError, match="history_tail"):
        StreamingLinearRegressionWithSGD().set_checkpoint(
            str(tmp_path / "ck2"), history_tail=0)


# ---- one micro-batch ahead: the fold is the in-turn fold's ----------------

def _blocked(monkeypatch, d, in_flight=2):
    """Hand-off blocks of ``_STAGE_ROWS`` rows, ``in_flight`` at a time."""
    import tpu_sgd.optimize.gradient_descent as gd

    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES", gd._STAGE_ROWS * d * 4)
    monkeypatch.setattr(gd, "_STAGE_IN_FLIGHT", in_flight)
    return gd


def _mixed_stream(kind, d=6, batches=5):
    """``batches`` micro-batches of 3 blocks and a bit, the third EMPTY;
    ``kind`` says where the features lie."""
    import jax.numpy as jnp

    from tpu_sgd.ops.sparse import sparse_data

    w_true = np.linspace(-1, 1, d).astype(np.float32)
    out = []
    for i in range(batches):
        r = np.random.default_rng(40 + i)
        rows = 0 if i == 2 else 3 * 1024 + 100
        if kind == "bcoo" and rows:
            X, y, _ = sparse_data(rows, d, nnz_per_row=3, seed=40 + i)
            out.append((X, np.asarray(y, np.float32)))
            continue
        X = r.normal(size=(rows, d)).astype(np.float32)
        y = (X @ w_true + 0.05 * r.normal(size=rows)).astype(np.float32)
        out.append((jnp.asarray(X) if kind == "device" else X, y))
    return out


@pytest.mark.parametrize("kind", ["host", "device", "bcoo"])
def test_train_on_ahead_is_the_in_turn_fold_bit_for_bit(kind, monkeypatch):
    """``train_on`` (one micro-batch staged ahead) against ``train_on_batch``
    called in turn: weights, loss history, stream position, and the
    listeners' calls, with an empty micro-batch in the middle."""
    _blocked(monkeypatch, d=6)
    stream = _mixed_stream(kind)

    def fold(how):
        alg = StreamingLinearRegressionWithSGD(step_size=0.2,
                                               num_iterations=8)
        alg.set_initial_weights(np.zeros(6, np.float32))
        calls = []
        alg.add_model_update_listener(
            lambda model, count: calls.append(
                (count, np.asarray(model.weights).copy(),
                 np.asarray(alg.algorithm.optimizer.loss_history).copy())))
        if how == "ahead":
            alg.train_on(iter(stream))
        else:
            for X, y in stream:
                alg.train_on_batch(X, y)
        return alg, calls

    (ahead, calls_a), (turn, calls_t) = fold("ahead"), fold("in turn")
    np.testing.assert_array_equal(np.asarray(ahead.latest_model().weights),
                                  np.asarray(turn.latest_model().weights))
    assert ahead.loss_history == turn.loss_history
    assert ahead._batch_count == turn._batch_count == 5  # the empty one too
    # once a micro-batch that updated the model, in order, the same model
    assert [c[0] for c in calls_a] == [c[0] for c in calls_t] == [1, 2, 4, 5]
    for (_, wa, la), (_, wt, lt) in zip(calls_a, calls_t):
        np.testing.assert_array_equal(wa, wt)
        np.testing.assert_array_equal(la, lt)


def test_dense_host_micro_batches_go_ahead_in_blocks(monkeypatch):
    """What the worker hands the fold: a dense host batch as blocks that no
    device program has touched, made whole bit for bit; everything else as
    it came."""
    import jax
    import jax.numpy as jnp
    import threading

    from tpu_sgd.models import streaming
    from tpu_sgd.optimize.gradient_descent import StagedAhead

    _blocked(monkeypatch, d=6)
    (X, y), = _mixed_stream("host", batches=1)
    began = threading.Event()
    staged, y_out = streaming._take(iter([(X, y)]), began, True, [None])
    assert began.is_set() and y_out is y
    assert isinstance(staged, StagedAhead) and len(staged.blocks) == 4
    assert [b.shape[0] for b in staged.blocks] == [1024, 1024, 1024, 100]
    whole = staged.whole()
    assert isinstance(whole, jax.Array) and staged.blocks is None
    np.testing.assert_array_equal(np.asarray(whole), X)
    # one block: no write at all
    small = streaming._take(iter([(X[:100], y[:100])]), began, True,
                            [None])[0]
    assert len(small.blocks) == 1 and small.whole().shape == (100, 6)
    # a device array, an empty batch, the stream's end
    Xd = jnp.asarray(X)
    assert streaming._take(iter([(Xd, y)]), began, True, [None])[0] is Xd
    assert isinstance(streaming._take(iter([(X[:0], y[:0])]),
                                      began, True, [None])[0],
                      np.ndarray)
    ended = threading.Event()
    assert streaming._take(iter([]), ended, True, [None]) is None
    assert streaming._take(iter([(Xd, y)]), ended, True, [None])[0] is Xd
    assert streaming._take(iter([(X, y)]), ended, False, [None])[0] is X
    training = [whole]  # what the fold trains: waited for, not kept
    streaming._take(iter([(X, y)]), began, True, training)
    assert training == []
    assert not ended.is_set()  # nothing of those was issued
    # a batch too large to lie beside the one in training stays on the host
    monkeypatch.setattr(streaming.plan_mod, "device_budget",
                        lambda *a, **k: (X.nbytes - 1, "test"))
    assert streaming._take(iter([(X, y)]), began, True, [None])[0] is X


def test_train_on_batch_leaves_a_device_array_on_the_device(monkeypatch):
    import jax
    import jax.numpy as jnp

    (X, y), = _mixed_stream("host", batches=1)
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=4)
    alg.set_initial_weights(np.zeros(6, np.float32))
    seen = []
    real = alg.algorithm.optimizer.optimize

    def optimize(data, w0):
        seen.append(data[0])
        return real(data, w0)

    monkeypatch.setattr(alg.algorithm.optimizer, "optimize", optimize)
    Xd = jnp.asarray(X)
    monkeypatch.setattr(np, "asarray", _no_fetch_of(Xd, np.asarray))
    alg.train_on_batch(Xd, y)
    assert seen[0] is Xd and isinstance(seen[0], jax.Array)


def _no_fetch_of(array, real):
    def asarray(a, *args, **kwargs):
        assert a is not array, "the device batch was fetched to the host"
        return real(a, *args, **kwargs)
    return asarray


def test_at_most_two_micro_batches_are_alive_at_once(monkeypatch):
    """The worker takes micro-batch j only once j - 2 is trained and gone,
    and after any fit the device holds the rows of at most two."""
    import jax

    _blocked(monkeypatch, d=6)
    stream = _mixed_stream("host", batches=6)
    rows = 3 * 1024 + 100
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=4)
    alg.set_initial_weights(np.zeros(6, np.float32))
    taken_at, alive = [], []

    def batches():
        for batch in stream:
            taken_at.append(alg._batch_count)
            yield batch

    real = alg.algorithm.run_warm

    def run_warm(data, model):
        out = real(data, model)
        alive.append(sum(a.shape[0] for a in jax.live_arrays()
                         if a.ndim == 2 and a.shape[1] == 6))
        return out

    monkeypatch.setattr(alg.algorithm, "run_warm", run_warm)
    alg.train_on(batches())
    assert alg._batch_count == 6
    for j, finished in enumerate(taken_at):
        assert finished >= j - 1, (j, taken_at)
    assert len(alive) == 5 and max(alive) <= 2 * rows


def test_a_stop_between_staged_and_trained_replays_that_batch(tmp_path):
    """The driver dies in batch 3's listener while batch 4 is already in the
    worker's hands: the checkpoint says 3, and the resumed replay trains
    batch 4 and reproduces the uninterrupted run."""
    import threading

    stream, _ = _replayable_stream(batches=6)
    kwargs = dict(step_size=0.3, num_iterations=10)
    full = StreamingLinearRegressionWithSGD(**kwargs)
    full.set_initial_weights(np.zeros(12, np.float32))
    full.train_on(stream)

    part = StreamingLinearRegressionWithSGD(**kwargs)
    part.set_initial_weights(np.zeros(12, np.float32))
    part.set_checkpoint(str(tmp_path / "ck"), every=1)
    taken = [threading.Event() for _ in stream]

    def batches():
        for event, batch in zip(taken, stream):
            event.set()
            yield batch

    def dies(model, count):
        if count == 3:
            assert taken[3].wait(30)  # batch 4 has been taken ahead
            raise KeyboardInterrupt("driver killed")

    part.add_model_update_listener(dies)
    with pytest.raises(KeyboardInterrupt):
        part.train_on(batches())
    assert part._batch_count == 3  # taken ahead is not consumed
    res = StreamingLinearRegressionWithSGD.resume_from(str(tmp_path / "ck"),
                                                       **kwargs)
    assert res._batch_count == 3
    res.train_on(stream)
    np.testing.assert_array_equal(np.asarray(res.latest_model().weights),
                                  np.asarray(full.latest_model().weights))
    np.testing.assert_array_equal(np.asarray(res.loss_history),
                                  np.asarray(full.loss_history))


def test_a_stream_that_raises_leaves_the_last_finished_model():
    stream, _ = _replayable_stream(batches=4)
    want = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=10)
    want.set_initial_weights(np.zeros(12, np.float32))
    want.train_on(stream[:2])

    def batches():
        yield from stream[:2]
        raise OSError("the source went away")

    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=10)
    alg.set_initial_weights(np.zeros(12, np.float32))
    with pytest.raises(OSError, match="went away"):
        alg.train_on(batches())
    assert alg._batch_count == 2
    np.testing.assert_array_equal(np.asarray(alg.latest_model().weights),
                                  np.asarray(want.latest_model().weights))
    # and the model trains on after it
    alg.train_on(stream[2:])
    assert alg._batch_count == 4


@pytest.mark.parametrize("schedule,totals", [("auto", 0),
                                             ("resident_gram", 1),
                                             ("capacity", 0)])
def test_the_folds_spans_tile_a_pass(tmp_path, schedule, totals):
    """``stream.wait`` / ``stream.batch`` (``fit.run``, ``stream.publish``)
    on the fold's thread, ``stream.stage`` on the worker's; ``ahead`` is 0
    for a pass's first micro-batch and 1 after it.  On the statistics
    schedule every block is ``folded`` under its copy, every fit runs from
    ``totals`` made ahead and builds nothing (no ``train.stats``); on the
    stock schedule (the planner's at these sizes) none.  ``capacity``: a
    logistic stream, whose micro-batches go at a row capacity (PR 52):
    ``stream.whole`` is a leaf of its own, inside ``stream.wait`` in turn
    (``ahead`` 0: the pass's first) and inside the fit BEFORE its own where
    the take was done as that fit was dispatched (``ahead`` 1, PR 60: the
    ``stream.wait`` in front of its fit is then empty), and
    ``stream.stage`` and ``stream.batch`` say the real ``rows``, the
    ``capacity`` and ``rows_read``."""
    import json
    import warnings

    from tpu_sgd import obs

    import threading
    import time

    stream, _ = _replayable_stream(batches=3)
    capacity = schedule == "capacity"
    sizes = [500, 470, 430] if capacity else [500] * 3
    if capacity:  # sizes that do not repeat (one that does keeps its own)
        schedule = "auto"
        stream = [(X[:n], (y[:n] > 0).astype(np.float32))
                  for (X, y), n in zip(stream, sizes)]
    alg = (StreamingLogisticRegressionWithSGD if capacity else
           StreamingLinearRegressionWithSGD)(step_size=0.3, num_iterations=5)
    alg.set_initial_weights(np.zeros(12, np.float32))
    alg.algorithm.set_schedule(schedule)
    warnings.simplefilter("ignore")  # forced: a net loss at these sizes
    taken = [threading.Event() for _ in range(4)]

    def batches():
        for event, batch in zip(taken, stream):
            event.set()
            yield batch
        taken[3].set()  # the worker came for a fourth

    real = alg.algorithm.run_warm

    def run_warm(data, model):  # a fit that outlasts the worker's take
        assert taken[alg._batch_count + 1].wait(30)
        time.sleep(0.05)  # the worker, from the stream's next() to its copy
        return real(data, model)

    alg.train_on(stream)  # the stream's first fit plans: then batches go ahead
    alg.set_initial_weights(np.zeros(12, np.float32))
    alg._batch_count = 0
    alg.algorithm.run_warm = run_warm
    path = tmp_path / "trace.jsonl"
    obs.enable(str(path))
    try:
        alg.train_on(batches())
    finally:
        obs.disable()
    spans = [json.loads(line) for line in open(path)]
    built = [s for s in spans if s.get("name") == "train.stats"]
    stats = [s["stats"] for s in spans if s.get("name") == "train.select"]
    assert built == [] and stats == [totals] * 3
    fits = {s["span_id"] for s in spans if s.get("name") == "train.run"}
    spans = [s for s in spans if s.get("name", "").startswith(("stream.",
                                                               "fit.run"))]
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    # at a capacity TWO micro-batches go ahead (three arrays of it fit): at
    # the stream's end the worker comes for nothing once more
    assert len(by["stream.wait"]) == 4
    assert len(by["stream.stage"]) == 4 + capacity
    assert len(by["stream.batch"]) == len(by["stream.publish"]) \
        == len(by["fit.run"]) == 3
    turns = sorted(by["stream.batch"], key=lambda s: s["t0_s"])
    assert [s["index"] for s in turns] == [0, 1, 2]
    assert [s["ahead"] for s in turns] == [0, 1, 1]
    assert [s["totals"] for s in turns] == [totals] * 3
    assert [s["rows"] for s in turns] == sizes
    ids = {s["span_id"] for s in turns}
    assert all(s["parent_id"] in ids
               for s in by["fit.run"] + by["stream.publish"])
    (root,) = by["stream.run"]
    assert all(s["parent_id"] == root["span_id"]
               for s in by["stream.wait"] + turns)
    assert root["parent_id"] == 0
    fold = {s["thread"] for s in by["stream.wait"] + turns}
    assert fold == {root["thread"]} and not fold & {s["thread"]
                                          for s in by["stream.stage"]}
    staged = [s for s in by["stream.stage"] if "blocks" in s]
    wire = (1024 if capacity else 500) * 12 * 4  # one piece of the capacity
    assert len(staged) == 3 and all(s["blocks"] == 1 and
                                    s["bytes"] == wire and
                                    s["folded"] == totals
                                    for s in staged)
    assert sorted(s["rows"] for s in staged) == sorted(sizes)
    whole = sorted(by.get("stream.whole", []), key=lambda s: s["t0_s"])
    # the rows form alone makes its blocks whole: inside the fold's wait,
    # behind the worker's answer (the two leaves tile the wait), or at a
    # capacity behind the fit that is running, which the worker's take was
    # done for (``run_warm`` above): no take and no leaf in the next wait
    behind = 2 * capacity
    assert len(whole) == (0 if totals else 3)
    assert len(by["stream.take"]) == 4 - behind
    assert [s["ahead"] for s in whole] == [0, 1, 1][:len(whole)] \
        if capacity else not any(s["ahead"] for s in whole)
    waits = {s["span_id"]: s for s in by["stream.wait"]}
    assert all(s["blocks"] == 1 and s["parent_id"] in (
        fits if s["ahead"] else waits) for s in whole)
    empty = 0
    for wait in by["stream.wait"]:
        inner = sorted((s for s in by["stream.take"] + whole
                        if s["parent_id"] == wait["span_id"]),
                       key=lambda s: s["t0_s"])
        empty += not inner
        assert not inner or inner[0]["name"] == "stream.take"
        # what is left of the wait under neither is the spans' own cost
        assert wait["dur_s"] - sum(s["dur_s"] for s in inner) < 5e-3
    assert empty == behind
    for s in staged + turns:
        assert ("capacity" in s) == ("rows_read" in s) == capacity
        if capacity:  # on the CPU a step reads all of the capacity, masked
            assert (s["capacity"], s["rows_read"]) == (1024, 1024)


def test_batches_go_ahead_only_beside_the_stock_and_totals_schedules(
        monkeypatch):
    """Until a stream's first fit has planned, and on any schedule that
    sizes device state of its own (the statistics' PREFIX form, forced over
    sliced windows), the micro-batches are copied inside their fits; beside
    the stock schedule and the statistics' TOTALS form (a full batch: 12 MB
    of device state at d = 1000 and a build with no temporary; PR 41) they go
    ahead; the fold is the same."""
    import warnings

    from tpu_sgd.models import streaming

    stream, _ = _replayable_stream(batches=4)
    made = []  # of each hand-off in blocks: whether it folded them to totals
    real = streaming.StagedAhead

    class Counted(real):
        def __init__(self, X, y=None, *more):
            made.append(y is not None)
            super().__init__(X, y, *more)

    monkeypatch.setattr(streaming, "StagedAhead", Counted)

    def fold(schedule, fraction=1.0):
        alg = StreamingLinearRegressionWithSGD(
            step_size=0.3, num_iterations=10, mini_batch_fraction=fraction)
        alg.set_initial_weights(np.zeros(12, np.float32))
        alg.algorithm.optimizer.set_sampling("sliced")
        alg.algorithm.set_schedule(schedule)
        del made[:]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # forced gram: a net loss here
            alg.train_on(stream)
        return alg, list(made)

    auto, n_auto = fold("auto")
    # batches 0 and 1 were taken before any plan; the stock plan keeps rows
    assert n_auto == [False] * 2
    assert auto._stages_ahead() and auto._totals_key() is None
    off, n_off = fold("off")
    assert n_off == [False] * 4  # nothing to wait for: it runs as it is
    gram, n_gram = fold("resident_gram")
    opt = gram.algorithm.optimizer
    assert opt.sufficient_stats and opt.stats_in_totals()
    # batch 0 plans in run(); batch 1, taken before that, is folded by its
    # own fit; 2 and 3 by the worker, ahead: none is ever made whole
    assert n_gram == [True] * 3 and gram._stages_ahead()
    assert gram._totals_key() == ((500, 12), "float32")
    prefix, n_prefix = fold("resident_gram", fraction=0.5)
    opt = prefix.algorithm.optimizer
    assert opt.sufficient_stats and not opt.stats_in_totals()
    assert n_prefix == [] and not prefix._stages_ahead()
    assert prefix._totals_key() is None
    for flag in ("host_streaming", "streamed_stats"):  # beside the totals
        setattr(gram.algorithm.optimizer, flag, True)
        assert not gram._stages_ahead()
        setattr(gram.algorithm.optimizer, flag, False)
    np.testing.assert_array_equal(np.asarray(auto.latest_model().weights),
                                  np.asarray(off.latest_model().weights))
    np.testing.assert_allclose(np.asarray(gram.latest_model().weights),
                               np.asarray(auto.latest_model().weights),
                               rtol=1e-3, atol=1e-4)


# ---- the statistics schedule in its totals form (PR 41) ----------------------

def _statistics_fold(stream, how="ahead", d=6, iterations=8,
                     schedule="resident_gram", fraction=1.0,
                     model=StreamingLinearRegressionWithSGD, manager=None):
    """A fold of ``stream`` on the statistics schedule (forced: the sizes
    are tiny) and what its listener saw; ``manager`` is given every
    checkpoint."""
    import warnings

    alg = model(step_size=0.2, num_iterations=iterations,
                mini_batch_fraction=fraction)
    alg.set_initial_weights(np.zeros(d, np.float32))
    alg.algorithm.set_schedule(schedule)
    if manager is not None:
        alg.set_checkpoint(manager)
    calls = []
    alg.add_model_update_listener(
        lambda model, count: calls.append(
            (count, np.asarray(model.weights).copy(),
             np.asarray(alg.algorithm.optimizer.loss_history).copy())))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # forced: a net loss at these sizes
        if how == "ahead":
            alg.train_on(iter(stream))
        else:
            for X, y in stream:
                alg.train_on_batch(X, y)
    return alg, calls


def _count_calls(monkeypatch, cls, name):
    """A list that gains a 1 for every call of ``cls.name``."""
    calls, real = [], getattr(cls, name)

    def counted(self, *args, **kwargs):
        calls.append(1)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(cls, name, counted)
    return calls


def _traced(tmp_path, fn, *args, **kwargs):
    """``(fn(*args, **kwargs), the spans it wrote)``."""
    import json

    from tpu_sgd import obs

    path = tmp_path / "trace.jsonl"
    obs.enable(str(path))
    try:
        out = fn(*args, **kwargs)
    finally:
        obs.disable()
    with open(path) as f:
        spans = [json.loads(line) for line in f]
    return out, sorted((s for s in spans if "name" in s),
                       key=lambda s: s["t0_s"])


@pytest.mark.parametrize("kind", ["host", "device"])
def test_the_statistics_fold_ahead_is_the_in_turn_fold_bit_for_bit(
        kind, monkeypatch, tmp_path):
    """As ``test_train_on_ahead_is_the_in_turn_fold_bit_for_bit``, under the
    statistics schedule: a host micro-batch's totals are folded from its row
    blocks by the same programs in the same order whether the worker issues
    them ahead or its own fit in turn (``folded`` says the worker did); a
    device array is built and trained where it lies, as before."""
    gd = _blocked(monkeypatch, d=6)
    folds = _count_calls(monkeypatch, gd.StagedAhead, "_fold")
    joins = _count_calls(monkeypatch, gd.StagedAhead, "whole")
    stream = _mixed_stream(kind)
    (ahead, calls_a), spans = _traced(tmp_path, _statistics_fold, stream,
                                      "ahead")
    staged = [s for s in spans if s["name"] == "stream.stage"
              and "blocks" in s]
    if kind == "host":
        # batch 0 plans by rows, 1 was taken before that and is folded by
        # its fit; 3 (under the empty 2, which has no fit) and 4 (under 3's
        # fit) by the worker
        assert [(s["blocks"], s["folded"]) for s in staged] \
            == [(4, 4), (4, 4)]
        assert folds == [1, 1, 1] and joins == []
        assert [s["totals"] for s in spans
                if s["name"] == "stream.batch"] == [0, 0, 0, 1, 1]
    else:
        assert staged == [] and folds == [] and joins == []
    (turn, calls_t) = _statistics_fold(stream, "in turn")
    assert len(folds) == (6 if kind == "host" else 0)
    for alg in (ahead, turn):
        opt = alg.algorithm.optimizer
        assert opt.last_plan.schedule == "resident_gram"
        assert opt._totals_gradient is not None and opt._gram_entry is None
    np.testing.assert_array_equal(np.asarray(ahead.latest_model().weights),
                                  np.asarray(turn.latest_model().weights))
    assert ahead.loss_history == turn.loss_history
    assert ahead._batch_count == turn._batch_count == 5  # the empty one too
    assert [c[0] for c in calls_a] == [c[0] for c in calls_t] == [1, 2, 4, 5]
    for (_, wa, la), (_, wt, lt) in zip(calls_a, calls_t):
        np.testing.assert_array_equal(wa, wt)
        np.testing.assert_array_equal(la, lt)


def test_micro_batches_of_one_shape_share_one_runner_and_one_build(
        monkeypatch):
    """Three micro-batches of one shape on the statistics schedule: ONE
    runner in ``_run_cache`` (its key holds the optimizer's one unbound
    executor, nothing of a micro-batch), ONE compiled build (the first
    micro-batch's, whose ``run()`` plans: the others' totals are folded from
    their row blocks), TWO block-fold programs (a full block's and the
    remainder's, whichever thread folds), and no compile request at all in
    a second pass."""
    import warnings

    from jax._src import monitoring

    from tpu_sgd.ops import gram

    # a width no other test of the process has compiled for: the counts
    # below are of THIS stream's programs
    _blocked(monkeypatch, d=7)
    stream = [b for b in _mixed_stream("host", d=7, batches=4)
              if b[0].shape[0]]
    assert len(stream) == 3
    builds = gram._stats_build._cache_size()
    folds = gram._stats_fold._cache_size()
    requests, seen = [0], []

    def count(event, duration, **kw):
        requests[0] += event == "/jax/core/compile/backend_compile_duration"

    monitoring.register_event_duration_secs_listener(count)
    try:
        alg = StreamingLinearRegressionWithSGD(step_size=0.2,
                                               num_iterations=8)
        alg.add_model_update_listener(
            lambda model, count: seen.append(requests[0]))
        alg.set_initial_weights(np.zeros(7, np.float32))
        alg.algorithm.set_schedule("resident_gram")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            alg.train_on(iter(stream))
            alg.set_initial_weights(np.zeros(7, np.float32))
            alg.train_on(iter(stream))  # a second pass, as the cell's
    finally:
        monitoring.unregister_event_duration_listener(count)
    opt = alg.algorithm.optimizer
    runners = [k for k in opt._run_cache if k[0] == "run"]
    assert len(runners) == 1 and runners[0][1] is opt._totals_gradient
    assert gram._stats_build._cache_size() == builds + 1
    assert gram._stats_fold._cache_size() == folds + 2
    assert 0 < seen[0] < seen[1] <= seen[2]  # the fold's programs, once
    assert seen[3:] == [seen[2]] * 3


def test_at_most_two_micro_batches_are_alive_on_the_statistics_schedule(
        monkeypatch):
    """``test_at_most_two_micro_batches_are_alive_at_once`` under the
    statistics schedule: the worker is one micro-batch ahead as there, and
    the totals are folded from the row blocks as they land, so after any fit
    but the first (whose ``run()`` plans, by rows) NO live device array has a
    micro-batch's row count: what is alive are blocks on their way."""
    import warnings

    import jax

    gd = _blocked(monkeypatch, d=6)
    stream = _mixed_stream("host", batches=6)
    rows = 3 * 1024 + 100
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=4)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.algorithm.set_schedule("resident_gram")
    taken_at, alive = [], []

    def batches():
        for batch in stream:
            taken_at.append(alg._batch_count)
            yield batch

    real = alg.algorithm.run_warm

    def run_warm(data, model):
        out = real(data, model)
        alive.append([a.shape[0] for a in jax.live_arrays()
                      if a.ndim == 2 and a.shape[1] == 6
                      and a.shape[0] > 6])  # rows, not the (6, 6) G
        return out

    monkeypatch.setattr(alg.algorithm, "run_warm", run_warm)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        alg.train_on(batches())
    assert alg._batch_count == 6
    assert alg.algorithm.optimizer._totals_gradient is not None
    for j, finished in enumerate(taken_at):
        assert finished >= j - 1, (j, taken_at)
    assert len(alive) == 5
    for held in alive[1:]:
        assert all(n <= 1024 for n in held), alive  # blocks, never a batch
        assert sum(held) <= gd._STAGE_IN_FLIGHT * 1024 < rows


def test_the_stock_and_the_statistics_folds_agree_within_the_cells_limits():
    """At the stream cell's ``tiny`` sizes (three micro-batches of 4,096 x 64
    bf16, 50 steps each) the statistics fold's pass is the stock fold's
    within the cell's three limits, which are an f32 reference's."""
    import json
    import os

    import jax.numpy as jnp

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "bench", "configs",
                           "dense1000-lsq-stream.json")) as f:
        config = json.load(f)
    m, d = config["tiny"]["micro_batch_rows"], config["tiny"]["features"]
    rng = np.random.default_rng(11)
    w_true = rng.uniform(-1, 1, d).astype(np.float32)
    stream = []
    for _ in range(3):
        X = np.asarray(jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16))
        y = (X.astype(np.float32) @ w_true
             + 0.1 * rng.normal(size=m)).astype(np.float32)
        stream.append((X, y))

    def fold(schedule):
        import warnings

        alg = StreamingLinearRegressionWithSGD(
            config["step_size"], config["num_iterations"],
            config["mini_batch_fraction"], config["reg_param"])
        alg.algorithm.optimizer.set_convergence_tol(0.0)
        alg.algorithm.set_schedule(schedule)
        alg.set_initial_weights(np.zeros(d, np.float32))
        losses = []
        alg.add_model_update_listener(lambda model, count: losses.append(
            np.asarray(alg.algorithm.optimizer.loss_history)))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            alg.train_on(iter(stream))
        return (np.asarray(alg.latest_model().weights, np.float64),
                np.concatenate(losses).astype(np.float64))

    (w, losses), (ref_w, ref_losses) = fold("resident_gram"), fold("off")
    assert losses.shape == ref_losses.shape == (150,)
    change = np.linalg.norm(ref_w)
    limits = config["limits"]
    assert np.linalg.norm(w - ref_w) / change < limits["w_rel_gap"]
    assert np.max(np.abs(losses - ref_losses)
                  / np.maximum(np.abs(ref_losses), 1e-3)) \
        < limits["loss_max_gap"]
    assert abs(np.linalg.norm(w) - change) / change < limits["dw_norm_gap"]


# ---- the totals folded from the row blocks as they land (PR 44) --------------

def _shaped_stream(shapes, d=6):
    """Micro-batches of ``shapes`` rows each (f32, host)."""
    w_true = np.linspace(-1, 1, d).astype(np.float32)
    out = []
    for i, rows in enumerate(shapes):
        r = np.random.default_rng(70 + i)
        X = r.normal(size=(rows, d)).astype(np.float32)
        y = (X @ w_true + 0.05 * r.normal(size=rows)).astype(np.float32)
        out.append((X, y))
    return out


def _same_fold(a, b, calls_a, calls_b):
    np.testing.assert_array_equal(np.asarray(a.latest_model().weights),
                                  np.asarray(b.latest_model().weights))
    assert a.loss_history == b.loss_history
    assert a._batch_count == b._batch_count
    assert [c[0] for c in calls_a] == [c[0] for c in calls_b]
    for (_, wa, la), (_, wb, lb) in zip(calls_a, calls_b):
        np.testing.assert_array_equal(wa, wb)
        np.testing.assert_array_equal(la, lb)


def test_micro_batches_of_two_shapes_fold_only_where_the_plan_is_theirs(
        monkeypatch):
    """A stream of shapes A, B, B, A, A on the statistics schedule: a
    micro-batch is folded to its totals where the plan in hand is for ITS
    shape when its turn comes, in turn and ahead alike, bit for bit; one of
    another shape than the planned one goes as rows, through ``whole()``
    where it went ahead, and its ``run()`` plans anew.  The worker folds
    ahead only what is SURE to be trained from totals (the fit before it
    keeps the plan): B2, taken while the plan was A's, lands as blocks and
    is folded where they lie once B1's fit has planned for B."""
    gd = _blocked(monkeypatch, d=6)
    a, b = 3 * 1024 + 100, 2 * 1024 + 7
    stream = _shaped_stream([a, b, b, a, a])
    folds = _count_calls(monkeypatch, gd.StagedAhead, "_fold")
    late = _count_calls(monkeypatch, gd.StagedAhead, "fold")
    joins = _count_calls(monkeypatch, gd.StagedAhead, "whole")
    ahead, calls_a = _statistics_fold(stream, "ahead")
    # B2 and the last A: folded where their blocks lay; the first A after
    # the Bs: made whole, as before, and planned by its run()
    assert (len(folds), len(late), len(joins)) == (2, 2, 1)
    turn, calls_t = _statistics_fold(stream, "in turn")
    assert (len(folds), len(late), len(joins)) == (4, 2, 1)
    _same_fold(ahead, turn, calls_a, calls_t)
    for alg in (ahead, turn):
        assert alg.algorithm.optimizer.last_plan.schedule == "resident_gram"
        assert alg._totals_key() == ((a, 6), "float32")
        assert alg._totals_key(stream[1][0]) is None  # B: not the plan's


@pytest.mark.parametrize("case", ["logistic", "half", "off", "intercept"])
def test_every_other_stream_goes_through_whole_as_before(case, monkeypatch):
    """A logistic stream, a fraction under 1 (stock: the planner's choice
    at these sizes), the statistics set by hand under ``set_schedule("off")``
    (no plan says what the next micro-batch's schedule is) and a harness
    that appends an intercept column (the optimizer's matrix is not the
    micro-batch): no block is ever folded, what went ahead is joined by
    ``whole()``, and the fold is the in-turn fold bit for bit.  The
    logistic stream's FIRST micro-batch goes at a row capacity (PR 52: a
    size seen for the first time), made whole by ``whole()`` inside its
    fit; the others REPEAT its size and keep arrays of their own rows, as
    before: joined by ``whole()`` ahead, copied by their fits in turn."""
    from tpu_sgd.models.streaming import StreamingLogisticRegressionWithSGD

    gd = _blocked(monkeypatch, d=6)
    stream = _shaped_stream([3 * 1024 + 100] * 4)
    model, schedule, fraction = StreamingLinearRegressionWithSGD, "auto", 1.0
    if case == "logistic":
        model = StreamingLogisticRegressionWithSGD
        stream = [(X, (y > 0).astype(np.float32)) for X, y in stream]
    elif case == "half":
        fraction = 0.5
    elif case == "off":
        schedule = "off"
    elif case == "intercept":
        schedule = "resident_gram"
    folds = _count_calls(monkeypatch, gd.StagedAhead, "_fold")
    joins = _count_calls(monkeypatch, gd.StagedAhead, "whole")

    def fold(how):
        real = model.__init__

        def init(self, *args, **kwargs):
            real(self, *args, **kwargs)
            if case == "off":
                self.algorithm.optimizer.set_sufficient_stats(True)
            if case == "intercept":
                self.algorithm.set_intercept(True)

        monkeypatch.setattr(model, "__init__", init)
        try:
            return _statistics_fold(stream, how, schedule=schedule,
                                    fraction=fraction, model=model)
        finally:
            monkeypatch.setattr(model, "__init__", real)

    ahead, calls_a = fold("ahead")
    staged = len(joins)
    assert staged == (4 if case in ("off", "logistic") else 2)
    assert folds == [] and ahead._totals_key() is None
    assert ahead._capacity == (4096 if case == "logistic" else 0)
    turn, calls_t = fold("in turn")
    assert len(joins) == staged + (case == "logistic")
    assert folds == []
    _same_fold(ahead, turn, calls_a, calls_t)
    if case in ("off", "intercept"):  # the totals all the same, built whole
        assert ahead.algorithm.optimizer._totals_gradient is not None


def test_a_stream_that_raises_between_two_folds_leaves_the_finished_model(
        monkeypatch):
    """``test_a_stream_that_raises_leaves_the_last_finished_model`` on the
    totals path: the source fails while the worker is asked for the fifth
    micro-batch, after the fourth was folded ahead; the fourth is trained,
    the model is the four's, the plan stands and the stream trains on."""
    gd = _blocked(monkeypatch, d=6)
    stream = _shaped_stream([2 * 1024 + 50] * 6)
    folds = _count_calls(monkeypatch, gd.StagedAhead, "_fold")
    want, _ = _statistics_fold(stream[:4])
    assert len(folds) == 3
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=8)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.algorithm.set_schedule("resident_gram")

    def batches():
        yield from stream[:4]
        raise OSError("the source went away")

    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(OSError, match="went away"):
            alg.train_on(batches())
        assert alg._batch_count == 4 and len(folds) == 6
        np.testing.assert_array_equal(
            np.asarray(alg.latest_model().weights),
            np.asarray(want.latest_model().weights))
        opt = alg.algorithm.optimizer
        assert opt.last_plan is not None and alg._totals_key() is not None
        alg.train_on(stream[4:])
    assert alg._batch_count == 6 and len(folds) == 8


def test_a_bundle_whose_optimizer_no_longer_trains_from_totals_is_refused():
    """The rows of a folded micro-batch are gone: a fit that could only run
    from them says so, at once."""
    from tpu_sgd.optimize.gradient_descent import StagedAhead

    (X, y), = _shaped_stream([64])
    alg = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=4)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.algorithm.set_schedule("off")
    staged = StagedAhead(X, y)
    assert staged.totals.G_tot.shape == (6, 6) and staged.folded == 1
    assert (staged.shape, staged.dtype, staged.nbytes, staged.count) \
        == (X.shape, X.dtype, X.nbytes, 1)
    with pytest.raises(RuntimeError, match="holds no rows"):
        alg.algorithm.run_warm((staged, staged.y), alg.model)
    # and the same bundle trains where the statistics are the schedule
    alg.algorithm.optimizer.set_sufficient_stats(True)
    model = alg.algorithm.run_warm((staged, staged.y), alg.model)
    ref = StreamingLinearRegressionWithSGD(step_size=0.2, num_iterations=4)
    ref.set_initial_weights(np.zeros(6, np.float32))
    ref.algorithm.set_schedule("off")
    ref.algorithm.optimizer.set_sufficient_stats(True)
    ref.train_on_batch(X, y)
    np.testing.assert_array_equal(np.asarray(model.weights),
                                  np.asarray(ref.latest_model().weights))


# ---- micro-batches of unequal sizes at a row capacity (PR 52) -----------------

def _uneven_logistic(sizes, d=6, seed=90, dtype=np.float32):
    """Logistic micro-batches of ``sizes`` rows each (host), one ``w_true``."""
    w_true = np.linspace(-1, 1, d).astype(np.float32)
    out = []
    for i, rows in enumerate(sizes):
        r = np.random.default_rng(seed + i)
        X = r.normal(size=(rows, d)).astype(dtype)
        y = (r.uniform(size=rows)
             < 1 / (1 + np.exp(-(X.astype(np.float32) @ w_true))))
        out.append((X, y.astype(np.float32)))
    return out


def _logistic_fold(stream, how="ahead", d=6, iterations=8, fraction=1.0,
                   manager=None):
    return _statistics_fold(stream, how, d=d, iterations=iterations,
                            schedule="auto", fraction=fraction,
                            model=StreamingLogisticRegressionWithSGD,
                            manager=manager)


UNEVEN = [3 * 1024 + 100, 2 * 1024 + 7, 4096, 2049, 3333, 2 * 1024 + 7]

#: when the worker's take of micro-batch k+1 is done against fit k
#: (``_paced``): left to the threads; before the fit is dispatched, or under
#: the running fit (the join then goes BEHIND that fit: PR 60); or not before
#: the fit has ended (every join in turn)
PACES = ["as it comes", "done", "during", "late"]
BEHIND = ("done", "during")


def _paced(monkeypatch, pace):
    """``batches(stream)``, the iterator to hand ``train_on``, and the pace
    set.  ``"done"`` has every fit at a capacity wait, as its program is
    queued, until the worker has finished every take it was given (they wait
    for nothing the host has still to do), so the next micro-batch is made
    whole behind the running fit wherever the fold allows it.  ``"during"``
    lets the stream yield micro-batch k only once k fits have been queued
    and tells the fold that its fit is still running for as long as it asks
    (on the CPU a fit is over in milliseconds): the take is waited for under
    the fit and joined behind it.  ``"late"`` lets the stream yield
    micro-batch k only once the fold has LEFT the k-th fit's queue hook,
    which it does when that fit has ended: no take is done while a fit runs
    and every join goes in turn."""
    import concurrent.futures
    import threading

    import tpu_sgd.optimize.gradient_descent as gd
    from tpu_sgd.models import streaming

    if pace == "as it comes":
        return iter
    takes, turn = [], threading.Condition()
    fits = {"entered": 0, "left": 0}  # of the queue hook, this stream

    class Pool(streaming.ThreadPoolExecutor):
        def submit(self, *args, **kwargs):
            takes.append(super().submit(*args, **kwargs))
            return takes[-1]

    real = gd.StagedAhead.queued

    def passed(mark):
        with turn:
            fits[mark] += 1
            turn.notify_all()

    def queued(self, done):
        if pace == "done":
            _, still = concurrent.futures.wait(
                [t for t in takes if not t.cancelled()], timeout=30)
            assert not still
        passed("entered")
        real(self, (lambda: False) if pace == "during" else done)
        passed("left")

    monkeypatch.setattr(streaming, "ThreadPoolExecutor", Pool)
    monkeypatch.setattr(gd.StagedAhead, "queued", queued)

    def batches(stream):
        fits.update(entered=0, left=0)  # the first take: before any fit
        mark = {"during": "entered", "late": "left"}.get(pace)
        for k, batch in enumerate(stream):
            if mark:
                with turn:
                    assert turn.wait_for(lambda: fits[mark] >= k, 30)
            yield batch

    return batches


class _Checkpoints:
    """A ``CheckpointManager`` that keeps what it is given."""

    def __init__(self):
        self.saved = []

    def save(self, iteration, weights, intercept, history, **more):
        self.saved.append((iteration, np.array(weights), np.array(history),
                           more["config_key"],
                           float(more["extras"]["intercept"])))


def _joins(spans):
    """``ahead`` of a fold's ``stream.whole`` spans, in time order."""
    return [s["ahead"] for s in spans if s["name"] == "stream.whole"]


@pytest.mark.parametrize("pace", PACES)
def test_an_uneven_logistic_stream_is_the_unpadded_fold(pace, monkeypatch,
                                                        tmp_path):
    """``train_on`` over micro-batches of unequal sizes (each in an array of
    the stream's row capacity, its count an operand) against the fold taken
    strictly in turn over arrays of the micro-batches' OWN rows through the
    batch model's ``run``: the weights and every step's loss agree to
    float32 rounding (the sums run over the capacity's rows with the
    padding's terms exact zeros, so the order of the additions differs, the
    terms do not), every micro-batch trained once, in order, the listener
    called after each; and the fold ahead is the fold in turn at the
    capacity bit for bit, weights, losses, listener calls and checkpoints,
    whether a micro-batch was made whole behind the fit before it (``done``
    and ``during``: every one from the third on, the first two come in
    turn) or in turn (``late``: all of them)."""
    from tpu_sgd import LogisticRegressionWithSGD

    _blocked(monkeypatch, d=6)
    stream = _uneven_logistic(UNEVEN)
    kept_a, kept_t = _Checkpoints(), _Checkpoints()
    (ahead, calls_a), spans = _traced(
        tmp_path, lambda: _logistic_fold(_paced(monkeypatch, pace)(stream),
                                         "ahead", manager=kept_a))
    turn, calls_t = _logistic_fold(stream, "in turn", manager=kept_t)
    _same_fold(ahead, turn, calls_a, calls_t)
    assert [c[0] for c in calls_a] == [1, 2, 3, 4, 5, 6]
    assert ahead._capacity == turn._capacity == 4096
    assert [c[0] for c in kept_a.saved] == [1, 2, 3, 4, 5, 6]
    for a, t in zip(kept_a.saved, kept_t.saved):
        assert a[0] == t[0] and a[3:] == t[3:]
        np.testing.assert_array_equal(a[1], t[1])
        np.testing.assert_array_equal(a[2], t[2])
    # the first is copied inside its fit, the second is taken after it
    if pace != "as it comes":
        assert _joins(spans) == [0] + [int(pace in BEHIND)] * 4
    assert len(_joins(spans)) == 5 and _joins(spans)[0] == 0
    w, history = np.zeros(6, np.float32), []
    for X, y in stream:  # the unpadded fold: a program a size
        batch = LogisticRegressionWithSGD(0.2, 8, 0.0, 1.0)
        w = np.asarray(batch.run((X, y), w).weights)
        history.append(np.asarray(batch.optimizer.loss_history))
    np.testing.assert_allclose(np.asarray(ahead.latest_model().weights), w,
                               rtol=0, atol=2e-6)
    for (_, _, got), want in zip(calls_a, history):
        np.testing.assert_allclose(got, want, rtol=2e-6)


def test_padding_rows_are_not_trained_and_not_counted(monkeypatch):
    """A micro-batch of 2,055 rows in a capacity of 4,096: the first step's
    loss is log 2 (every real row at zero weights: a divisor of the
    capacity would read half of it), the fold is the one over an array of
    the 2,055 rows, and a row appended behind them changes it."""
    _blocked(monkeypatch, d=6)
    (X, y), = _uneven_logistic([2055])
    big, _ = _logistic_fold(_uneven_logistic([4000]) + [(X, y)])
    assert big._capacity == 4096
    history = np.asarray(big.algorithm.optimizer.loss_history)
    fresh, calls = _logistic_fold([(X, y)])
    assert fresh._capacity == 4096 and len(calls) == 1
    np.testing.assert_allclose(calls[0][2][0], np.log(2.0), rtol=1e-6)
    assert history.shape == (8,)
    more = (np.concatenate([X, 9 * np.ones((1, 6), np.float32)]),
            np.concatenate([y, np.zeros(1, np.float32)]))
    other, _ = _logistic_fold([more])
    assert not np.allclose(np.asarray(other.latest_model().weights),
                           np.asarray(fresh.latest_model().weights),
                           atol=1e-4)


def _compiles():
    """A list that gains the name of every backend compile of this
    process from now on (``jax.monitoring``; never removed)."""
    from jax import monitoring

    seen = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: seen.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return seen


def test_any_number_of_sizes_share_one_plan_and_one_set_of_programs(
        monkeypatch, tmp_path):
    """Six micro-batches of five distinct sizes, then six more of other
    sizes under the same capacity: the second stream compiles NOTHING, the
    first no more than a constant set (the fit, the two joins, the zero
    blocks), the optimizer holds one runner traced once, and the probe and
    the plan ran once (one ``fit.plan`` that planned, the others the
    repeat-run key's hit)."""
    gd = _blocked(monkeypatch, d=6)
    compiles = _compiles()
    alg = StreamingLogisticRegressionWithSGD(step_size=0.2, num_iterations=8)
    alg.set_initial_weights(np.zeros(6, np.float32))
    _, spans = _traced(tmp_path, alg.train_on,
                       iter(_uneven_logistic(UNEVEN)))
    first = len(compiles)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.train_on(iter(_uneven_logistic([2500, 3999, 2051, 4001, 3100, 2222],
                                       seed=300)))
    assert len(compiles) == first and alg._capacity == 4096
    assert alg._batch_count == 12
    opt = alg.algorithm.optimizer
    (key, runner), = [(k, fn) for k, fn in opt._run_cache.items()
                      if k[0] == "run"]
    # one signature resolved by the store's wrapper (no cache directory
    # here: the jitted runner itself), traced once
    assert key[-1] is True and len(runner._fns) == 1 \
        and runner.fresh._cache_size() == 1
    # the rows' (the first array, and the one written over it), the labels'
    assert gd._stage_join._cache_size() == 3
    plans = [s for s in spans if s["name"] == "fit.plan"]
    assert [s["cached"] for s in plans] == [False] + [True] * 5
    assert {s["schedule"] for s in plans} == {"resident_stock"}
    assert opt._plan_key[0] == (4096, 6)
    assert not [s for s in spans if s["name"] == "stream.regrow"]


def test_a_micro_batch_over_the_capacity_regrows_once_and_trains_whole(
        monkeypatch, tmp_path):
    """Sizes under 2,048, then one of 5,000 (taken ahead), then small ones
    again: the capacity is raised once (``stream.regrow`` says from what to
    what), the large micro-batch is trained whole in one fit, the ones
    after it train at the raised capacity, and the fold is the unpadded
    one."""
    from tpu_sgd import LogisticRegressionWithSGD

    _blocked(monkeypatch, d=6)
    sizes = [1500, 2000, 1100, 5000, 1200, 1800]
    stream = _uneven_logistic(sizes)
    (alg, calls), spans = _traced(tmp_path, _logistic_fold, stream)
    regrown = [s for s in spans if s["name"] == "stream.regrow"]
    assert [(s["rows"], s["was"], s["capacity"]) for s in regrown] \
        == [(5000, 2048, 8192)]
    assert alg._capacity == 8192 and alg._batch_count == 6
    turns = [s for s in spans if s["name"] == "stream.batch"]
    assert [s["rows"] for s in turns] == sizes
    assert [s["capacity"] for s in turns] == [2048] * 3 + [8192] * 3
    plans = [s["cached"] for s in spans if s["name"] == "fit.plan"]
    assert plans == [False, True, True, False, True, True]
    w = np.zeros(6, np.float32)
    for X, y in stream:
        w = np.asarray(LogisticRegressionWithSGD(0.2, 8, 0.0, 1.0)
                       .run((X, y), w).weights)
    np.testing.assert_allclose(np.asarray(alg.latest_model().weights), w,
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("how", ["ahead", "in turn"])
def test_a_size_that_repeats_keeps_an_array_of_its_own_rows(how, monkeypatch):
    """What the stream observes is the SIZES: a micro-batch whose size is
    the one before it's trains an array of its own rows (a stream of equal
    micro-batches lowers, from its second one on, the programs it lowered
    before there was a capacity), one of a size not seen just before goes
    at the capacity again; the fold is the unpadded one either way."""
    from tpu_sgd import LogisticRegressionWithSGD
    from tpu_sgd.models.glm import GeneralizedLinearAlgorithm

    _blocked(monkeypatch, d=6)
    sizes = [2100, 2100, 2100, 3000, 3000, 2100]
    stream = _uneven_logistic(sizes)
    seen, real = [], GeneralizedLinearAlgorithm.run_warm

    def run_warm(self, data, model):
        seen.append((np.shape(data[0])[0], getattr(data[0], "capacity", 0)))
        return real(self, data, model)

    monkeypatch.setattr(GeneralizedLinearAlgorithm, "run_warm", run_warm)
    alg, calls = _logistic_fold(stream, how)
    assert seen == [(4096, 4096), (2100, 0), (2100, 0), (4096, 4096),
                    (3000, 0), (4096, 4096)]
    assert [c[0] for c in calls] == [1, 2, 3, 4, 5, 6]
    w = np.zeros(6, np.float32)
    for X, y in stream:
        w = np.asarray(LogisticRegressionWithSGD(0.2, 8, 0.0, 1.0)
                       .run((X, y), w).weights)
    np.testing.assert_allclose(np.asarray(alg.latest_model().weights), w,
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("arrays,ahead", [(3, 2), (2, 1)])
def test_two_go_ahead_where_three_arrays_of_the_capacity_fit(
        arrays, ahead, monkeypatch):
    """Where the memory that is free as the capacity is made holds THREE
    arrays of it, the worker holds two micro-batches ahead of the one in
    training (a large copy behind a small fit then has the fit before it to
    land under too) and never a third; where it holds two, one, as before.
    The fold is the unpadded one either way."""
    import threading
    import time

    from tpu_sgd import LogisticRegressionWithSGD
    from tpu_sgd.models import streaming
    from tpu_sgd.models.glm import GeneralizedLinearAlgorithm

    _blocked(monkeypatch, d=6)
    monkeypatch.setattr(streaming.plan_mod, "device_budget",
                        lambda *a, **k: (arrays * 4096 * 6 * 4, "test"))
    sizes = [2100, 4000, 2300, 3900, 2049, 3000, 2500]
    stream = _uneven_logistic(sizes)
    pulled = [threading.Event() for _ in range(len(sizes) + ahead + 1)]

    def batches():
        for event, batch in zip(pulled, stream):
            event.set()
            yield batch
        for event in pulled[len(stream):]:  # the worker came for the end
            event.set()
            yield from ()

    held, real = [], GeneralizedLinearAlgorithm.run_warm

    def run_warm(self, data, model):
        k = len(held)
        if k:  # the first is copied inside its fit: the takes come after it
            assert pulled[min(k + ahead, len(sizes))].wait(30)
            time.sleep(0.05)  # a worker that came for one more would have
            held.append(sum(e.is_set() for e in pulled[:len(sizes)]) - k - 1)
        else:
            held.append(0)
        return real(self, data, model)

    monkeypatch.setattr(GeneralizedLinearAlgorithm, "run_warm", run_warm)
    alg = StreamingLogisticRegressionWithSGD(step_size=0.2, num_iterations=8)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.train_on(batches())
    assert alg._ahead == ahead and alg._capacity == 4096
    n = len(sizes)
    assert held == [0] + [min(ahead, n - 1 - k) for k in range(1, n)]
    w = np.zeros(6, np.float32)
    for X, y in stream:
        w = np.asarray(LogisticRegressionWithSGD(0.2, 8, 0.0, 1.0)
                       .run((X, y), w).weights)
    np.testing.assert_allclose(np.asarray(alg.latest_model().weights), w,
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("pace", PACES)
def test_the_array_of_the_capacity_is_written_over_in_place(pace, monkeypatch,
                                                            tmp_path):
    """A stream's array of the capacity is made once: every later
    micro-batch's rows are written over the one trained before it (given up:
    deleted, its memory the new array's), so that the device never frees
    one array of the capacity to find room for the next while blocks land
    beside them; a raised capacity's first array is a new one.  Behind a
    running fit (``done``, ``during``) as in turn: the array the fit is reading is the
    one given up, and the device holds ONE array of a capacity's shape
    whenever a join has been dispatched; a capacity that regrows is made in
    turn, in an array of its own."""
    import gc

    import jax

    def buffers(shape=None):
        """The device buffers of live arrays (of ``shape``; None: any)."""
        return {a.unsafe_buffer_pointer() for a in jax.live_arrays()
                if shape in (None, a.shape)}

    gd = _blocked(monkeypatch, d=6)
    seen, alive, real = [], [], gd.StagedAhead.whole
    gc.collect()
    before = buffers()  # what earlier tests of this process left alive

    def whole(self, into=None):
        spent = None if into is None else into.X
        at = spent is not None and spent.unsafe_buffer_pointer()
        out = real(self, into)
        if spent is not None:
            seen.append((spent.shape[0], out.X.shape[0], spent.is_deleted(),
                         out.X.unsafe_buffer_pointer() == at))
            del spent
            alive.append(len(buffers(out.X.shape) - before))
        return out

    monkeypatch.setattr(gd.StagedAhead, "whole", whole)
    sizes = [2100, 4000, 2300, 5000, 2049]
    stream = _paced(monkeypatch, pace)(_uneven_logistic(sizes))
    (alg, calls), spans = _traced(tmp_path, _logistic_fold, stream)
    assert [c[0] for c in calls] == [1, 2, 3, 4, 5]
    assert seen == [(4096, 4096, True, True), (4096, 4096, True, True),
                    (4096, 8192, False, False), (8192, 8192, True, True)]
    assert alive == [1, 1, 1, 1]
    if pace != "as it comes":
        behind = int(pace in BEHIND)  # the third's, and the fifth's
        assert _joins(spans) == [0, behind, 0, behind]


def test_nothing_small_crosses_the_wire_as_a_fit_starts(monkeypatch):
    """A scalar or a vector of weights sent as a fit starts waits behind
    every row block in flight on the one wire (40 ms of an idle chip a
    micro-batch on the v5e: PERF.md, PR 52).  So a micro-batch at a capacity
    brings its row count with it, issued in front of its labels and rows;
    a warm start takes the last fit's weights where they lie, on the
    device; and initial weights given on the device stay there."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.ops.gradients import RowCount

    gd = _blocked(monkeypatch, d=6)
    order, real_asarray = [], gd.jnp.asarray
    (X, y), = _uneven_logistic([2100])
    staged = gd.StagedAhead(X, y, capacity=4096, issue=False)
    assert staged.valid is None

    class Spy:  # the module's ``jnp`` with ``asarray`` watched
        def __getattr__(self, name):
            return getattr(jnp, name)

        def asarray(self, a, *args, **kwargs):
            order.append(np.ndim(a))
            return real_asarray(a, *args, **kwargs)

    monkeypatch.setattr(gd, "jnp", Spy())
    staged._issue()
    monkeypatch.undo()
    assert order[0] == 0 and order[1] == 1 and order[-1] == 2
    assert isinstance(staged.valid, RowCount) and int(staged.valid.rows) == 2100

    _blocked(monkeypatch, d=6)
    starts, real = [], gd.GradientDescent.optimize

    def optimize(self, data, initial_weights):
        starts.append(isinstance(initial_weights, jax.Array))
        return real(self, data, initial_weights)

    monkeypatch.setattr(gd.GradientDescent, "optimize", optimize)
    stream = _uneven_logistic([2100, 3000, 2500])
    alg, _ = _logistic_fold(stream)  # the model holds them on the device
    assert starts == [True, True, True]
    del starts[:]
    there = StreamingLogisticRegressionWithSGD(step_size=0.2,
                                               num_iterations=8)
    zeros = jnp.zeros(6, jnp.float32)
    there.set_initial_weights(zeros)
    assert there.latest_model().weights is zeros  # not fetched and sent again
    there.train_on(iter(stream))
    assert starts == [True, True, True]
    np.testing.assert_array_equal(np.asarray(there.latest_model().weights),
                                  np.asarray(alg.latest_model().weights))
    # anything else is made float32 on the host, as before
    there.set_initial_weights([0] * 6)
    assert there.latest_model().weights.dtype == np.float32


def test_a_first_fit_that_plans_another_schedule_trains_its_own_rows():
    """A stream's first micro-batch is wrapped at the capacity BEFORE its
    fit plans (the plan has to be the capacity's); where that plan is
    another schedule than the stock one (forced here by name) the fit takes
    the rows the wrapper still holds (``StagedAhead.host``), nothing is
    issued at a capacity, and the next micro-batches come as they are."""
    import warnings

    stream = _uneven_logistic([1500, 1300, 1700])

    def fold(schedule):
        alg = StreamingLogisticRegressionWithSGD(step_size=0.2,
                                                 num_iterations=4)
        alg.set_initial_weights(np.zeros(6, np.float32))
        alg.algorithm.set_schedule(schedule)
        seen, real = [], alg.algorithm.optimizer._optimize

        def optimize(data, *more):
            seen.append(data[0])
            return real(data, *more)

        alg.algorithm.optimizer._optimize = optimize
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # forced: a loss at these sizes
            alg.train_on(iter(stream))
        return alg, seen

    alg, seen = fold("host_streamed")
    assert alg.algorithm.optimizer.host_streaming
    assert getattr(seen[0], "capacity", 0) == 2048 and seen[0].X is None
    assert seen[0].blocks is None  # never issued
    assert [getattr(X, "capacity", 0) for X in seen[1:]] == [0, 0]
    stock, _ = fold("auto")
    np.testing.assert_allclose(np.asarray(alg.latest_model().weights),
                               np.asarray(stock.latest_model().weights),
                               rtol=0, atol=2e-6)


def test_what_crosses_the_wire_is_the_rows_and_one_blocks_remainder(
        monkeypatch):
    """The capacity form's hand-off: the micro-batch's own rows in blocks,
    the last one filled to a whole block on the host, the labels in pieces
    of 16 blocks' rows in front of them; made whole, the capacity's tail is
    zeros made on the device; ``wire_bytes`` is within one block of the
    rows' bytes."""
    import jax

    from tpu_sgd.optimize.gradient_descent import StagedAhead, row_capacity

    _blocked(monkeypatch, d=6)
    (X, y), = _uneven_logistic([3 * 1024 + 100])
    assert row_capacity(X) == 4096 and row_capacity(X, 8192) == 8192
    assert row_capacity(X[:1024]) == 1024 and row_capacity(X[:1025]) == 2048
    staged = StagedAhead(X, y, capacity=8192)
    assert staged.shape == (8192, 6) and staged.rows == 3172
    assert staged.count == 4 and staged.nbytes == X.nbytes
    assert [b.shape for b in staged.blocks] == [(1024, 6)] * 4
    assert [b.shape for b in staged.labels] == [(2048,)] * 2  # in flight
    assert 0 <= staged.wire_bytes - X.nbytes < 1024 * 6 * 4
    whole = staged.whole()
    assert whole is staged and staged.blocks is None
    assert isinstance(staged.X, jax.Array) and staged.X.shape == (8192, 6)
    np.testing.assert_array_equal(np.asarray(staged.X)[:3172], X)
    np.testing.assert_array_equal(np.asarray(staged.y)[:3172], y)
    assert not np.asarray(staged.X)[3172:].any()
    assert not np.asarray(staged.y)[3172:].any()
    # in turn: nothing is issued until the fit asks, then the same arrays
    lazy = StagedAhead(X, y, capacity=8192, issue=False)
    assert lazy.blocks is None and lazy.host()[0] is X
    np.testing.assert_array_equal(np.asarray(lazy.whole().X),
                                  np.asarray(staged.X))
    with pytest.raises(RuntimeError, match="stock resident schedule"):
        lazy.host()
    # under one block: one piece of the capacity's rows, no program
    small = StagedAhead(X[:700], y[:700], capacity=1024).whole()
    assert small.count == 1 and small.X.shape == (1024, 6)
    np.testing.assert_array_equal(np.asarray(small.X)[:700], X[:700])


@pytest.mark.parametrize("case", ["least_squares", "intercept", "mesh",
                                  "too_large", "device", "listener"])
def test_everything_else_keeps_arrays_of_its_own_rows(case, monkeypatch):
    """The capacity form is the stock one-device logistic stream's alone: a
    least-squares stream (its plan may be the statistics schedule, whose
    totals are keyed by the micro-batch's own shape), a harness that
    appends an intercept, an optimizer under a mesh or with a
    per-iteration listener, a micro-batch whose capacity does not fit the
    device twice over, and a device array all train arrays of their own
    rows, as before."""
    import jax
    import jax.numpy as jnp

    from tpu_sgd.models import streaming

    _blocked(monkeypatch, d=6)
    stream = _uneven_logistic([2100, 3000, 2500])
    model = StreamingLogisticRegressionWithSGD
    if case == "least_squares":
        model = StreamingLinearRegressionWithSGD
    alg = model(step_size=0.2, num_iterations=4)
    alg.set_initial_weights(np.zeros(6, np.float32))
    if case == "intercept":
        alg.algorithm.set_intercept(True)
    if case == "mesh":
        from tpu_sgd.parallel.mesh import data_mesh

        alg.algorithm.optimizer.set_mesh(data_mesh(jax.devices()[:2]))
    if case == "listener":
        from tpu_sgd.utils.events import CollectingListener

        alg.algorithm.optimizer.set_listener(CollectingListener())
    if case == "too_large":
        monkeypatch.setattr(streaming.plan_mod, "device_budget",
                            lambda *a, **k: (4096 * 24 - 1, "test"))
    if case == "device":
        stream = [(jnp.asarray(X), y) for X, y in stream]
    seen, real = [], alg.algorithm.run_warm

    def run_warm(data, model):
        seen.append(data[0])
        return real(data, model)

    monkeypatch.setattr(alg.algorithm, "run_warm", run_warm)
    alg.train_on(iter(stream))
    assert alg._batch_count == 3
    assert alg._capacity == 0 or case == "too_large"
    assert [np.shape(X)[0] for X in seen] == [2100, 3000, 2500]
    assert not any(getattr(X, "capacity", 0) for X in seen)


def test_a_capacity_form_is_trained_by_the_stock_schedule_alone():
    """An optimizer whose schedule left the stock one after a micro-batch
    was wrapped for its fit in turn trains the micro-batch's own rows (the
    wrapper still holds them); one whose blocks are on the device already
    is refused."""
    from tpu_sgd import LogisticRegressionWithSGD
    from tpu_sgd.optimize.gradient_descent import StagedAhead

    (X, y), = _uneven_logistic([1500])
    alg = LogisticRegressionWithSGD(0.2, 4, 0.0, 1.0)
    alg.set_schedule("off")
    want = np.asarray(alg.run((X, y)).weights)
    from tpu_sgd.utils.events import CollectingListener

    alg.optimizer.set_listener(CollectingListener())  # stepwise: no count
    assert not alg.optimizer.trains_at_capacity()
    got = alg.run((StagedAhead(X, y, capacity=2048, issue=False), y))
    np.testing.assert_allclose(np.asarray(got.weights), want, atol=1e-6)
    with pytest.raises(RuntimeError, match="stock resident schedule"):
        alg.run((StagedAhead(X, y, capacity=2048), y))


# ---- the next micro-batch made whole BEHIND the running fit (PR 60) ------------

def _children(spans, parent, name):
    return [s for s in spans
            if s["parent_id"] == parent["span_id"] and s["name"] == name]


@pytest.mark.parametrize("pace", BEHIND)
def test_the_next_join_is_queued_between_a_fits_dispatch_and_its_fetch(
        pace, monkeypatch, tmp_path):
    """The ORDER on the fold's thread where the worker's take is done as fit
    k is dispatched, or while it runs: micro-batch k+1's ``stream.whole``
    (``ahead`` 1) lies inside fit k, behind its ``train.dispatch`` and in
    front of its ``train.fetch``, so the join is in the device's queue
    before the host begins to wait (``during``: behind a ``stream.take``
    leaf, ``behind`` 1, the wait for the worker under the running fit);
    ``stream.publish`` of k (the listeners) follows the fetch as before, and
    fit k+1 is selected and dispatched only after it has returned."""
    _blocked(monkeypatch, d=6)
    stream = _paced(monkeypatch, pace)(_uneven_logistic(UNEVEN))
    (alg, calls), spans = _traced(tmp_path, _logistic_fold, stream)
    assert [c[0] for c in calls] == [1, 2, 3, 4, 5, 6]

    def end(s):
        return s["t0_s"] + s["dur_s"]

    fits = [s for s in spans if s["name"] == "train.run"]
    turns = [s for s in spans if s["name"] == "stream.batch"]
    published = [s for s in spans if s["name"] == "stream.publish"]
    assert len(fits) == len(turns) == len(published) == 6
    behind, waits = {}, 0
    for k, fit in enumerate(fits):
        (called,), (answered,) = (_children(spans, fit, name) for name
                                  in ("train.dispatch", "train.fetch"))
        for whole in _children(spans, fit, "stream.whole"):
            behind[k] = whole
            assert whole["ahead"] == 1 and whole["thread"] == fit["thread"]
            assert end(called) <= whole["t0_s"]
            assert end(whole) <= answered["t0_s"] < end(answered)
            waited = _children(spans, fit, "stream.take")
            assert all(s["behind"] == 1 and end(called) <= s["t0_s"]
                       and end(s) <= whole["t0_s"] for s in waited)
            waits += len(waited)
        assert end(answered) <= published[k]["t0_s"]
        if k:  # from the model that stands once k - 1's listeners are back
            assert end(published[k - 1]) <= called["t0_s"]
    # the first is copied inside its fit and the second taken after it:
    # fits 2 to 5 (of 1 to 6) have the next one queued behind them
    assert sorted(behind) == [1, 2, 3, 4]
    assert waits <= 4 and (waits if pace == "during" else not waits)
    assert _joins(spans) == [0, 1, 1, 1, 1]
    assert [s["ahead"] for s in turns] == [0, 0, 1, 1, 1, 1]


@pytest.mark.parametrize("pace", PACES)
def test_weights_a_listener_sets_are_where_the_next_micro_batch_starts(
        pace, monkeypatch, tmp_path):
    """Why fit k+1 is NOT dispatched behind fit k with the join: a
    model-update listener may set the weights the next micro-batch starts
    from (``bench/entries/stream_train_on_uneven.py`` does, at each pass's
    end).  Every micro-batch trains from what the listener of the one
    before it set, ahead as in turn, bit for bit."""
    from tpu_sgd import LogisticRegressionWithSGD

    _blocked(monkeypatch, d=6)
    stream = _uneven_logistic(UNEVEN)
    starts = [np.linspace(k, -k, 6).astype(np.float32) / 4 for k in range(7)]

    def fold(batches):
        alg = StreamingLogisticRegressionWithSGD(step_size=0.2,
                                                 num_iterations=8)
        alg.set_initial_weights(starts[0])
        seen = []

        def listener(model, count):
            seen.append(np.asarray(model.weights).copy())
            alg.set_initial_weights(starts[count])

        alg.add_model_update_listener(listener)
        if batches is None:
            for X, y in stream:
                alg.train_on_batch(X, y)
        else:
            alg.train_on(batches)
        return seen

    ahead, spans = _traced(tmp_path, fold, _paced(monkeypatch, pace)(stream))
    turn = fold(None)
    if pace != "as it comes":
        assert _joins(spans) == [0] + [int(pace in BEHIND)] * 4
    assert len(ahead) == len(turn) == 6
    for k, ((X, y), got, same) in enumerate(zip(stream, ahead, turn)):
        np.testing.assert_array_equal(got, same)
        want = LogisticRegressionWithSGD(0.2, 8, 0.0, 1.0).run(
            (X, y), starts[k]).weights
        np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=2e-6)


@pytest.mark.parametrize("form", ["rows", "totals"])
def test_the_rows_and_the_totals_forms_are_never_joined_behind_a_fit(
        form, monkeypatch, tmp_path):
    """Behind a running fit goes the join that writes the capacity's array
    over in place, and no other.  A micro-batch in the ROWS form (a size
    that repeats: its join makes an array of its own, and the device would
    hold it beside the one in training) and one in the TOTALS form (nothing
    to join) come in turn, whatever the worker has done; a capacity form
    that follows a rows form finds no array to be written over and is made
    in turn too; the fold is the in-turn fold bit for bit."""
    _blocked(monkeypatch, d=6)
    if form == "rows":
        sizes = [2100, 3000, 3000, 2500, 2600, 2600, 2700]
        stream, fold = _uneven_logistic(sizes), _logistic_fold
    else:
        stream, fold = _shaped_stream([3 * 1024 + 100] * 5), _statistics_fold
    (ahead, calls_a), spans = _traced(
        tmp_path, fold, _paced(monkeypatch, "done")(stream))
    turn, calls_t = fold(stream, "in turn")
    _same_fold(ahead, turn, calls_a, calls_t)
    turns = [s for s in spans if s["name"] == "stream.batch"]
    if form == "totals":
        assert _joins(spans) == [] and ahead._capacity == 0
        # the first two were taken before the stream's first fit had planned
        assert [s["totals"] for s in turns] == [0, 0, 1, 1, 1]
        return
    # at the capacity: 1 (in its fit), 2, 4, 5 and 7; own rows: 3 and 6.
    # Behind a fit: 5 behind 4 and no other (2 follows the first, which is
    # copied in its fit; 4 and 7 follow rows forms: no array to write over)
    assert [s.get("capacity", 0) for s in turns] \
        == [4096, 4096, 0, 4096, 4096, 0, 4096]
    assert _joins(spans) == [0, 0, 0, 1, 0, 0]


def test_a_join_queued_behind_a_running_fit_leaves_its_result_unchanged(
        monkeypatch):
    """What the early join rests on: the array a dispatched fit reads may be
    DONATED to a program dispatched behind it.  The device's queue runs the
    join after the fit and the runtime keeps the buffer until then: the
    fit's weights and losses are the ones it has with no join behind it, bit
    for bit, though the join writes other rows over every row it reads; the
    join's result lies where the trained array lay and holds the next
    micro-batch's rows, zeros behind them."""
    from tpu_sgd import LogisticRegressionWithSGD

    gd = _blocked(monkeypatch, d=16)
    (Xa, ya), (Xb, yb) = _uneven_logistic([60000, 50000], d=16)
    Xb = Xb + 9  # rows that would show in any sum they were read into

    def fit(behind):
        a = gd.StagedAhead(Xa, ya, capacity=65536).whole()
        b = gd.StagedAhead(Xb, yb, capacity=65536)
        assert b.lands_in(a) and not a.lands_in(b)
        lay = a.X.unsafe_buffer_pointer()
        if behind:  # dispatched once the fit's program is queued
            a.behind = lambda training, done: b.whole(training)
        opt = LogisticRegressionWithSGD(0.2, 30, 0.0, 1.0).optimizer
        w, losses = opt.optimize_with_history((a, a.y),
                                              np.zeros(16, np.float32))
        assert (a.X is None) == behind
        if not behind:
            b.whole(a)
        return np.asarray(w), np.asarray(losses), b, lay

    w, losses, b, lay = fit(behind=True)
    w_turn, losses_turn, b_turn, _ = fit(behind=False)
    np.testing.assert_array_equal(w, w_turn)
    np.testing.assert_array_equal(losses, losses_turn)
    assert losses.shape == (30,) and np.isfinite(losses).all()
    assert b.X.unsafe_buffer_pointer() == lay  # in place, as in turn
    for made in (b, b_turn):
        np.testing.assert_array_equal(np.asarray(made.X[:50000]), Xb)
        assert not np.asarray(made.X[50000:]).any()
        np.testing.assert_array_equal(np.asarray(made.y[:50000]), yb)


def test_a_fit_that_fails_at_its_fetch_ends_the_stream_there(monkeypatch):
    """An error fit k raises once its program has run (a non-finite loss
    under ``check_numerics``) ends the stream at k as in turn: k is not
    published, the model is k - 1's, and the join of k+1 that was queued
    behind the fit is harmless (its micro-batch is not consumed: a replay
    trains it)."""
    _blocked(monkeypatch, d=6)
    stream = _uneven_logistic(UNEVEN)
    bad = (stream[3][0] * np.float32("inf"), stream[3][1])
    want, _ = _logistic_fold(stream[:3])

    alg = StreamingLogisticRegressionWithSGD(step_size=0.2, num_iterations=8)
    alg.set_initial_weights(np.zeros(6, np.float32))
    alg.algorithm.optimizer.set_check_numerics(True)
    batches = _paced(monkeypatch, "done")(stream[:3] + [bad] + stream[4:])
    with pytest.raises(FloatingPointError):
        alg.train_on(batches)
    assert alg._batch_count == 3
    np.testing.assert_array_equal(np.asarray(alg.latest_model().weights),
                                  np.asarray(want.latest_model().weights))
    alg.train_on(iter(stream[4:]))  # and the model trains on after it
    assert alg._batch_count == 5
