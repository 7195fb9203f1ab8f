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
                                             ("resident_gram", 1)])
def test_the_folds_spans_tile_a_pass(tmp_path, schedule, totals):
    """``stream.wait`` / ``stream.batch`` (``fit.run``, ``stream.publish``)
    on the fold's thread, ``stream.stage`` on the worker's; ``ahead`` is 0
    for a pass's first micro-batch and 1 after it.  On the statistics
    schedule every block is ``folded`` under its copy, every fit runs from
    ``totals`` made ahead and builds nothing (no ``train.stats``); on the
    stock schedule (the planner's at these sizes) none."""
    import json
    import warnings

    from tpu_sgd import obs

    import threading

    stream, _ = _replayable_stream(batches=3)
    alg = StreamingLinearRegressionWithSGD(step_size=0.3, num_iterations=5)
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
    spans = [s for s in spans if s.get("name", "").startswith(("stream.",
                                                               "fit.run"))]
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert len(by["stream.wait"]) == 4 and len(by["stream.stage"]) == 4
    assert len(by["stream.batch"]) == len(by["stream.publish"]) \
        == len(by["fit.run"]) == 3
    turns = sorted(by["stream.batch"], key=lambda s: s["t0_s"])
    assert [s["index"] for s in turns] == [0, 1, 2]
    assert [s["ahead"] for s in turns] == [0, 1, 1]
    assert [s["totals"] for s in turns] == [totals] * 3
    assert all(s["rows"] == 500 for s in turns)
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
    assert len(staged) == 3 and all(s["blocks"] == 1 and
                                    s["bytes"] == 500 * 12 * 4 and
                                    s["folded"] == totals
                                    for s in staged)


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
        def __init__(self, X, y=None, alive=None):
            made.append(y is not None)
            super().__init__(X, y, alive)

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
                     model=StreamingLinearRegressionWithSGD):
    """A fold of ``stream`` on the statistics schedule (forced: the sizes
    are tiny) and what its listener saw."""
    import warnings

    alg = model(step_size=0.2, num_iterations=iterations,
                mini_batch_fraction=fraction)
    alg.set_initial_weights(np.zeros(d, np.float32))
    alg.algorithm.set_schedule(schedule)
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
    ``whole()``, and the fold is the in-turn fold bit for bit."""
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
    assert staged == (4 if case == "off" else 2) and folds == []
    assert ahead._totals_key() is None
    turn, calls_t = fold("in turn")
    assert len(joins) == staged and folds == []
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
