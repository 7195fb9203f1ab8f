"""A dense host array reaches the device in row blocks written into ONE
``(N, d)`` array in place (``gradient_descent._stage_dense``), each block in
the form that leaves the runtime least to re-tile (PR 49, ``_wire_form``: a
C-ordered array's rows flat, a Fortran-ordered array's 2-byte items as 32-bit
words, anything else as strided rows): the array is ``jnp.asarray``'s value
for value whatever the rows, the width, the type and the host array's order;
what is no large numpy array takes the calls it took before; the ``train.h2d``
span says how many pieces went and how many of them flat; a fit from blocks
is the fit from one piece bit for bit.  Tiny, CPU, the block cut to a few
rows' bytes."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import tpu_sgd
from tpu_sgd.obs.spans import disable_tracing, enable_tracing, span
from tpu_sgd.ops.sparse import sparse_data
from tpu_sgd.optimize import gradient_descent as gd

#: rows a block holds in these tests (the least the helper cuts: whole
#: multiples of ``_STAGE_ROWS``) and the blocks in flight
ROWS, IN_FLIGHT = gd._STAGE_ROWS, 2
#: what ``span("train.h2d")`` is with tracing off: the helper reads no clock
NO_SPAN = span("train.h2d")


def _blocks_of(monkeypatch, row_bytes, rows=ROWS, in_flight=IN_FLIGHT):
    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES", rows * row_bytes)
    monkeypatch.setattr(gd, "_STAGE_IN_FLIGHT", in_flight)


def _host(n, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.bool_:
        return rng.random((n, d)) < 0.5
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.integers(-3, 4, (n, d)).astype(dtype)
    return rng.normal(size=(n, d)).astype(dtype)


def _same(got, X):
    want = jnp.asarray(X)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got.sharding == want.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class Told:
    """A span that is not live and keeps what it is told."""
    live = False

    def __init__(self):
        self.said = {}

    def set(self, **stats):
        self.said.update(stats)


def _ordered(X, order):
    return np.asfortranarray(X) if order == "fortran" else X


# -- the array ----------------------------------------------------------------------

#: under one block; one block exactly; an exact multiple; a remainder of one
#: row; a remainder of most of a block; more blocks than may be in flight
ROW_CASES = {"under": ROWS - 1, "one_block": ROWS, "multiple": 3 * ROWS,
             "one_row_over": 2 * ROWS + 1, "remainder": 2 * ROWS + 1000,
             "beyond_in_flight": 5 * ROWS + 7}


@pytest.mark.parametrize("order", ["c", "fortran"])
@pytest.mark.parametrize("d", [1000, 128, 7])
@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_the_staged_array_is_jnp_asarrays(monkeypatch, case, d, order):
    """A C-ordered array's blocks cross flat, a Fortran-ordered f32 array's
    as its strided rows (its items are words already)."""
    n = ROW_CASES[case]
    X = _ordered(_host(n, d, np.float32), order)
    _blocks_of(monkeypatch, d * 4)
    told = Told()
    got, blocks, block_bytes = gd._stage_dense(X, told)
    _same(got, X)
    assert blocks == max(1, -(-n // ROWS))
    assert block_bytes == (X.nbytes if blocks == 1 else ROWS * d * 4)
    assert told.said["flat"] == (blocks if order == "c" and blocks > 1 else 0)


@pytest.mark.parametrize("d", [1000, 128, 7])
@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_a_fortran_ordered_array_of_two_byte_items_crosses_as_words(
        monkeypatch, case, d):
    """Rows ``2k`` and ``2k + 1`` of a column in one 32-bit word, unzipped on
    the device: ``jnp.asarray``'s bits.  A last block that ends on an odd
    row goes as its rows; an odd number of rows has no words at all."""
    n = ROW_CASES[case]
    X = np.asfortranarray(_host(n, d, ml_dtypes.bfloat16))
    _blocks_of(monkeypatch, d * 2)
    told = Told()
    seen = []
    real = gd._stage_block
    monkeypatch.setattr(gd, "_stage_block", lambda dest, block, offset: (
        seen.append((block.dtype, block.shape)) or real(dest, block, offset)))
    got, blocks, block_bytes = gd._stage_dense(X, told)
    _same(got, X)
    assert blocks == max(1, -(-n // ROWS))
    if blocks == 1:
        assert told.said["flat"] == 0 and seen == []
        return
    assert block_bytes == ROWS * d * 2
    words = 0 if n % 2 else blocks
    assert told.said["flat"] == words
    assert [k for k, _ in seen] == [jnp.uint32 if words else jnp.bfloat16] \
        * blocks
    assert seen[0][1] == ((ROWS // 2, d) if words else (ROWS, d))


@pytest.mark.parametrize("order", ["c", "fortran"])
@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.float64,
                                   np.int8, np.bool_, np.float16, np.int16],
                         ids=lambda t: np.dtype(t).name)
def test_every_type_arrives_as_the_single_copy_brings_it(monkeypatch, dtype,
                                                         order):
    """bf16 and f32 as they are, f64 as ``jnp.asarray`` narrows it, int8 and
    bool in their own type: the cast to f32 is the caller's, after the copy.
    Every 2-byte type goes through the words when Fortran-ordered."""
    X = _ordered(_host(2 * ROWS + 6, 16, dtype), order)
    _blocks_of(monkeypatch, 16 * X.itemsize)
    told = Told()
    got, blocks, _ = gd._stage_dense(X, told)
    assert blocks == 3
    _same(got, X)
    assert told.said["flat"] == (
        3 if order == "c" or X.itemsize == 2 else 0)


def test_items_off_a_word_boundary_go_as_their_rows(monkeypatch):
    """Fortran-ordered 2-byte items whose first one is not 4-byte aligned:
    no words, the strided rows, the right array."""
    n, d = 4 * ROWS, 8
    run = np.zeros(n * d + 1, ml_dtypes.bfloat16)[1:]
    run[:] = _host(n, d, ml_dtypes.bfloat16).T.reshape(-1)
    X = run.reshape(d, n).T
    assert X.flags.f_contiguous and X.ctypes.data % 4 == 2
    _blocks_of(monkeypatch, d * 2)
    told = Told()
    got, blocks, _ = gd._stage_dense(X, told)
    assert blocks == 4 and told.said["flat"] == 0
    _same(got, X)


@pytest.mark.parametrize("order", ["fortran", "strided_rows", "strided_columns",
                                   "reversed"])
def test_a_host_array_in_another_order_arrives_right(monkeypatch, order):
    """``np.asarray`` of a device array stored feature-major comes back
    Fortran-ordered (the benchmark's from-host array is one); a user slices."""
    base = _host(4 * ROWS + 6, 24, np.float32)
    X = {"fortran": np.asfortranarray(base), "strided_rows": base[::2],
         "strided_columns": base[:, ::3], "reversed": base[::-1]}[order]
    assert not X.flags.c_contiguous
    _blocks_of(monkeypatch, X.shape[1] * 4)
    told = Told()
    got, blocks, _ = gd._stage_dense(X, told)
    assert blocks == -(-X.shape[0] // ROWS) > 1
    _same(got, X)
    # the fallback: neither flat nor words, the strided rows as before
    assert told.said["flat"] == 0


@pytest.mark.parametrize("order", ["strided_rows", "strided_columns",
                                   "reversed", "wider"])
def test_a_strided_view_of_two_byte_items_takes_the_fallback(monkeypatch,
                                                             order):
    """Neither C- nor Fortran-contiguous: the strided row blocks, ``flat``
    0, whatever the item size."""
    base = np.asfortranarray(_host(4 * ROWS + 6, 24, ml_dtypes.bfloat16))
    X = {"strided_rows": base[::2], "strided_columns": base[:, ::3],
         "reversed": base[::-1], "wider": base[2:-4]}[order]
    assert not X.flags.c_contiguous and not X.flags.f_contiguous
    _blocks_of(monkeypatch, X.shape[1] * 2)
    told = Told()
    seen = []
    real = gd._stage_block
    monkeypatch.setattr(gd, "_stage_block", lambda dest, block, offset: (
        seen.append(block.shape) or real(dest, block, offset)))
    got, blocks, _ = gd._stage_dense(X, told)
    assert blocks == len(seen) == -(-X.shape[0] // ROWS) > 1
    assert seen[0] == (ROWS, X.shape[1]) and told.said["flat"] == 0
    _same(got, X)


def test_a_wide_array_of_few_rows_goes_in_one_piece(monkeypatch):
    """Over one block's bytes and under one block's least rows."""
    X = _host(ROWS - 24, 64, np.float32)
    monkeypatch.setattr(gd, "_STAGE_BLOCK_BYTES", 4096)
    before = gd._stage_block._cache_size()
    got, blocks, block_bytes = gd._stage_dense(X, NO_SPAN)
    _same(got, X)
    assert (blocks, block_bytes) == (1, X.nbytes)
    assert gd._stage_block._cache_size() == before


def test_what_is_no_large_numpy_array_takes_the_calls_it_took(monkeypatch):
    """A device array and a BCOO come back as the same object, a numpy array
    under one block goes through one ``jnp.asarray`` and no writer."""
    _blocks_of(monkeypatch, 64)
    Xd = jnp.asarray(_host(4 * ROWS, 16, np.float32))
    before = gd._stage_block._cache_size()
    with jax.transfer_guard("disallow"):
        got, blocks, block_bytes = gd._stage_dense(Xd, NO_SPAN)
    assert got is Xd and (blocks, block_bytes) == (0, 0)
    small = _host(ROWS - 1, 16, np.float32)
    got, blocks, block_bytes = gd._stage_dense(small, NO_SPAN)
    _same(got, small)
    assert (blocks, block_bytes) == (1, small.nbytes)
    assert gd._stage_block._cache_size() == before
    # BCOO features never reach the helper: the fit hands them on untouched
    Xs, ys, _ = sparse_data(256, 32, nnz_per_row=4, seed=1)
    seen = []
    monkeypatch.setattr(gd, "_stage_dense", lambda X, h2d: seen.append(X))
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_num_iterations(2))
    routed = []
    monkeypatch.setattr(
        opt, "_optimize_routed",
        lambda X, *a: routed.append(X) or (np.zeros(32, np.float32), []))
    opt.optimize_with_history((Xs, ys), np.zeros(32, np.float32))
    assert seen == [] and routed[0] is Xs


@pytest.mark.parametrize("form", ["flat", "words", "strided"])
def test_the_blocks_held_are_bounded_by_the_blocks_in_flight(monkeypatch,
                                                             form):
    """The host waits for the oldest write before it issues a block beyond
    the bound: at no call of the writer are more than ``_STAGE_IN_FLIGHT``
    earlier writes not known to be done, the pieces they hold are at most
    ``_STAGE_IN_FLIGHT x _STAGE_BLOCK_BYTES`` bytes in whatever form they
    crossed, and every block is deleted."""
    X = {"flat": lambda: _host(7 * ROWS, 8, np.float32),
         "words": lambda: np.asfortranarray(
             _host(7 * ROWS, 16, ml_dtypes.bfloat16)),
         "strided": lambda: _host(7 * ROWS, 16, np.float32)[:, ::2]}[form]()
    _blocks_of(monkeypatch, 32, in_flight=3)
    waited, blocks = [], []
    real = gd._stage_block

    class Written:
        def __init__(self, token):
            self.token = token

        def block_until_ready(self):
            waited.append(self)
            return self.token.block_until_ready()

    def write(dest, block, offset):
        held.append(block.nbytes)
        dest, token = real(dest, block, offset)
        blocks.append(block)
        pending = len(blocks) - len(waited)
        assert pending <= 3
        # in bytes: the pieces not known to be written, this one among them
        assert sum(held[len(waited):]) <= 3 * gd._STAGE_BLOCK_BYTES
        return dest, Written(token)

    held = []
    monkeypatch.setattr(gd, "_stage_block", write)
    got, n_blocks, _ = gd._stage_dense(X, NO_SPAN)
    _same(got, X)
    assert n_blocks == len(blocks) == 7 and len(waited) == 7 - 3
    assert all(b.is_deleted() for b in blocks)
    assert {(b.ndim, b.dtype.name) for b in blocks} == {
        {"flat": (1, "float32"), "words": (2, "uint32"),
         "strided": (2, "float32")}[form]}


# -- the span ---------------------------------------------------------------------

class Sink:
    def __init__(self):
        self.records = []

    def emit(self, kind, payload):
        self.records.append(dict(payload))

    def h2d(self):
        return [p for p in self.records if p["name"] == "train.h2d"]


def _opt():
    return (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                    tpu_sgd.SimpleUpdater())
            .set_step_size(0.1).set_num_iterations(6)
            .set_mini_batch_fraction(0.5).set_convergence_tol(0.0))


def test_train_h2d_says_how_many_pieces_went(monkeypatch):
    X = _host(3 * ROWS + 9, 8, np.float32)
    y = X @ np.arange(8, dtype=np.float32)
    w0 = np.zeros(8, np.float32)
    sink = Sink()
    enable_tracing(sink)
    try:
        _opt().optimize_with_history((X, y), w0)  # under the real block
        _blocks_of(monkeypatch, 32)
        _opt().optimize_with_history((X, y), w0)
        _opt().optimize_with_history((jnp.asarray(X), jnp.asarray(y)), w0)
    finally:
        disable_tracing()
    one, many, device = sink.h2d()
    assert (one["bytes"], one["blocks"], one["block_bytes"]) == (
        X.nbytes + y.nbytes, 1, X.nbytes)
    assert (many["bytes"], many["blocks"], many["block_bytes"]) == (
        X.nbytes + y.nbytes, 4, ROWS * 32)
    assert (device["bytes"], device["blocks"], device["block_bytes"]) == (
        0, 0, 0)
    # the pieces that crossed flat (PR 49): a C-ordered array's blocks, all
    assert (one["flat"], many["flat"], device["flat"]) == (0, 4, 0)
    # the stall counter (PR 37): 4 blocks, 2 in flight: the host stood in
    # the flow-control wait twice; one piece and a device array, never
    assert many["stalls"] == 4 - IN_FLIGHT and many["stall_ms"] >= 0
    for span in (one, device):
        assert (span["stalls"], span["stall_ms"]) == (0, 0)


CALLS = ("put_ms", "write_ms", "free_ms", "own_ms", "stall_ms")


def _traced_fit(X, clock=None, monkeypatch=None):
    """The ``train.h2d`` record of one fit of ``X`` with tracing on."""
    y = X @ np.arange(X.shape[1], dtype=np.float32)
    sink = Sink()
    enable_tracing(sink)
    try:
        if clock is not None:
            monkeypatch.setattr(gd, "time", clock)
        _opt().optimize_with_history((X, y), np.zeros(X.shape[1], np.float32))
    finally:
        disable_tracing()
    return sink.h2d()[0]


def test_train_h2d_says_where_the_issuing_threads_time_went(monkeypatch):
    """PR 46: a live span carries the four parts of a block's issue beside
    the wait."""
    X = _host(5 * ROWS + 9, 8, np.float32)
    _blocks_of(monkeypatch, 32)
    many = _traced_fit(X)
    assert many["blocks"] == 6 and many["stalls"] == 6 - IN_FLIGHT
    for name in CALLS:
        assert isinstance(many[name], float) and many[name] >= 0, name
    assert many["put_ms"] > 0 and many["write_ms"] > 0
    # one piece and a device array time no call
    monkeypatch.undo()
    for record in (_traced_fit(X), _traced_fit(jnp.asarray(X))):
        assert not set(CALLS[:4]) & set(record)
        assert (record["stalls"], record["stall_ms"]) == (0, 0)


def test_the_five_parts_are_the_threads_time_in_the_loop(monkeypatch,
                                                         stepped_clock):
    """On a clock that advances a second a reading: a put, a write's
    dispatch, a delete and a wait are each one step long, the loop's own
    time is the steps between them, and the five add up to the first
    reading in ``send`` less the last."""
    X = _host(5 * ROWS + 9, 8, np.float32)
    _blocks_of(monkeypatch, 32)
    clock = stepped_clock
    record = _traced_fit(X, clock, monkeypatch)
    readings, = clock.read_by.values()  # the fit's thread alone
    blocks, stalls = 6, 6 - IN_FLIGHT
    # in and out, four readings a block, two a wait
    assert len(readings) == 2 + 4 * blocks + 2 * stalls
    assert [record[n] for n in ("put_ms", "write_ms", "free_ms",
                                "stall_ms")] == [
        blocks * 1e3, blocks * 1e3, blocks * 1e3, stalls * 1e3]
    assert sum(record[n] for n in CALLS) == pytest.approx(
        (readings[-1] - readings[0]) * 1e3)
    assert record["own_ms"] == (len(readings) - 1 - 3 * blocks - stalls) * 1e3


@pytest.mark.parametrize("form", ["flat", "words", "strided"])
def test_an_untraced_hand_off_reads_no_clock_and_makes_the_same_calls(
        monkeypatch, no_clock, form):
    """With ``NO_SPAN`` a clock that raises is never read, and the puts,
    the fill, the writes and the deletes come in the order a traced
    hand-off makes them in: a put a block, of the block in its form."""
    d = 8
    X = {"flat": lambda: _host(4 * ROWS + 10, d, np.float32),
         "words": lambda: np.asfortranarray(
             _host(4 * ROWS + 10, d, ml_dtypes.bfloat16)),
         "strided": lambda: _host(4 * ROWS + 10, 2 * d, np.float32)[:, ::2]
         }[form]()
    _blocks_of(monkeypatch, d * X.itemsize)
    # what the runtime is handed for ``rows`` rows
    shape = {"flat": lambda rows: (rows * d,),
             "words": lambda rows: (rows // 2, d),
             "strided": lambda rows: (rows, d)}[form]
    calls = []
    puts, fills, writes = jnp.asarray, gd._stage_dest, gd._stage_block

    class Block:
        def __init__(self, array):
            self.array, self.dtype = array, array.dtype

        def delete(self):
            calls.append("delete")
            self.array.delete()

    class Jnp:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def asarray(piece):
            calls.append(("put", piece.shape))
            return Block(puts(piece))

    monkeypatch.setattr(gd, "jnp", Jnp())
    monkeypatch.setattr(gd, "_stage_dest",
                        lambda *a: calls.append("fill") or fills(*a))
    monkeypatch.setattr(
        gd, "_stage_block", lambda dest, block, offset: calls.append(
            ("write", offset)) or writes(dest, block.array, offset))
    sink = Sink()
    enable_tracing(sink)
    try:
        with span("train.h2d") as h2d:
            traced, _, _ = gd._stage_dense(X, h2d)
    finally:
        disable_tracing()
    record, = sink.h2d()
    assert record["put_ms"] > 0
    assert record["flat"] == (0 if form == "strided" else 5)
    as_traced, calls[:] = list(calls), []
    monkeypatch.setattr(gd, "time", no_clock)
    got, blocks, _ = gd._stage_dense(X, NO_SPAN)
    assert blocks == 5
    assert calls == as_traced == [("put", shape(ROWS)), "fill", ("write", 0),
                                  "delete"] + [
        step for k in range(1, 5) for step in (
            ("put", shape(ROWS if k < 4 else 10)), ("write", k * ROWS),
            "delete")]
    np.testing.assert_array_equal(np.asarray(got), X)
    np.testing.assert_array_equal(np.asarray(traced), X)


# -- the fit ----------------------------------------------------------------------

@pytest.mark.parametrize("model,dtype", [
    ("LogisticRegressionWithSGD", np.float32),
    ("LogisticRegressionWithSGD", ml_dtypes.bfloat16),
    ("LinearRegressionWithSGD", np.float32),
    ("LinearRegressionWithSGD", np.int8)],
    ids=lambda v: v if isinstance(v, str) else np.dtype(v).name)
@pytest.mark.parametrize("order", ["c", "fortran"])
def test_a_fit_from_blocks_is_the_fit_from_one_piece_bit_for_bit(
        monkeypatch, model, dtype, order):
    """Flat pieces (C-ordered), words (Fortran-ordered bf16) and strided
    rows (Fortran-ordered f32 and int8): the fit from one piece."""
    rng = np.random.default_rng(3)
    X = _ordered(_host(3 * ROWS + 100, 12, dtype, seed=3), order)
    margin = X.astype(np.float32) @ rng.uniform(-1, 1, 12).astype(np.float32)
    y = (margin > 0).astype(np.float32) if model.startswith("Logistic") \
        else margin

    def fit():
        alg = getattr(tpu_sgd, model)(0.05, 10, mini_batch_fraction=0.5)
        m = alg.run((X, y))
        return np.asarray(m.weights), np.asarray(alg.optimizer.loss_history)

    staged, flat = [], []

    def stage(X, h2d):
        told = Told()
        staged.append(real(X, told))
        flat.append(told.said["flat"])
        return staged[-1]

    real = gd._stage_dense
    monkeypatch.setattr(gd, "_stage_dense", stage)
    w_one, loss_one = fit()
    _blocks_of(monkeypatch, 12 * X.itemsize)
    w_blocks, loss_blocks = fit()
    assert [s[1] for s in staged] == [1, 4]
    assert flat == [0, 4 if order == "c" or X.itemsize == 2 else 0]
    assert len(loss_one) == 10 and np.isfinite(loss_one).all()
    np.testing.assert_array_equal(w_blocks, w_one)
    np.testing.assert_array_equal(loss_blocks, loss_one)


@pytest.mark.parametrize("order,dtype", [
    ("c", np.float32), ("fortran", ml_dtypes.bfloat16),
    ("fortran", np.float32)], ids=["flat", "words", "strided"])
def test_a_second_fit_of_the_same_shape_builds_no_program(monkeypatch, order,
                                                          dtype):
    X = _ordered(_host(2 * ROWS + 300, 10, dtype), order)
    y = (np.asarray(X[:, 0], np.float32) > 0).astype(np.float32)
    _blocks_of(monkeypatch, 10 * X.itemsize)
    alg = tpu_sgd.LogisticRegressionWithSGD(0.1, 4, mini_batch_fraction=0.5)
    alg.run((X, y))
    sizes = gd._stage_block._cache_size(), gd._stage_dest._cache_size()
    runners = len(alg.optimizer._run_cache)
    first = np.asarray(alg.optimizer.loss_history)
    alg.run((X.copy(order="K"), y))
    assert (gd._stage_block._cache_size(),
            gd._stage_dest._cache_size()) == sizes
    assert len(alg.optimizer._run_cache) == runners
    np.testing.assert_array_equal(np.asarray(alg.optimizer.loss_history),
                                  first)
