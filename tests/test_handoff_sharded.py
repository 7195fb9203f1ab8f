"""A dense host array handed to a fit over a 1-D data mesh goes to the chips
in ``_stage_dense``'s row blocks, each block to the device that owns its rows
(``gradient_descent._stage_dense`` with a destination a device,
``parallel.shard_dataset``'s host branch) and in the form that leaves the
runtime least to re-tile (PR 49: flat, 32-bit words, or the strided rows):
the sharded array is
``jax.device_put(X, NamedSharding(mesh, P('data', None)))`` value for value
whatever the type, the order and the rows, every shard's buffer on its own
device; no device holds more than its shard and the blocks in flight to it;
rows that do not divide are padded and masked; ``y`` and ``valid`` lie
sharded; the spans say so; the meshed ``run()`` from host arrays is the fit on
pre-sharded arrays bit for bit.  Tiny, CPU, the forced 8 host devices, a mesh
of 4, the block cut to a few rows' bytes as ``tests/test_handoff_blocks.py``
cuts it."""

import threading

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import tpu_sgd
from bench import cells, correct
from tpu_sgd.obs.spans import disable_tracing, enable_tracing
from tpu_sgd.optimize import gradient_descent as gd
from tpu_sgd.parallel import shard_dataset

ROWS, IN_FLIGHT, SHARDS = gd._STAGE_ROWS, 2, 4

CALLS = ("put_ms", "write_ms", "free_ms", "own_ms", "stall_ms")


@pytest.fixture(scope="module")
def mesh():
    return tpu_sgd.data_mesh(jax.devices()[:SHARDS])


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


class Told:
    """A span that is not live and keeps what it is told."""
    live = False

    def __init__(self):
        self.said = {}

    def set(self, **stats):
        self.said.update(stats)


def _padded(X, shards=SHARDS):
    rem = (-X.shape[0]) % shards
    return np.concatenate([X, np.zeros((rem,) + X.shape[1:], X.dtype)])


def _same(got, X, mesh):
    """``got`` is the plain sharded placement of ``X`` (zero rows behind it
    where the rows do not divide), each shard's buffer on its own device."""
    want = jax.device_put(_padded(X), NamedSharding(mesh, P("data", None)))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
    local = want.shape[0] // SHARDS
    for s, (shard, device) in enumerate(zip(got.addressable_shards,
                                            mesh.devices.flat)):
        assert shard.device == device and shard.data.devices() == {device}
        assert shard.data.shape == (local,) + X.shape[1:]
        assert shard.index[0] == slice(s * local, (s + 1) * local)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the array ----------------------------------------------------------------------

#: a shard under one block; a shard one block exactly; whole blocks a shard;
#: a remainder block a shard; rows that do not divide by the shards (a shard
#: short of one row; of most of a block); more blocks a shard than in flight
ROW_CASES = {"under": SHARDS * (ROWS - 1), "one_block": SHARDS * ROWS,
             "multiple": SHARDS * 3 * ROWS,
             "remainder": SHARDS * (2 * ROWS + 1000),
             "one_row_short": SHARDS * (2 * ROWS + 8) - 1,
             "a_block_short": SHARDS * 3 * ROWS - ROWS - 5,
             "few_rows": SHARDS + 1,
             "beyond_in_flight": SHARDS * (5 * ROWS + 7)}


@pytest.mark.parametrize("d", [1000, 7])
@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_the_sharded_array_is_device_puts(monkeypatch, mesh, no_clock, case,
                                          d):
    n = ROW_CASES[case]
    X = _host(n, d, np.float32)
    _blocks_of(monkeypatch, X.strides[0])
    monkeypatch.setattr(gd, "time", no_clock)  # no span: no clock is read
    got, blocks, block_bytes = gd._stage_dense(X, mesh=mesh)
    _same(got, X, mesh)
    local = -(-n // SHARDS)
    if local <= ROWS:  # one piece a shard
        assert (blocks, block_bytes) == (SHARDS, local * d * 4)
    else:
        assert blocks == sum(
            -(-max(0, min(local, n - s * local)) // ROWS)
            for s in range(SHARDS))
        assert block_bytes == ROWS * d * 4


@pytest.mark.parametrize("order", ["c", "fortran"])
@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.float64,
                                   np.int8, np.bool_],
                         ids=lambda t: np.dtype(t).name)
@pytest.mark.parametrize("n", [SHARDS * (2 * ROWS + 5), SHARDS * 2 * ROWS - 3,
                               SHARDS * 8],
                         ids=["blocks", "blocks_padded", "one_piece"])
def test_every_type_arrives_as_the_plain_placement_brings_it(
        monkeypatch, mesh, dtype, n, order):
    X = _host(n, 16, dtype)
    if order == "fortran":
        X = np.asfortranarray(X)
    _blocks_of(monkeypatch, 16 * X.itemsize)
    got, _, _ = gd._stage_dense(X, mesh=mesh)
    _same(got, X, mesh)


#: rows a shard: whole blocks; a short last block; a last shard short of two
#: rows (its rows end on an even row); an odd shard (shards 1 and 3 start on
#: an odd row and have no words, the last block of shards 0 and 2 ends on
#: one); an odd array, short of one row and of three (no words at all: a
#: column's bytes are no whole number of words)
WORD_CASES = {"multiple": (SHARDS * 3 * ROWS, SHARDS * 3),
              "remainder": (SHARDS * (2 * ROWS + 1000), SHARDS * 3),
              "two_rows_short": (SHARDS * (2 * ROWS + 8) - 2, SHARDS * 3),
              "odd_shards": (SHARDS * (2 * ROWS + 7), 2 * 2),
              "odd_array": (SHARDS * (2 * ROWS + 8) - 1, 0),
              "odd_array_three_short": (SHARDS * (2 * ROWS + 8) - 3, 0)}


@pytest.mark.parametrize("d", [1000, 128, 7])
@pytest.mark.parametrize("case", sorted(WORD_CASES))
def test_a_fortran_ordered_two_byte_array_crosses_as_words_to_four(
        monkeypatch, mesh, case, d):
    """The benchmark's four-chip array in small: bf16, Fortran-ordered.
    Every block whose rows start and end on an even row of an even array
    goes as 32-bit words, the others as their rows; the shards are the
    plain placement's bit for bit."""
    n, words = WORD_CASES[case]
    X = np.asfortranarray(_host(n, d, ml_dtypes.bfloat16))
    _blocks_of(monkeypatch, d * 2)
    told = Told()
    got, blocks, block_bytes = gd._stage_dense(X, told, mesh)
    _same(got, X, mesh)
    assert (blocks, block_bytes) == (SHARDS * 3, ROWS * d * 2)
    assert (told.said["shards"], told.said["flat"]) == (SHARDS, words)


@pytest.mark.parametrize("order", ["c", "fortran"])
def test_one_destination_and_four_take_the_same_blocks(monkeypatch, mesh,
                                                       order):
    """The array that goes to four devices in ``4 x 3`` blocks goes to one
    in 12, in the same form."""
    X = _host(SHARDS * 3 * ROWS, 8, ml_dtypes.bfloat16)
    if order == "fortran":
        X = np.asfortranarray(X)
    _blocks_of(monkeypatch, 16)
    for on, shards in ((mesh, SHARDS), (None, 1)):
        told = Told()
        got, blocks, _ = gd._stage_dense(X, told, on)
        assert (blocks, told.said["flat"], told.said["shards"]) == (
            12, 12, shards)
        np.testing.assert_array_equal(np.asarray(got), X)


@pytest.mark.parametrize("order", ["c", "fortran", "strided_rows",
                                   "strided_columns"])
@pytest.mark.parametrize("n", [SHARDS * (2 * ROWS + 6), SHARDS * 2 * ROWS - 1],
                         ids=["divides", "padded"])
def test_a_host_array_in_either_order_arrives_right(monkeypatch, mesh, order,
                                                    n):
    base = _host(2 * n, 24, np.float32)
    X = {"c": base[:n], "fortran": np.asfortranarray(base[:n]),
         "strided_rows": base[::2], "strided_columns": base[:n, ::3]}[order]
    _blocks_of(monkeypatch, X.shape[1] * 4)
    told = Told()
    got, blocks, _ = gd._stage_dense(X, told, mesh)
    assert blocks > SHARDS
    _same(got, X, mesh)
    # C-ordered rows cross flat; every other order of f32 as strided rows
    assert told.said["flat"] == (blocks if order == "c" else 0)


@pytest.mark.parametrize("form", ["flat", "words", "strided"])
def test_no_device_holds_more_than_its_shard_and_the_blocks_in_flight(
        monkeypatch, mesh, form):
    """The host waits for a device's oldest write before it issues that
    device a block beyond the bound, so a device holds its destination and
    at most ``_STAGE_IN_FLIGHT x _STAGE_BLOCK_BYTES`` bytes of pieces in
    whatever form they crossed; every block goes to the device that owns
    its rows, each device's in the rows' order from a thread of its own,
    and is deleted."""
    X = {"flat": lambda: _host(SHARDS * 7 * ROWS, 8, np.float32),
         "words": lambda: np.asfortranarray(
             _host(SHARDS * 7 * ROWS, 16, ml_dtypes.bfloat16)),
         "strided": lambda: _host(SHARDS * 7 * ROWS, 16,
                                  np.float32)[:, ::2]}[form]()
    _blocks_of(monkeypatch, 32, in_flight=3)
    held = {}
    devices = list(mesh.devices.flat)
    waited = {d: 0 for d in devices}
    issued = {d: 0 for d in devices}
    blocks, order, threads = [], [], set()
    real = gd._stage_block

    class Written:
        def __init__(self, token, device):
            self.token, self.device = token, device

        def block_until_ready(self):
            waited[self.device] += 1
            return self.token.block_until_ready()

    def write(dest, block, offset):
        device, = block.devices()
        assert dest.devices() == {device}
        # the block's rows are this device's: its shard starts at s * local
        s = devices.index(device)
        rows = X[s * 7 * ROWS + offset:s * 7 * ROWS + offset + ROWS]
        if form == "words":  # rows 2k and 2k + 1 of a column in one word
            rows = np.ascontiguousarray(rows.T).view(np.uint32).T
        np.testing.assert_array_equal(
            np.asarray(block).reshape(rows.shape), rows)
        assert (block.ndim, block.dtype.name) == {
            "flat": (1, "float32"), "words": (2, "uint32"),
            "strided": (2, "float32")}[form]
        held.setdefault(device, []).append(block.nbytes)
        dest, token = real(dest, block, offset)
        blocks.append(block)
        order.append((s, offset))
        threads.add((s, threading.get_ident()))
        issued[device] += 1
        assert issued[device] - waited[device] <= 3
        assert sum(held[device][waited[device]:]) \
            <= 3 * gd._STAGE_BLOCK_BYTES
        return dest, Written(token, device)

    monkeypatch.setattr(gd, "_stage_block", write)
    got, n_blocks, _ = gd._stage_dense(X, mesh=mesh)
    _same(got, X, mesh)
    assert n_blocks == len(blocks) == SHARDS * 7
    assert all(n == 7 - 3 for n in waited.values())
    assert all(b.is_deleted() for b in blocks)
    for s in range(SHARDS):
        assert [a for d, a in order if d == s] == [ROWS * i for i in range(7)]
    # a thread a device, none of them the caller's: all receive at once
    assert len(threads) == len({t for _, t in threads}) == SHARDS
    assert threading.get_ident() not in {t for _, t in threads}


def test_without_a_mesh_the_calls_are_the_calls_it_made(monkeypatch,
                                                        no_clock):
    """One destination on the default device, ``jnp.asarray`` of each block,
    one fill and one write a block: nothing of the sharded road."""
    X = _host(3 * ROWS + 9, 8, np.float32)
    _blocks_of(monkeypatch, 32)
    calls = []
    for name in ("device_put", "make_array_from_single_device_arrays",
                 "default_device"):
        monkeypatch.setattr(
            jax, name, lambda *a, _n=name, **k: calls.append(_n) or 1 / 0)
    monkeypatch.setattr(gd, "ThreadPoolExecutor",
                        lambda *a: calls.append("threads") or 1 / 0)
    monkeypatch.setattr(gd, "time", no_clock)  # no span: no clock is read
    fills, writes = gd._stage_dest, gd._stage_block
    monkeypatch.setattr(gd, "_stage_dest",
                        lambda *a: calls.append("fill") or fills(*a))
    monkeypatch.setattr(gd, "_stage_block",
                        lambda *a: calls.append("write") or writes(*a))
    got, blocks, block_bytes = gd._stage_dense(X)
    assert calls == ["fill"] + ["write"] * 4
    assert (blocks, block_bytes) == (4, ROWS * 32)
    assert got.sharding == jnp.asarray(X).sharding
    np.testing.assert_array_equal(np.asarray(got), X)


# -- rows that do not divide; y and valid ------------------------------------------

@pytest.mark.parametrize("n", [SHARDS * 2 * ROWS, SHARDS * 2 * ROWS - 2,
                               SHARDS * 8 - 3],
                         ids=["divides", "padded_blocks", "padded_one_piece"])
def test_shard_dataset_pads_and_masks_as_it_did(monkeypatch, mesh, n):
    """The host branch against the device branch (which pads on the devices
    and was not touched): the same arrays, laid out the same."""
    X = _host(n, 8, np.float32)
    y = np.arange(n, dtype=np.float32)
    _blocks_of(monkeypatch, 32)
    Xd, yd, valid = shard_dataset(mesh, X, y)
    Xw, yw, validw = shard_dataset(mesh, jnp.asarray(X), jnp.asarray(y))
    _same(Xd, X, mesh)
    assert (valid is None) == (validw is None) == (n % SHARDS == 0)
    for got, want in ((Xd, Xw), (yd, yw)) + (
            () if valid is None else ((valid, validw),)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
        assert [s.device for s in got.addressable_shards] == list(
            mesh.devices.flat)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and what comes back is laid out for the mesh: handed in again, as it is
    X2, y2, v2 = shard_dataset(mesh, Xd, yd)
    assert X2 is Xd and y2 is yd and v2 is None


# -- the spans ----------------------------------------------------------------------

class Sink:
    def __init__(self):
        self.records = []

    def emit(self, kind, payload):
        self.records.append(dict(payload))

    def spans(self, name):
        return [p for p in self.records if p["name"] == name]


def _opt(mesh=None, iterations=6):
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_step_size(0.1).set_num_iterations(iterations)
           .set_mini_batch_fraction(0.5).set_convergence_tol(0.0))
    return opt if mesh is None else opt.set_mesh(mesh)


def test_train_h2d_says_the_shards_and_train_place_moves_nothing(
        monkeypatch, mesh):
    n = SHARDS * (3 * ROWS + 9)
    X = _host(n, 8, np.float32)
    y = X @ np.arange(8, dtype=np.float32)
    w0 = np.zeros(8, np.float32)
    sink = Sink()
    enable_tracing(sink)
    try:
        _opt(mesh).optimize_with_history((X, y), w0)  # under the real block
        _blocks_of(monkeypatch, 32)
        _opt(mesh).optimize_with_history((X, y), w0)
        _opt(mesh).optimize_with_history((X[:-1], y[:-1]), w0)
        _opt().optimize_with_history((X, y), w0)
        _opt(mesh).optimize_with_history((jnp.asarray(X), jnp.asarray(y)), w0)
    finally:
        disable_tracing()
    one, many, padded, no_mesh, device = sink.spans("train.h2d")
    assert (one["shards"], one["blocks"], one["block_bytes"]) == (
        SHARDS, SHARDS, X.nbytes // SHARDS)
    assert (many["shards"], many["blocks"], many["block_bytes"]) == (
        SHARDS, SHARDS * 4, ROWS * 32)
    assert many["bytes"] == X.nbytes + y.nbytes
    # 4 blocks a device, 2 in flight: the host stood twice for each device
    assert many["stalls"] == SHARDS * (4 - IN_FLIGHT)
    assert many["stall_ms"] >= 0
    assert (padded["shards"], padded["blocks"]) == (SHARDS, SHARDS * 4)
    assert (no_mesh["shards"], no_mesh["blocks"]) == (1, 13)
    assert (device["shards"], device["blocks"]) == (0, 0)
    places = sink.spans("train.place")
    assert len(places) == 4
    for rec in places[:3]:  # from the host: the hand-off laid them out
        assert (rec["in_place"], rec["bytes"], rec["shards"]) == (
            1, 0, SHARDS)
    assert (places[3]["in_place"], places[3]["bytes"]) == (
        0, X.nbytes + y.nbytes)


def _traced_hand_off(mesh, X, shards=SHARDS):
    """``shard_dataset``'s host branch under a live ``train.h2d``: the
    span's record."""
    from tpu_sgd.obs.spans import span

    sink = Sink()
    enable_tracing(sink)
    try:
        with span("train.h2d") as h2d:
            Xd, _, _ = shard_dataset(mesh, X, np.zeros(len(X), np.float32),
                                     h2d)
    finally:
        disable_tracing()
    if shards == SHARDS:
        _same(Xd, X, mesh)
    else:
        np.testing.assert_array_equal(np.asarray(Xd), X)
    record, = sink.spans("train.h2d")
    return record


def test_train_h2d_says_where_the_four_threads_time_went(monkeypatch, mesh):
    """PR 46: the five sums are over the devices' threads."""
    X = _host(SHARDS * (5 * ROWS + 9), 8, np.float32)
    _blocks_of(monkeypatch, 32)
    record = _traced_hand_off(mesh, X)
    assert (record["shards"], record["blocks"]) == (SHARDS, SHARDS * 6)
    assert record["stalls"] == SHARDS * (6 - IN_FLIGHT)
    for name in CALLS:
        assert isinstance(record[name], float) and record[name] >= 0, name
    assert record["put_ms"] > 0 and record["write_ms"] > 0
    # a mesh of ONE device: one thread
    one = _traced_hand_off(tpu_sgd.data_mesh(jax.devices()[:1]),
                           X[:5 * ROWS + 9], shards=1)
    assert (one["shards"], one["blocks"]) == (1, 6)
    assert one["put_ms"] > 0


def test_the_five_parts_are_the_four_threads_time_in_their_loops(
        monkeypatch, mesh, stepped_clock):
    """On a clock that advances a second a reading, whichever thread reads
    it: each thread's five parts are its last reading less its first, so the
    span's sums are the threads' time in ``send``."""
    X = _host(SHARDS * (5 * ROWS + 9), 8, np.float32)
    _blocks_of(monkeypatch, 32)
    clock = stepped_clock
    monkeypatch.setattr(gd, "time", clock)
    record = _traced_hand_off(mesh, X)
    assert threading.get_ident() not in clock.read_by
    blocks, stalls = 6, 6 - IN_FLIGHT
    # in and out, four readings a block, two a wait: a device's loop; a
    # pool's thread that came back early may have run two of them in turn
    a_loop = 2 + 4 * blocks + 2 * stalls
    loops = [readings[i:i + a_loop] for readings in clock.read_by.values()
             for i in range(0, len(readings), a_loop)]
    assert [len(loop) for loop in loops] == [a_loop] * SHARDS
    in_send = sum(loop[-1] - loop[0] for loop in loops)
    assert sum(record[n] for n in CALLS) == pytest.approx(in_send * 1e3)
    # a part is at least its readings' one step each, more where another
    # thread read the clock in between
    for name, least in (("put_ms", blocks), ("write_ms", blocks),
                        ("free_ms", blocks), ("stall_ms", stalls)):
        assert record[name] >= SHARDS * least * 1e3, name


# -- the fit ----------------------------------------------------------------------

def _run(alg, data):
    model = alg.run(data)
    return np.asarray(model.weights), np.asarray(alg.optimizer.loss_history)


def _alg(model, mesh, iterations=10):
    alg = getattr(tpu_sgd, model)(0.05, iterations, mini_batch_fraction=0.5)
    alg.optimizer.set_convergence_tol(0.0).set_mesh(mesh)
    return alg


@pytest.mark.parametrize("model,dtype,n", [
    ("LinearRegressionWithSGD", np.float32, SHARDS * (3 * ROWS + 100)),
    ("LinearRegressionWithSGD", ml_dtypes.bfloat16, SHARDS * (3 * ROWS + 100)),
    ("LinearRegressionWithSGD", np.int8, SHARDS * (3 * ROWS + 100)),
    ("LinearRegressionWithSGD", np.float32, SHARDS * 3 * ROWS - 3),
    ("LogisticRegressionWithSGD", np.float32, SHARDS * 2 * ROWS + 1),
    ("LinearRegressionWithSGD", np.float32, SHARDS * 64)],
    ids=["f32", "bf16", "int8", "padded", "logistic_padded", "one_piece"])
def test_a_meshed_run_from_host_arrays_is_the_fit_on_pre_sharded_arrays(
        monkeypatch, mesh, model, dtype, n):
    """Bit for bit, weights and loss history; the pre-sharded arrays are the
    plain placement (``jax.device_put`` by sharding), padded and masked on
    the host where the rows do not divide: ``dp_optimize``'s own road."""
    rng = np.random.default_rng(3)
    X = _host(n, 12, dtype, seed=3)
    margin = X.astype(np.float32) @ rng.uniform(-1, 1, 12).astype(np.float32)
    y = (margin > 0).astype(np.float32) if model.startswith("Logistic") \
        else margin
    _blocks_of(monkeypatch, X.strides[0])
    alg = _alg(model, mesh)
    w, losses = _run(alg, (X, y))
    assert len(losses) == 10 and np.isfinite(losses).all()
    # the fit on arrays placed plainly
    from tpu_sgd.parallel.data_parallel import dp_run_fn, pad_to_multiple

    Xp, yp, validp = pad_to_multiple(X, y, SHARDS)
    Xd = jax.device_put(Xp, NamedSharding(mesh, P("data", None)))
    if not jnp.issubdtype(Xd.dtype, jnp.inexact):
        Xd = Xd.astype(jnp.float32)
    yd = jax.device_put(yp, NamedSharding(mesh, P("data")))
    opt = alg.optimizer
    args = (jnp.zeros(12, jnp.float32), Xd, yd, opt.config.hyper())
    if n % SHARDS:
        args += (jax.device_put(validp, NamedSharding(mesh, P("data"))),)
    fn = dp_run_fn(opt.gradient, opt.updater, opt.config, mesh,
                   bool(n % SHARDS))
    w_pre, losses_pre, _ = fn(*args)
    np.testing.assert_array_equal(w, np.asarray(w_pre))
    np.testing.assert_array_equal(losses, np.asarray(losses_pre))
    if not n % SHARDS:
        # and through the entry point itself, on the cached arrays
        w_cached, losses_cached = _run(_alg(model, mesh), (Xd, yd))
        np.testing.assert_array_equal(w, w_cached)
        np.testing.assert_array_equal(losses, losses_cached)


def test_the_meshed_run_from_the_host_follows_the_data_parallel_reference(
        monkeypatch, mesh):
    """``run()`` on host rows over four shards against
    ``bench/reference/glm_dense_dp.py`` at the tiny size, blocks forced."""
    cell = cells.Cell("dense1000-lsq-dp4.resident-sharded",
                      overrides={"rows": 4096, "features": 32,
                                 "num_iterations": 8, "step_size": 0.5})
    config = cell.config
    Xd, yd = cell.generator.make(config, cell.rows, 5)
    X, y = np.asarray(Xd), np.asarray(yd)
    _blocks_of(monkeypatch, 2 * 32, rows=256)
    monkeypatch.setattr(gd, "_STAGE_ROWS", 256)
    alg = tpu_sgd.LinearRegressionWithSGD(0.5, 8, mini_batch_fraction=0.1)
    alg.optimizer.set_convergence_tol(0.0).set_seed(42).set_mesh(mesh)
    sink = Sink()
    enable_tracing(sink)
    try:
        w, losses = _run(alg, (X, y))
    finally:
        disable_tracing()
    h2d, = sink.spans("train.h2d")
    assert (h2d["shards"], h2d["blocks"]) == (SHARDS, SHARDS * 4)
    w0 = np.zeros(32, np.float32)
    ref = cell.reference.fit(config, Xd, yd, w0, 42)
    got = correct.readings(w, losses, *ref, w0)
    assert max(got.values()) < 5e-3, got


def test_a_second_fit_of_the_same_shape_builds_no_program(monkeypatch, mesh):
    n = SHARDS * (2 * ROWS + 300)
    X = _host(n, 10, np.float32)
    y = X[:, 0].copy()
    _blocks_of(monkeypatch, X.strides[0])
    alg = _alg("LinearRegressionWithSGD", mesh, iterations=4)
    _, first = _run(alg, (X, y))
    sizes = gd._stage_block._cache_size(), gd._stage_dest._cache_size()
    runners = len(alg.optimizer._run_cache)
    _, second = _run(alg, (X.copy(), y))
    assert (gd._stage_block._cache_size(),
            gd._stage_dest._cache_size()) == sizes
    assert len(alg.optimizer._run_cache) == runners
    np.testing.assert_array_equal(second, first)


def test_the_observed_driver_takes_the_hand_offs_mask(monkeypatch, mesh):
    """The stepwise (listener) driver over rows that do not divide: the mask
    of the rows the hand-off padded reaches its step."""
    from tpu_sgd.utils.events import CollectingListener

    n = SHARDS * 2 * ROWS - 3
    X = _host(n, 8, np.float32)
    y = X @ np.arange(8, dtype=np.float32)
    w0 = np.zeros(8, np.float32)
    _blocks_of(monkeypatch, 32)
    fused = _opt(mesh, iterations=3).optimize_with_history((X, y), w0)
    seen = CollectingListener()
    w, losses = _opt(mesh, iterations=3).set_listener(seen) \
        .optimize_with_history((X, y), w0)
    assert len(seen.iterations) == 3
    np.testing.assert_allclose(np.asarray(w), np.asarray(fused[0]), rtol=1e-6)
    np.testing.assert_allclose(losses, fused[1], rtol=1e-6)


@pytest.mark.parametrize("n", [8 * ROWS, 8 * ROWS - 3],
                         ids=["divides", "padded"])
def test_a_mesh_with_a_model_axis_is_placed_plainly(monkeypatch, n):
    """Under a 2 x 2 data x model mesh every shard lies on two devices: the
    placement by sharding, as before, not a destination a device."""
    mesh2 = tpu_sgd.make_mesh(n_data=2, n_model=2, devices=jax.devices()[:4])
    X = _host(n, 8, np.float32)
    y = np.arange(n, dtype=np.float32)
    _blocks_of(monkeypatch, 32)
    monkeypatch.setattr(gd, "_stage_block", lambda *a: 1 / 0)
    Xd, yd, valid = shard_dataset(mesh2, X, y)
    assert Xd.sharding.is_equivalent_to(
        NamedSharding(mesh2, P("data", None)), 2)
    assert len(Xd.addressable_shards) == 4
    np.testing.assert_array_equal(np.asarray(Xd), _padded(X, 2))
    np.testing.assert_array_equal(np.asarray(yd)[:n], y)
    assert (valid is None) == (n % 2 == 0)
    if valid is not None:
        assert np.asarray(valid).sum() == n
