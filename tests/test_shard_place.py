"""A dataset cached across the chips trains where it lies: ``shard_dataset``
hands back arrays already laid out for its mesh, re-lays device arrays that
are not without the host, and the meshed fit on pre-sharded arrays is the fit
from host arrays bit for bit; then the system's sharded fits against the plain
data-parallel reference (``bench/reference/glm_dense_dp.py``).  Tiny, CPU, the
forced 8 host devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import tpu_sgd
from bench import cells, correct
from bench.reference import glm_dense, glm_dense_dp
from tpu_sgd.obs.spans import disable_tracing, enable_tracing
from tpu_sgd.parallel import shard_dataset

CELL = "dense1000-lsq-dp4.resident-sharded"
N, D = 4096, 32


@pytest.fixture(scope="module")
def mesh():
    return tpu_sgd.data_mesh(jax.devices()[:4])


@pytest.fixture(scope="module")
def host():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(N, D)).astype(np.float32)
    w = rng.uniform(-1, 1, D).astype(np.float32)
    return X, (X @ w + 0.1 * rng.normal(size=N)).astype(np.float32)


def _buffers(a):
    return [s.data.unsafe_buffer_pointer() for s in a.addressable_shards]


def _opt(mesh, gradient="LeastSquaresGradient", updater="SimpleUpdater",
         iterations=8, fraction=0.5, step=0.5, reg=0.01):
    return (tpu_sgd.GradientDescent(getattr(tpu_sgd, gradient)(),
                                    getattr(tpu_sgd, updater)())
            .set_step_size(step).set_num_iterations(iterations)
            .set_reg_param(reg).set_mini_batch_fraction(fraction)
            .set_convergence_tol(0.0).set_seed(42).set_mesh(mesh))


class Sink:
    def __init__(self):
        self.records = []

    def emit(self, kind, payload):
        self.records.append((kind, dict(payload)))

    def spans(self, name):
        return [p for k, p in self.records
                if k == "trace_span" and p["name"] == name]


# -- (a) the placement ---------------------------------------------------------

def test_arrays_sharded_for_the_mesh_come_back_as_they_are(mesh, host):
    Xd, yd, valid = shard_dataset(mesh, *host)
    assert valid is None
    with jax.transfer_guard("disallow"):  # no host copy, either way
        X2, y2, valid2 = shard_dataset(mesh, Xd, yd)
    assert X2 is Xd and y2 is yd and valid2 is None
    assert _buffers(X2) == _buffers(Xd)


def test_an_equivalent_sharding_of_another_mesh_object_counts(mesh, host):
    """By equivalence of shardings, not identity of the mesh: a dataset laid
    out by other code (the benchmark's generator builds its own ``Mesh``) over
    the same devices in the same order."""
    other = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    X = jax.device_put(host[0], NamedSharding(other, P("data")))
    y = jax.device_put(host[1], NamedSharding(other, P("data")))
    Xd, yd, valid = shard_dataset(mesh, X, y)
    assert Xd is X and yd is y and valid is None
    # and the canonical 2-D (data, model=1) mesh spells the same layout
    Xd, _, _ = shard_dataset(tpu_sgd.make_mesh(devices=jax.devices()[:4]),
                             X, y)
    assert Xd is X


@pytest.mark.parametrize("layout", ["one device", "other devices",
                                    "sharded by columns"])
def test_a_device_array_laid_out_otherwise_is_re_laid_without_the_host(
        mesh, host, layout):
    if layout == "one device":
        X, y = jnp.asarray(host[0]), jnp.asarray(host[1])
    elif layout == "other devices":
        other = tpu_sgd.data_mesh(jax.devices()[4:8])
        X, y, _ = shard_dataset(other, *host)
    else:
        X = jax.device_put(host[0], NamedSharding(mesh, P(None, "data")))
        y = jax.device_put(host[1], NamedSharding(mesh, P()))
    with jax.transfer_guard_device_to_host("disallow"), \
            jax.transfer_guard_host_to_device("disallow"):
        Xd, yd, valid = shard_dataset(mesh, X, y)
    assert valid is None and Xd is not X
    assert Xd.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", None)), 2)
    assert yd.sharding.is_equivalent_to(NamedSharding(mesh, P("data")), 1)
    np.testing.assert_array_equal(np.asarray(Xd), host[0])
    np.testing.assert_array_equal(np.asarray(yd), host[1])


def test_device_rows_that_do_not_divide_are_padded_and_masked(mesh, host):
    X, y = jnp.asarray(host[0][:4094]), jnp.asarray(host[1][:4094])
    Xd, yd, valid = shard_dataset(mesh, X, y)
    Xh, yh, validh = shard_dataset(mesh, host[0][:4094], host[1][:4094])
    assert Xd.shape == Xh.shape == (4096, D)
    for got, want in ((Xd, Xh), (yd, yh), (valid, validh)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)


def test_a_host_array_goes_the_way_it_went(mesh, host):
    Xd, yd, valid = shard_dataset(mesh, *host)
    assert valid is None and isinstance(Xd, jax.Array)
    assert [s.data.shape for s in Xd.addressable_shards] == [(N // 4, D)] * 4
    assert [s.device for s in Xd.addressable_shards] == list(
        mesh.devices.flat)
    np.testing.assert_array_equal(np.asarray(Xd), host[0])
    _, _, valid = shard_dataset(mesh, host[0][:4094], host[1][:4094])
    assert np.asarray(valid).sum() == 4094


# -- (b) the fit ------------------------------------------------------------------

def test_a_fit_on_pre_sharded_arrays_is_the_fit_from_host_arrays(mesh, host):
    """Bit for bit; and the second fit compiles nothing and moves nothing."""
    w0 = np.zeros(D, np.float32)
    from_host = _opt(mesh).optimize_with_history(host, w0)
    Xd, yd, _ = shard_dataset(mesh, *host)
    opt = _opt(mesh)
    sink = Sink()
    enable_tracing(sink)
    try:
        first = opt.optimize_with_history((Xd, yd), w0)
        built = len(opt._run_cache)
        with jax.transfer_guard_device_to_host("allow"), \
                jax.transfer_guard_host_to_device("allow"):
            second = opt.optimize_with_history((Xd, yd), w0)
        _opt(mesh).optimize_with_history(
            (jnp.asarray(host[0]), jnp.asarray(host[1])), w0)
        _opt(mesh).optimize_with_history(host, w0)
    finally:
        disable_tracing()
    for got in (first, second):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(from_host[0]))
        np.testing.assert_array_equal(got[1], from_host[1])
    assert len(opt._run_cache) == built == 1
    # a dataset on ONE device is re-laid by the placement; host arrays are
    # laid out by the hand-off itself (``train.h2d``: every block to the
    # device that owns it), so the placement finds them in place too
    in_place, in_place_again, moved, from_host_ = sink.spans("train.place")
    for rec in (in_place, in_place_again, from_host_):
        assert (rec["bytes"], rec["in_place"], rec["shards"]) == (0, 1, 4)
    assert (moved["bytes"], moved["in_place"]) == (
        host[0].nbytes + host[1].nbytes, 0)
    runs = sink.spans("train.run")
    assert [r["shards"] for r in runs] == [4, 4, 4, 4]
    assert [r["path"] for r in runs] == ["mesh"] * 4
    assert [d["built"] for d in sink.spans("train.dispatch")] == [1, 0, 1, 1]


def test_the_observed_driver_trains_in_place_too(mesh, host):
    """The stepwise (listener) driver calls the same placement."""
    w0 = np.zeros(D, np.float32)
    Xd, yd, _ = shard_dataset(mesh, *host)
    from tpu_sgd.utils.events import CollectingListener

    seen = CollectingListener()
    opt = _opt(mesh, iterations=3).set_listener(seen)
    sink = Sink()
    enable_tracing(sink)
    try:
        w, losses = opt.optimize_with_history((Xd, yd), w0)
    finally:
        disable_tracing()
    place, = sink.spans("train.place")
    assert (place["bytes"], place["in_place"], place["shards"]) == (0, 1, 4)
    ref = _opt(mesh, iterations=3).optimize_with_history(host, w0)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref[0]), rtol=1e-6)
    assert len(losses) == 3 and len(seen.iterations) == 3


# -- (c) against the plain references --------------------------------------------

def _cell_config(**kw):
    cell = cells.Cell(CELL, overrides={"rows": N, "features": D,
                                       "num_iterations": 8, **kw})
    return cell


@pytest.mark.parametrize("gradient,updater,labels,step", [
    ("LeastSquaresGradient", "SimpleUpdater", "linear", 0.5),
    ("LogisticGradient", "SquaredL2Updater", "logistic", 2.0),
    ("HingeGradient", "L1Updater", "logistic", 0.5)])
def test_the_four_shard_fit_follows_the_data_parallel_reference(
        mesh, gradient, updater, labels, step):
    """The program's sharded fit and the reference's agree step by step only
    if every shard draws its own rows from the key folded with its index: the
    gaps are of the limits' order with the program's seed, far over with
    another, and over with the one-device reference's unfolded draws."""
    cell = _cell_config(gradient=gradient, updater=updater, labels=labels,
                        step_size=step, reg_param=0.01,
                        mini_batch_fraction=0.5)
    config = cell.config
    assert cell.reference.__file__.endswith("glm_dense_dp.py")
    X, y = cell.generator.make(config, cell.rows, 5)
    assert X.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", None)), 2)
    w0 = np.zeros(D, np.float32)
    w, losses = cell.entry.prepare(config, X, y, 42)()
    ref = cell.reference.fit(config, X, y, w0, 42)
    got = correct.readings(np.asarray(w), losses, *ref, w0)
    assert max(got.values()) < 5e-3, got
    other = cell.reference.fit(config, X, y, w0, 43)
    assert correct.readings(np.asarray(w), losses, *other, w0)[
        "loss_max_gap"] > 10 * max(got["loss_max_gap"], 1e-3)
    unfolded = glm_dense.fit(config, X, y, w0, 42)
    assert correct.readings(np.asarray(w), losses, *unfolded, w0)[
        "loss_max_gap"] > 10 * max(got["loss_max_gap"], 1e-3)


def test_the_one_shard_fit_follows_the_reference_on_one_shard(host):
    """A mesh of one device folds shard index 0 into the key: the
    data-parallel reference with one shard draws the same rows."""
    cell = _cell_config(mini_batch_fraction=0.5)
    config = dict(cell.config, as_run=dict(cell.config["as_run"],
                                           data_parallel=1))
    X, y = jnp.asarray(host[0]), jnp.asarray(host[1])  # float32 rows
    w0 = np.zeros(D, np.float32)
    opt = _opt(tpu_sgd.data_mesh(jax.devices()[:1]), step=1.0, reg=0.0)
    w, losses = opt.optimize_with_history((X, y), w0)
    ref = glm_dense_dp.fit(config, X, y, w0, 42)
    got = correct.readings(np.asarray(w), losses, *ref, w0)
    assert max(got.values()) < 1e-4, got
    # and not the one-device program's unfolded draws
    unfolded = glm_dense.fit(config, X, y, w0, 42)
    assert correct.readings(np.asarray(w), losses, *unfolded, w0)[
        "loss_max_gap"] > 1e-2


@pytest.mark.parametrize("shards", [1, 4])
def test_the_two_references_agree_where_the_contract_is_the_same(host,
                                                                 shards):
    """Full batch: no draw, so no shard index in any key; the sums over all
    shards are the sums over all rows."""
    cell = _cell_config(mini_batch_fraction=1.0)
    config = dict(cell.config, as_run=dict(cell.config["as_run"],
                                           data_parallel=shards))
    w0 = np.zeros(D, np.float32)
    a = glm_dense_dp.fit(config, *host, w0, 42)
    b = glm_dense.fit(config, *host, w0, 42)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(a[1], b[1], rtol=1e-6)


def test_the_reference_draws_each_shards_rows_from_its_own_key():
    key = jax.random.PRNGKey(42)
    mask = np.asarray(glm_dense_dp.draws(key, 3, 4, 256, 0.25))
    assert mask.shape == (1024,)
    for s in range(4):
        own = jax.random.bernoulli(
            jax.random.fold_in(jax.random.fold_in(key, 3), s), 0.25, (256,))
        np.testing.assert_array_equal(mask[256 * s:256 * (s + 1)],
                                      np.asarray(own))
    with pytest.raises(ValueError, match="do not divide"):
        glm_dense_dp.fit({"as_run": {"data_parallel": 4}}, np.zeros((6, 2)),
                         np.zeros(6), np.zeros(2), 1)


# -- the cell's other files -------------------------------------------------------

def test_the_generator_makes_each_chips_rows_on_that_chip():
    cell = _cell_config()
    X, y = cell.generator.make(cell.config, N, 3)
    assert X.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    assert [s.device for s in X.addressable_shards] == jax.devices()[:4]
    assert [s.data.shape for s in X.addressable_shards] == [(N // 4, D)] * 4
    Xh = np.asarray(X, np.float32)
    # shards differ (each folds its index), one w_true serves them all
    assert not np.array_equal(Xh[:N // 4], Xh[N // 4:N // 2])
    w, *_ = np.linalg.lstsq(Xh, np.asarray(y), rcond=None)
    for s in range(4):
        rows = slice(s * N // 4, (s + 1) * N // 4)
        assert np.std(Xh[rows] @ w - np.asarray(y)[rows]) < 0.15
    again, _ = cell.generator.make(cell.config, N, 3)
    other, _ = cell.generator.make(cell.config, N, 4)
    assert np.array_equal(np.asarray(again, np.float32), Xh)
    assert not np.array_equal(np.asarray(other, np.float32), Xh)
    with pytest.raises(ValueError, match="shards"):
        cell.generator.make(cell.config, N + 2, 3)


def test_work_is_one_chips_share_and_the_cap_is_the_whole_datasets():
    cell = cells.Cell(CELL)
    assert cell.chips == 4 and cell.rows == 10_000_000
    assert cell.work.dataset_bytes(cell.config, cell.rows) == 20_000_000_000
    work = cell.work.step_work(cell.config, cell.rows)
    assert work["least"]["bytes"] == 250_000 * 1000 * 2 + 250_000 * 4
    assert work["as_laid_out"]["bytes"] == 2_500_000 * 1000 * 2 \
        + 3 * 2_500_000 * 4
    one_chip = cells.Cell("dense1000-logistic.resident").work.step_work(
        dict(cell.config), cell.rows // 4)
    assert work["least"] == one_chip["least"]


def test_the_entry_refuses_a_placement_that_moves_the_dataset(monkeypatch,
                                                              mesh):
    """What the parent of PR 28 did: fetch to the host, send back."""
    cell = _cell_config()
    X, y = cell.generator.make(cell.config, N, 3)
    real = tpu_sgd.parallel.shard_dataset
    monkeypatch.setattr(
        tpu_sgd.parallel, "shard_dataset",
        lambda mesh, X, y: real(mesh, np.asarray(X), np.asarray(y)))
    with pytest.raises(RuntimeError, match="moves a dataset"):
        cell.entry.prepare(cell.config, X, y, 42)
    monkeypatch.undo()
    assert callable(cell.entry.prepare(cell.config, X, y, 42))
