"""``glm_dense_dp``'s plain data-parallel fit for rows that come from the
HOST: the reference puts the rows on the chips itself and runs that fit on
them (float32, matmuls at ``highest``, shard ``s`` drawing
``bernoulli(fold_in(fold_in(PRNGKey(seed), t), s), fraction)`` over its
rows).  No program code is imported.

The placement is plain too: shard ``s`` of ``as_run.data_parallel`` is rows
``[s n/S, (s + 1) n/S)`` of the host array, sent to device ``s`` by
``jax.device_put`` in row ranges of at most 1 GiB (a host array over 4 GiB is
copied at a sixtieth of the rate: PERF.md section 4), made one array on that
device, and the devices' arrays assembled by their sharding.  While a shard
is put together its device holds it twice; the program's arrays are gone by
then.  Arrays that are on the devices already are taken as they lie."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import glm_dense_dp

PIECE_BYTES = 1 << 30


def place(X, shards: int):
    """The host rows sharded by rows over the first ``shards`` devices."""
    devices = jax.devices()[:shards]
    if len(devices) != shards or X.shape[0] % shards:
        raise ValueError(f"{X.shape[0]} rows over {shards} shards need "
                         f"{shards} devices that divide them; there are "
                         f"{len(devices)}")
    local = X.shape[0] // shards
    step = max(1, PIECE_BYTES // max(1, X.nbytes // X.shape[0]))
    # the devices in turn, so that all of them receive at once
    pieces = [[] for _ in devices]
    for a in range(0, local, step):
        for s, device in enumerate(devices):
            lo = s * local + a
            pieces[s].append(jax.device_put(
                X[lo:min(lo + step, (s + 1) * local)], device))
    whole = []
    for mine in pieces:
        whole.append(mine[0] if len(mine) == 1 else jnp.concatenate(mine))
        if len(mine) > 1:
            for piece in mine:
                piece.delete()
    mesh = Mesh(np.asarray(devices), ("data",))
    return jax.make_array_from_single_device_arrays(
        X.shape, NamedSharding(mesh, P("data")), whole), mesh


def fit(config: dict, X, y, w0, seed: int, operands=None):
    """``(weights, loss history)`` as numpy: ``glm_dense_dp.fit`` on the
    rows placed as above."""
    if not isinstance(X, jax.Array):
        X, mesh = place(X, int(config["as_run"]["data_parallel"]))
        y = jax.device_put(np.asarray(y, np.float32),
                           NamedSharding(mesh, P("data")))
    return glm_dense_dp.fit(config, X, y, w0, seed, operands)
