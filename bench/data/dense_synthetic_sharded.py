"""Dense rows made SHARDED by rows over the configuration's chips, each
chip's rows on that chip: one program under ``shard_map`` in which shard ``s``
draws its rows and its noise from the seed's keys folded with ``s`` and every
shard draws the one ``w_true`` from the same key.  No chip holds more than its
own share and the temporaries of making it, and nothing goes through the host
(20 GB of rows have no single home).  Same distributions as
``dense_synthetic``: ``X`` bf16 standard normal, ``w_true`` uniform, targets
from the bf16-rounded ``X``."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bench.data.dense_synthetic import EPS

AXIS = "data"


def generator(mesh, n_local: int, d: int, dtype, labels: str):
    """Jitted ``key -> (X (shards * n_local, d), y f32)`` sharded by rows over
    ``mesh``; ``labels`` is ``"logistic"`` or ``"linear"`` as in
    ``dense_synthetic``."""
    if labels not in ("logistic", "linear"):
        raise ValueError(f"labels must be 'logistic' or 'linear', got {labels!r}")

    def local(key):
        kx, kw, ky = jax.random.split(key, 3)
        s = jax.lax.axis_index(AXIS)
        X = jax.random.normal(jax.random.fold_in(kx, s), (n_local, d), dtype)
        w = jax.random.uniform(kw, (d,), jnp.float32, -1.0, 1.0)
        margin = jnp.dot(X, w.astype(dtype),
                         preferred_element_type=jnp.float32)
        ky = jax.random.fold_in(ky, s)
        if labels == "logistic":
            y = jax.random.uniform(ky, (n_local,)) < jax.nn.sigmoid(margin)
            return X, y.astype(jnp.float32)
        return X, margin + EPS * jax.random.normal(ky, (n_local,),
                                                   jnp.float32)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=(P(AXIS, None), P(AXIS)),
        check_vma=False))


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` over the first ``as_run.data_parallel`` devices, one
    program."""
    shards = int(config["as_run"]["data_parallel"])
    devices = jax.devices()[:shards]
    if len(devices) != shards or rows % shards:
        raise ValueError(f"{rows} rows over {shards} shards need {shards} "
                         f"devices that divide them; there are {len(devices)}")
    gen = generator(Mesh(np.asarray(devices), (AXIS,)), rows // shards,
                    int(config["features"]), jnp.dtype(config["x_dtype"]),
                    config["labels"])
    return jax.block_until_ready(gen(jax.random.PRNGKey(seed)))
