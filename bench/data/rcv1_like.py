"""``(X: BCOO, y)`` shaped like RCV1 (copied from ``chip_smoke.make_rcv1_like``,
PR 23, itself the recipe of ``tpu_sgd.utils.rcv1_like_data``): a fixed number
of stored entries in every row, Zipf feature popularity sampled without
replacement (Gumbel-top-k, the n*d part, one device program), log-normal
values on unit-L2 rows, labels from a sparse linear model split at the median
margin (the n*nnz part, numpy)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.sparse import BCOO

ZIPF_EXPONENT = 0.9


def columns_generator(n: int, d: int, nnz: int):
    """Jitted ``key -> (n_chunks, chunk, nnz)`` int32 feature columns."""
    chunk = max(1, min(n, (1 << 28) // (4 * d)))  # <= 256 MB of keys
    n_chunks = -(-n // chunk)

    @jax.jit
    def gen(key):
        log_pop = -ZIPF_EXPONENT * jnp.log(
            jnp.arange(1, d + 1, dtype=jnp.float32))

        def rows(k):
            keys = log_pop[None, :] + jax.random.gumbel(k, (chunk, d))
            return jax.lax.approx_max_k(keys, nnz)[1].astype(jnp.int32)

        return jax.lax.map(rows, jax.random.split(key, n_chunks))

    return gen


def make(config: dict, rows: int, seed: int):
    n, d, nnz = rows, int(config["features"]), int(config["nnz_per_row"])
    cols = np.asarray(columns_generator(n, d, nnz)(jax.random.PRNGKey(seed)))
    cols = np.sort(cols.reshape(-1, nnz)[:n], axis=1)
    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, d + 1) ** ZIPF_EXPONENT
    w = np.zeros((d,), np.float32)
    active = rng.choice(d, size=max(8, d // 100), replace=False,
                        p=pop / pop.sum())
    w[active] = rng.normal(scale=1.5, size=active.shape).astype(np.float32)
    vals = rng.lognormal(mean=0.0, sigma=0.5,
                         size=(n, nnz)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    margins = np.einsum("ij,ij->i", vals, w[cols])
    y = (margins + 0.05 * rng.normal(size=n) > np.median(margins)).astype(
        np.float32)
    idx = np.empty((n * nnz, 2), np.int32)
    idx[:, 0] = np.repeat(np.arange(n, dtype=np.int32), nnz)
    idx[:, 1] = cols.reshape(-1)
    X = BCOO((jnp.asarray(vals.reshape(-1)), jnp.asarray(idx)),
             shape=(n, d), indices_sorted=True, unique_indices=True)
    return jax.block_until_ready(X), y
