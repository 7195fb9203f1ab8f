"""A stream of dense micro-batches made from the seed, one micro-batch at a
time on the device in the type it is trained in and fetched to the host as
it is made, so that the device never holds the stream: ``X`` bf16 standard
normal, ONE ``w_true`` uniform(-1, 1) for the whole stream, targets
``x . w_true + EPS * noise`` from the bf16-rounded ``X``
(``dense_synthetic``'s recipe).  Micro-batch ``k`` draws its rows and its
noise from the seed's key folded with ``k``: a stationary stream.

The stream is ONE host array, Fortran-ordered (what the fetch of a device
array that the chip stores feature-major gives; a micro-batch is a range of
its rows), handed over as ``HostRows``: the harness's ``place`` takes it as it
takes a device array."""

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.data.dense_synthetic import EPS


class HostRows(np.ndarray):
    """A numpy array with the one method of a device array that
    ``bench.harness.place`` calls after ``np.asarray`` (which gives the
    plain array, a view): nothing is held on the device to delete."""

    def delete(self):
        pass


@functools.lru_cache(maxsize=None)
def generator(m: int, d: int, dtype):
    """Jitted ``(key, k) -> (X (m, d), y (m,) f32)``: micro-batch ``k``."""

    @jax.jit
    def gen(key, k):
        kw, kb = jax.random.split(key)
        w = jax.random.uniform(kw, (d,), jnp.float32, -1.0, 1.0)
        kx, ky = jax.random.split(jax.random.fold_in(kb, k))
        X = jax.random.normal(kx, (m, d), dtype)
        margin = jnp.dot(X, w.astype(dtype),
                         preferred_element_type=jnp.float32)
        return X, margin + EPS * jax.random.normal(ky, (m,), jnp.float32)

    return gen


#: a micro-batch is fetched by this many threads, each a few columns at a
#: time: the host's part of a fetch is the first touch of fresh pages (4.8 s
#: for 4.19 GB on one thread, twice: the runtime's buffer and the stream's
#: array; my chip run, PR 40), which threads take side by side.  The columns
#: in flight are device arrays beside the micro-batch: 6 x 16 of 2,097,152
#: rows are 0.40 GB, so set-up peaks at 4.6 GB, under what a fold in turn
#: holds (4.73 GB), and ``memory_peak_bytes`` stays the fold's own
FETCHERS, COLUMNS = 6, 16


def _fetch(Xk, out, pool) -> None:
    """The device array ``Xk`` into the host array ``out`` (as many rows,
    Fortran-ordered); a column of a feature-major array is one piece on the
    device and in ``out``."""
    def columns(j):
        out[:, j:j + COLUMNS] = np.asarray(Xk[:out.shape[0], j:j + COLUMNS])

    list(pool.map(columns, range(0, out.shape[1], COLUMNS)))


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the HOST; the last micro-batch is cut where the rows
    are no multiple of the configuration's ``micro_batch_rows``."""
    m, d = min(int(config["micro_batch_rows"]), rows), int(config["features"])
    dtype = jnp.dtype(config["x_dtype"])  # bfloat16: the ml_dtypes type
    gen = generator(m, d, dtype)
    key = jax.random.PRNGKey(seed)
    X = np.empty((rows, d), dtype, order="F").view(HostRows)
    y = np.empty((rows,), np.float32).view(HostRows)
    with ThreadPoolExecutor(FETCHERS) as pool:
        for k, a in enumerate(range(0, rows, m)):
            Xk, yk = gen(key, k)
            b = min(a + m, rows)
            _fetch(Xk, X[a:b], pool)
            y[a:b] = np.asarray(yk)[:b - a]
            Xk.delete()
            yk.delete()
    return X, y
