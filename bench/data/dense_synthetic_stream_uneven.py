"""A stream of dense micro-batches of UNEQUAL sizes for a logistic model:
``dense_synthetic_stream``'s rows (bf16 standard normal, ONE ``w_true``
uniform(-1, 1) for the whole stream, made on the device a chunk at a time and
fetched to ONE Fortran-ordered host array as they are made) with
``dense_synthetic``'s logistic labels, Bernoulli(sigmoid(x . w_true)) from the
bf16-rounded ``X``; chunk ``k`` draws its rows and labels from the seed's key
folded with ``k``.

The chunks the rows are MADE in (``generator_rows``: one shape, one program)
are not the micro-batches they ARRIVE in.  :func:`boundaries` cuts the pass
into the configuration's ``micro_batches`` ranges as ISSUE 52 draws them: all
but the last size uniform on the integers ``micro_batch_rows_min`` ..
``micro_batch_rows_max`` (1,048,576..2,097,152) at a granularity of ONE row,
the last one what is left, the draw made again until that lies in the range
too: no two runs share a size, so a program compiled for a row count is cold
in every run.  The sizes are a pure function of the generated data (a 64-bit
seed hashed from the bytes of the stream's first row, which the run's data
seed made), so the entry and the reference, which the harness hands the same
``(X, y)`` and no data seed, cut the same ranges without sharing any state.
Where every pass is a stream of its own its first copy and last fit lie
bare and their sizes are drawn, so ``rows_per_s`` follows the draw from run to
run (4.5 to 7% between the quartiles of six seeds on the chip: PERF.md, PR
52): the cell's entry runs the passes as ONE stream."""

import functools
import hashlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.data.dense_synthetic_stream import FETCHERS, HostRows, _fetch


def boundaries(config: dict, X) -> list:
    """``[(a, b), ...]``: the micro-batches' row ranges of the pass ``X``, in
    order; the same for every caller that holds the same rows."""
    rows, count = X.shape[0], int(config["micro_batches"])
    lo, hi = (int(config[f"micro_batch_rows_{end}"]) for end in ("min", "max"))
    if rows * 2 != count * (lo + hi):
        # a pass of another length than the configuration's (a test that
        # hands the entry half the rows) keeps the range about its own mean
        lo, hi = (-(-2 * rows * lo // (count * (lo + hi))),
                  2 * rows * hi // (count * (lo + hi)))
    first = np.ascontiguousarray(np.asarray(X[:1])).view(np.uint8)
    seed = int.from_bytes(
        hashlib.blake2b(first.tobytes(), digest_size=8).digest(), "little")
    rng = np.random.default_rng(seed)
    while True:
        sizes = [int(n) for n in rng.integers(lo, hi + 1, size=count - 1)]
        sizes.append(rows - sum(sizes))
        if lo <= sizes[-1] <= hi:
            break
    ends = np.cumsum(sizes)
    return [(int(b - s), int(b)) for s, b in zip(sizes, ends)]


@functools.lru_cache(maxsize=None)
def generator(m: int, d: int, dtype):
    """Jitted ``(key, k) -> (X (m, d), y (m,) f32 in {0, 1})``: chunk ``k``."""

    @jax.jit
    def gen(key, k):
        kw, kb = jax.random.split(key)
        w = jax.random.uniform(kw, (d,), jnp.float32, -1.0, 1.0)
        kx, ky = jax.random.split(jax.random.fold_in(kb, k))
        X = jax.random.normal(kx, (m, d), dtype)
        margin = jnp.dot(X, w.astype(dtype),
                         preferred_element_type=jnp.float32)
        y = jax.random.uniform(ky, (m,)) < jax.nn.sigmoid(margin)
        return X, y.astype(jnp.float32)

    return gen


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the HOST, made in chunks of ``generator_rows`` rows (the
    last one cut where the rows are no multiple of them)."""
    m, d = min(int(config["generator_rows"]), rows), int(config["features"])
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
