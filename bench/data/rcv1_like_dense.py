"""``(X, y)`` shaped like RCV1 DENSIFIED (BASELINE.json config 3 as it is
written: "RCV1 sparse->densified"): the rows of ``bench/data/rcv1_like.py``
for the same seed, entry for entry, written out dense in the type they are
trained in.

The sparse recipe makes the rows' stored entries (a few MB: 75 a row); this
module lays them into the one resident ``(n, d)`` array ON THE DEVICE in row
blocks: each entry of a block is the value of the row's stored entry in that
column, if it has one, else zero (a row's entries compared with the column's
index one after the other, in one fusion: on the chip 0.39 s for all of X,
and a program that compiles in 3 s where the scatter that does the same took
15 and held 1.5 GB of temporaries), rounded to X's type and written in place
into the array.  No host array of X's size, no float32 copy of it: at 131,072
x 47,236 bf16 the array is 12.38 GB of a 16 GB chip and a block 0.19 GB.  The
last block starts at ``n - block`` and overwrites what it overlaps, so every
block has one shape."""

import functools

import jax
import jax.numpy as jnp

from bench.data import rcv1_like

#: rows densified at a time
BLOCK_ROWS = 2048


@functools.lru_cache(maxsize=None)
def densifier(n: int, d: int, nnz: int, dtype, block: int = BLOCK_ROWS):
    """Jitted ``(values (n, nnz) f32, columns (n, nnz) int32) -> X (n, d)``."""
    block = min(block, n)
    blocks = -(-n // block)

    @jax.jit
    def dense(vals, cols):
        column = jax.lax.broadcasted_iota(jnp.int32, (block, d), 1)

        def body(b, X):
            start = jnp.minimum(b * block, n - block)
            v = jax.lax.dynamic_slice_in_dim(vals, start, block, 0)
            c = jax.lax.dynamic_slice_in_dim(cols, start, block, 0)
            xb = jnp.zeros((block, d), jnp.float32)
            for k in range(nnz):  # a row's columns are distinct
                xb = jnp.where(column == c[:, k:k + 1], v[:, k:k + 1], xb)
            return jax.lax.dynamic_update_slice_in_dim(
                X, xb.astype(dtype), start, 0)

        return jax.lax.fori_loop(0, blocks, body, jnp.zeros((n, d), dtype))

    return dense


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the first device: ``rcv1_like.make``'s rows and labels
    for this seed, the rows densified and rounded to ``x_dtype``."""
    sparse, y = rcv1_like.make(config, rows, seed)
    nnz = int(config["nnz_per_row"])
    vals = sparse.data.reshape(rows, nnz)
    cols = sparse.indices[:, 1].reshape(rows, nnz)
    del sparse
    X = densifier(rows, int(config["features"]), nnz,
                  jnp.dtype(config["x_dtype"]))(vals, cols)
    return jax.block_until_ready((X, jnp.asarray(y, jnp.float32)))
