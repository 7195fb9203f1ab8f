"""Dense 8-bit integer rows with one of K class labels, made on the device
from the seed in the bytes they are trained in: ``q = clip(round(64 z), -128,
127)`` as int8, ``z`` standard normal (a pixel less 128 has about that spread;
the source's pixels cannot be fetched), ``W_true`` a ``(K-1, d)`` matrix drawn
as ``dense_synthetic_classes`` draws it, labels drawn from the softmax of
``[0, (q / 64) . W_true]`` (the pivot class 0 has the zero logit, as in
MLlib's multinomial ``LogisticGradient``): the rows at the scale the labels
were made at are ``x = q / 64``, and a fit on ``q`` at the step size and the
regulariser the configuration derives is the fit on ``x``, step for step.

One program, in ROW BLOCKS written in place into the one ``(n, d)`` int8
array, as ``dense_synthetic_classes`` does; the block is small (16,384 rows:
a block's float32 draw is 201 MB at 3,072 features) so that set-up does not
set the process's peak beside 12.29 GB of rows.  Block ``b`` draws its rows
and labels from the seed's key folded with ``b``; the last block starts at
``n - block`` and overwrites what it overlaps, so every block has one
shape."""

import functools

import jax
import jax.numpy as jnp

#: rows made at a time
BLOCK_ROWS = 1 << 14
#: ``q = round(SCALE z)``: 64 keeps two standard deviations inside int8
SCALE = 64.0
#: ``W_true`` is uniform(-SPREAD, SPREAD) / sqrt(d), as
#: ``dense_synthetic_classes``: the logits of ``x = q / SCALE`` have a
#: standard deviation of about SPREAD / sqrt(3) whatever the width
SPREAD = 4.0


@functools.lru_cache(maxsize=None)
def generator(n: int, d: int, classes: int, block: int = BLOCK_ROWS):
    """Jitted ``key -> (X (n, d) int8, y (n,) f32 in 0 .. classes - 1)``."""
    block = min(block, n)
    blocks = -(-n // block)

    @jax.jit
    def gen(key):
        kw, kb = jax.random.split(key)
        W = jax.random.uniform(kw, (classes - 1, d), jnp.float32,
                               -SPREAD, SPREAD) / jnp.sqrt(float(d))

        def body(b, carry):
            X, y = carry
            kx, ky = jax.random.split(jax.random.fold_in(kb, b))
            start = jnp.minimum(b * block, n - block)
            z = jax.random.normal(kx, (block, d), jnp.float32)
            qb = jnp.clip(jnp.round(SCALE * z), -128, 127).astype(jnp.int8)
            # every int8 is exact in bf16; W_true rounded as the bf16
            # configuration's generator rounds it
            margins = jnp.dot(qb.astype(jnp.bfloat16),
                              W.T.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32) / SCALE
            logits = jnp.concatenate(
                [jnp.zeros((block, 1), jnp.float32), margins], axis=1)
            yb = jax.random.categorical(ky, logits, axis=1)
            return (jax.lax.dynamic_update_slice_in_dim(X, qb, start, 0),
                    jax.lax.dynamic_update_slice_in_dim(
                        y, yb.astype(jnp.float32), start, 0))

        return jax.lax.fori_loop(
            0, blocks, body,
            (jnp.zeros((n, d), jnp.int8), jnp.zeros((n,), jnp.float32)))

    return gen


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the first device, one program."""
    if config["x_dtype"] != "int8":
        raise ValueError("dense_int8_classes makes int8 rows; the "
                         f"configuration states {config['x_dtype']}")
    gen = generator(rows, int(config["features"]), int(config["classes"]))
    return jax.block_until_ready(gen(jax.random.PRNGKey(seed)))
