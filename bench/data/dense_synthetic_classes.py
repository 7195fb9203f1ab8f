"""Dense rows with one of K class labels, made on the device from the seed in
the type they are trained in: ``X`` bf16 standard normal, ``W_true`` a
``(K-1, d)`` matrix, labels drawn from the softmax of ``[0, x . W_true]`` (the
pivot class 0 has the zero logit, as in MLlib's multinomial
``LogisticGradient``) computed from the bf16-rounded ``X``.

One program, in ROW BLOCKS written in place into the one ``(n, d)`` array: a
block's ``(rows, K)`` logits and noise are small beside it, where those of all
8,100,000 rows at once do not fit beside 12.7 GB of X on a 16 GB chip.  Block
``b`` draws its rows and labels from the seed's key folded with ``b``; the
last block starts at ``n - block`` and overwrites what it overlaps, so every
block has one shape."""

import functools

import jax
import jax.numpy as jnp

#: rows made at a time
BLOCK_ROWS = 1 << 18
#: ``W_true`` is uniform(-SPREAD, SPREAD) / sqrt(d): a row's logits have a
#: standard deviation of SPREAD / sqrt(3) whatever the width, so the classes
#: overlap (the best classifier is wrong on some rows) and no class is empty
SPREAD = 4.0


@functools.lru_cache(maxsize=None)
def generator(n: int, d: int, classes: int, dtype, block: int = BLOCK_ROWS):
    """Jitted ``key -> (X (n, d), y (n,) f32 in 0 .. classes - 1)``."""
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
            xb = jax.random.normal(kx, (block, d), dtype)
            margins = jnp.dot(xb, W.T.astype(dtype),
                              preferred_element_type=jnp.float32)
            logits = jnp.concatenate(
                [jnp.zeros((block, 1), jnp.float32), margins], axis=1)
            yb = jax.random.categorical(ky, logits, axis=1)
            return (jax.lax.dynamic_update_slice_in_dim(X, xb, start, 0),
                    jax.lax.dynamic_update_slice_in_dim(
                        y, yb.astype(jnp.float32), start, 0))

        return jax.lax.fori_loop(
            0, blocks, body,
            (jnp.zeros((n, d), dtype), jnp.zeros((n,), jnp.float32)))

    return gen


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the first device, one program."""
    gen = generator(rows, int(config["features"]), int(config["classes"]),
                    jnp.dtype(config["x_dtype"]))
    return jax.block_until_ready(gen(jax.random.PRNGKey(seed)))
