"""Dense rows made on the device from the seed, in the type they are trained
in: ``X`` bf16 standard normal, ``w_true`` uniform, targets computed from the
bf16-rounded ``X`` (copied from ``chip_smoke.dense_generator``, PR 23)."""

import jax
import jax.numpy as jnp

#: the noise level of least-squares targets
EPS = 0.1


def generator(n: int, d: int, dtype, labels: str):
    """Jitted ``key -> (X (n, d), y (n,) f32)``; ``labels`` is ``"logistic"``
    (Bernoulli(sigmoid(x.w))) or ``"linear"`` (x.w + EPS * noise)."""
    if labels not in ("logistic", "linear"):
        raise ValueError(f"labels must be 'logistic' or 'linear', got {labels!r}")

    @jax.jit
    def gen(key):
        kx, kw, ky = jax.random.split(key, 3)
        X = jax.random.normal(kx, (n, d), dtype)
        w = jax.random.uniform(kw, (d,), jnp.float32, -1.0, 1.0)
        margin = jnp.dot(X, w.astype(dtype),
                         preferred_element_type=jnp.float32)
        if labels == "logistic":
            y = (jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(margin))
            return X, y.astype(jnp.float32)
        return X, margin + EPS * jax.random.normal(ky, (n,), jnp.float32)

    return gen


def make(config: dict, rows: int, seed: int):
    """``(X, y)`` on the first device, one program."""
    gen = generator(rows, int(config["features"]),
                    jnp.dtype(config["x_dtype"]), config["labels"])
    return jax.block_until_ready(gen(jax.random.PRNGKey(seed)))
