"""A Richtmyer–Meshkov mixing layer, after the LLNL simulation's density
field: a heavy fluid (high values) above a light one (low values),
joined across an interface perturbed by a few modes, with noise that is
strongest where the fluids mix, rounded to 0–255 as the published
``uint8`` volume stores it, and read as float32.

The interface's modes are drawn from the key alone, so every row of a
volume sees the same interface; row ``z``'s noise comes from
``fold_in(key, z)``.
"""
import jax
import jax.numpy as jnp

#: the fold of the key that draws the modes: no row has this index
_MODES = 0x7FFFFFFF


def _interface(p: dict, key, Y: int, X: int):
    """Height of the interface over the (row, col) plane, in slices as a
    share of the volume's depth: a sum of ``modes`` periodic waves."""
    m = int(p["modes"])
    ka, kw, kp = jax.random.split(jax.random.fold_in(key, _MODES), 3)
    amp = p["amplitude"] * jax.random.uniform(ka, (m,), jnp.float32,
                                              0.25, 1.0)
    waves = jax.random.randint(kw, (m, 2), 1, int(p["max_wave"]) + 1)
    phase = jax.random.uniform(kp, (m,), jnp.float32, 0.0, 2.0 * jnp.pi)
    y = jnp.arange(Y, dtype=jnp.float32)[:, None] / Y
    x = jnp.arange(X, dtype=jnp.float32)[None, :] / X
    h = jnp.zeros((Y, X), jnp.float32)
    for i in range(m):
        arg = 2.0 * jnp.pi * (waves[i, 0] * y + waves[i, 1] * x) + phase[i]
        h = h + amp[i] * jnp.sin(arg) / m
    return h


def rows(p: dict, shape: tuple, key, rows):
    """Rows ``rows`` (global slice indices, int32) of the volume."""
    Z, Y, X = shape
    h = _interface(p, key, Y, X)
    noise = jax.vmap(lambda z: jax.random.normal(
        jax.random.fold_in(key, z), (Y, X), jnp.float32))(rows)
    d = (rows.astype(jnp.float32)[:, None, None] / Z - p["center"]
         - h[None]) / p["width"]
    heavy = jax.nn.sigmoid(d)
    mix = 4.0 * heavy * (1.0 - heavy)  # 1 at the interface, 0 far off
    v = (p["light"] + (p["heavy"] - p["light"]) * heavy
         + p["noise"] * (0.25 + 0.75 * mix) * noise)
    return jnp.clip(jnp.round(v), 0.0, 255.0).astype(jnp.uint8).astype(
        jnp.float32)
