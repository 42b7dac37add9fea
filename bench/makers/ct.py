"""A CT phantom, after the repository's chip smoke: a body ellipse in
every slice with a denser rim, Gaussian noise, and Hounsfield units
rounded to int16 as a scanner stores them, read as float32."""
import jax
import jax.numpy as jnp


def rows(p: dict, shape: tuple, key, rows):
    """Rows ``rows`` (global slice indices, int32) of the volume."""
    _, Y, X = shape
    y = jnp.linspace(-1.0, 1.0, Y, dtype=jnp.float32)[:, None]
    x = jnp.linspace(-1.0, 1.0, X, dtype=jnp.float32)[None, :]
    noise = jax.vmap(lambda z: jax.random.normal(
        jax.random.fold_in(key, z), (Y, X), jnp.float32))(rows)
    r2 = (y / 0.85) ** 2 + (x / 0.75) ** 2
    body = jnp.where(r2 < 1.0, jnp.where(r2 > 0.8, p["rim"], p["inside"]),
                     p["outside"])
    v = body[None] + p["noise"] * noise
    return jnp.clip(jnp.round(v), p["lo"], p["hi"]).astype(
        jnp.int16).astype(jnp.float32)
