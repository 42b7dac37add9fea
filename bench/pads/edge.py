"""'same' padding by repeating the edge (``pad_value="edge"``): a stage's
output has its input's extent, and reads past the volume see the
nearest voxel inside it."""
import jax.numpy as jnp


def extent(n: int, r: int):
    """(output length, offset of output index 0 in the input) of an axis
    of length ``n`` under a stencil of radius ``r``."""
    return n, 0


def rows(v, lo, first, count: int, n_in: int):
    """Input rows ``first … first + count`` (global, unclipped) from
    ``v``, whose row 0 is global row ``lo``; rows outside the input
    repeat its edge."""
    z = jnp.clip(jnp.arange(count, dtype=jnp.int32) + first, 0, n_in - 1)
    return jnp.take(v, z - lo, axis=0)


def plane(v, r: int):
    """``v`` padded by ``r`` on both in-plane axes (1 and 2)."""
    pad = [(0, 0), (r, r), (r, r)] + [(0, 0)] * (v.ndim - 3)
    return jnp.pad(v, pad, mode="edge")
