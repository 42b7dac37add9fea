"""``gradient``: central differences (f(+1) − f(−1)) / 2 along each axis,
one channel per axis, slices first."""
import jax.numpy as jnp

from bench.reference import tap


def radius(kw) -> int:
    return 1


def channels(c_in: int, kw) -> int:
    return 3 * c_in


def ops(kw, c_in: int) -> int:
    """A subtraction and a halving per axis."""
    return c_in * 3 * 2


def diff(vp, r: int, axis: int):
    """Central difference along ``axis`` of ``vp``, padded by ``r``."""
    e = [0, 0, 0]
    e[axis] = 1
    return 0.5 * (tap(vp, r, *e) - tap(vp, r, *(-d for d in e)))


def apply(vp, r: int, kw, dtype):
    return jnp.stack([diff(vp, r, a) for a in range(3)], axis=-1)
