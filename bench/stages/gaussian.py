"""``gaussian(sigma)``: the normalized isotropic Gaussian over a box of
±max(1, ceil(2·sigma)) voxels per axis, applied as three 1-D passes (the
box and the isotropic kernel factor exactly)."""
import math

import jax.numpy as jnp
import numpy as np


def radius(kw) -> int:
    return max(1, math.ceil(2.0 * float(kw["sigma"])))


def channels(c_in: int, kw) -> int:
    return c_in


def ops(kw, c_in: int) -> int:
    """A multiply and an add per tap, 2r+1 taps along each of 3 axes."""
    return c_in * 3 * 2 * (2 * radius(kw) + 1)


def taps(kw) -> np.ndarray:
    sigma, r = float(kw["sigma"]), radius(kw)
    t = np.arange(-r, r + 1, dtype=np.float64)
    g = np.exp(-0.5 * t * t / (sigma * sigma))
    return g / g.sum()


def apply(vp, r: int, kw, dtype):
    """The stage over ``vp``, its input padded by ``r`` on every axis."""
    g = [jnp.asarray(w, dtype) for w in taps(kw)]
    out = vp
    for axis in range(3):
        n = out.shape[axis] - 2 * r
        out = sum(g[i] * _slice(out, axis, i, n) for i in range(len(g)))
    return out


def _slice(v, axis, start, n):
    idx = [slice(None)] * v.ndim
    idx[axis] = slice(start, start + n)
    return v[tuple(idx)]
