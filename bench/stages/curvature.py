"""``curvature``: Gaussian curvature K = det(H) / (1 + |∇f|²)² of the raw
volume, with ∇f from central differences and H from central second
differences (mixed ones as differences of the first)."""
import jax.numpy as jnp

from bench.reference import tap


def radius(kw) -> int:
    return 1


def channels(c_in: int, kw) -> int:
    return c_in


def ops(kw, c_in: int) -> int:
    """3 first differences (2 each), 3 second differences on the axes (4
    each), 3 mixed ones (2 each), the 3×3 determinant (14) and the
    denominator with its square and the division (9)."""
    return c_in * (3 * 2 + 3 * 4 + 3 * 2 + 14 + 9)


def _e(*pairs):
    d = [0, 0, 0]
    for axis, s in pairs:
        d[axis] = s
    return d


def apply(vp, r: int, kw, dtype):
    f = lambda *pairs: tap(vp, r, *_e(*pairs))  # noqa: E731
    g = [0.5 * (f((a, 1)) - f((a, -1))) for a in range(3)]
    two, w = jnp.asarray(2.0, dtype), f()
    h = {(a, a): f((a, 1)) + f((a, -1)) - two * w for a in range(3)}
    for i, j in ((0, 1), (0, 2), (1, 2)):
        # d_j of the central difference along i
        gp = 0.5 * (f((i, 1), (j, 1)) - f((i, -1), (j, 1)))
        gm = 0.5 * (f((i, 1), (j, -1)) - f((i, -1), (j, -1)))
        h[i, j] = 0.5 * (gp - gm)
    a, b, c = h[0, 0], h[0, 1], h[0, 2]
    d, e, f_ = h[1, 1], h[1, 2], h[2, 2]
    det = a * (d * f_ - e * e) - b * (b * f_ - e * c) + c * (b * e - d * c)
    den = jnp.asarray(1.0, dtype) + g[0] * g[0] + g[1] * g[1] + g[2] * g[2]
    return det / (den * den)
