"""``moments(order=2)``: count, mean and variance per channel over every
voxel, per block on the device and merged over blocks in float64 on the
host (Chan's merge)."""
import jax
import jax.numpy as jnp
import numpy as np


def radius(kw) -> int:
    return 0


def channels(c_in: int, kw) -> int:
    return c_in


def ops(kw, c_in: int) -> int:
    """A subtraction, a multiply and two adds per channel value."""
    return 4 * c_in


def out_bytes(voxels: int, c_in: int, kw) -> int:
    """The state: count, mean and M2..M4 per channel, float32."""
    return 5 * 4 * c_in


@jax.jit
def reduce(v):
    """(count, mean, M2) per channel of one block, channels last."""
    c = v.reshape(-1, v.shape[-1]) if v.ndim == 4 else v.reshape(-1, 1)
    mean = jnp.mean(c, axis=0)
    return c.shape[0] * jnp.ones_like(mean), mean, jnp.sum(
        (c - mean) ** 2, axis=0)


def merge(parts) -> tuple:
    """Chan's merge of per-block (count, mean, M2) in float64 → (count,
    mean, variance)."""
    n = mean = m2 = None
    for c, mu, s in parts:
        c, mu, s = (np.asarray(a, np.float64) for a in (c, mu, s))
        if n is None:
            n, mean, m2 = c, mu, s
            continue
        tot = n + c
        d = mu - mean
        mean = mean + d * c / tot
        m2 = m2 + s + d * d * n * c / tot
        n = tot
    return n, mean, m2 / n
