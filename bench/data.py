"""Seeded synthetic volumes, made on the device in one jitted call.

Each maker is a pure function of a key and of the global row (slice)
indices it is asked for: row ``z`` draws its noise from
``fold_in(key, z)``.  So the benchmark makes a whole volume in one call,
and the reference makes any block of rows again, bit for bit, without
holding the volume.

The configuration's ``maker`` names its kind, whose ``rows(params,
shape, key, rows)`` in ``bench/makers/<kind>.py`` draws the rows; the
other keys of ``maker`` are its parameters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import manifest

__all__ = ["key_for", "shape_of", "make_rows", "make_volume"]


def key_for(seed: int, *path: int) -> jax.Array:
    """A raw threefry key from any whole-number seed (64-bit and negative
    seeds included) and a path of indices such as (study,) or
    (patient, modality)."""
    words = np.random.SeedSequence(
        [int(seed) % (1 << 64)] + [int(p) for p in path]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def shape_of(cfg: dict) -> tuple:
    """The volume's shape, slices first, from the configuration's axes."""
    return tuple(int(cfg[k]) for k in cfg["axes"])


def _rows(kind: str, params: tuple, shape: tuple, key, rows):
    """Rows ``rows`` (global slice indices, int32) of the volume, float32,
    drawn by ``bench/makers/<kind>.py``."""
    return manifest.module("makers", kind).rows(dict(params), shape, key,
                                                rows)


@functools.lru_cache(maxsize=None)
def _maker(kind: str, params: tuple, shape: tuple, n: int):
    def make(key, z0):
        rows = jnp.clip(jnp.arange(n, dtype=jnp.int32) + z0, 0, shape[0] - 1)
        return _rows(kind, params, shape, key, rows)

    return jax.jit(make)


def _params(cfg: dict) -> tuple:
    m = cfg["maker"]
    return tuple(sorted((k, v) for k, v in m.items() if k != "kind"))


def make_rows(cfg: dict, shape: tuple, key, z0, n: int, device=None):
    """``n`` rows from global row ``z0`` on, rows outside the volume
    clamped to its edge (so a block's halo needs no special case)."""
    f = _maker(cfg["maker"]["kind"], _params(cfg), tuple(shape), int(n))
    z0 = jnp.asarray(z0, jnp.int32)
    if device is not None:
        key, z0 = jax.device_put((key, z0), device)
    return f(key, z0)


def make_volume(cfg: dict, shape: tuple, key, device=None):
    """The whole volume, one jitted call, on ``device`` (else on the
    default device)."""
    return make_rows(cfg, shape, key, 0, shape[0], device=device)
