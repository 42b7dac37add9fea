"""The plain reference: a graph's semantics in straightforward jax.numpy.

It imports nothing of the program.  A graph is a list of stages
``(op, kwargs)``; each stage's semantics is ``bench/stages/<op>.py``:

- an array stage has ``radius(kw)`` and ``apply(vp, r, kw, dtype)``,
  which computes its output from ``vp``, its input padded by ``r`` on
  every axis, as plain stencil arithmetic (:func:`tap`);
- a reducing stage, last in a graph, has ``reduce(rows)`` per block and
  ``merge(parts)`` over blocks.

Each array stage pads its own input, stage by stage, as
``bench/pads/<pad>.py`` says (the stage's ``padding``, or the run's
``pad_value`` where that is 'same').  Volumes are processed in blocks of
output slices, each block's input made again from the seed with its halo
(``bench.data.make_rows``), so the reference never holds more than a
block and runs after the program's state is freed.  ``dtype`` is the
precision of the array stages: float32 for the reference, bfloat16 for
the control that must fail the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import data, manifest

__all__ = ["tap", "linear", "out_shape", "block_rows", "block_fn",
           "array_blocks", "reduced"]


def tap(vp, r: int, dz: int = 0, dy: int = 0, dx: int = 0):
    """The input at offset (dz, dy, dx) for every output voxel, from
    ``vp``, the input padded by ``r`` on each of the first three axes."""
    n = [vp.shape[a] - 2 * r for a in range(3)]
    return vp[r + dz:r + dz + n[0], r + dy:r + dy + n[1],
              r + dx:r + dx + n[2]]


def _stage(op: str):
    return manifest.module("stages", op)


def linear(graph) -> list:
    """The graph's array stages, in order."""
    return [(op, kw) for op, kw in graph if hasattr(_stage(op), "apply")]


def _plan(graph, shape, pad_value):
    """Per array stage: (module, kw, pad module, r, offset, input shape)."""
    out, shp = [], tuple(shape)
    for op, kw in linear(graph):
        mod = _stage(op)
        pad = manifest.module("pads", manifest.pad_of(kw, pad_value))
        r = int(mod.radius(kw))
        ext = [pad.extent(n, r) for n in shp]
        out.append((mod, kw, pad, r, ext[0][1], shp))
        shp = tuple(n for n, _ in ext)
    return out, shp


def out_shape(graph, shape, pad_value) -> tuple:
    """The shape (without channels) of the graph's last array output."""
    return _plan(graph, shape, pad_value)[1]


def block_rows(shape: tuple, budget: int = 1 << 25) -> int:
    """The largest divisor of the slice count whose block holds at most
    ``budget`` voxels (one slice at the least), so every block has one
    shape and compiles once."""
    Z, per = shape[0], 1
    for n in shape[1:]:
        per *= int(n)
    best = 1
    for n in range(1, Z + 1):
        if Z % n == 0 and n * per <= budget:
            best = n
    return best


def _freeze(graph):
    return tuple((op, tuple(sorted(kw.items()))) for op, kw in graph)


@functools.lru_cache(maxsize=None)
def block_fn(graph: tuple, shape: tuple, pad_value: str, n: int,
             dtype_name: str):
    """``(x_ext, z0) -> rows [z0, z0 + n) of the graph's last array
    output``, where ``x_ext`` holds the input's rows from ``z0 +
    first_row(...)`` on (see :func:`_reach`), clamped to the volume."""
    dtype = jnp.dtype(dtype_name)
    stages, _ = _plan([(op, dict(kw)) for op, kw in graph], shape, pad_value)
    firsts, counts = _reach(stages, n)

    def f(x_ext, z0):
        v, lo = x_ext.astype(dtype), z0 + firsts[0]
        for k, (mod, kw, pad, r, off, shp) in enumerate(stages):
            a = z0 + firsts[k + 1]
            vp = pad.plane(pad.rows(v, lo, a + off - r, counts[k + 1] + 2 * r,
                                    shp[0]), r)
            v, lo = mod.apply(vp, r, kw, dtype), a
        return v.astype(jnp.float32)

    return jax.jit(f)


def _reach(stages, n):
    """Rows each stage's output is computed at, relative to the block's
    first output row: ``firsts[k]``, ``counts[k]`` for the input (k = 0)
    and each stage's output (k ≥ 1)."""
    firsts, counts = [0], [n]
    for mod, kw, pad, r, off, shp in reversed(stages):
        firsts.insert(0, firsts[0] + off - r)
        counts.insert(0, counts[0] + 2 * r)
    return firsts, counts


def array_blocks(cfg, shape, key, graph, pad_value, dtype=jnp.float32,
                 devices=None):
    """The graph's last array output, block by block: ``(z0, n, rows)``,
    the blocks spread round robin over ``devices``."""
    stages, out = _plan(graph, shape, pad_value)
    n = block_rows(out)
    firsts, counts = _reach(stages, n)
    f = block_fn(_freeze(graph), tuple(shape), pad_value, n,
                 jnp.dtype(dtype).name)
    for i, z0 in enumerate(range(0, out[0], n)):
        dev = devices[i % len(devices)] if devices else None
        x_ext = data.make_rows(cfg, shape, key, z0 + firsts[0], counts[0],
                               device=dev)
        z = jnp.int32(z0) if dev is None else jax.device_put(jnp.int32(z0),
                                                              dev)
        yield z0, n, f(x_ext, z)


def reduced(cfg, shape, key, graph, pad_value, dtype=jnp.float32,
            devices=None):
    """The graph's reducing last stage over its array output, merged."""
    op, kw = graph[-1]
    mod = _stage(op)
    parts = [mod.reduce(rows) for _, _, rows in
             array_blocks(cfg, shape, key, graph, pad_value, dtype, devices)]
    return mod.merge(jax.device_get(parts))
