"""The work a graph needs, from shapes alone.

``least_bytes`` is what any implementation must move through HBM for one
call: the input read once and the graph's output written once.  It does
not depend on how kernels implement the graph, so its share of the HBM
peak cannot pass 100%.

``ops`` counts floating-point operations per call at the graph's own
stencils, as each stage's ``ops(kw, channels)`` in
``bench/stages/<op>.py`` gives them per voxel.  It is reported beside
the byte count as operations per byte, with no roofline ratio: the
vector unit's float32 peak is not published.
"""
from __future__ import annotations

import math

from bench import manifest

__all__ = ["out_channels", "least_bytes", "ops"]


def _stage(op):
    return manifest.module("stages", op)


def out_channels(graph) -> int:
    """Channels of the graph's last stage (1 for a plain volume)."""
    c = 1
    for op, kw in graph:
        c = _stage(op).channels(c, kw)
    return c


def least_bytes(shape, graph, itemsize: int = 4) -> int:
    """Input read once plus output written once, in bytes.  A stage that
    reduces says its output's size (``out_bytes``); an array output is
    every voxel's channels."""
    vox = math.prod(shape)
    op, kw = graph[-1]
    last = _stage(op)
    if hasattr(last, "out_bytes"):
        out = last.out_bytes(vox, out_channels(graph[:-1]), kw)
    else:
        out = vox * out_channels(graph) * itemsize
    return vox * itemsize + out


def ops(shape, graph) -> int:
    """Floating-point operations for one call."""
    total, c = 0, 1
    for op, kw in graph:
        total += _stage(op).ops(kw, c)
        c = _stage(op).channels(c, kw)
    return math.prod(shape) * total
