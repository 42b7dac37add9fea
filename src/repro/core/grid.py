"""Quasi-grid shape algebra (paper §3.1, the ``f1`` component).

The *quasi-grid* maps the shape of an input tensor ``x`` under the action of
an operator tensor ``m`` (same rank) to the output grid shape ``s'`` — the
set of points at which the operator is evaluated.  Everything here is pure
Python/numpy shape math: no device arrays, usable at trace time.

Conventions
-----------
- ``padding='same'``   : global filtering — grid == x.shape (stride 1) and the
  input is virtually padded by the operator half-width (paper: "the requisite
  grid is the structure of the tensor x itself").
- ``padding='valid'``  : shrinking manipulations — grid points are the
  crossover points of the orthogonal hyperplane families moved with ``stride``
  (paper: padding-layer / down-sampling case).
- ``dilation``         : à-trous expansion of the operator footprint.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence, Tuple

import numpy as np

__all__ = [
    "QuasiGrid",
    "normalize_tuple",
    "normalize_pad_value",
    "grid_shape",
    "neighborhood_offsets",
    "make_quasi_grid",
    "pass_grids",
    "stage_footprint",
    "compose_footprints",
    "chain_same_margins",
    "tile_read_region",
]

#: padding modes accepted as string ``pad_value``s (jnp.pad mode names)
PAD_MODES = ("edge", "reflect")


def normalize_pad_value(pad_value):
    """Canonicalize a ``pad_value`` to a float or a known mode string.

    Numeric values (ints, numpy scalars, ...) become ``float`` so that plan
    keys hash consistently (``0`` and ``0.0`` are the same plan) and so that
    execution paths can branch on ``isinstance(pv, str)`` instead of
    comparing a possibly-string value against floats.
    """
    if isinstance(pad_value, str):
        if pad_value not in PAD_MODES:
            raise ValueError(
                f"unknown pad_value mode {pad_value!r}; "
                f"expected a number or one of {PAD_MODES}"
            )
        return pad_value
    return float(pad_value)


def normalize_tuple(v, rank: int, name: str) -> Tuple[int, ...]:
    """Broadcast a scalar-or-sequence to a rank-length tuple of ints."""
    if isinstance(v, (int, np.integer)):
        return (int(v),) * rank
    t = tuple(int(e) for e in v)
    if len(t) != rank:
        raise ValueError(f"{name} must have length {rank}, got {len(t)}")
    return t


def grid_shape(
    in_shape: Sequence[int],
    op_shape: Sequence[int],
    stride: Sequence[int],
    padding: str,
    dilation: Sequence[int],
) -> Tuple[int, ...]:
    """Output grid shape ``s'`` = f1(x.shape) for each dimension."""
    out = []
    for n, k, s, d in zip(in_shape, op_shape, stride, dilation):
        eff = (k - 1) * d + 1  # effective operator extent
        if padding == "same":
            out.append(-(-n // s))  # ceil(n / s)
        elif padding == "valid":
            if n < eff:
                raise ValueError(
                    f"input extent {n} smaller than effective operator {eff}"
                )
            out.append((n - eff) // s + 1)
        else:
            raise ValueError(f"unknown padding mode {padding!r}")
    return tuple(out)


def neighborhood_offsets(
    op_shape: Sequence[int], dilation: Sequence[int]
) -> np.ndarray:
    """Relative offsets of every operator element w.r.t. the operator center.

    Returns an int array of shape ``(numel(m), rank)``; row ordering is the
    ravel (row-major) order of the operator tensor, matching the column order
    of the melt matrix.
    """
    axes = [
        (np.arange(k) - (k - 1) // 2) * d for k, d in zip(op_shape, dilation)
    ]
    mesh = np.meshgrid(*axes, indexing="ij")
    return np.stack([m.ravel() for m in mesh], axis=-1).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class QuasiGrid:
    """Static description of a melt: all shape/indexing metadata.

    Attributes
    ----------
    in_shape    : shape of the (unpadded) input tensor
    op_shape    : shape of the operator tensor ``m`` (same rank)
    stride, dilation : per-dim ints
    padding     : 'same' | 'valid'
    out_shape   : the grid shape ``s'``
    pad_lo/pad_hi : virtual padding applied per dim (same-mode only)
    offsets     : (numel(m), rank) relative offsets (operator ravel order)
    """

    in_shape: Tuple[int, ...]
    op_shape: Tuple[int, ...]
    stride: Tuple[int, ...]
    dilation: Tuple[int, ...]
    padding: str
    out_shape: Tuple[int, ...]
    pad_lo: Tuple[int, ...]
    pad_hi: Tuple[int, ...]

    @property
    def rank(self) -> int:
        return len(self.in_shape)

    @property
    def num_rows(self) -> int:
        return int(math.prod(self.out_shape))

    @property
    def num_cols(self) -> int:
        return int(math.prod(self.op_shape))

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        return tuple(
            n + lo + hi
            for n, lo, hi in zip(self.in_shape, self.pad_lo, self.pad_hi)
        )

    def offsets(self) -> np.ndarray:
        return neighborhood_offsets(self.op_shape, self.dilation)

    def flat_offsets(self) -> np.ndarray:
        """Offsets flattened against the *padded* input strides: (numel(m),)."""
        strides = np.ones(self.rank, dtype=np.int64)
        pshape = self.padded_shape
        for i in range(self.rank - 2, -1, -1):
            strides[i] = strides[i + 1] * pshape[i + 1]
        return self.offsets() @ strides

    def base_flat_indices(self) -> np.ndarray:
        """Flat index (into padded input) of the *center* of each grid row."""
        pshape = self.padded_shape
        strides = np.ones(self.rank, dtype=np.int64)
        for i in range(self.rank - 2, -1, -1):
            strides[i] = strides[i + 1] * pshape[i + 1]
        axes = []
        for g, s, lo, k, d in zip(
            self.out_shape, self.stride, self.pad_lo, self.op_shape, self.dilation
        ):
            center = (k - 1) // 2 * d
            if self.padding == "same":
                # grid point i sits at padded position i*s + lo
                axes.append(np.arange(g, dtype=np.int64) * s + lo)
            else:  # valid: first center at `center`
                axes.append(np.arange(g, dtype=np.int64) * s + center)
        mesh = np.meshgrid(*axes, indexing="ij")
        pos = np.stack([m.ravel() for m in mesh], axis=-1)
        return pos @ strides

    def halo(self) -> Tuple[Tuple[int, int], ...]:
        """Per-dim (lo, hi) halo widths a shard needs beyond its own slab."""
        out = []
        for k, d in zip(self.op_shape, self.dilation):
            lo = (k - 1) // 2 * d
            hi = (k - 1 - (k - 1) // 2) * d
            out.append((lo, hi))
        return tuple(out)


def stage_footprint(grid: "QuasiGrid") -> Tuple[Tuple[int, int], ...]:
    """Per-dim (lo, hi) *input reach* of one stage around an output point.

    'same' output ``g`` reads unpadded input ``[g·s − lo, g·s + hi]`` (the
    halo); 'valid' output ``g`` reads ``[g·s, g·s + eff − 1]`` — so its
    reach is ``(0, eff − 1)``.  This is the per-stage ingredient of the
    tiled scheduler's footprint composition (DESIGN.md §12).
    """
    out = []
    for d in range(grid.rank):
        if grid.padding == "same":
            out.append(grid.halo()[d])
        else:
            eff = (grid.op_shape[d] - 1) * grid.dilation[d] + 1
            out.append((0, eff - 1))
    return tuple(out)


def compose_footprints(grids: Sequence["QuasiGrid"]
                       ) -> Tuple[Tuple[int, int, int], ...]:
    """Total input footprint of a stage chain, per dim as ``(α, β, γ)``.

    An output tile ``[a, b)`` of the composed program needs input coords
    ``[α·a − β, α·(b−1) + γ + 1)`` (before clamping to the volume).  The
    affine form is exact for any mix of 'same'/'valid' stages, strides and
    dilations: pre-composing a stage with stride ``s`` and reach
    ``(lo, hi)`` maps ``(α, β, γ) → (s·α, s·β + lo, s·γ + hi)``.  Stride-1
    chains degenerate to ``α = 1`` with ``(β, γ)`` the classic halo sums.
    """
    if not grids:
        return ()
    rank = grids[0].rank
    abg = [(1, 0, 0)] * rank
    for g in reversed(list(grids)):
        reach = stage_footprint(g)
        abg = [
            (a * g.stride[d], g.stride[d] * b + reach[d][0],
             g.stride[d] * c + reach[d][1])
            for d, (a, b, c) in enumerate(abg)
        ]
    return tuple(abg)


def chain_same_margins(grids: Sequence["QuasiGrid"]
                       ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Accumulated 'same' pad margins ``(B, C)`` of a stride-1 chain.

    ``B_d = Σ pad_lo``/``C_d = Σ pad_hi`` bound the output positions whose
    transitive reads can touch fill: chain output ``g`` bottoms out on
    input ``[g − B_d, g + C_d]``, so ``[B_d, n_d − C_d)`` per dim is the
    *interior* where the chain equals its composed-'valid' rewrite (offset
    ``B``) and ``B_d + C_d + 1`` is the composite operator extent — the
    planner's interior/boundary split (DESIGN.md §11) is built on exactly
    this identity.
    """
    rank = grids[0].rank
    lo = [0] * rank
    hi = [0] * rank
    for g in grids:
        for d in range(rank):
            lo[d] += g.pad_lo[d]
            hi[d] += g.pad_hi[d]
    return tuple(lo), tuple(hi)


def tile_read_region(
    footprint: Sequence[Tuple[int, int, int]],
    tile_lo: Sequence[int],
    tile_hi: Sequence[int],
    in_shape: Sequence[int],
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Clamped input region an output tile ``[tile_lo, tile_hi)`` reads.

    Applies the :func:`compose_footprints` affine per dim and clamps to the
    volume — the out-of-volume remainder is what the per-tile executor
    re-creates with the pad mode (only ever at true volume boundaries, so
    tiled results match the in-memory run under every pad mode).
    """
    lo, hi = [], []
    for (a, b, c), tl, th, n in zip(footprint, tile_lo, tile_hi, in_shape):
        if th <= tl:
            raise ValueError(f"empty tile [{tl}, {th})")
        lo.append(max(0, a * tl - b))
        hi.append(min(n, a * (th - 1) + c + 1))
    return tuple(lo), tuple(hi)


def make_quasi_grid(
    in_shape: Sequence[int],
    op_shape: Sequence[int],
    stride=1,
    padding: str = "same",
    dilation=1,
) -> QuasiGrid:
    in_shape = tuple(int(s) for s in in_shape)
    rank = len(in_shape)
    op_shape_t = normalize_tuple(op_shape, rank, "op_shape")
    stride_t = normalize_tuple(stride, rank, "stride")
    dil_t = normalize_tuple(dilation, rank, "dilation")
    out = grid_shape(in_shape, op_shape_t, stride_t, padding, dil_t)
    if padding == "same":
        pad_lo, pad_hi = [], []
        for n, g, k, s, d in zip(in_shape, out, op_shape_t, stride_t, dil_t):
            center = (k - 1) // 2 * d
            lo = center
            # last grid center at (g-1)*s ; needs up to +((k-1)-(k-1)//2)*d
            hi_needed = (g - 1) * s + ((k - 1) - (k - 1) // 2) * d - (n - 1)
            pad_lo.append(lo)
            pad_hi.append(max(0, hi_needed))
        pads = (tuple(pad_lo), tuple(pad_hi))
    else:
        pads = ((0,) * rank, (0,) * rank)
    return QuasiGrid(
        in_shape=in_shape,
        op_shape=op_shape_t,
        stride=stride_t,
        dilation=dil_t,
        padding=padding,
        out_shape=out,
        pad_lo=pads[0],
        pad_hi=pads[1],
    )


def pass_grids(grid: QuasiGrid) -> Tuple[QuasiGrid, ...]:
    """The 1-D grids of ``grid``'s separable rewrite: pass ``i`` applies
    dim ``i``'s operator extent and stride to the previous pass's output
    (the first to ``grid.in_shape``), so the shapes walk to
    ``grid.out_shape``."""
    out, shape = [], grid.in_shape
    for i in range(grid.rank):
        g = make_quasi_grid(
            shape, [grid.op_shape[i] if j == i else 1
                    for j in range(grid.rank)],
            [grid.stride[i] if j == i else 1 for j in range(grid.rank)],
            grid.padding, grid.dilation)
        out.append(g)
        shape = g.out_shape
    return tuple(out)
