"""jit'd wrappers: rank-agnostic canonicalization → Pallas kernels.

The canonical trick (melt_stencil.py docstring): a stride-1 stencil on
any rank is computed at EVERY position of the padded flattened volume
(output position p ↔ padded flat position p, offsets =
QuasiGrid.flat_offsets) and the true output region is cropped afterwards
('same' recovers in_shape, 'valid' shrinks to out_shape — one rule,
`_valid_slices`).  Extra positions cost (P−N)/N compute (a few %) and buy
exact flat-offset addressing.  Every family runs on ``(B, C, P)`` flat
volumes — batch items and channels lead, so the kernel's 128-lane rows
are always dense; bank and depthwise channels move back to channels-last
only for the public API.

``interpret`` defaults to True off-TPU (interpret mode for CPU tests); on
TPU backends the same code emits compiled Mosaic kernels.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grid import QuasiGrid, make_quasi_grid, pass_grids
from repro.core.melt import pad_array
from repro.kernels import bilateral as _bil
from repro.kernels import local_attn as _la
from repro.kernels import melt_stencil as _ms
from repro.obs.metrics import counter as _counter


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _valid_slices(grid: QuasiGrid):
    """Per-dim output crop of the all-positions canonical result.

    Stride-1 grids compute a value at EVERY (padded) flat position; the
    true outputs sit at the operator-*center* positions.  For 'same' the
    center offset equals ``pad_lo`` and the crop recovers ``in_shape``; for
    'valid' there is no padding and the crop shrinks to ``out_shape`` —
    one rule covers both.
    """
    starts = tuple((k - 1) // 2 * d
                   for k, d in zip(grid.op_shape, grid.dilation))
    return tuple(slice(s, s + n) for s, n in zip(starts, grid.out_shape))


def _check_fused_grid(grid: QuasiGrid):
    if grid.stride != (1,) * grid.rank or grid.padding not in ("same",
                                                               "valid"):
        raise NotImplementedError(
            "fused path covers stride-1 'same'/'valid' stencils")


def _flat(xc, grid: QuasiGrid, pad_value):
    """(B, C, *spatial) → (B, C, P): the grid's padded volume, flat, with
    whole 128-lane rows — the linear kernel's input view."""
    B, C = xc.shape[:2]
    pads = [(0, 0), (0, 0)] + list(zip(grid.pad_lo, grid.pad_hi))
    xp = pad_array(xc, pads, pad_value)
    # extra trailing planes on the first axis make the flat volume fill
    # whole 128-lane rows, so it reaches the kernel by an N-d pad and a
    # reshape (padding the flat vector instead lays the whole volume out
    # one-dimensionally, which XLA compiles slowly); the strides, so the
    # offsets, do not change, and the extra outputs fall outside the crop
    extra = _extra_planes(grid)
    if extra:
        xp = jnp.pad(xp, [(0, 0), (0, 0), (0, extra)]
                     + [(0, 0)] * (grid.rank - 1))
    return xp.reshape(B, C, -1)


def _melt_rows(xc, grid: QuasiGrid, W, pad_value, tile_rows, interpret):
    """(B, C, *spatial) → (B, C·kper, rows, 128) float32: the linear
    kernel's own output, every position of the padded flat volume."""
    _check_fused_grid(grid)
    interpret = _interpret_default() if interpret is None else interpret
    rows = _ms.fused_melt_rows(_flat(xc, grid, pad_value), jnp.asarray(W),
                               grid.flat_offsets(), tile_rows=tile_rows,
                               interpret=interpret)
    return rows.reshape(xc.shape[0], rows.shape[1], -1, _ms.LANES)


def _extra_planes(grid: QuasiGrid) -> int:
    lead, rest = grid.padded_shape[0], int(np.prod(grid.padded_shape[1:]))
    return -lead % (_ms.LANES // math.gcd(rest, _ms.LANES))


def _crop_rows(rows, grid: QuasiGrid):
    """(..., rows, 128) → (..., *out_shape): the padded volume back from
    its rows, cropped.  On the chip this is a relayout of every channel
    (the rows' tiling is not the volume's), so it comes last."""
    lead = rows.shape[:-2]
    out = rows.reshape(lead + (grid.padded_shape[0] + _extra_planes(grid),)
                       + grid.padded_shape[1:])
    return out[(slice(None),) * len(lead) + _valid_slices(grid)]


def _melt(xc, grid: QuasiGrid, W, pad_value, tile_rows, interpret):
    """(B, C, *spatial) → (B, C·kper, *out_shape) float32 through the one
    linear kernel: pad, flatten, every-position pass, crop."""
    return _crop_rows(_melt_rows(xc, grid, W, pad_value, tile_rows,
                                 interpret), grid)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "pad_value", "interpret", "batched",
                     "tile_rows"))
def fused_stencil(x, grid: QuasiGrid, weights, pad_value=0.0,
                  interpret=None, batched=False, tile_rows=None):
    """Rank-agnostic fused melt×contract (stride-1 'same'/'valid' grids).

    ``batched=True``: leading dim of ``x`` is a stack of independent tensors;
    the Pallas grid gains a batch axis (one kernel launch for the stack).
    ``tile_rows=None`` sizes the tile from the VMEM budget
    (``pick_tile_rows``, DESIGN.md §16).
    """
    xb = x if batched else x[None]
    out = _melt(xb[:, None], grid, jnp.reshape(weights, (-1, 1)), pad_value,
                tile_rows, interpret)[:, 0]
    return (out if batched else out[0]).astype(x.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "pad_value", "interpret", "batched",
                     "tile_rows", "pointwise"))
def fused_stencil_bank(x, grid: QuasiGrid, weight_matrix, pad_value=0.0,
                       interpret=None, batched=False, tile_rows=None,
                       pointwise=None):
    """K operators over one melt pass: (..., *spatial) → (..., *spatial, K).

    ``weight_matrix`` is (numel(m), K); each grid step reads the input
    windows of one output tile once and accumulates all K operators from
    them, so the window load is amortized across the bank and ``M``
    never exists in HBM.  ``tile_rows=None`` sizes the tile from the
    VMEM budget (``pick_tile_rows``, DESIGN.md §16).

    ``pointwise`` is an elementwise function of channel-major values,
    (..., K, *spatial) → (..., [K',] *spatial).  It runs on the kernel's
    own rows — the spatial axes it sees are the flat padded volume's,
    ``(1, …, rows, 128)`` — before they are cropped, so the K-channel
    field is never relaid out; only its result is.  The result is then
    channel-major: (..., [K',] *out_shape).
    """
    xb = x if batched else x[None]
    if pointwise is None:
        out = _melt(xb[:, None], grid, weight_matrix, pad_value, tile_rows,
                    interpret)
        out = jnp.moveaxis(out, 1, -1)
        return (out if batched else out[0]).astype(x.dtype)
    rows = _melt_rows(xb[:, None], grid, weight_matrix, pad_value,
                      tile_rows, interpret).astype(x.dtype)
    B, K, R = rows.shape[:3]
    flat = ((R * _ms.LANES,) if grid.rank == 1
            else (1,) * (grid.rank - 2) + (R, _ms.LANES))
    res = pointwise(rows.reshape((B, K) + flat) if batched
                    else rows.reshape((K,) + flat))
    res = res.reshape(res.shape[:res.ndim - grid.rank] + (R, _ms.LANES))
    return _crop_rows(res, grid)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "pad_value", "interpret", "batched",
                     "tile_rows"))
def fused_stencil_depthwise(xc, grid: QuasiGrid, weights, pad_value=0.0,
                            interpret=None, batched=False, tile_rows=None):
    """Per-lane stencil: lane k of ``xc`` (..., *spatial, K) is filtered by
    column k of ``weights`` (numel(m), K) — the separable 1-D pass primitive.
    ``tile_rows=None`` sizes the tile from the VMEM budget (DESIGN.md §16).
    """
    xb = xc if batched else xc[None]
    out = _melt(jnp.moveaxis(xb, -1, 1), grid, weights, pad_value,
                tile_rows, interpret)
    out = jnp.moveaxis(out, 1, -1)
    return (out if batched else out[0]).astype(xc.dtype)


@functools.partial(
    jax.jit, static_argnames=("grid", "pad_value", "interpret", "batched"))
def fused_separable_bank(x, grid: QuasiGrid, factors, pad_value=0.0,
                         interpret=None, batched=False):
    """A factored bank as successive 1-D passes: a bank pass along dim 0
    (``factors[0]``, 1 → K channels), then a depthwise pass along each
    further dim ``d`` (``factors[d]``, K → K).

    Where ``_keeps_rows`` holds, the volume is padded by every dim's halo
    and laid out as the kernel's rows once; each pass reads the previous
    pass's rows and computes at every padded position, and one crop at
    the end keeps the outputs.  Exact, bit for bit, against padding and
    cropping around each pass: a kept output reads, through every pass,
    only positions inside the padded volume (pass ``d`` reads along dim
    ``d`` only), and zero, edge and reflect padding along one dim commute
    with a 1-D filter along another, so the margins each pass sees hold
    the values a per-pass pad would make.  Elsewhere each pass pads,
    lays out and crops its own volume.  The K channels stay leading
    throughout and move to channels-last once, at the end.
    """
    _check_fused_grid(grid)
    interpret = _interpret_default() if interpret is None else interpret
    xb = x if batched else x[None]
    if _keeps_rows(grid):
        h = _flat(xb[:, None], grid, pad_value)
        strides = np.cumprod((grid.padded_shape[1:] + (1,))[::-1])[::-1]
        for d, f in enumerate(factors):
            k, dil = grid.op_shape[d], grid.dilation[d]
            offsets = (np.arange(k) - (k - 1) // 2) * dil * int(strides[d])
            h = _ms.fused_melt_rows(h, jnp.asarray(f), offsets,
                                    interpret=interpret)
        _counter("kernels/separable_rows_kept").inc(len(factors) - 1)
        out = _crop_rows(h.reshape(h.shape[:2] + (-1, _ms.LANES)), grid)
    else:
        out = xb[:, None]
        for g, f in zip(pass_grids(grid), factors):
            out = _melt(out, g, f, pad_value, None, interpret)
    out = jnp.moveaxis(out, 1, -1)
    return (out if batched else out[0]).astype(x.dtype)


def _keeps_rows(grid: QuasiGrid) -> bool:
    """Whether a separable group's passes share one padded volume's rows
    (a cost rule): yes unless padding leaves the volume's planes worse
    aligned on the 128 lanes than the input's — a 'same' pad around a
    lane-aligned plane, say.  Such planes make XLA relay the whole
    K-channel field out through a loop, and add cropped planes
    (``_extra_planes``), which costs more than a pad and crop around each
    pass whose own planes stay aligned: on a TPU v5e a 7-tap 'same'
    Gaussian over a 256×512×512 study took 48 ms with its rows kept
    against 29 ms pass by pass.  A 'valid' group, which the planner and
    the sharded slabs run, pads nothing."""
    def lanes(shape):
        return math.gcd(int(np.prod(shape[1:])), _ms.LANES)

    return lanes(grid.padded_shape) >= lanes(grid.in_shape)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_rows",
                                             "order"))
def fused_moment_sums(x3, interpret=None, tile_rows=None, order=4):
    """Tile-reduction sufficient statistics of a (B, R, W) row block.

    Returns ``(sums, counts)``: ``sums`` is (B, tiles, order, W) float32
    per-tile, per-column ``[Σx, Σ(x−x̄_t)², Σ(x−x̄_t)³, Σ(x−x̄_t)⁴][:order]``
    from the Pallas kernel (one pass over the input, no melt matrix in HBM
    — DESIGN.md §10) and ``counts`` the matching (tiles,) static valid-row
    counts (every column of a tile holds the same count).  ``order=2`` is
    the variance fast path.
    """
    interpret = _interpret_default() if interpret is None else interpret
    R, W = x3.shape[1:]
    sums = _ms.fused_moment_rows(x3, R, tile_rows=tile_rows,
                                 interpret=interpret, order=order)
    counts = jnp.asarray(_ms.moment_tile_counts(R, R, W,
                                                tile_rows=tile_rows))
    return sums, counts


@functools.partial(
    jax.jit,
    static_argnames=("op_shape", "sigma_d", "sigma_r", "pad_value", "interpret"),
)
def fused_bilateral(x, op_shape, sigma_d, sigma_r="adaptive",
                    pad_value="edge", interpret=None):
    """Rank-agnostic bilateral filter (paper Eq. 3) via the Pallas kernel."""
    from repro.core.filters import _spatial_log_weights

    interpret = _interpret_default() if interpret is None else interpret
    rank = x.ndim
    op = (op_shape,) * rank if isinstance(op_shape, int) else tuple(op_shape)
    grid = make_quasi_grid(x.shape, op, 1, "same", 1)
    log_sp = _spatial_log_weights(grid, sigma_d)
    center = int(np.ravel_multi_index(
        tuple((k - 1) // 2 for k in grid.op_shape), grid.op_shape))
    offs = grid.flat_offsets()
    halo_lo, halo_hi = int(max(0, -offs.min())), int(max(0, offs.max()))
    xp = pad_array(x.astype(jnp.float32),
                   list(zip(grid.pad_lo, grid.pad_hi)), pad_value)
    flat = jnp.pad(xp.reshape(-1, 1), ((halo_lo, halo_hi), (0, 0)))
    total = int(np.prod(grid.padded_shape))
    rows = _bil.bilateral_rows(
        flat, log_sp, offs, total, halo_lo, center, sigma_r=sigma_r,
        interpret=interpret)
    return rows[:, 0].reshape(grid.padded_shape)[
        _valid_slices(grid)].astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("window", "tile", "interpret"))
def sliding_window_attention(q, k, v, window: int, tile: int = 128,
                             interpret=None):
    """(B,S,H,dh) sliding-window flash attention (melt over sequence)."""
    interpret = _interpret_default() if interpret is None else interpret
    return _la.local_attention(q, k, v, window, tile=tile,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def depthwise_conv1d(x, w, interpret=None):
    """Causal depthwise conv (B,L,C)·(K,C) — per-channel weighted melt.

    Channel-in-lanes layout: offsets shift L rows per batch; implemented via
    the generic stencil kernel applied per (batch, tap) shift with
    per-channel weights broadcast in lanes.
    """
    interpret = _interpret_default() if interpret is None else interpret
    B, L, C = x.shape
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return _dw(xp, w.astype(x.dtype), L, interpret)


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def _dw(xp, w, L, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, LP, C = xp.shape
    K = w.shape[0]

    def kernel(x_ref, w_ref, o_ref):
        b = pl.program_id(0)
        acc = jnp.zeros((L, C), jnp.float32)
        for k in range(K):
            sl = x_ref[b, pl.ds(k, L), :]
            acc = acc + sl.astype(jnp.float32) * w_ref[k, :][None, :].astype(jnp.float32)
        o_ref[b] = acc.astype(o_ref.dtype)

    # whole arrays in VMEM: this helper serves small sequence models only
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((B, L, C), xp.dtype),
        interpret=interpret,
    )(xp, w)
