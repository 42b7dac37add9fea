"""Fused melt×contract Pallas kernels — the TPU-native melt matrix.

DESIGN.md §2: the paper materializes the melt matrix ``M`` (rows = grid
points, cols = operator elements) in memory and broadcasts over it.  On TPU
that inflates HBM traffic by ``numel(m)``; these kernels instead build each
tile of melt rows in VMEM from shifted slices of halo-extended input
windows and contract with the operator weights on the fly — ``M`` never
exists in HBM.

Canonical layout (lane-dense).  Any rank-k stride-1 stencil — 'same' or
'valid', the wrapper's output crop is the only difference
(``ops._valid_slices``) — is computed at every position of the padded,
flattened volume, with a static per-operator-element *flat offset* table
derived from ``QuasiGrid.flat_offsets``: one rule for every rank.  The
flat volume of each (batch item, channel) is viewed as ``(rows, 128)``,
so flat position ``p`` sits at row ``p // 128``, lane ``p % 128``.  A tap
at (non-negative, halo-shifted) flat offset ``o = 128·q + r`` reads rows
``q`` and ``q + 1`` of the input window, rolls both by ``r`` along the
lanes, and selects by ``lane < 128 − r``.

Memory.  The input stays in HBM (``memory_space=pl.ANY``).  Each grid step
``(b, c, i)`` copies the input rows its output tile ``[i·T, (i+1)·T)``
reads into a VMEM scratch buffer: one DMA per *window*, where a window is
a run of taps whose rows overlap (a 3×3×3 operator over a volume needs
three windows, one per z-plane, instead of one slab spanning two whole
planes of halo).  The copy is synchronous; the tile is then swept in
8-row chunks so the accumulators stay in vector registers.  Weights live
in SMEM as scalars.

Families.  One kernel serves all three linear families: input
``(B, C, rows, 128)``, ``kper`` outputs per input channel, weights
``(numel, C·kper)``:

- stencil   — C = 1, kper = 1;
- bank      — C = 1, kper = K: one window pass feeds K operators
  (DESIGN.md §9);
- depthwise — C = K, kper = 1: channel k is filtered by weight column k
  (the separable 1-D pass primitive).

The moment kernel (statistics engine, DESIGN.md §10) reduces blocked
``(T, W)`` row tiles of a ``(B, rows, W)`` input.
``pick_tile_rows`` sizes tiles from a VMEM budget; validated against the
materialized melt and ``lax`` in tests/test_kernels.py, and compiled for
the chip in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lane width of one TPU vector register; the canonical row width
LANES = 128

#: rows per inner chunk — one f32 vreg (8 sublanes × 128 lanes)
_CHUNK = 8

#: default VMEM working-set target per grid step (input windows plus the
#: double-buffered output block); the kernels request ``_VMEM_LIMIT`` of
#: scoped VMEM, which leaves the compiler headroom above this
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024

#: min sublane count per dtype itemsize (TPU tiling: (sublane, 128) tiles =
#: 32 bytes of sublanes per lane, so sublanes = 32 // itemsize; itemsize 8
#: — f64 under x64, int64 indices — is listed explicitly rather than
#: falling through a silent default)
_SUBLANES = {8: 4, 4: 8, 2: 16, 1: 32}

#: largest tile, in 128-lane rows
_MAX_TILE_ROWS = 1024


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def pick_tile_rows(numel: int, c_in: int, c_out: int, dtype,
                   vmem_budget: Optional[int] = None, windows: int = 1,
                   span_rows: int = 0) -> int:
    """Choose ``tile_rows`` (128-lane rows per grid step) from a VMEM budget.

    Each grid step holds ``windows`` input windows of ``tile_rows`` rows
    plus their static spans (``span_rows`` in all, taken off the budget
    before the rows divide it up), and a double-buffered output block of
    ``c_out // c_in`` channels.  Rows are f32 in VMEM whatever the input
    dtype (the wrappers compute in f32).  ``numel`` does not enter: taps
    are swept one at a time and the weights sit in SMEM.  ``tile_rows`` is
    the largest sublane-aligned row count whose working set fits
    ``vmem_budget``, clamped to [sublane, 1024] so tiny problems never
    explode the grid and wide banks never overrun VMEM.
    """
    del numel
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    sub = max(_SUBLANES.get(jnp.dtype(dtype).itemsize, 8), _CHUNK)
    kper = max(int(c_out), 1) // max(int(c_in), 1) or 1
    row = LANES * 4
    per_row = row * (int(windows) + 2 * kper)
    fixed = row * (int(span_rows) + 2 * _CHUNK * int(windows))
    t = ((budget - fixed) // per_row // sub) * sub
    return int(max(sub, min(t, _MAX_TILE_ROWS)))


# -- the linear melt kernel --------------------------------------------------


def plan_windows(offsets: Sequence[int], tile_rows: int):
    """Static DMA plan for one output tile of ``tile_rows`` rows.

    ``offsets`` are non-negative flat offsets.  Returns ``(taps,
    windows)``: ``taps[t] = (row, shift)`` locates tap ``t`` in the VMEM
    scratch (its first row, relative to the output row, and its lane
    roll); ``windows`` lists ``(src_row, dst_row, nrows)`` copies, each
    8-row aligned, relative to the tile's first row.  Taps whose rows
    overlap share one window.
    """
    qs = sorted({int(o) // LANES for o in offsets})
    spans = []  # [q_lo, q_hi] per window
    for q in qs:
        if spans and q <= spans[-1][1] + 1 + tile_rows:
            spans[-1][1] = q
        else:
            spans.append([q, q])
    windows, where, dst = [], {}, 0
    for q_lo, q_hi in spans:
        src = (q_lo // _CHUNK) * _CHUNK
        n = _round_up(q_hi + 1 + tile_rows, _CHUNK) - src
        windows.append((src, dst, n))
        for q in qs:
            if q_lo <= q <= q_hi:
                where[q] = dst + q - src
        dst += n
    taps = tuple((where[int(o) // LANES], int(o) % LANES) for o in offsets)
    return taps, tuple(windows)


def _melt_kernel(w_ref, x_hbm, o_ref, slab, sems, *, taps, windows,
                 tile_rows: int, kper: int, wcols: int):
    b, c, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = pl.multiple_of(i * tile_rows, _CHUNK)
    copies = [
        pltpu.make_async_copy(x_hbm.at[b, c, pl.ds(base + src, n), :],
                              slab.at[pl.ds(dst, n), :], sems.at[k])
        for k, (src, dst, n) in enumerate(windows)
    ]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    lane = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, LANES), 1)
    wbase = c * kper

    def chunk(j, carry):
        r0 = pl.multiple_of(j * _CHUNK, _CHUNK)
        accs = [jnp.zeros((_CHUNK, LANES), jnp.float32)] * kper
        for t, (row, shift) in enumerate(taps):
            v = slab[pl.ds(r0 + row, _CHUNK), :]
            if shift:
                nxt = slab[pl.ds(r0 + row + 1, _CHUNK), :]
                v = jnp.where(lane < LANES - shift,
                              pltpu.roll(v, LANES - shift, 1),
                              pltpu.roll(nxt, LANES - shift, 1))
            for k in range(kper):
                accs[k] = accs[k] + w_ref[t * wcols + wbase + k] * v
        for k in range(kper):
            o_ref[0, k, pl.ds(r0, _CHUNK), :] = accs[k]
        return carry

    jax.lax.fori_loop(0, tile_rows // _CHUNK, chunk, 0)


def fused_melt_rows(x: jax.Array, weights: jax.Array, offsets,
                    tile_rows: Optional[int] = None,
                    interpret: bool = True):
    """The canonical linear melt pass, every family.

    x: ``(B, C, P)`` flat padded volumes, ``P`` a multiple of 128 (whole
    rows of the ``(rows, 128)`` view).  Output position ``p`` of input
    channel ``c`` sums ``x[b, c, p + offsets[t]]`` over taps ``t``, reading
    zeros outside ``[0, P)``; weights ``(numel, C·kper)`` give output
    channel ``c·kper + k`` the weight ``weights[t, c·kper + k]`` on tap
    ``t``.  Returns ``(B, C·kper, P)`` float32.  ``tile_rows=None`` means
    :func:`pick_tile_rows` for these channels; any tile is capped at what
    fits the VMEM budget for this call's window geometry.
    """
    B, C, P = x.shape
    offsets = tuple(int(o) for o in offsets)
    numel, wcols = weights.shape
    if numel != len(offsets) or wcols % C:
        raise ValueError(f"weights {weights.shape} do not match "
                         f"{len(offsets)} taps over {C} channels")
    if P % LANES:
        raise ValueError(f"flat volumes must fill whole {LANES}-lane rows, "
                         f"got {P} positions")
    kper = wcols // C
    # the front halo in whole rows, so both pads below are row pads of
    # the (rows, 128) view
    front = -(-max(0, -min(offsets)) // LANES)
    shifted = tuple(o + front * LANES for o in offsets)
    if tile_rows is None:
        tile_rows = pick_tile_rows(numel, C, wcols, x.dtype)
    out_rows = P // LANES
    T = max(_CHUNK, min(_round_up(tile_rows, _CHUNK),
                        _round_up(out_rows, _CHUNK)))
    taps, windows = plan_windows(shifted, T)
    spans = sum(n - T for _, _, n in windows)
    cap = pick_tile_rows(numel, C, wcols, jnp.float32,
                         windows=len(windows), span_rows=spans)
    if T > cap:
        T = cap
        taps, windows = plan_windows(shifted, T)
    tiles = -(-out_rows // T)
    in_rows = (tiles - 1) * T + max(src + n for src, _, n in windows)
    slab_rows = sum(n for _, _, n in windows)
    # one pad: the front halo, then zeros up to whole input rows (the
    # back halo lies inside them, since every window stays in range)
    xf = jnp.pad(x.astype(jnp.float32).reshape(B, C, out_rows, LANES),
                 ((0, 0), (0, 0), (front, in_rows - front - out_rows),
                  (0, 0)))
    kernel = functools.partial(_melt_kernel, taps=taps, windows=windows,
                               tile_rows=T, kper=kper, wcols=wcols)
    out = pl.pallas_call(
        kernel,
        grid=(B, C, tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # weights as scalars
            pl.BlockSpec(memory_space=pl.ANY),      # input stays in HBM
        ],
        out_specs=pl.BlockSpec((1, kper, T, LANES),
                               lambda b, c, i: (b, c, i, 0)),
        # exactly the rows P needs: the last block may overhang, and its
        # overhang is dropped
        out_shape=jax.ShapeDtypeStruct((B, wcols, out_rows, LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((slab_rows, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((len(windows),))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(weights.astype(jnp.float32).reshape(-1), xf)
    return out.reshape(B, wcols, P)


# -- tile moment reduction (statistics engine, DESIGN.md §10) ---------------
#
# The statistics engine's sufficient statistics are mergeable per-tile
# reductions over ``(B, R, W)`` row blocks: each grid step reads one
# ``(T, W)`` row tile into VMEM (a blocked spec: no halo, so the
# pipeline's own DMA suffices) and emits that tile's (Σx, Σ(x−x̄)²,
# Σ(x−x̄)³, Σ(x−x̄)⁴) per column, so the melt matrix never exists in HBM
# and the input is read exactly once.  ``W`` is the full row width —
# the reduced values' own trailing axis, or 128 for flat-packed values —
# so a volume reaches the kernel by a reshape that keeps its minor axis.
# The power sums are *tile-centered* (about the tile's own masked mean):
# raw Σx²…Σx⁴ cancel catastrophically in f32 once |mean| ≫ std, while
# centered sums bound the cancellation to one tile; the Chan merge tree
# downstream combines tiles and columns without ever forming a global raw
# sum (DESIGN.md §10).  Rows past ``valid_rows`` (the last tile's
# overhang) are masked out of both the pivot mean and the sums; per-tile
# counts are static host-side knowledge.

#: elements per moment tile at 128-lane width (a 256 KiB f32 block)
MOMENT_TILE_ELEMS = 512 * LANES


def _moment_tile(num_rows: int, width: int, tile_rows: Optional[int]) -> int:
    if tile_rows is None:
        tile_rows = MOMENT_TILE_ELEMS // _round_up(width, LANES)
    return max(_CHUNK, min(_round_up(tile_rows, _CHUNK),
                           _round_up(max(int(num_rows), 1), _CHUNK)))


def _moment_kernel(x_ref, o_ref, *, tile_rows: int, valid_rows: int,
                   order: int):
    i = pl.program_id(1)
    rows = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape[1:], 0)
    valid = rows < valid_rows - i * tile_rows
    n = jnp.clip(valid_rows - i * tile_rows, 1, tile_rows).astype(
        jnp.float32)
    sl = jnp.where(valid, x_ref[0].astype(jnp.float32), 0.0)
    s1 = jnp.sum(sl, axis=0, keepdims=True)
    c = jnp.where(valid, sl - s1 / n, 0.0)  # centered about the tile pivot
    c2 = c * c
    stats = [s1, jnp.sum(c2, axis=0, keepdims=True)]
    if order == 4:
        stats += [jnp.sum(c2 * c, axis=0, keepdims=True),
                  jnp.sum(c2 * c2, axis=0, keepdims=True)]
    for k, s in enumerate(stats):
        o_ref[0, 0, pl.ds(k, 1), :] = s


def fused_moment_rows(x: jax.Array, valid_rows: int,
                      tile_rows: Optional[int] = None,
                      interpret: bool = True, order: int = 4) -> jax.Array:
    """Per-tile sufficient statistics of a ``(B, R, W)`` row block.

    Rows ``≥ valid_rows`` are ignored.  Returns ``(B, tiles, order, W)``
    float32: per item, tile and column,
    ``[Σx, Σ(x−x̄_t)², Σ(x−x̄_t)³, Σ(x−x̄_t)⁴][:order]`` with ``x̄_t`` the
    tile column's own valid-row mean (``order=2`` drops the cubic/quartic
    sums — the variance fast path).  Together with the (static) per-tile
    valid counts of :func:`moment_tile_counts` these are exact
    :class:`~repro.stats.moments.MomentState` tiles, merged by the
    caller's Chan tree (DESIGN.md §10).  ``tile_rows=None`` sizes tiles
    to :data:`MOMENT_TILE_ELEMS` at the lane-padded width.
    """
    if order not in (2, 4):
        raise ValueError(f"order must be 2 or 4, got {order}")
    B, R, W = x.shape
    T = _moment_tile(R, W, tile_rows)
    tiles = max(1, -(-R // T))
    kernel = functools.partial(_moment_kernel, tile_rows=T,
                               valid_rows=int(valid_rows), order=order)
    return pl.pallas_call(
        kernel,
        grid=(B, tiles),
        # the last tile may overhang R: its rows past valid_rows are masked
        in_specs=[pl.BlockSpec((1, T, W), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, 1, order, W),
                               lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, tiles, order, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)


def moment_tile_counts(valid_rows: int, num_rows: int, width: int,
                       tile_rows: Optional[int] = None) -> np.ndarray:
    """Static per-tile valid-row counts matching :func:`fused_moment_rows`.

    Must mirror the kernel's tile sizing exactly — the counts are the
    ``count`` leaves of the per-tile states the caller builds.
    """
    T = _moment_tile(num_rows, width, tile_rows)
    tiles = max(1, -(-num_rows // T))
    edges = np.arange(tiles, dtype=np.int64) * T
    return np.clip(valid_rows - edges, 0, T).astype(np.float32)
