"""Bilateral-filter Pallas kernel: data-dependent melt weights in VMEM.

Unlike the linear stencil, the bilateral weight (paper Eq. 3) depends on
the melt-row *values*: W_c = exp(log_sp_c − (x_c − center)²/(2σ_r²)).  The
kernel builds the melt tile (T, numel) in VMEM from shifted slices (same
canonicalization as melt_stencil: 1-D row offsets over a flattened,
halo-padded input), computes the weight tile in registers, normalizes rows
and reduces — the weight matrix, like M itself, never reaches HBM.

Supports constant σ_r and the paper's adaptive σ_r (per-row variance of
the melt tile — §3.2's "dynamic ruler").
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bilateral_kernel(x_ref, lsp_ref, o_ref, *, offsets: Tuple[int, ...],
                      tile_rows: int, center_idx: int, sigma_r: float,
                      adaptive: bool, eps: float):
    i = pl.program_id(0)
    base = i * tile_rows
    cols = []
    for off in offsets:
        cols.append(x_ref[pl.ds(base + off, tile_rows), :]
                    .astype(jnp.float32))
    tile = jnp.stack(cols, axis=-1)[:, 0, :]  # (T, numel) melt tile in VMEM
    center = tile[:, center_idx][:, None]
    diff2 = (tile - center) ** 2
    if adaptive:
        var = jnp.mean((tile - jnp.mean(tile, 1, keepdims=True)) ** 2, 1,
                       keepdims=True) + eps
        log_rng = -diff2 / (2.0 * var)
    else:
        log_rng = -diff2 / (2.0 * sigma_r * sigma_r)
    w = jnp.exp(lsp_ref[0, :][None, :] + log_rng)  # (T, numel)
    out = jnp.sum(w * tile, axis=1) / (jnp.sum(w, axis=1) + eps)
    o_ref[...] = out[:, None].astype(o_ref.dtype)


def bilateral_rows(x_halo: jax.Array, log_spatial: jax.Array, row_offsets,
                   out_rows: int, halo_lo: int, center_idx: int,
                   sigma_r="adaptive", tile_rows: int = 256,
                   eps: float = 1e-6, interpret: bool = True):
    """1-lane canonical form: x_halo (out_rows + halo_lo + halo_hi, 1)."""
    R = out_rows
    tiles = -(-R // tile_rows)
    need = tiles * tile_rows + (x_halo.shape[0] - R)
    if need > x_halo.shape[0]:
        x_halo = jnp.pad(x_halo, ((0, need - x_halo.shape[0]), (0, 0)),
                         mode="edge")
    offs = tuple(int(o) + halo_lo for o in np.asarray(row_offsets))
    lsp = log_spatial.reshape(1, -1).astype(jnp.float32)
    kernel = functools.partial(
        _bilateral_kernel, offsets=offs, tile_rows=tile_rows,
        center_idx=center_idx,
        sigma_r=0.0 if isinstance(sigma_r, str) else float(sigma_r),
        adaptive=isinstance(sigma_r, str), eps=eps,
    )
    out = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[
            # whole input in VMEM: this kernel serves small volumes only
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lsp.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles * tile_rows, 1), x_halo.dtype),
        interpret=interpret,
    )(x_halo, lsp)
    return out[:R]
