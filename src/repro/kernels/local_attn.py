"""Sliding-window flash attention Pallas kernel (melt over the sequence).

The window-W causal attention pattern is a stride-1 melt over the sequence
grid (DESIGN.md §4): each query block's key/value neighbourhood is the melt
row.  Kernel structure:

  grid = (B·H, S/T)           # one program per (batch·head, q tile)
  for each q tile i: loop the static window of kv tiles
      j ∈ {i - W/T, …, i};    # the melt-row halo
      online-softmax accumulate (f32 m/l/acc), masked by causal+window.

q/k/v arrive as whole arrays in VMEM (short sequences only); kv tiles are
sliced with ``pl.ds``.  MXU-aligned when dh and T are multiples of 128.  Requires
W % T == 0, S % T == 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _local_attn_kernel(q_ref, k_ref, v_ref, o_ref, *, tile: int, window: int,
                       scale: float):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[bh, pl.ds(qi * tile, tile), :]  # (T, dh)
    q = q.astype(jnp.float32) * scale
    dh = q.shape[-1]
    n_kv_tiles = window // tile + 1  # halo tiles + own tile

    m = jnp.full((tile,), NEG_INF, jnp.float32)
    l = jnp.zeros((tile,), jnp.float32)
    acc = jnp.zeros((tile, dh), jnp.float32)

    q_pos = qi * tile + jax.lax.iota(jnp.int32, tile)
    for t in range(n_kv_tiles):
        j = qi - (n_kv_tiles - 1) + t  # kv tile index (may be < 0)
        start = j * tile
        safe = jnp.maximum(start, 0)
        k = k_ref[bh, pl.ds(safe, tile), :]
        v = v_ref[bh, pl.ds(safe, tile), :]
        k_pos = safe + jax.lax.iota(jnp.int32, tile)
        valid = (start >= 0) & (q_pos[:, None] >= k_pos[None, :]) & \
                (q_pos[:, None] - k_pos[None, :] < window)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (T, T)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m = m_new
    out = acc / jnp.maximum(l[:, None], 1e-30)
    o_ref[bh, pl.ds(qi * tile, tile), :] = out.astype(o_ref.dtype)


def local_attention(q, k, v, window: int, *, tile: int = 128,
                    interpret: bool = True):
    """q,k,v: (B,S,H,dh) with S % tile == 0, window % tile == 0."""
    B, S, H, dh = q.shape
    assert S % tile == 0 and window % tile == 0, (S, window, tile)
    scale = 1.0 / math.sqrt(dh)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    qf, kf, vf = fold(q), fold(k), fold(v)
    kernel = functools.partial(_local_attn_kernel, tile=tile, window=window,
                               scale=scale)
    # whole arrays in VMEM: this kernel serves short sequences only
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // tile),
        in_specs=[vmem] * 3,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((B * H, S, dh), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
