"""Generic rank-agnostic filters built on the melt matrix (paper §3.2).

Applications, all pure array programming over the melt decomposition:

- ``gaussian_filter``     — linear stencil, the Fig 6/7 benchmark subject
- ``bilateral_filter``    — Eq. (3): data-dependent weights, adaptive σ_r
- ``gradient``/``hessian`` — Eq. (6): all first/second partials as ONE
                            operator-bank pass (DESIGN.md §9)
- ``gaussian_curvature``  — Eq. (6)/(7): the rank + rank² bank, det/trace
                            in a rank-2 container

Every function takes tensors of *any* rank; rank is data, not code structure
(the Hilbert-completeness contract of §2.2).  The derivative family runs
through ``apply_stencil_bank``: one melt pass feeds every operator on all
three execution paths — the fused path never materializes ``M``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hilbert
from repro.core.grid import QuasiGrid, make_quasi_grid, neighborhood_offsets
from repro.core.melt import MeltMatrix, melt, unmelt

__all__ = [
    "gaussian_weights",
    "gaussian_weights_np",
    "gaussian_filter",
    "bilateral_filter",
    "difference_stencils",
    "curvature_bank",
    "gradient",
    "hessian",
    "gaussian_curvature",
]


def gaussian_weights_np(op_shape, sigma, dilation=1, mask=None) -> np.ndarray:
    """Pure-numpy :func:`gaussian_weights` — safe to call at plan-build
    time *inside* someone's trace (no jnp op ever stages)."""
    op_shape = tuple(int(k) for k in op_shape)
    rank = len(op_shape)
    dil = (dilation,) * rank if isinstance(dilation, int) else tuple(dilation)
    offs = neighborhood_offsets(op_shape, dil).astype(np.float64)  # (cols, rank)
    cov = hilbert.as_covariance(sigma, rank)
    prec = np.linalg.inv(cov)
    quad = np.einsum("ci,ij,cj->c", offs, prec, offs)
    w = np.exp(-0.5 * quad)
    if mask is not None:
        w = w * np.asarray(mask, dtype=np.float64).ravel()
    w = w / w.sum()
    return w.astype(np.float32)


def gaussian_weights(op_shape, sigma, dilation=1, mask=None) -> jnp.ndarray:
    """Spatial Gaussian kernel over the operator footprint, raveled: (cols,).

    ``sigma`` may be scalar / per-dim vector / full covariance (anisotropy
    support for e.g. medical voxels — paper Eq. 3's Σ_d).
    """
    return jnp.asarray(gaussian_weights_np(op_shape, sigma, dilation, mask))


def _pipe_for(x, batched: bool):
    from repro.pipe import pipe  # local import, avoids cycle

    return pipe.batched(x) if batched else pipe(x)


def gaussian_filter(
    x: jax.Array,
    op_shape,
    sigma,
    *,
    method: str = "auto",
    pad_value=0.0,
    batched: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Rank-agnostic Gaussian smoothing: melt → broadcast → couple.

    Thin wrapper over a single-stage pipe graph (DESIGN.md §11), which
    lowers back onto the ``StencilPlan`` cache — chain further stages with
    ``pipe(x).gaussian(...)`` directly.  ``batched=True``: the leading dim
    of ``x`` is a stack of independent tensors, filtered in one batched
    stencil dispatch (DESIGN.md §3).
    """
    rank = x.ndim - (1 if batched else 0)
    op = (op_shape,) * rank if isinstance(op_shape, int) else tuple(op_shape)
    return _pipe_for(x, batched).gaussian(sigma, op_shape=op).run(
        method=method, pad_value=pad_value, out_dtype=out_dtype)


def _spatial_log_weights(grid: QuasiGrid, sigma_d) -> jnp.ndarray:
    offs = grid.offsets().astype(np.float64)
    cov = hilbert.as_covariance(sigma_d, grid.rank)
    prec = np.linalg.inv(cov)
    quad = np.einsum("ci,ij,cj->c", offs, prec, offs)
    return jnp.asarray(-0.5 * quad, dtype=jnp.float32)


def bilateral_filter(
    x: jax.Array,
    op_shape,
    sigma_d,
    sigma_r="adaptive",
    *,
    pad_value="edge",
    eps: float = 1e-6,
    batched: bool = False,
) -> jax.Array:
    """Generic bilateral filter, Eq. (3), any rank.

    ``sigma_d``: scalar / vector / covariance for the spatial term (Σ_d).
    ``sigma_r``: positive float (constant range regulator), or ``'adaptive'``
    — the paper's proposal that σ_r should be a function of the grid point:
    we use the *local standard deviation of the melt row*, i.e. a dynamic
    ruler per scanned scope (§3.2).

    ``batched=True``: leading dim of ``x`` is a stack; all row-wise math
    below reduces over the last (column) axis, so one batched melt feeds the
    whole stack.
    """
    rank = x.ndim - (1 if batched else 0)
    op = (op_shape,) * rank if isinstance(op_shape, int) else tuple(op_shape)
    M = melt(x.astype(jnp.float32), op, pad_value=pad_value, batched=batched)
    data = M.data  # (..., rows, cols)
    center = M.center_column()[..., None]  # (..., rows, 1)
    log_sp = _spatial_log_weights(M.grid, sigma_d)  # (cols,)
    diff2 = (data - center) ** 2
    if isinstance(sigma_r, str):
        if sigma_r != "adaptive":
            raise ValueError(f"unknown sigma_r mode {sigma_r!r}")
        var_local = jnp.var(data, axis=-1, keepdims=True) + eps
        log_rng = -diff2 / (2.0 * var_local)
    else:
        log_rng = -diff2 / (2.0 * float(sigma_r) ** 2)
    W = jnp.exp(log_sp + log_rng)
    out_rows = jnp.sum(W * data, axis=-1) / (jnp.sum(W, axis=-1) + eps)
    return unmelt(out_rows, M.grid, batched=batched).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def difference_stencils(rank: int) -> tuple[np.ndarray, np.ndarray]:
    """Central-difference weight vectors over a 3^rank footprint.

    Returns ``(grad_w, hess_w)`` with shapes (cols, rank) and
    (cols, rank, rank); ``M @ grad_w`` gives all first partials and
    ``M @ hess_w.reshape(cols, rank*rank)`` all second partials — the paper's
    claim that Hessian computation on any-rank tensors reduces to containers
    of rank ≤ 4 (here: one rank-2 matmul each).

    Cached per rank (the offset/weight tables are pure functions of it) and
    returned read-only so cache hits can never be corrupted in place.
    """
    op_shape = (3,) * rank
    offs = neighborhood_offsets(op_shape, (1,) * rank)  # (cols, rank)
    cols = offs.shape[0]
    grad_w = np.zeros((cols, rank))
    hess_w = np.zeros((cols, rank, rank))
    for i in range(rank):
        others = [j for j in range(rank) if j != i]
        on_axis = np.all(offs[:, others] == 0, axis=1) if others else np.ones(cols, bool)
        # ∂/∂xi : central difference (f(+1) - f(-1)) / 2
        grad_w[on_axis & (offs[:, i] == 1), i] += 0.5
        grad_w[on_axis & (offs[:, i] == -1), i] -= 0.5
        # ∂²/∂xi² : f(+1) - 2 f(0) + f(-1)
        hess_w[on_axis & (offs[:, i] == 1), i, i] += 1.0
        hess_w[on_axis & (offs[:, i] == -1), i, i] += 1.0
        hess_w[on_axis & (offs[:, i] == 0), i, i] -= 2.0
    for i in range(rank):
        for j in range(i + 1, rank):
            others = [k for k in range(rank) if k not in (i, j)]
            on_plane = (
                np.all(offs[:, others] == 0, axis=1)
                if others
                else np.ones(cols, bool)
            )
            for si in (-1, 1):
                for sj in (-1, 1):
                    sel = on_plane & (offs[:, i] == si) & (offs[:, j] == sj)
                    hess_w[sel, i, j] += si * sj * 0.25
                    hess_w[sel, j, i] += si * sj * 0.25
    grad_w.setflags(write=False)
    hess_w.setflags(write=False)
    return grad_w, hess_w


@functools.lru_cache(maxsize=None)
def curvature_bank(rank: int) -> np.ndarray:
    """The (3^rank, rank + rank²) derivative bank: [∇ | vec(H)] columns.

    One contraction against this matrix computes every first and second
    partial — the K = rank + rank² operator bank behind ``gradient``,
    ``hessian`` and ``gaussian_curvature``.
    """
    grad_w, hess_w = difference_stencils(rank)
    cols = 3 ** rank
    W = np.concatenate([grad_w, hess_w.reshape(cols, rank * rank)], axis=1)
    W = W.astype(np.float32)
    W.setflags(write=False)
    return W


def gradient(x: jax.Array, *, method: str = "auto", pad_value="edge",
             batched: bool = False) -> jax.Array:
    """All first partials in one bank pass: (..., *shape, rank).

    ``out[..., i] = ∂x/∂dᵢ`` by central differences (exact on quadratics).
    Thin wrapper over a single-stage pipe graph — chain a fused reduction
    with ``pipe(x).gradient().moments(...)`` to keep the derivative field
    out of HBM entirely.
    """
    return _pipe_for(x.astype(jnp.float32), batched).gradient().run(
        method=method, pad_value=pad_value, out_dtype=x.dtype)


def hessian(x: jax.Array, *, method: str = "auto", pad_value="edge",
            batched: bool = False) -> jax.Array:
    """All second partials in one bank pass: (..., *shape, rank, rank).

    The paper's claim that Hessians of any-rank tensors reduce to a rank-2
    container per grid point — here literally one (numel, rank²) matmul
    (a single-stage pipe graph riding the ``BankPlan`` cache).
    """
    rank = x.ndim - (1 if batched else 0)
    D = _pipe_for(x.astype(jnp.float32), batched).hessian().run(
        method=method, pad_value=pad_value, out_dtype=x.dtype)
    return D.reshape(D.shape[:-1] + (rank, rank))


def _det_planes(H, r: int):
    """det of the r×r matrix field ``H(i, j)`` (one plane per entry).

    Rank ≤ 3 expands the cofactors, term for term as ``jnp.linalg.det``
    does for 2×2 and 3×3 (all entries, no symmetry assumed); larger
    ranks stack the planes into (..., r, r) for ``jnp.linalg.det``."""
    if r == 1:
        return H(0, 0)
    if r == 2:
        return H(0, 0) * H(1, 1) - H(0, 1) * H(1, 0)
    if r == 3:
        return (H(0, 0) * H(1, 1) * H(2, 2)
                + H(0, 1) * H(1, 2) * H(2, 0)
                + H(0, 2) * H(1, 0) * H(2, 1)
                - H(0, 2) * H(1, 1) * H(2, 0)
                - H(0, 0) * H(1, 2) * H(2, 1)
                - H(0, 1) * H(1, 0) * H(2, 2))
    rows = [jnp.stack([H(i, j) for j in range(r)], -1) for i in range(r)]
    return jnp.linalg.det(jnp.stack(rows, -2))


def _curvature_combine(rank: int):
    """det(H) / (1 + |∇|²)² over the channel-major [∇ | vec(H)] planes.

    Takes the bank's channels on the leading non-batch axis, (..., K,
    *spatial) — a ``channels_first`` pointwise stage — so every operand
    is a whole plane and the combine is one elementwise pass."""

    def fn(C):
        cax = C.ndim - rank - 1

        def plane(k):
            return C[(slice(None),) * cax + (k,)]

        sq = plane(0) * plane(0)
        for i in range(1, rank):
            sq = sq + plane(i) * plane(i)
        det = _det_planes(lambda i, j: plane(rank + rank * i + j), rank)
        return det / (1.0 + sq) ** 2

    return fn


def gaussian_curvature(x: jax.Array, *, pad_value="edge",
                       method: str = "auto",
                       batched: bool = False) -> jax.Array:
    """Generalized Gaussian curvature, Eq. (6)/(7), for any-rank dense tensors.

    K = det(H(I)) / (1 + Σ_i I_{d_i}²)²  with H the melt-derived Hessian.
    A two-stage pipe graph: ONE rank + rank² operator-bank pass
    (``curvature_bank``) plus the pointwise det/trace combine, compiled
    into a single executor — the slab is loaded once for all K operators,
    the derivative field never leaves the computation, and on the fused
    path the melt matrix never materializes.  ``batched=True`` stacks
    independent tensors along the leading dim.
    """
    rank = x.ndim - (1 if batched else 0)
    P = (_pipe_for(x.astype(jnp.float32), batched)
         .bank((3,) * rank, curvature_bank(rank))
         .pointwise(_curvature_combine(rank), key=f"gauss-curv-{rank}",
                    channels_first=True))
    return P.run(method=method, pad_value=pad_value, out_dtype=x.dtype)
