"""Streaming moments — mergeable sufficient statistics (DESIGN.md §10).

The enabling primitive for distributed statistics (HPSC, DistStat.jl) is a
small pytree of *mergeable sufficient statistics*: :class:`MomentState`
carries ``(count, mean, M2, M3, M4)`` — central power sums — and
:func:`merge_moments` combines two disjoint-data states with the
numerically-stable pairwise formulas of Chan et al. / Pébay.  Everything
else is derived: streaming mean/var/std/skew/kurtosis over arrays too large
for one pass, per-tile kernel reductions, and the distributed tree merge in
``repro.core.distributed`` are all the same algebra at different scales.

Three execution paths implement identical math (the engine convention):

- ``materialize`` — the melt-matrix oracle: the trivial (1,)*rank operator
  melt really builds ``M`` (one row per element), then reduces it.  Slowest,
  semantics-defining, and it moves ``melt_call_count``.
- ``lax``         — the same chunked-centered single-traversal scheme in
  pure XLA (per-chunk states + Chan tree); the fast CPU path.
- ``fused``       — the Pallas tile-reduction kernel
  (``repro.kernels.melt_stencil.fused_moment_rows``): one pass over
  row tiles, per-tile centered sums in VMEM, Chan tree-merge across
  tiles and columns — ``M`` never exists in HBM, asserted via
  ``melt.melt_call_count``.

Concrete calls dispatch through the shared plan cache
(:class:`repro.core.plan.StatsPlan`); traced calls execute inline.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MomentState",
    "merge_moments",
    "merge_along_axis",
    "moments",
    "stream_moments",
    "execute_moments",
    "reduce_direct",
]

#: lane width for packing a fully-global reduction into the kernel's
#: (rows × lanes) canonical layout — one TPU lane tile
_LANES = 128


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MomentState:
    """Mergeable sufficient statistics: count, mean, central sums M2–M4.

    All five leaves share one shape (the kept axes of the reduction; ``()``
    for global stats), so the state is an ordinary pytree: it vmaps,
    all-gathers, and donates like any array bundle.  ``count`` is floating
    so the distributed combiners can treat every leaf uniformly.

    ``order`` (static pytree metadata, 2 or 4) records which moments the
    state actually carries: order-2 states (the variance fast path) keep
    M3/M4 pinned at zero through *every* merge — Chan cross-terms would
    otherwise repopulate them with junk — so skewness/kurtosis of an
    order-2 state read 0/−3 everywhere, never silently-wrong values.
    Merging states of mixed order yields the weaker order.

    An all-zeros state is the merge identity — padding a merge tree with
    :meth:`zero` states is a no-op by construction.
    """

    count: jax.Array
    mean: jax.Array
    m2: jax.Array
    m3: jax.Array
    m4: jax.Array
    order: int = 4

    def tree_flatten(self):
        return ((self.count, self.mean, self.m2, self.m3, self.m4),
                self.order)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, order=aux)

    @classmethod
    def zero(cls, shape=(), dtype=jnp.float32, order: int = 4
             ) -> "MomentState":
        z = jnp.zeros(shape, dtype)
        return cls(z, z, z, z, z, order=order)

    # -- derived statistics -------------------------------------------------
    @property
    def variance(self) -> jax.Array:
        """Population variance M2 / n (0 for empty states)."""
        return _safe_div(self.m2, self.count)

    @property
    def sample_variance(self) -> jax.Array:
        """Unbiased variance M2 / (n − 1)."""
        return _safe_div(self.m2, self.count - 1.0)

    @property
    def std(self) -> jax.Array:
        return jnp.sqrt(self.variance)

    @property
    def skewness(self) -> jax.Array:
        """g1 = √n · M3 / M2^{3/2} (0 where M2 == 0)."""
        denom = self.m2 ** 1.5
        return _safe_div(jnp.sqrt(self.count) * self.m3, denom)

    @property
    def kurtosis(self) -> jax.Array:
        """Excess kurtosis g2 = n · M4 / M2² − 3 (−3 convention; 0-safe)."""
        return _safe_div(self.count * self.m4, self.m2 ** 2) - 3.0

    def merge(self, other: "MomentState") -> "MomentState":
        return merge_moments(self, other)

    def __repr__(self):
        shape = jnp.shape(self.count)
        return f"MomentState(shape={shape})"


def _safe_div(a, b):
    return a / jnp.where(b == 0, 1.0, b) * (b != 0)


def merge_moments(a: MomentState, b: MomentState) -> MomentState:
    """Chan/Pébay pairwise merge of two disjoint-data states (elementwise).

    Associative and permutation-invariant up to float rounding (the property
    tests pin this against a numpy one-shot oracle); exact when either side
    is empty.  This one function is the whole merge algebra: tile→array,
    chunk→stream, and device→cluster reductions all call it.
    """
    na, nb = a.count, b.count
    n = na + nb
    ns = jnp.where(n == 0, 1.0, n)
    delta = b.mean - a.mean
    mean = a.mean + delta * nb / ns
    nab = na * nb
    m2 = a.m2 + b.m2 + delta**2 * nab / ns
    m3 = (a.m3 + b.m3
          + delta**3 * nab * (na - nb) / ns**2
          + 3.0 * delta * (na * b.m2 - nb * a.m2) / ns)
    m4 = (a.m4 + b.m4
          + delta**4 * nab * (na * na - nab + nb * nb) / ns**3
          + 6.0 * delta**2 * (na * na * b.m2 + nb * nb * a.m2) / ns**2
          + 4.0 * delta * (na * b.m3 - nb * a.m3) / ns)
    order = min(a.order, b.order)
    if order == 2:  # keep the order-2 contract: M3/M4 stay zero, always
        m3 = m4 = jnp.zeros_like(m2)
    return MomentState(n, mean, m2, m3, m4, order=order)


def merge_along_axis(state: MomentState, axis: int = 0) -> MomentState:
    """Pairwise tree-reduce a stacked state along ``axis`` (log₂ depth).

    The input is one state whose leaves carry an extra ``axis`` of
    independent sub-states (per tile, per lane, per device after
    ``all_gather``).  Odd extents are padded with the zero state (merge
    identity).  Shapes are static, so the halving loop unrolls at trace
    time into a balanced merge tree — this is the numerical stability
    argument: error grows with tree depth, not data size.
    """
    n = state.count.shape[axis]
    while n > 1:
        if n % 2:
            state = jax.tree.map(
                lambda l: jnp.concatenate(
                    [l, jnp.zeros_like(jax.lax.slice_in_dim(l, 0, 1,
                                                            axis=axis))],
                    axis=axis),
                state)
            n += 1
        half = n // 2
        a = jax.tree.map(
            lambda l: jax.lax.slice_in_dim(l, 0, half, axis=axis), state)
        b = jax.tree.map(
            lambda l: jax.lax.slice_in_dim(l, half, n, axis=axis), state)
        state = merge_moments(a, b)
        n = half
    return jax.tree.map(lambda l: jnp.squeeze(l, axis=axis), state)


# -- execution paths ---------------------------------------------------------


def _split_axes(ndim: int, axes: Tuple[int, ...]):
    kept = tuple(d for d in range(ndim) if d not in axes)
    return axes, kept


def _direct_state(xcr, order: int = 4) -> MomentState:
    """One-shot centered reduction over the LAST axis of (C, R) → (C,).

    Lanes-first layout: kept lanes lead, reduction rows trail — a
    *zero-copy* reshape of the common layouts (batched stacks, global
    flats), so no physical transpose sits in front of the reduction.  The
    oracle's reduction step and the single-chunk base case: mean first,
    then centered power sums — numerically equivalent to the kernel's
    per-tile scheme at single-tile scale.  ``order=2`` leaves M3/M4 at
    zero (the variance fast path).
    """
    R = xcr.shape[-1]
    xf = xcr.astype(jnp.float32)
    count = jnp.full(xf.shape[:-1], float(R), jnp.float32)
    z = jnp.zeros(xf.shape[:-1], jnp.float32)
    if R == 0:
        return MomentState(count * 0.0, z, z, z, z)
    mean = jnp.mean(xf, axis=-1)
    c = xf - mean[..., None]
    c2 = c * c
    m3 = jnp.sum(c2 * c, axis=-1) if order == 4 else z
    m4 = jnp.sum(c2 * c2, axis=-1) if order == 4 else z
    return MomentState(count, mean, jnp.sum(c2, axis=-1), m3, m4)


#: row-chunk size for the lax streaming path — large enough to amortize the
#: merge tree, small enough to keep the per-chunk working set cache-local
_LAX_CHUNK_ROWS = 16384


def _chunked_state_cr(xcr, order: int = 4) -> MomentState:
    """Pure-XLA mirror of the kernel's scheme: per-chunk centered states
    over the last axis of (C, R), folded by the Chan tree → state (C,).

    One traversal of the input (the streaming claim on the lax path);
    single-chunk inputs degenerate to :func:`_direct_state` exactly.
    """
    C, R = xcr.shape
    T = min(R, _LAX_CHUNK_ROWS) or 1
    tiles = R // T
    if tiles <= 1:
        return _direct_state(xcr, order)
    bulk = xcr[:, :tiles * T].astype(jnp.float32).reshape(C, tiles, T)
    mu = jnp.mean(bulk, axis=2)                       # (C, tiles)
    c = bulk - mu[..., None]
    c2 = c * c
    z = jnp.zeros_like(mu)
    state = MomentState(
        jnp.full(mu.shape, float(T), jnp.float32), mu,
        jnp.sum(c2, axis=2),
        jnp.sum(c2 * c, axis=2) if order == 4 else z,
        jnp.sum(c2 * c2, axis=2) if order == 4 else z,
    )
    state = merge_along_axis(state, axis=1)
    if tiles * T < R:
        state = merge_moments(state,
                              _direct_state(xcr[:, tiles * T:], order))
    return state


def _states_from_tiles(sums, counts) -> MomentState:
    """(C, tiles, order, W) kernel sums + (tiles,) counts → stacked
    states with (C, tiles·W) leaves (one per tile column)."""
    C, tiles, order, width = sums.shape
    n = jnp.broadcast_to(counts[None, :, None], (C, tiles, width))
    ns = jnp.where(n == 0, 1.0, n)
    s1, m2 = sums[:, :, 0], sums[:, :, 1]
    z = jnp.zeros_like(s1)
    m3 = sums[:, :, 2] if order == 4 else z
    m4 = sums[:, :, 3] if order == 4 else z
    st = MomentState(n, s1 / ns, m2, m3, m4)
    return jax.tree.map(lambda l: l.reshape(C, tiles * width), st)


#: trailing extents the moment kernel reads as whole rows; outside this
#: range the flat values pack 128 to a row instead
_ROW_WIDTHS = (32, 8192)


def _fused_state(xt, order: int = 4) -> MomentState:
    """Kernel path over kept-first ``(C, *reduced)`` → state (C,).

    The kernel's rows are the reduced values' own trailing axis,
    ``(C, M, W)`` — a reshape that keeps the minor axis, so XLA never lays
    a whole volume out one-dimensionally (slow to compile at CT sizes)
    — when ``W`` lies in :data:`_ROW_WIDTHS`.  Otherwise each channel's
    flat values pack 128 to a row, with a direct tail state for the
    ragged remainder — zero padding is never counted as data.  Per-column
    tile states merge pairwise across tiles and columns.
    """
    from repro.kernels import ops as _ops  # lazy: kernels optional

    C, W = xt.shape[0], xt.shape[-1]
    if xt.ndim > 2 and _ROW_WIDTHS[0] <= W <= _ROW_WIDTHS[1]:
        rows, tail = xt.reshape(C, -1, W), None
    else:
        flat = xt.reshape(C, -1)
        bulk = flat.shape[1] - flat.shape[1] % _LANES
        rows, tail = flat[:, :bulk].reshape(C, -1, _LANES), flat[:, bulk:]
    parts = []
    if rows.size:
        sums, counts = _ops.fused_moment_sums(rows, order=order)
        parts.append(merge_along_axis(_states_from_tiles(sums, counts),
                                      axis=1))
    if tail is not None and tail.shape[1]:
        parts.append(_direct_state(tail, order))
    if not parts:  # zero-element input: the merge identity
        return MomentState.zero((C,))
    state = parts[0]
    for p in parts[1:]:
        state = merge_moments(state, p)
    return state


def _materialize_state(x, axes, kept, order: int = 4) -> MomentState:
    """The melt oracle: build the trivial-operator melt matrix, reduce it.

    ``melt`` with op_shape (1,)*rank produces one melt row per element —
    the paper-faithful decouple step — so this path genuinely materializes
    ``M`` (and moves ``melt_call_count``, which is how tests prove the
    fused path doesn't).
    """
    from repro.core.melt import melt  # deferred: keep import DAG shallow

    if kept:
        # kept axes ride the melt batch dim: (C, R) batched melt, op (1,)
        xt = jnp.transpose(x, kept + axes)
        C = int(np.prod([x.shape[k] for k in kept]))
        R = int(np.prod([x.shape[a] for a in axes]))
        xm = xt.reshape(C, R)
        M = melt(xm, (1,), batched=True)          # data: (C, R, 1)
        return _direct_state(M.data[..., 0], order)    # lanes × rows
    flat = x.reshape(-1)
    M = melt(flat, (1,))                          # data: (N, 1)
    st = _direct_state(M.data.reshape(1, -1), order)
    return jax.tree.map(lambda l: jnp.squeeze(l, axis=0), st)


def reduce_direct(x, axes: Tuple[int, ...], order: int = 4) -> MomentState:
    """The materialize oracle's reduction WITHOUT the trivial-op melt.

    Used by fused pipelines (``repro.pipe``): a reduction fused into its
    producing melt pass consumes the producer's value directly — the
    trivial (1,)*rank melt of :func:`_materialize_state` is an identity
    gather, so skipping it is numerically exact while the melt-call
    counter stays put (the no-extra-melt contract of DESIGN.md §11).
    """
    axes, kept = _split_axes(x.ndim, tuple(axes))
    kept_shape = tuple(x.shape[k] for k in kept)
    if kept:
        C = int(np.prod(kept_shape))
        xcr = jnp.transpose(x, kept + axes).reshape(C, -1)
        state = _direct_state(xcr, order)
    else:
        st = _direct_state(x.reshape(1, -1), order)
        state = jax.tree.map(lambda l: jnp.squeeze(l, axis=0), st)
    if order == 2:
        z = jnp.zeros_like(state.m2)
        state = MomentState(state.count, state.mean, state.m2, z, z, order=2)
    return jax.tree.map(lambda l: l.reshape(kept_shape), state)


def execute_moments(x, axes: Tuple[int, ...], method: str,
                    order: int = 4) -> MomentState:
    """Run one resolved moments problem — shared by plans and direct calls.

    ``axes`` must already be normalized (see
    :func:`repro.core.plan.normalize_axes`).  Returns a state whose leaves
    have the kept-axes shape (scalar leaves for a global reduction).
    """
    axes, kept = _split_axes(x.ndim, tuple(axes))
    kept_shape = tuple(x.shape[k] for k in kept)
    if method == "materialize":
        state = _materialize_state(x, axes, kept, order)
    elif method == "lax":
        if kept:
            # lanes-first: zero-copy when the kept axes lead (batched stacks)
            C = int(np.prod(kept_shape))
            xcr = jnp.transpose(x, kept + axes).reshape(C, -1)
            state = _chunked_state_cr(xcr, order)
        else:
            st = _chunked_state_cr(x.reshape(1, -1), order)
            state = jax.tree.map(lambda l: jnp.squeeze(l, axis=0), st)
    elif method == "fused":
        if kept:
            C = int(np.prod(kept_shape))
            xt = jnp.transpose(x, kept + axes)
            state = _fused_state(xt.reshape((C,) + xt.shape[len(kept):]),
                                 order)
        else:
            st = _fused_state(x[None], order)
            state = jax.tree.map(lambda l: jnp.squeeze(l, axis=0), st)
    else:
        raise ValueError(f"unknown method {method!r}")
    if order == 2:
        # the internal tile merges deposit junk in the unsummed M3/M4
        # slots; pin them and stamp the static order so every downstream
        # merge (stream, distributed tree) preserves the zeros
        z = jnp.zeros_like(state.m2)
        state = MomentState(state.count, state.mean, state.m2, z, z,
                            order=2)
    return jax.tree.map(lambda l: l.reshape(kept_shape), state)


def moments(
    x: jax.Array,
    axis=None,
    *,
    method: str = "auto",
    batched: bool = False,
    order: int = 4,
) -> MomentState:
    """Sufficient statistics of ``x`` over ``axis`` (all axes by default).

    ``axis`` follows numpy reduce semantics (the *reduced* axes); the
    state's leaves take the shape of the kept axes — ``axis=(0, 1)`` on an
    (H, W, C) image yields per-channel statistics of shape (C,).
    ``batched=True`` keeps dim 0 (a stack of independent tensors — one
    state per item, one dispatch).  ``order=2`` computes count/mean/M2
    only (M3/M4 stay zero; skewness/kurtosis are undefined) — the
    streaming-variance fast path, roughly half the flops.

    Thin wrapper over a reduction-only pipe graph (DESIGN.md §11), which
    lowers straight back onto the process-wide
    :class:`~repro.core.plan.StatsPlan` cache for concrete inputs and
    executes inline for traced ones — identical dispatch to the pre-pipe
    implementation.
    """
    from repro.pipe import pipe  # deferred: pipe builds on this module

    P = pipe.batched(x) if batched else pipe(x)
    return P.moments(order=order, axis=axis).run(method=method)


def stream_moments(
    chunks: Iterable[jax.Array],
    axis=None,
    *,
    method: str = "auto",
    batched: bool = False,
    order: int = 4,
) -> MomentState:
    """Fold an iterable of chunks into one state — O(state) memory.

    Every chunk is reduced independently (same ``axis`` spec, so kept-axes
    shapes must agree across chunks) and Chan-merged into the running
    state: the single-machine face of the distributed merge tree.  Chunk
    boundaries are invisible in the result (the chunking-invariance
    property test).
    """
    state: Optional[MomentState] = None
    for chunk in chunks:
        s = moments(jnp.asarray(chunk), axis, method=method, batched=batched,
                    order=order)
        state = s if state is None else merge_moments(state, s)
    if state is None:
        raise ValueError("stream_moments needs at least one chunk")
    return state
