"""MeltEngine — decouple → compute → couple (paper Fig. 2), path-selectable.

``apply_stencil`` is the generic linear-stencil entry point; its
multi-operator sibling ``apply_stencil_bank`` evaluates K operators over
*one* melt pass (DESIGN.md §9).  Three execution paths implement the
identical math:

- ``materialize`` : paper-faithful — build the melt matrix ``M`` in memory,
  contract ``M @ v`` (array-programming broadcast), fold back.  This is the
  oracle and the semantics definition.
- ``fused``       : TPU production path — the Pallas kernel in
  ``repro.kernels.melt_stencil`` streams melt tiles through VMEM and feeds
  the MXU; ``M`` never exists in HBM (DESIGN.md §2 hardware adaptation).
- ``lax``         : XLA-native convolution lowering, used as a second
  independent reference and as the fast CPU path.

All paths are rank-agnostic, and all three accept an optional leading
*batch* dimension (``batched=True``): every melt row of every batch item is
independent (paper §3.1), so a batch is just more rows — one dispatch, one
kernel launch (DESIGN.md §3).

Banks additionally support **separable factorization**: when every bank
column is a rank-1 outer product (Gaussian weights, every finite-difference
stencil), the rank-k dense pass is rewritten as k successive 1-D passes —
O(Σkᵢ) work per grid point instead of O(Πkᵢ) — detected automatically on
concrete weights and opt-out-able (``separable=False``).

Concrete (non-traced) calls are routed through the :class:`StencilPlan` /
:class:`BankPlan` cache (DESIGN.md §7): repeated calls with the same shape
signature reuse a pre-derived ``QuasiGrid`` and a pre-traced jitted
executor.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grid import (
    QuasiGrid,
    make_quasi_grid,
    normalize_pad_value,
    normalize_tuple,
    pass_grids,
)
from repro.core.melt import melt, pad_array, unmelt
from repro.core.plan import (
    ExecOptions,
    get_bank_plan,
    get_plan,
    separable_eligible,
    separable_profitable,
)


__all__ = [
    "apply_stencil",
    "apply_stencil_bank",
    "execute_stencil",
    "execute_stencil_bank",
    "apply_channels_first",
    "execute_separable_bank",
    "separable_factors",
    "MeltEngine",
]


def _cast_out(out, opts: ExecOptions):
    """Apply the validated ``out_dtype`` option (no-op when ``None``)."""
    return out if opts.out_dtype is None else out.astype(opts.out_dtype)


def _stencil_materialize(x, grid: QuasiGrid, weights, pad_value, batched):
    M = melt(x, grid.op_shape, grid.stride, grid.padding, grid.dilation,
             pad_value=pad_value, grid=grid, batched=batched)
    rows = M.data @ weights.astype(M.data.dtype)
    return unmelt(rows, grid, batched=batched)


def _stencil_lax(x, grid: QuasiGrid, weights, pad_value, batched):
    lead = [(0, 0)] if batched else []
    xp, pad_cfg = _conv_lhs_pads(x, grid, pad_value, lead)
    kern = weights.reshape(grid.op_shape).astype(x.dtype)
    lhs = xp[:, None] if batched else xp[None, None]  # N, C, spatial...
    rhs = kern[None, None]  # O, I, spatial...
    spatial = "".join(chr(ord("0") + i) for i in range(grid.rank))
    dn = jax.lax.conv_dimension_numbers(
        lhs.shape, rhs.shape,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial),
    )
    out = jax.lax.conv_general_dilated(
        lhs, rhs,
        window_strides=grid.stride,
        padding=pad_cfg,
        rhs_dilation=grid.dilation,
        dimension_numbers=dn,
    )
    return out[:, 0] if batched else out[0, 0]


def execute_stencil(x, grid: QuasiGrid, weights, pad_value, method: str,
                    batched: bool = False):
    """Run one resolved stencil problem — shared by plans and direct calls."""
    support = _fused_support(weights) if method == "fused" else None
    weights = jnp.asarray(weights)
    if method == "materialize":
        return _stencil_materialize(x, grid, weights, pad_value, batched)
    if method == "lax":
        return _stencil_lax(x, grid, weights, pad_value, batched)
    if method == "fused":
        from repro.kernels import melt_stencil_ops  # lazy: kernels optional

        return melt_stencil_ops.fused_stencil(
            x, grid, weights, pad_value=normalize_pad_value(pad_value),
            batched=batched, support=support,
        )
    raise ValueError(f"unknown method {method!r}")


def _fused_support(weights):
    """The static zero pattern of concrete weights, for the fused kernel
    to skip their zero products (``melt_stencil.weight_support``).  Traced
    weights, such as a plan executor's argument or an array made inside a
    trace, run dense (``None``): callers that hold their weights in numpy
    (the pipe's steps) pass them as such."""
    if isinstance(weights, jax.core.Tracer):
        return None
    from repro.kernels.melt_stencil import weight_support

    W = np.asarray(weights)
    return weight_support(W.reshape(W.shape[0], -1))


# -- operator banks (DESIGN.md §9) -----------------------------------------


def _bank_materialize(x, grid: QuasiGrid, W, pad_value, batched):
    M = melt(x, grid.op_shape, grid.stride, grid.padding, grid.dilation,
             pad_value=pad_value, grid=grid, batched=batched)
    rows = M.data @ W.astype(M.data.dtype)  # (..., rows, K)
    return unmelt(rows, grid, batched=batched)


def _conv_lhs_pads(x, grid: QuasiGrid, pad_value, lead):
    """Shared lax-path padding split: pre-pad for non-zero/mode fills."""
    pv = normalize_pad_value(pad_value)
    if isinstance(pv, str) or pv != 0.0:
        xp = pad_array(x, lead + list(zip(grid.pad_lo, grid.pad_hi)), pv)
        return xp, [(0, 0)] * grid.rank
    return x, list(zip(grid.pad_lo, grid.pad_hi))


def _bank_lax(x, grid: QuasiGrid, W, pad_value, batched,
              depthwise: bool = False, channels_last: bool = True):
    """Grouped ``conv_general_dilated`` with K output channels.

    Dense bank: input channel 1 fans out to K outputs.  ``depthwise``:
    input channel k maps to output k via ``feature_group_count=K`` (the
    separable per-lane pass); the caller passes ``x`` with a trailing
    channel axis.  ``channels_last=False`` keeps the convolution's own
    (N, K, *out_shape) layout.
    """
    K = W.shape[1]
    if not depthwise:
        lead = [(0, 0)] if batched else []
        xp, pad_cfg = _conv_lhs_pads(x, grid, pad_value, lead)
        lhs = xp[:, None] if batched else xp[None, None]  # (N, 1, *spatial)
    else:
        xc = jnp.moveaxis(x, -1, 1 if batched else 0)  # channels first
        if not batched:
            xc = xc[None]
        xp, pad_cfg = _conv_lhs_pads(xc, grid, pad_value, [(0, 0), (0, 0)])
        lhs = xp  # (N, K, *spatial)
    # (..., I, O): the bank's own row order, so no transpose of W
    kern = W.reshape(grid.op_shape + (1, K)).astype(x.dtype)
    spatial = "".join(chr(ord("0") + i) for i in range(grid.rank))
    dn = jax.lax.conv_dimension_numbers(
        lhs.shape, kern.shape,
        ("NC" + spatial, spatial + "IO", "NC" + spatial),
    )
    out = jax.lax.conv_general_dilated(
        lhs, kern,
        window_strides=grid.stride,
        padding=pad_cfg,
        rhs_dilation=grid.dilation,
        dimension_numbers=dn,
        feature_group_count=K if depthwise else 1,
    )  # (N, K, *out_shape)
    if channels_last:
        out = jnp.moveaxis(out, 1, -1)
    return out if batched else out[0]


def _trailing_channels(out, rank: int, batched: bool):
    """A channel axis on the leading non-batch axis moves to trailing."""
    lead = 1 if batched else 0
    return jnp.moveaxis(out, lead, -1) if out.ndim > lead + rank else out


def apply_channels_first(fn, h, rank: int, batched: bool,
                         channel_major: bool = False):
    """Run ``fn``, elementwise over channel-major values, on ``h``.

    A trailing channel axis of ``h`` moves to the leading non-batch axis
    first, unless ``channel_major`` says it is there already; a channel
    axis in the result moves back to trailing.  ``rank`` is the spatial
    rank (DESIGN.md §11)."""
    lead = 1 if batched else 0
    if h.ndim > lead + rank and not channel_major:
        h = jnp.moveaxis(h, -1, lead)
    return _trailing_channels(fn(h), rank, batched)


def execute_stencil_bank(x, grid: QuasiGrid, weight_matrix, pad_value,
                         method: str, batched: bool = False,
                         pointwise=None):
    """K operators, one melt pass: (..., *spatial) → (..., *out_shape, K).

    ``pointwise`` is an elementwise function of channel-major values
    (``apply_channels_first``) run on the bank's output where it is
    computed: the lax and fused paths compute channel-major, and the
    fused path hands it the kernel's rows before their crop, so the
    K-channel field is never relaid out (DESIGN.md §11).
    """
    W = jnp.asarray(weight_matrix)
    if method == "fused":
        from repro.kernels import melt_stencil_ops  # lazy: kernels optional

        out = melt_stencil_ops.fused_stencil_bank(
            x, grid, W, pad_value=normalize_pad_value(pad_value),
            batched=batched, pointwise=pointwise,
            support=_fused_support(weight_matrix),
        )
        return (out if pointwise is None
                else _trailing_channels(out, grid.rank, batched))
    if method == "materialize":
        out = _bank_materialize(x, grid, W, pad_value, batched)
    elif method == "lax":
        out = _bank_lax(x, grid, W, pad_value, batched,
                        channels_last=pointwise is None)
    else:
        raise ValueError(f"unknown method {method!r}")
    if pointwise is None:
        return out
    return apply_channels_first(pointwise, out, grid.rank, batched,
                                channel_major=method == "lax")


def _depthwise_materialize(xc, grid: QuasiGrid, Wd, pad_value, batched):
    """Per-lane pass via batched melt: lanes ride the melt batch axis."""
    K = xc.shape[-1]
    lead = xc.shape[:1] if batched else ()
    xm = jnp.moveaxis(xc, -1, len(lead))  # (..., K, *spatial)
    flatb = xm.reshape((-1,) + grid.in_shape)
    M = melt(flatb, grid.op_shape, grid.stride, grid.padding, grid.dilation,
             pad_value=pad_value, grid=grid, batched=True)
    data = M.data.reshape(lead + (K, grid.num_rows, grid.num_cols))
    rows = jnp.einsum("...krc,ck->...kr", data, Wd.astype(data.dtype))
    out = rows.reshape(lead + (K,) + grid.out_shape)
    return jnp.moveaxis(out, len(lead), -1)


def execute_stencil_depthwise(xc, grid: QuasiGrid, weights, pad_value,
                              method: str, batched: bool = False):
    """Per-lane stencil: lane k of ``xc`` (..., *spatial, K) is filtered by
    column k of ``weights`` (numel, K) — the separable 1-D pass primitive.
    """
    Wd = jnp.asarray(weights)
    if method == "materialize":
        return _depthwise_materialize(xc, grid, Wd, pad_value, batched)
    if method == "lax":
        return _bank_lax(xc, grid, Wd, pad_value, batched, depthwise=True)
    if method == "fused":
        from repro.kernels import melt_stencil_ops  # lazy: kernels optional

        return melt_stencil_ops.fused_stencil_depthwise(
            xc, grid, Wd, pad_value=normalize_pad_value(pad_value),
            batched=batched,
        )
    raise ValueError(f"unknown method {method!r}")


def execute_separable_bank(x, grid: QuasiGrid, factors, pad_value,
                           method: str, batched: bool = False,
                           pointwise=None):
    """Run a factored bank as ``rank`` successive 1-D passes.

    ``factors[i]`` is (op_shape[i], K).  Pass 0 is a dense 1-D bank (one
    input channel fans out to K lanes); passes 1..rank-1 are depthwise (each
    lane carries its own factor).  Exact for stride-1 'same' grids under
    zero / edge / reflect padding (``separable_eligible`` refuses nonzero
    constants — they don't commute with per-dim passes), and exact for
    'valid' grids unconditionally, strides included (no fill is ever
    read): pass ``i`` decimates only dim ``i`` by the grid's own stride
    there, so ``Σ_a Π_d w_d[a_d] · x[s·g + a]`` factors into the per-dim
    passes and the intermediate shapes walk from ``in_shape`` down to
    ``out_shape``.  ``pointwise`` is as for ``execute_stencil_bank``.
    """
    if method == "fused":
        from repro.kernels import melt_stencil_ops  # lazy: kernels optional

        out = melt_stencil_ops.fused_separable_bank(
            x, grid, tuple(factors),
            pad_value=normalize_pad_value(pad_value), batched=batched)
    else:
        grids = pass_grids(grid)
        out = execute_stencil_bank(x, grids[0], factors[0], pad_value,
                                   method, batched)
        for g, f in zip(grids[1:], factors[1:]):
            out = execute_stencil_depthwise(out, g, f, pad_value, method,
                                            batched)
    if pointwise is None:
        return out
    return apply_channels_first(pointwise, out, grid.rank, batched)


#: memoized factorization results keyed on (weight bytes, dtype, shape, op
#: shape) — the detection is numpy work plus device puts, and it would
#: otherwise run on EVERY concrete bank call, defeating the BankPlan
#: cache's amortization.  Content-keyed (hashing pulls W host-side once per
#: call — cheap for operator-sized matrices), LRU-bounded like the plan
#: cache, and locked for the same reason; entries are immutable.
_FACTOR_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_FACTOR_CACHE_CAPACITY = 128
_FACTOR_LOCK = threading.Lock()


def _cached_separable_factors(W, op_t):
    Wh = np.asarray(W)
    key = (Wh.tobytes(), Wh.dtype.str, Wh.shape, op_t)
    with _FACTOR_LOCK:
        if key in _FACTOR_CACHE:
            _FACTOR_CACHE.move_to_end(key)
            return _FACTOR_CACHE[key]
    factors = separable_factors(Wh, op_t)
    with _FACTOR_LOCK:
        _FACTOR_CACHE[key] = factors
        while len(_FACTOR_CACHE) > _FACTOR_CACHE_CAPACITY:
            _FACTOR_CACHE.popitem(last=False)
    return factors


def separable_factors(weight_matrix, op_shape, tol: float = 1e-6):
    """Factor every bank column into a rank-1 outer product, or ``None``.

    Returns ``[f_0, …, f_{rank-1}]`` with ``f_i`` of shape
    ``(op_shape[i], K)`` such that column k of the weight matrix equals
    ``⊗_i f_i[:, k]``; ``None`` when any column is not rank-1 within
    ``tol`` (relative to the column's max magnitude).  Pure numpy on
    concrete weights — runs at plan-build time, never inside a trace.

    Gaussian weights with diagonal covariance factor exactly; so does every
    central-difference stencil (each is a product of per-dim difference /
    indicator vectors).  Full-covariance Gaussians (cross terms) do not.
    """
    W = np.asarray(weight_matrix, dtype=np.float64)
    op_shape = tuple(int(k) for k in op_shape)
    rank = len(op_shape)
    if W.ndim != 2 or rank < 2:
        return None
    K = W.shape[1]
    facs = [np.zeros((k, K)) for k in op_shape]
    for col in range(K):
        T = W[:, col].reshape(op_shape)
        amax = float(np.abs(T).max())
        if amax == 0.0:
            continue  # all-zero operator: zero factors reproduce it
        idx = np.unravel_index(int(np.argmax(np.abs(T))), op_shape)
        piv = T[idx]
        vecs = []
        for i in range(rank):
            sl = list(idx)
            sl[i] = slice(None)
            vecs.append(T[tuple(sl)].copy())
        vecs[0] /= piv ** (rank - 1)
        recon = vecs[0]
        for v in vecs[1:]:
            recon = np.multiply.outer(recon, v)
        if not np.allclose(recon, T, rtol=0.0, atol=tol * amax):
            return None
        for i in range(rank):
            facs[i][:, col] = vecs[i]
    # factors keep the bank's own float dtype (under x64 a float64 bank
    # must not silently lose precision when the rewrite engages)
    w_dt = np.asarray(weight_matrix).dtype
    out_dt = w_dt if np.issubdtype(w_dt, np.floating) else np.float32
    return [jnp.asarray(f, dtype=out_dt) for f in facs]


def apply_stencil(
    x: jax.Array,
    op_shape,
    weights: jax.Array,
    *,
    stride=1,
    padding: str = "same",
    dilation=1,
    pad_value=0.0,
    method: str = "auto",
    grid: Optional[QuasiGrid] = None,
    batched: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Apply a linear stencil (operator ravel-vector ``weights``) to ``x``.

    Correlation convention: output[g] = Σ_c weights[c] · x[g + offset_c].

    With ``batched=True`` the leading dim of ``x`` is a stack of independent
    tensors and ``op_shape``/``stride``/... describe the trailing dims; the
    result keeps the batch dim.  ``method``/``pad_value``/``batched``/
    ``out_dtype`` are validated up front through
    :class:`~repro.core.plan.ExecOptions` (bad spellings raise with the
    valid choices).  Concrete inputs dispatch through the process-wide
    :class:`~repro.core.plan.StencilPlan` cache; traced inputs (already
    inside someone's jit/shard_map) execute inline.
    """
    opts = ExecOptions.make(method, pad_value, batched, out_dtype)
    weights = jnp.asarray(weights).reshape(-1)
    if grid is None:
        if not isinstance(x, jax.core.Tracer):
            plan = get_plan(x.shape, x.dtype, op_shape, stride, padding,
                            dilation, opts.pad_value, method, batched)
            _check_weights(weights, plan.grid)
            return _cast_out(plan(x, weights), opts)
        spatial = x.shape[1:] if batched else x.shape
        grid = make_quasi_grid(spatial, op_shape, stride, padding, dilation)
    _check_weights(weights, grid)
    return _cast_out(
        execute_stencil(x, grid, weights, opts.pad_value,
                        opts.resolved_method, batched), opts)


def apply_stencil_bank(
    x: jax.Array,
    op_shape,
    weight_matrix: jax.Array,
    *,
    stride=1,
    padding: str = "same",
    dilation=1,
    pad_value=0.0,
    method: str = "auto",
    separable="auto",
    grid: Optional[QuasiGrid] = None,
    batched: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Apply K linear operators over one melt pass (DESIGN.md §9).

    ``weight_matrix`` is (numel(m), K) — one ravel-vector column per
    operator; a 1-D vector is treated as K=1.  Returns the K results
    stacked on a trailing axis: ``(*out_shape, K)`` (plus the leading batch
    dim when ``batched``).  Column k equals
    ``apply_stencil(x, op_shape, weight_matrix[:, k], ...)`` on every path.

    ``separable`` controls the O(Σkᵢ)-vs-O(Πkᵢ) rewrite:

    - ``"auto"`` (default): factor concrete weights when the geometry
      allows (stride-1 'same', rank ≥ 2) *and* the cost gate predicts a
      win (``separable_profitable``: Πkᵢ ≳ 4·Σkᵢ); else the dense bank.
    - ``True``: require the rewrite (raises if weights don't factor or the
      geometry forbids it).
    - ``False``: always run the dense bank (the opt-out).

    Concrete inputs dispatch through the :class:`~repro.core.plan.BankPlan`
    cache; traced inputs execute inline.
    """
    opts = ExecOptions.make(method, pad_value, batched, out_dtype)
    W = jnp.asarray(weight_matrix)
    if W.ndim == 1:
        W = W[:, None]
    if W.ndim != 2:
        raise ValueError(
            f"weight_matrix must be (numel, K), got shape {W.shape}")
    K = W.shape[1]
    spatial = x.shape[1:] if batched else x.shape
    rank = len(spatial)
    op_t = normalize_tuple(op_shape, rank, "op_shape")
    stride_t = normalize_tuple(stride, rank, "stride")
    _check_bank_weights(W, op_t)

    factors = None
    eligible = separable_eligible(rank, stride_t, padding, pad_value)
    concrete_w = not isinstance(W, jax.core.Tracer)
    if separable == "auto":
        if eligible and concrete_w and separable_profitable(op_t):
            factors = _cached_separable_factors(W, op_t)
    elif separable is True:
        if not eligible:
            raise ValueError(
                "separable execution requires a stride-1 'same' grid of "
                "rank >= 2 with zero/edge/reflect padding")
        if not concrete_w:
            raise ValueError(
                "separable=True needs concrete weights (factorization "
                "happens outside the trace); pass separable=False under jit")
        factors = _cached_separable_factors(W, op_t)
        if factors is None:
            raise ValueError(
                "weight_matrix is not rank-1 factorable; pass "
                "separable=False for the dense bank")
    elif separable is not False:
        raise ValueError(f"separable must be 'auto'/True/False, "
                         f"got {separable!r}")

    wargs = tuple(factors) if factors is not None else W
    if grid is None and not isinstance(x, jax.core.Tracer):
        plan = get_bank_plan(x.shape, x.dtype, op_t, stride_t, padding,
                             dilation, opts.pad_value, method, batched, K,
                             separable=factors is not None)
        return _cast_out(plan(x, wargs), opts)
    if grid is None:
        grid = make_quasi_grid(spatial, op_t, stride_t, padding, dilation)
    meth = opts.resolved_method
    pv = opts.pad_value
    if factors is not None:
        return _cast_out(
            execute_separable_bank(x, grid, wargs, pv, meth, batched), opts)
    return _cast_out(execute_stencil_bank(x, grid, W, pv, meth, batched),
                     opts)


def _check_weights(weights, grid: QuasiGrid):
    if weights.shape[0] != grid.num_cols:
        raise ValueError(
            f"weights has {weights.shape[0]} elements, operator needs "
            f"{grid.num_cols}"
        )


def _check_bank_weights(W, op_t):
    numel = int(np.prod(op_t))
    if W.shape[0] != numel:
        raise ValueError(
            f"weight_matrix has {W.shape[0]} rows, operator needs {numel}"
        )


class MeltEngine:
    """Explicit decouple→compute→couple driver (paper Fig. 2).

    Mostly useful for inspection/benchmarks; production code calls
    ``apply_stencil`` / the ``repro.pipe`` graph API directly.
    ``batched=True`` treats the leading dim of every input as a stack of
    independent tensors.  ``__call__`` is a thin wrapper over a
    single-stage pipe graph (which lowers right back to the
    :class:`~repro.core.plan.StencilPlan` cache).
    """

    def __init__(self, op_shape, stride=1, padding="same", dilation=1,
                 pad_value=0.0, method="auto", batched=False):
        opts = ExecOptions.make(method, pad_value, batched)
        self.op_shape = op_shape
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.pad_value = opts.pad_value
        self.method = method
        self.batched = batched

    def grid_for(self, x) -> QuasiGrid:
        spatial = x.shape[1:] if self.batched else x.shape
        return make_quasi_grid(
            spatial, self.op_shape, self.stride, self.padding, self.dilation
        )

    def decouple(self, x):
        return melt(x, self.op_shape, self.stride, self.padding,
                    self.dilation, pad_value=self.pad_value,
                    batched=self.batched)

    def compute(self, M, weights):
        return M.data @ jnp.asarray(weights).reshape(-1).astype(M.data.dtype)

    def couple(self, rows, grid: QuasiGrid):
        return unmelt(rows, grid, batched=self.batched)

    def __call__(self, x, weights):
        if isinstance(weights, jax.core.Tracer):
            # traced weights can't become a graph record (ops carry a
            # concrete weight digest); the plan executor takes weights as
            # a jitted argument, so delegate straight to it
            return apply_stencil(
                x, self.op_shape, weights,
                stride=self.stride, padding=self.padding,
                dilation=self.dilation, pad_value=self.pad_value,
                method=self.method, batched=self.batched,
            )
        from repro.pipe import pipe  # deferred: pipe builds on this module

        P = pipe.batched(x) if self.batched else pipe(x)
        return P.stencil(
            self.op_shape, weights, stride=self.stride, padding=self.padding,
            dilation=self.dilation,
        ).run(method=self.method, pad_value=self.pad_value)
