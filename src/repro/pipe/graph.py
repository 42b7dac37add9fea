"""The pipe graph IR — deferred op records + the :class:`Pipe` builder.

``pipe(x)`` (or ``pipe.batched(xs)``) starts a *lazy* pipeline: every
builder method (`.stencil`, `.bank`, `.gaussian`, `.gradient`, `.zscore`,
`.moments`, …) appends an immutable op record and returns a new
:class:`Pipe` — nothing executes until ``.run()`` / ``.grad()``.  The op
chain is a pure *signature*: each op knows its static geometry and a
content digest of its weights, so a whole pipeline hashes into one plan
key and repeated ``.run()`` calls intern a single compiled executor
(DESIGN.md §11).

The planner composes adjacent linear stages aggressively: 'valid'
chains merge into one operator-bank pass under *any* strides (composite
stride = product of stage strides), and stride-1 'same' chains plan as
a composed interior pass plus boundary slabs that replay the original
stages — so multi-stage smoothing/derivative graphs usually execute as
ONE data traversal.  Dilation, K>1 predecessors, and mixed padding keep
their own passes.

Graph validity is enforced at build time with actionable errors:

- a ``bank``-kind op appends a trailing channel axis, so it must be the
  *last* linear stage (a stencil over a channeled value is ambiguous);
- reductions (``moments`` / ``hist`` / ``cov``) are terminal;
- ``moments(axis=...)`` with an explicit axis spec is only meaningful for
  a reduction-only pipeline (multi-stage graphs reduce the spatial axes).
"""
from __future__ import annotations

import hashlib
import math
from typing import Optional, Tuple

import numpy as np

from repro.core.grid import normalize_tuple

__all__ = [
    "Pipe",
    "pipe",
    "LinearOp",
    "PointwiseOp",
    "ZscoreOp",
    "MomentsOp",
    "HistOp",
    "CovOp",
]


def weight_digest(arr) -> str:
    """Short content digest of a weight array — the key fragment that lets
    two pipelines with identical weights share one interned plan."""
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha1(a.tobytes())
    h.update(repr((a.shape, a.dtype.str)).encode())
    return h.hexdigest()[:16]


class LinearOp:
    """One linear melt stage: ``kind='stencil'`` keeps the value's shape
    algebra (no channel axis); ``kind='bank'`` appends a trailing K axis."""

    __slots__ = ("kind", "op_shape", "weights", "K", "stride", "padding",
                 "dilation", "_digest")

    def __init__(self, kind, op_shape, weights, stride, padding, dilation):
        rank = len(op_shape)
        self.kind = kind
        self.op_shape = tuple(int(k) for k in op_shape)
        W = np.asarray(weights)
        if W.ndim == 1:
            W = W[:, None]
        if W.ndim != 2:
            raise ValueError(f"weights must be (numel,) or (numel, K), got "
                             f"shape {W.shape}")
        numel = int(math.prod(self.op_shape))
        if W.shape[0] != numel:
            raise ValueError(f"weights have {W.shape[0]} rows, operator "
                             f"{self.op_shape} needs {numel}")
        if kind == "stencil" and W.shape[1] != 1:
            raise ValueError(".stencil takes one operator column; use "
                             ".bank for a (numel, K) matrix")
        # private, read-only copy: the op's digest goes into the plan key,
        # so a caller mutating their weight buffer after build must not
        # desync the cached plan from the digest it was interned under
        W = np.array(W, copy=True)
        W.setflags(write=False)
        self.weights = W
        self.K = int(W.shape[1])
        self.stride = normalize_tuple(stride, rank, "stride")
        self.padding = padding
        if padding not in ("same", "valid"):
            raise ValueError(f"unknown padding mode {padding!r}; "
                             f"expected 'same' or 'valid'")
        self.dilation = normalize_tuple(dilation, rank, "dilation")
        self._digest = weight_digest(W)

    def signature(self) -> tuple:
        return (self.kind, self.op_shape, self.stride, self.padding,
                self.dilation, self.K, self._digest)


class PointwiseOp:
    """An elementwise stage; rides whichever fused group surrounds it.

    ``key`` names the function for plan interning; anonymous functions key
    on ``id(fn)`` (the plan pins ``fn``, so the id cannot be recycled while
    the plan lives).  ``channels_first`` is ``fn``'s layout contract: it
    takes (and returns) a channel axis on the leading non-batch axis
    rather than the trailing one (DESIGN.md §11); it is part of the
    signature, since the same ``fn`` under the other contract computes
    something else.
    """

    __slots__ = ("fn", "key", "channels_first")

    def __init__(self, fn, key: Optional[str] = None,
                 channels_first: bool = False):
        if not callable(fn):
            raise ValueError(f"pointwise op needs a callable, got {fn!r}")
        self.fn = fn
        self.key = key
        self.channels_first = bool(channels_first)

    def signature(self) -> tuple:
        return ("ptw", self.key if self.key is not None
                else ("id", id(self.fn)), self.channels_first)


class ZscoreOp:
    """Local z-score over a window — one bank pass ([x, x²] on the batch
    axis) plus the pointwise combine, all inside one fused group."""

    __slots__ = ("window", "wkind", "sigma", "eps", "_sig")

    def __init__(self, window, rank, wkind="box", sigma=None, eps=1e-5):
        if wkind not in ("box", "gaussian"):
            raise ValueError(f"unknown window kind {wkind!r}; expected "
                             f"box/gaussian")
        self.window = normalize_tuple(window, rank, "window")
        self.wkind = wkind
        self.eps = float(eps)
        # sigma may be scalar / per-dim vector / covariance in any
        # array-like spelling — normalize so the plan key always hashes
        if sigma is None:
            self.sigma, ssig = None, None
        elif np.isscalar(sigma) and not isinstance(sigma, str):
            self.sigma = ssig = float(sigma)
        else:
            # read-only copy, same contract as LinearOp.weights: the digest
            # in the signature must stay true to the stored array
            s = np.array(sigma, np.float64)
            s.setflags(write=False)
            self.sigma = s
            ssig = weight_digest(self.sigma)
        self._sig = ("zscore", self.window, wkind, ssig, self.eps)

    def signature(self) -> tuple:
        return self._sig


class MomentsOp:
    """Terminal streaming-moments reduction → ``MomentState``."""

    __slots__ = ("order", "axis")

    def __init__(self, order=4, axis=None):
        if order not in (2, 4):
            raise ValueError(f"order must be 2 or 4, got {order}")
        self.order = int(order)
        self.axis = axis

    def signature(self) -> tuple:
        ax = self.axis
        if ax is not None and not isinstance(ax, int):
            ax = tuple(int(a) for a in ax)
        return ("moments", self.order, ax)


class HistOp:
    """Terminal fixed-grid histogram → ``Histogram`` (static bin grid)."""

    __slots__ = ("bins", "lo", "hi")

    def __init__(self, bins, range):
        if range is None:
            raise ValueError(
                ".hist needs an explicit range=(lo, hi) — the bin grid is "
                "static plan metadata and cannot depend on pipeline values")
        self.bins = int(bins)
        self.lo, self.hi = float(range[0]), float(range[1])
        if not self.hi > self.lo:
            raise ValueError(f"need hi > lo, got [{self.lo}, {self.hi}]")

    def signature(self) -> tuple:
        return ("hist", self.bins, self.lo, self.hi)


class CovOp:
    """Terminal channel covariance → ``CovState`` (trailing axis =
    channels; every other axis is a sample)."""

    __slots__ = ()

    def signature(self) -> tuple:
        return ("cov",)


_TERMINAL = (MomentsOp, HistOp, CovOp)


def _default_gaussian_op(sigma, rank) -> Tuple[int, ...]:
    """Default footprint: ±2σ support per dim, odd, at least 3 wide."""
    from repro.core import hilbert

    cov = hilbert.as_covariance(sigma, rank)
    sds = np.sqrt(np.diag(np.asarray(cov, dtype=np.float64)))
    return tuple(max(3, 2 * int(np.ceil(2.0 * s)) + 1) for s in sds)


class Pipe:
    """An immutable lazy pipeline over one input array.

    Built by :data:`pipe` / :meth:`pipe.batched`; every method returns a
    *new* ``Pipe`` with one more op recorded.  Execution entry points
    (``run`` / ``grad`` / ``plan``) live in ``repro.pipe.compile``.
    """

    __slots__ = ("x", "batched", "ops")

    def __init__(self, x, batched: bool = False, ops: tuple = ()):
        self.x = x
        self.batched = bool(batched)
        if self.batched and x.ndim < 2:
            raise ValueError("pipe.batched needs a leading batch dim plus "
                             "at least one spatial dim")
        self.ops = tuple(ops)

    # -- shape algebra -----------------------------------------------------
    @property
    def rank(self) -> int:
        """Spatial rank of the pipeline input (batch dim excluded)."""
        return self.x.ndim - (1 if self.batched else 0)

    @property
    def spatial_shape(self) -> Tuple[int, ...]:
        return tuple(self.x.shape[1:] if self.batched else self.x.shape)

    def signature(self) -> tuple:
        return tuple(op.signature() for op in self.ops)

    # -- builder plumbing --------------------------------------------------
    def _append(self, op) -> "Pipe":
        if self.ops and isinstance(self.ops[-1], _TERMINAL):
            raise ValueError(
                f"cannot add ops after the terminal reduction "
                f"{self.ops[-1].signature()[0]!r}")
        if isinstance(op, (LinearOp, ZscoreOp)) and self._has_channels():
            raise ValueError(
                "a bank stage appends a trailing channel axis and must be "
                "the last linear stage; only pointwise ops and a terminal "
                "reduction (moments/hist/cov) may follow it")
        return Pipe(self.x, self.batched, self.ops + (op,))

    def _has_channels(self) -> bool:
        return any(isinstance(op, LinearOp) and op.kind == "bank"
                   for op in self.ops)

    # -- linear stages -----------------------------------------------------
    def stencil(self, op_shape, weights, *, stride=1, padding="same",
                dilation=1) -> "Pipe":
        """One linear operator (ravel-vector ``weights``); output keeps the
        value's shape algebra (no channel axis)."""
        op_t = normalize_tuple(op_shape, self.rank, "op_shape")
        return self._append(LinearOp("stencil", op_t, weights, stride,
                                     padding, dilation))

    def bank(self, op_shape, weight_matrix, *, stride=1, padding="same",
             dilation=1) -> "Pipe":
        """K operators over one melt pass; output gains a trailing K axis."""
        op_t = normalize_tuple(op_shape, self.rank, "op_shape")
        return self._append(LinearOp("bank", op_t, weight_matrix, stride,
                                     padding, dilation))

    def gaussian(self, sigma, *, op_shape=None, padding="same",
                 dilation=1) -> "Pipe":
        """Gaussian smoothing stage (scalar / per-dim / covariance sigma);
        footprint defaults to ±2σ support per dim."""
        from repro.core.filters import gaussian_weights_np

        op_t = (normalize_tuple(op_shape, self.rank, "op_shape")
                if op_shape is not None
                else _default_gaussian_op(sigma, self.rank))
        w = gaussian_weights_np(op_t, sigma, dilation=dilation)
        return self._append(LinearOp("stencil", op_t, w, 1, padding,
                                     dilation))

    def gradient(self, *, padding="same") -> "Pipe":
        """All first partials as a K=rank bank (central differences)."""
        from repro.core.filters import difference_stencils

        grad_w, _ = difference_stencils(self.rank)
        return self._append(LinearOp(
            "bank", (3,) * self.rank, np.asarray(grad_w, np.float32),
            1, padding, 1))

    def hessian(self, *, padding="same") -> "Pipe":
        """All second partials as a K=rank² bank (flat channel axis; see
        ``repro.core.filters.hessian`` for the (rank, rank) container)."""
        from repro.core.filters import difference_stencils

        r = self.rank
        _, hess_w = difference_stencils(r)
        return self._append(LinearOp(
            "bank", (3,) * r,
            np.asarray(hess_w.reshape(3 ** r, r * r), np.float32),
            1, padding, 1))

    # -- nonlinear / window stages -----------------------------------------
    def pointwise(self, fn, *, key: Optional[str] = None,
                  channels_first: bool = False) -> "Pipe":
        """Elementwise stage ``fn(value) -> value`` (fused into the
        surrounding group; never costs a melt pass).

        A bank's channel axis reaches ``fn`` trailing, (..., *spatial, K);
        ``channels_first=True`` declares that ``fn`` takes it leading,
        (..., K, *spatial), and returns any channel axis it keeps there
        too.  A bank directly before such a stage hands over its output
        as it computes it, with no relayout: on the fused path the
        kernel's flat rows stand in for the spatial axes, so ``fn`` must
        not depend on their extents (DESIGN.md §11)."""
        return self._append(PointwiseOp(fn, key, channels_first))

    def zscore(self, window, *, weights="box", sigma=None,
               eps: float = 1e-5) -> "Pipe":
        """Local z-score ``(x − μ_w) / √(σ²_w + eps)`` over a window."""
        return self._append(ZscoreOp(window, self.rank, weights, sigma, eps))

    # -- terminal reductions ----------------------------------------------
    def moments(self, order: int = 4, *, axis=None) -> "Pipe":
        """Reduce to a ``MomentState`` (per batch item, per channel)."""
        return self._append(MomentsOp(order, axis))

    def hist(self, bins: int = 64, *, range=None) -> "Pipe":
        """Reduce to a fixed-grid ``Histogram`` over all elements."""
        return self._append(HistOp(bins, range))

    def cov(self) -> "Pipe":
        """Reduce to a channel ``CovState`` (trailing axis = channels)."""
        if self.ops and not self._has_channels():
            raise ValueError(
                ".cov in a multi-stage pipeline needs a bank stage (e.g. "
                ".gradient()) to provide the trailing channel axis")
        if not self.ops and self.x.ndim < 2:
            raise ValueError(".cov needs a trailing channel axis")
        return self._append(CovOp())

    # -- execution (implemented in repro.pipe.compile) ---------------------
    def plan(self, method: str = "auto", pad_value="edge", out_dtype=None):
        """Compile without running: the fused :class:`PipelineProgram`
        (steps, planned passes, materialize-path melt calls).

        Note ``melt_calls`` describes the *fused program*; single-op
        graphs never execute it — ``run`` lowers them onto the legacy
        entry points (e.g. a standalone ``moments`` uses the melt oracle
        on the materialize path, one melt, where the fused reduction
        would pay none)."""
        from repro.pipe import compile as _compile

        return _compile.build_program_for(self, method=method,
                                          pad_value=pad_value,
                                          out_dtype=out_dtype)

    def run(self, method: str = "auto", pad_value="edge", out_dtype=None,
            *, tiles=None, memory_budget=None, tile_order: str = "hilbert",
            mesh=None, axis_name=None, prefetch: bool = True, out=None,
            out_path=None, trace=None):
        """Compile through the planner and execute.

        Single-op graphs lower straight onto the legacy plan kinds
        (``StencilPlan`` / ``BankPlan`` / ``StatsPlan``) — the pipe API is
        a strict superset of the eager entry points, not a parallel
        engine.  Multi-stage graphs intern a
        :class:`~repro.core.plan.PipePlan`.

        With ``tiles=`` (int or per-dim counts) or ``memory_budget=``
        (bytes), the program runs *out-of-core* (DESIGN.md §12): the
        input streams through halo-padded tiles, reductions fold through
        the merge algebra, and array outputs assemble host-side through
        the async double-buffered writeback — results match the
        in-memory run under every pad mode.  ``tile_order`` (the
        ``order=`` of ``repro.pipe.tiled``) picks the streaming order;
        ``prefetch=False`` disables the input-prefetch/writeback overlap
        (one fully synchronous tile at a time); ``out=`` assembles into
        a caller-supplied arena and ``out_path=`` into a ``.npy`` memmap
        on disk (results larger than RAM).  ``mesh``/``axis_name`` shard
        the tile stream across devices.

        ``trace=`` observes the run (DESIGN.md §14): ``None`` defers to
        the ``REPRO_TRACE`` env var, ``True`` records spans into
        ``repro.obs``'s global tracer, a path additionally exports the
        Chrome-trace JSON there, ``False`` is a hard off.
        """
        from repro.obs import trace_scope
        from repro.pipe import compile as _compile

        if tiles is not None or memory_budget is not None:
            from repro.pipe.tiled import run_tiled

            return run_tiled(self, tiles=tiles,
                             memory_budget=memory_budget, method=method,
                             pad_value=pad_value, out_dtype=out_dtype,
                             order=tile_order, mesh=mesh,
                             axis_name=axis_name, prefetch=prefetch,
                             out=out, out_path=out_path, trace=trace)
        if mesh is not None or axis_name is not None:
            raise ValueError("mesh=/axis_name= shard the *tiled* stream; "
                             "pass tiles= or memory_budget= too (or use "
                             "distributed.sharded_pipe_fn for slab "
                             "sharding)")
        if tile_order != "hilbert":
            raise ValueError("tile_order only applies to tiled execution; "
                             "pass tiles= or memory_budget= too")
        if prefetch is not True:
            raise ValueError("prefetch= tunes the tiled stream's overlap; "
                             "pass tiles= or memory_budget= too")
        if out is not None or out_path is not None:
            raise ValueError("out=/out_path= assemble the *tiled* array "
                             "output; pass tiles= or memory_budget= too")
        with trace_scope(trace):
            return _compile.run(self, method=method, pad_value=pad_value,
                                out_dtype=out_dtype)

    def plan_tiled(self, *, tiles=None, memory_budget=None,
                   method: str = "auto", pad_value="edge", out_dtype=None,
                   tile_order: str = "hilbert"):
        """Compile the out-of-core schedule without running it — the
        :class:`~repro.pipe.tiled.TiledProgram` (tile boxes, shape
        classes, assembled ``out_shape``/``out_dtype``, melt/trace
        accounting).  ``tile_order`` maps to ``order=`` of
        :func:`repro.pipe.tiled.plan_tiled`, same as in :meth:`run`;
        run-time knobs (``prefetch=``, ``out=``, ``out_path=``) live on
        :meth:`TiledProgram.run`."""
        from repro.pipe.tiled import plan_tiled as _plan_tiled

        return _plan_tiled(self, tiles=tiles, memory_budget=memory_budget,
                           method=method, pad_value=pad_value,
                           out_dtype=out_dtype, order=tile_order)

    def grad(self, method: str = "auto", pad_value="edge"):
        """∂ sum(pipeline(x)) / ∂x — the pipeline's VJP with a ones
        cotangent (array-valued pipelines; lax/materialize paths)."""
        from repro.pipe import compile as _compile

        return _compile.grad(self, method=method, pad_value=pad_value)

    def __repr__(self):
        names = [op.signature()[0] for op in self.ops]
        return (f"Pipe(shape={tuple(self.x.shape)}, batched={self.batched}, "
                f"ops=[{', '.join(names)}])")


class _PipeFactory:
    """``pipe(x)`` starts an unbatched pipeline; ``pipe.batched(xs)``
    treats dim 0 of ``xs`` as a stack of independent tensors."""

    def __call__(self, x) -> Pipe:
        return Pipe(x, batched=False)

    @staticmethod
    def batched(xs) -> Pipe:
        return Pipe(xs, batched=True)


pipe = _PipeFactory()
