"""The melt-fusing planner: op chain → minimum-pass step program.

Four fusion rules (DESIGN.md §11):

1. **Weight composition** — adjacent linear stages merge into ONE
   operator-bank column when the rewrite is *exact*.  Every stage but the
   last must be single-column (K=1) and dilation-1; then

   - **'valid' chains compose for any strides**: in absolute melt indices
     a stride-``s1`` stage reads ``x[s1·g + a1]`` and a stride-``s2``
     successor reads stage-1 outputs at ``s2·h + a2``, so the chain reads
     ``x[(s1·s2)·h + (a1 + s1·a2)]`` — the composite is the *strided
     correlation* of the operator tensors (extent ``k1 + s1·(k2−1)`` per
     dim) at composite stride ``s1·s2``;
   - **stride-1 'same' chains split**: the output interior — positions
     whose every transitive read lands inside the input — is EXACTLY the
     composed-'valid' pass over the full input, placed at offset
     ``B = Σ pad_lo``; the thin boundary slabs that do read fill run the
     original per-stage program through the out-of-core tile machinery
     (pad at true volume edges + 'valid'), bit-identical to the unfused
     run.  The stitch is planned once (:class:`SplitStep`); when a slab
     cannot be planned (no interior, or reflect-pad wider than a slab)
     the chain falls back to per-stage passes.

   Composition is still *declined* for dilated stages, K>1 predecessors,
   and mixed 'same'/'valid' chains.  Composites accumulate in float64 and
   are cast to float32 once at plan time — a ≥3-stage chain never
   round-trips through float32 between merges.

2. **Trailing-reduction fusion** — a terminal ``moments``/``hist``/``cov``
   consumes the producing group's value inside the same executor: the
   intermediate is never re-melted (0 extra melt passes on the
   materialize path; never leaves the compiled computation on lax/fused).

3. **Separable rewrite** — each planned group's final weight matrix is
   re-examined with ``separable_factors``: bank-kind and composed groups
   whose columns are rank-1 outer products run as per-dim 1-D passes past
   the ``separable_profitable`` crossover ('same' needs a zero/mode fill;
   'valid' is unconditionally exact, strided included — each 1-D pass
   carries its own dim's stride).  Plain ``.stencil``/``.gaussian``
   stages stay dense for parity with ``apply_stencil``.

4. **Channel-major hand-off** — a ``channels_first`` pointwise stage
   directly after a bank group rides that group's step
   (``LinearStep.pointwise``): the bank hands it the K channels where
   it computes them, before any relayout, and only the stage's result
   is laid out as the value (``pipe/channel_major_handoff`` counts the
   plans that do).  After any other step the stage runs on its own,
   with the channels moved to the front for it.

The program records ``passes`` (logical fused traversals; a split counts
as one) and ``melt_calls`` (the exact ``melt()`` count the materialize
path pays: separable groups pay one 1-D melt per dim, a split pays its
interior plus every boundary slab's per-stage replay) — the numbers the
no-extra-melt tests assert against.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np

from repro.core.grid import (
    QuasiGrid,
    chain_same_margins,
    compose_footprints,
    make_quasi_grid,
)
from repro.core.plan import ExecOptions, separable_profitable
from repro.obs.metrics import counter as _counter
from repro.pipe.graph import (
    CovOp,
    HistOp,
    LinearOp,
    MomentsOp,
    Pipe,
    PointwiseOp,
    ZscoreOp,
)

__all__ = [
    "LinearStep",
    "PointwiseStep",
    "ZscoreStep",
    "ReduceStep",
    "SplitStep",
    "PipelineProgram",
    "compose_weights",
    "composable",
    "build_program",
]


def compose_weights(W1: np.ndarray, op1, W2: np.ndarray, op2,
                    stride1=None) -> np.ndarray:
    """Exact weights of ``stage2 ∘ stage1`` (both 'valid'), in float64.

    ``W1`` is (numel(op1), 1), ``W2`` (numel(op2), K); returns the
    (numel(op1 ⊕ op2), K) float64 composite — callers cast to float32
    exactly once when the whole chain is folded, so multi-stage merges
    never quantize intermediates.  In absolute melt indices a valid row
    ``g`` of stage 1 reads ``x[s1·g + a1]``; a successor tap ``a2`` reads
    stage-1 output ``g + a2`` — i.e. ``x[s1·g + (a1 + s1·a2)]`` — so the
    composite tap set is ``{a1 + s1·a2}`` with weights ``w1[a1]·w2[a2]``
    (extent ``k1 + s1·(k2−1)`` per dim; ``stride1=None`` means unit, the
    plain full N-D convolution), and the ravel order matches the melt
    column order by construction.
    """
    op1 = tuple(int(k) for k in op1)
    op2 = tuple(int(k) for k in op2)
    s1 = ((1,) * len(op1) if stride1 is None
          else tuple(int(v) for v in stride1))
    K = W2.shape[1]
    k_out = tuple(a + s * (b - 1) for a, b, s in zip(op1, op2, s1))
    T1 = np.asarray(W1, np.float64).reshape(op1)
    T2 = np.asarray(W2, np.float64).reshape(op2 + (K,))
    out = np.zeros(k_out + (K,))
    for idx in np.ndindex(*op2):
        sl = tuple(slice(s * i, s * i + k)
                   for i, k, s in zip(idx, op1, s1))
        out[sl + (slice(None),)] += T2[idx] * T1[..., None]
    return out.reshape(-1, K)


def composable(a: LinearOp, b: LinearOp) -> bool:
    """Whether stage ``b`` may join stage ``a``'s fused melt pass exactly.

    'valid'→'valid' composes for any strides (strided correlation);
    'same'→'same' requires unit strides (the interior/boundary split's
    offset algebra).  Dilation and K>1 predecessors always decline.
    """
    unit = (1,) * len(a.op_shape)
    if a.K != 1 or a.dilation != unit or b.dilation != unit:
        return False
    if a.padding == "valid" and b.padding == "valid":
        return True
    return (a.padding == "same" and b.padding == "same"
            and a.stride == unit and b.stride == unit)


@dataclasses.dataclass
class LinearStep:
    """One fused linear group: a (possibly composed) bank over one grid."""

    grid: QuasiGrid
    weights: np.ndarray            # (numel, K) float32
    kind: str                      # 'stencil' (squeeze K) | 'bank' (keep K)
    factors: Optional[tuple]       # separable per-dim factors, or None
    fused_from: int                # how many graph ops merged into this pass
    pointwise: object = None       # a channels-first stage on its output

    @property
    def melt_calls(self) -> int:
        return self.grid.rank if self.factors is not None else 1


@dataclasses.dataclass
class PointwiseStep:
    fn: object
    channels_first: bool = False   # fn takes its channels leading


def _channels_after(op: PointwiseOp, lead: tuple, spatial: tuple,
                    channels: int, dtype) -> int:
    """The channel extent an elementwise stage leaves (0 = none).

    ``fn`` may keep, resize or drop the channel axis it is given; its
    abstract result says which, so the executors crop and reduce the
    axes the value really has."""
    cax = len(lead) if op.channels_first else len(lead) + len(spatial)
    shape = list(lead + spatial)
    shape.insert(cax, channels)
    out = jax.eval_shape(op.fn, jax.ShapeDtypeStruct(tuple(shape), dtype))
    return out.shape[cax] if len(out.shape) > len(shape) - 1 else 0


@dataclasses.dataclass
class ZscoreStep:
    grid: QuasiGrid
    window_col: np.ndarray         # normalized (numel,) window weights
    eps: float

    melt_calls = 1


@dataclasses.dataclass
class ReduceStep:
    kind: str                      # 'moments' | 'hist' | 'cov'
    order: int = 4
    bins: int = 0
    lo: float = 0.0
    hi: float = 0.0
    axis: object = None            # explicit spec (reduction-only graphs)


@dataclasses.dataclass
class SplitStep:
    """A stride-1 'same' chain planned as interior ∘ boundary (rule 1b).

    ``interior`` is the composed-'valid' group over the FULL input — its
    output is the 'same' chain's output on ``[B, n−C)`` per dim (``B``/
    ``C`` the accumulated pad margins, ``interior_lo = B``).  Each
    boundary slab replays ``inner`` (the original per-stage program)
    through the tile machinery's pad-at-true-edge + 'valid' schedule
    (``specs``), bit-identical to the unfused run where fill is read.
    One logical traversal; the materialize path pays the interior's
    melts plus every slab's per-stage replay.
    """

    interior: LinearStep
    inner: "PipelineProgram"       # the unfused per-stage chain
    specs: Tuple                   # one TileSpec per boundary slab
    interior_lo: Tuple[int, ...]   # B: interior offset on the output grid
    out_shape: Tuple[int, ...]
    kind: str                      # 'stencil' | 'bank'
    fused_from: int

    @property
    def melt_calls(self) -> int:
        return (self.interior.melt_calls
                + len(self.specs) * self.inner.melt_calls)


@dataclasses.dataclass
class PipelineProgram:
    """The planner's output: executable steps + the pass/melt accounting."""

    steps: Tuple
    passes: int                    # logical fused data traversals
    melt_calls: int                # exact melt() count on the materialize path
    out_shape: Tuple[int, ...]     # spatial shape after the last linear step
    channels: int                  # trailing channel extent (0 = none)
    out_kind: str                  # 'array' | 'moments' | 'hist' | 'cov'

    def describe(self) -> str:
        names = []
        for s in self.steps:
            if isinstance(s, LinearStep):
                tag = "x".join(map(str, s.grid.op_shape))
                sep = "sep" if s.factors is not None else "dense"
                names.append(f"linear[{tag},K={s.weights.shape[1]},{sep},"
                             f"fused={s.fused_from}]"
                             + ("+pointwise" if s.pointwise else ""))
            elif isinstance(s, SplitStep):
                tag = "x".join(map(str, s.interior.grid.op_shape))
                names.append(f"split[{tag},K={s.interior.weights.shape[1]},"
                             f"slabs={len(s.specs)},fused={s.fused_from}]")
            elif isinstance(s, ZscoreStep):
                names.append("zscore")
            elif isinstance(s, PointwiseStep):
                names.append("pointwise")
            else:
                names.append(f"reduce[{s.kind}]")
        return (f"{' -> '.join(names)} | passes={self.passes} "
                f"melt_calls(materialize)={self.melt_calls}")


def _separable_ok(padding: str, pad_value, rank: int) -> bool:
    """Exactness gate for the per-dim rewrite inside a pipeline group."""
    if rank < 2:
        return False
    if padding == "valid":
        return True  # no fill is ever read
    return isinstance(pad_value, str) or pad_value == 0.0


def _plan_linear(op_shape, W, kind, cur_shape, stride, padding, dilation,
                 pad_value, fused_from, try_separable) -> LinearStep:
    from repro.core.engine import separable_factors  # deferred: cycle

    grid = make_quasi_grid(cur_shape, op_shape, stride, padding, dilation)
    factors = None
    unit = (1,) * grid.rank
    # quantize the (possibly float64-folded) bank exactly once, here;
    # factors derive from the quantized operator so they stay float32
    W32 = np.asarray(W, np.float32)
    # strided 'valid' grids stay separable-eligible: each 1-D pass carries
    # its own dim's stride, which is exact when no fill is ever read
    if (try_separable and grid.dilation == unit
            and (grid.stride == unit or padding == "valid")
            and separable_profitable(op_shape)
            and _separable_ok(padding, pad_value, grid.rank)):
        factors = separable_factors(W32, op_shape)
        if factors is not None:
            factors = tuple(factors)
    return LinearStep(grid=grid, weights=W32, kind=kind, factors=factors,
                      fused_from=fused_from)


def _compose_chain(chain) -> Tuple[np.ndarray, tuple, tuple]:
    """Fold a 'valid' chain's operator tensors left-to-right in float64.

    Returns ``(weights, op_shape, stride)`` of the composite: pairwise
    strided correlation with the *accumulated* predecessor stride, so the
    running composite after k stages has extent ``Σ (Π_{j<i} s_j)·(k_i−1)
    + 1`` and stride ``Π s_i`` per dim.
    """
    op = chain[0]
    W = np.asarray(op.weights, np.float64)
    shape = op.op_shape
    stride = tuple(op.stride)
    for nxt in chain[1:]:
        W = compose_weights(W, shape, nxt.weights, nxt.op_shape,
                            stride1=stride)
        shape = tuple(k1 + s * (k2 - 1)
                      for k1, k2, s in zip(shape, nxt.op_shape, stride))
        stride = tuple(s * t for s, t in zip(stride, nxt.stride))
    return W, shape, stride


def _boundary_boxes(shape, lo_m, hi_m):
    """Onion decomposition of the interior's complement into 2·rank
    disjoint slabs: slab ``d`` spans the lo/hi margin along dim ``d``,
    the *interior* range on dims < d, and the full extent on dims > d —
    together with the interior box they tile the output exactly once."""
    rank = len(shape)
    boxes = []
    for d in range(rank):
        base_lo = [lo_m[i] if i < d else 0 for i in range(rank)]
        base_hi = [shape[i] - hi_m[i] if i < d else shape[i]
                   for i in range(rank)]
        if lo_m[d] > 0:
            lo, hi = list(base_lo), list(base_hi)
            lo[d], hi[d] = 0, lo_m[d]
            boxes.append((tuple(lo), tuple(hi)))
        if hi_m[d] > 0:
            lo, hi = list(base_lo), list(base_hi)
            lo[d], hi[d] = shape[d] - hi_m[d], shape[d]
            boxes.append((tuple(lo), tuple(hi)))
    return boxes


def _plan_same_split(chain, cur_shape, opts) -> Optional[SplitStep]:
    """Plan a stride-1 'same' chain as interior ∘ boundary, or ``None``
    when the split cannot be planned (no interior survives the margins,
    or a slab is too thin for this pad mode)."""
    from repro.pipe import tiled  # deferred: tiled imports this module

    rank = len(cur_shape)
    kind = "bank" if chain[-1].kind == "bank" else "stencil"
    K = chain[-1].K
    # the unfused per-stage steps — exactly what the declined-composition
    # plan would run; the boundary slabs replay them bit-identically
    inner_steps = []
    shp = tuple(cur_shape)
    for op in chain:
        st = _plan_linear(op.op_shape, op.weights, op.kind, shp,
                          op.stride, op.padding, op.dilation,
                          opts.pad_value, 1,
                          try_separable=(op.kind == "bank"))
        inner_steps.append(st)
        shp = st.grid.out_shape
    inner = PipelineProgram(
        steps=tuple(inner_steps), passes=len(inner_steps),
        melt_calls=sum(s.melt_calls for s in inner_steps),
        out_shape=tuple(shp), channels=(K if kind == "bank" else 0),
        out_kind="array")
    B, C = chain_same_margins([s.grid for s in inner_steps])
    if any(n - b - c < 1 for n, b, c in zip(cur_shape, B, C)):
        return None  # the whole output is boundary: keep per-stage passes
    W, comp_shape, _ = _compose_chain(chain)
    interior = _plan_linear(comp_shape, W, kind, cur_shape, (1,) * rank,
                            "valid", (1,) * rank, opts.pad_value,
                            len(chain), try_separable=True)
    geoms = tiled._linear_geoms(inner)
    footprint = (compose_footprints([s.grid for s in geoms])
                 or ((1, 0, 0),) * rank)
    specs = []
    try:
        for lo, hi in _boundary_boxes(cur_shape, B, C):
            specs.append(tiled._tile_spec(geoms, footprint, lo, hi,
                                          tuple(cur_shape), opts.pad_value))
    except ValueError:
        return None  # slab too thin for this pad mode (e.g. wide reflect)
    return SplitStep(interior=interior, inner=inner, specs=tuple(specs),
                     interior_lo=tuple(B), out_shape=tuple(cur_shape),
                     kind=kind, fused_from=len(chain))


def build_program(P: Pipe, opts: ExecOptions,
                  split_same: bool = True) -> PipelineProgram:
    """Fuse a pipe graph into the minimum-pass step program.

    ``split_same=False`` pins 'same' chains to per-stage passes (no
    :class:`SplitStep`) — the out-of-core and sharded front ends route
    per stage themselves, and their tile/slab machinery already provides
    the pad-at-true-edge execution the split would nest inside it.
    """
    from repro.stats.local import window_weights_np  # deferred cycle

    steps = []
    cur_shape = P.spatial_shape
    lead = tuple(P.x.shape[:1]) if P.batched else ()
    channels = 0
    out_kind = "array"

    # gather ops; accumulate the longest composable linear chain, then
    # plan it as one group in flush() (composites fold in float64 there —
    # never through a per-merge float32 round-trip)
    pending: list = []

    def flush():
        nonlocal pending, cur_shape, channels
        if not pending:
            return
        chain, pending = pending, []
        if len(chain) == 1:
            op = chain[0]
            step = _plan_linear(
                op.op_shape, op.weights, op.kind, cur_shape, op.stride,
                op.padding, op.dilation, opts.pad_value, 1,
                try_separable=(op.kind == "bank"))
            steps.append(step)
            cur_shape = step.grid.out_shape
        elif chain[0].padding == "valid":
            W, comp_shape, comp_stride = _compose_chain(chain)
            kind = "bank" if chain[-1].kind == "bank" else "stencil"
            step = _plan_linear(
                comp_shape, W, kind, cur_shape, comp_stride, "valid",
                (1,) * len(comp_shape), opts.pad_value, len(chain),
                try_separable=True)
            steps.append(step)
            cur_shape = step.grid.out_shape
        else:  # stride-1 'same' chain: interior/boundary split
            split = (_plan_same_split(chain, cur_shape, opts)
                     if split_same else None)
            if split is not None:
                steps.append(split)
                cur_shape = split.out_shape
            else:
                for op in chain:
                    step = _plan_linear(
                        op.op_shape, op.weights, op.kind, cur_shape,
                        op.stride, op.padding, op.dilation,
                        opts.pad_value, 1,
                        try_separable=(op.kind == "bank"))
                    steps.append(step)
                    cur_shape = step.grid.out_shape
        if chain[-1].kind == "bank":
            channels = chain[-1].K

    for op in P.ops:
        if isinstance(op, LinearOp):
            if pending and not composable(pending[-1], op):
                flush()
            pending.append(op)
        elif isinstance(op, PointwiseOp):
            flush()
            last = steps[-1] if steps else None
            if (op.channels_first and isinstance(last, LinearStep)
                    and last.kind == "bank" and last.pointwise is None):
                # rule 4: the bank hands its channel-major output to the
                # stage where it computes it — no relayout of its K
                # channels (the lax and fused paths)
                steps[-1] = dataclasses.replace(last, pointwise=op.fn)
                _counter("pipe/channel_major_handoff").inc()
            else:
                steps.append(PointwiseStep(op.fn, op.channels_first))
            if channels:
                channels = _channels_after(op, lead, tuple(cur_shape),
                                           channels, P.x.dtype)
        elif isinstance(op, ZscoreOp):
            flush()
            grid = make_quasi_grid(cur_shape, op.window, 1, "same", 1)
            col = window_weights_np(op.window, op.wkind, op.sigma)
            steps.append(ZscoreStep(grid=grid, window_col=col, eps=op.eps))
        elif isinstance(op, MomentsOp):
            flush()
            if op.axis is not None and len(P.ops) > 1:
                raise ValueError(
                    "moments(axis=...) is only valid as a standalone "
                    "reduction (pipe(x).moments(axis=...)); multi-stage "
                    "pipelines reduce the spatial axes, keeping batch and "
                    "channel dims")
            steps.append(ReduceStep("moments", order=op.order,
                                    axis=op.axis))
            out_kind = "moments"
        elif isinstance(op, HistOp):
            flush()
            steps.append(ReduceStep("hist", bins=op.bins, lo=op.lo,
                                    hi=op.hi))
            out_kind = "hist"
        elif isinstance(op, CovOp):
            flush()
            steps.append(ReduceStep("cov"))
            out_kind = "cov"
        else:  # pragma: no cover — builder only produces the types above
            raise TypeError(f"unknown pipe op {op!r}")
    flush()

    traversals = sum(1 for s in steps
                     if isinstance(s, (LinearStep, ZscoreStep, SplitStep)))
    passes = max(traversals, 1 if steps else 0)
    melt_calls = sum(getattr(s, "melt_calls", 0) for s in steps)
    return PipelineProgram(
        steps=tuple(steps), passes=passes, melt_calls=melt_calls,
        out_shape=tuple(cur_shape), channels=channels, out_kind=out_kind)
