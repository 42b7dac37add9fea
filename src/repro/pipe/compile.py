"""Pipe compilation: trivial-graph lowering, PipePlan interning, execution.

Two-tier lowering keeps the pipe API a *superset* of the eager entry
points rather than a parallel engine:

- **Trivial graphs** (a single op) lower straight onto the legacy plan
  kinds: one ``.stencil`` → ``apply_stencil`` (StencilPlan), one ``.bank``
  → ``apply_stencil_bank`` (BankPlan, separable auto), one ``.moments`` →
  the StatsPlan dispatch, one ``.hist``/``.cov`` → the eager stats calls.
  The rewritten wrappers (``filters.*``, ``stats.*``, ``MeltEngine``) are
  therefore bit-identical to their pre-pipe selves, plan counters
  included.
- **Multi-stage graphs** run the fusing planner (``repro.pipe.fuse``) and
  intern a :class:`~repro.core.plan.PipePlan` whose jitted executor walks
  the fused steps — one compiled computation for the whole chain.
- **Out-of-core graphs** (``Pipe.run(tiles=…/memory_budget=…)``) are a
  third tier layered on top: ``repro.pipe.tiled`` re-uses this module's
  step executors — ``_apply_reduce`` for fused terminal reductions and
  ``_check_out_dtype`` for option validation are shared contracts, not
  private details — while swapping the 'same' grids for per-tile
  pad-at-boundary + 'valid' execution (DESIGN.md §12).

Traced inputs execute inline (no interning), matching the engine-wide
convention.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import (
    ExecOptions,
    PipePlan,
    get_pipe_plan,
    get_stats_plan,
    normalize_axes,
)
from repro.pipe.fuse import (
    LinearStep,
    PipelineProgram,
    PointwiseStep,
    ReduceStep,
    SplitStep,
    ZscoreStep,
    build_program,
)
from repro.pipe.graph import (
    CovOp,
    HistOp,
    LinearOp,
    MomentsOp,
    Pipe,
    PointwiseOp,
)

__all__ = ["run", "grad", "build_program_for", "plan_key_for"]


def _opts(method, pad_value, out_dtype, batched) -> ExecOptions:
    return ExecOptions.make(method=method, pad_value=pad_value,
                            batched=batched, out_dtype=out_dtype)


def build_program_for(P: Pipe, method="auto", pad_value="edge",
                      out_dtype=None) -> PipelineProgram:
    return build_program(P, _opts(method, pad_value, out_dtype, P.batched))


# -- trivial lowering --------------------------------------------------------


def _lower_trivial(P: Pipe, opts: ExecOptions):
    """Single-op graphs → the legacy entry machinery (or None)."""
    if len(P.ops) != 1:
        return None
    op = P.ops[0]
    x = P.x
    if isinstance(op, LinearOp):
        from repro.core.engine import apply_stencil, apply_stencil_bank

        if op.kind == "stencil":
            return apply_stencil(
                x, op.op_shape, jnp.asarray(op.weights[:, 0]),
                stride=op.stride, padding=op.padding, dilation=op.dilation,
                pad_value=opts.pad_value, method=opts.method,
                batched=P.batched, out_dtype=opts.out_dtype)
        return apply_stencil_bank(
            x, op.op_shape, jnp.asarray(op.weights),
            stride=op.stride, padding=op.padding, dilation=op.dilation,
            pad_value=opts.pad_value, method=opts.method,
            batched=P.batched, out_dtype=opts.out_dtype)
    if isinstance(op, MomentsOp):
        from repro.stats.moments import execute_moments

        if not isinstance(x, jax.core.Tracer):
            plan = get_stats_plan(x.shape, x.dtype, op.axis, opts.method,
                                  P.batched, op.order)
            return plan(x)
        axes = normalize_axes(x.ndim, op.axis, P.batched)
        return execute_moments(x, axes, opts.resolved_method, op.order)
    if isinstance(op, HistOp):
        from repro.stats.hist import histogram_fixed

        return histogram_fixed(x, op.bins, op.lo, op.hi)
    if isinstance(op, CovOp):
        from repro.stats.cov import channel_cov

        return channel_cov(x)
    return None


# -- step execution ----------------------------------------------------------


def _apply_linear(h, step: LinearStep, opts: ExecOptions, batched: bool):
    from repro.core import engine

    meth = opts.resolved_method
    if step.factors is not None:
        out = engine.execute_separable_bank(
            h, step.grid, step.factors, opts.pad_value, meth, batched,
            pointwise=step.pointwise)
        return out[..., 0] if step.kind == "stencil" else out
    if step.kind == "stencil":
        return engine.execute_stencil(
            h, step.grid, step.weights[:, 0], opts.pad_value, meth, batched)
    return engine.execute_stencil_bank(
        h, step.grid, step.weights, opts.pad_value, meth, batched,
        pointwise=step.pointwise)


def _apply_pointwise(h, step, batched: bool, rank: int):
    """An elementwise stage under its layout contract: a channel axis
    reaches a ``channels_first`` stage leading (DESIGN.md §11)."""
    if not step.channels_first:
        return step.fn(h)
    from repro.core.engine import apply_channels_first

    return apply_channels_first(step.fn, h, rank, batched)


def _apply_zscore(h, step: ZscoreStep, opts: ExecOptions, batched: bool):
    """(x − μ_w)/√(σ²_w + eps): the [x, x²] pair rides the batch axis of
    ONE dense bank pass inside the group (DESIGN.md §10)."""
    from repro.core import engine

    xf = h.astype(jnp.float32)
    stacked = (jnp.concatenate([xf, xf * xf], axis=0) if batched
               else jnp.stack([xf, xf * xf]))
    col = jnp.asarray(step.window_col)[:, None]
    out = engine.execute_stencil_bank(
        stacked, step.grid, col, opts.pad_value, opts.resolved_method,
        batched=True)[..., 0]
    b = h.shape[0] if batched else 1
    mean, ex2 = (out[:b], out[b:]) if batched else (out[0], out[1])
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    return ((xf - mean) / jnp.sqrt(var + step.eps)).astype(h.dtype)


def _apply_split(h, step: SplitStep, opts: ExecOptions, batched: bool):
    """Interior/boundary execution of a fused 'same' chain (DESIGN.md §11).

    The interior — every output whose transitive reads stay inside the
    volume — is the composed-'valid' group over the FULL input, scattered
    at offset ``interior_lo``.  Each boundary slab replays the original
    per-stage program through the tile executor (pad at true volume edges
    + 'valid'), bit-identical to the unfused run.  Pure ``.at[].set`` on
    disjoint boxes: differentiable, and every branch lives inside the one
    jitted pipeline computation.
    """
    import dataclasses as _dc

    from repro.pipe.tiled import _run_tile

    interior = _apply_linear(h, step.interior, opts, batched)
    lead = (slice(None),) if batched else ()
    out_shape = ((h.shape[:1] if batched else ()) + step.out_shape
                 + ((step.interior.weights.shape[1],)
                    if step.kind == "bank" else ()))
    canvas = jnp.zeros(out_shape, interior.dtype)
    isl = tuple(slice(b, b + e) for b, e in
                zip(step.interior_lo, step.interior.grid.out_shape))
    canvas = canvas.at[lead + isl].set(interior)
    # the slab executor applies the final out_dtype cast itself; strip it
    # so the cast happens once, on the assembled result (_run_program)
    slab_opts = (_dc.replace(opts, out_dtype=None)
                 if opts.out_dtype is not None else opts)
    for spec in step.specs:
        rsl = tuple(slice(a, b) for a, b in zip(spec.read_lo, spec.read_hi))
        res = _run_tile(h[lead + rsl], step.inner, spec, slab_opts, batched)
        osl = tuple(slice(a, b) for a, b in zip(spec.out_lo, spec.out_hi))
        canvas = canvas.at[lead + osl].set(res.astype(canvas.dtype))
    return canvas


def _reduce_axes(ndim: int, batched: bool, channels: int) -> Tuple[int, ...]:
    lo = 1 if batched else 0
    hi = ndim - (1 if channels else 0)
    if hi <= lo:
        raise ValueError("pipeline reduction has no spatial axes left to "
                         "reduce")
    return tuple(range(lo, hi))


def _apply_reduce(h, step: ReduceStep, opts: ExecOptions, batched: bool,
                  channels: int):
    meth = opts.resolved_method
    if step.kind == "moments":
        from repro.stats.moments import execute_moments, reduce_direct

        axes = (normalize_axes(h.ndim, step.axis, batched)
                if step.axis is not None
                else _reduce_axes(h.ndim, batched, channels))
        if meth == "materialize":
            # the fused-reduction contract: consume the producer's value
            # directly — same math as the melt oracle minus the trivial-op
            # melt (which is an identity gather), so the intermediate is
            # never re-melted
            return reduce_direct(h, axes, order=step.order)
        return execute_moments(h, axes, meth, step.order)
    if step.kind == "hist":
        from repro.stats.hist import histogram_fixed

        return histogram_fixed(h, step.bins, step.lo, step.hi)
    if step.kind == "cov":
        from repro.stats.cov import channel_cov

        if not channels:
            raise ValueError(".cov in a multi-stage pipeline needs a bank "
                             "stage to provide the channel axis")
        return channel_cov(h)
    raise ValueError(f"unknown reduction {step.kind!r}")  # pragma: no cover


def _run_program(x, program: PipelineProgram, opts: ExecOptions,
                 batched: bool):
    h = x
    for step in program.steps:
        if isinstance(step, LinearStep):
            h = _apply_linear(h, step, opts, batched)
        elif isinstance(step, SplitStep):
            h = _apply_split(h, step, opts, batched)
        elif isinstance(step, PointwiseStep):
            h = _apply_pointwise(h, step, batched, len(program.out_shape))
        elif isinstance(step, ZscoreStep):
            h = _apply_zscore(h, step, opts, batched)
        elif isinstance(step, ReduceStep):
            h = _apply_reduce(h, step, opts, batched, program.channels)
        else:  # pragma: no cover
            raise TypeError(f"unknown step {step!r}")
    if program.out_kind == "array" and opts.out_dtype is not None:
        h = h.astype(opts.out_dtype)
    return h


# -- entry points ------------------------------------------------------------


def _plan_key(P: Pipe, opts: ExecOptions) -> tuple:
    return (tuple(P.x.shape), jnp.dtype(P.x.dtype).name, P.batched,
            opts.key(), P.signature())


def plan_key_for(P: Pipe, method="auto", pad_value="edge",
                 out_dtype=None) -> tuple:
    """The cache key this pipeline would intern under — a hashable tuple
    of (shape, dtype, batched, options, graph signature).

    This is the serving tier's grouping key (``repro.serve``): two
    requests with equal keys are guaranteed to compile to the same plan,
    so they can be stacked into one ``pipe.batched`` dispatch and served
    from a single interned executor.  Note the key embeds the *input
    shape*, so a coalescer never has to re-check shape compatibility.
    (Dispatching the compiled plan is already non-blocking — jax arrays
    are futures; only ``block_until_ready``/host reads synchronize.)
    """
    opts = _opts(method, pad_value, out_dtype, P.batched)
    _check_out_dtype(P, opts)
    return ("pipe",) + _plan_key(P, opts)


def _check_out_dtype(P: Pipe, opts: ExecOptions):
    """``out_dtype`` must not silently no-op on state-valued pipelines."""
    if opts.out_dtype is None or not P.ops:
        return
    from repro.pipe.graph import CovOp, HistOp, MomentsOp

    terminal = P.ops[-1]
    if isinstance(terminal, (MomentsOp, HistOp, CovOp)):
        raise ValueError(
            f"out_dtype applies to array-valued pipelines; this one ends "
            f"in the {terminal.signature()[0]!r} reduction, whose state "
            f"pytree is float32 by contract — drop out_dtype or cast the "
            f"derived statistics yourself")


def run(P: Pipe, method="auto", pad_value="edge", out_dtype=None):
    opts = _opts(method, pad_value, out_dtype, P.batched)
    _check_out_dtype(P, opts)
    x = P.x
    if not P.ops:
        return x if opts.out_dtype is None else x.astype(opts.out_dtype)
    if all(isinstance(op, PointwiseOp) for op in P.ops):
        for op in P.ops:
            x = _apply_pointwise(x, op, P.batched, P.rank)
        return x if opts.out_dtype is None else x.astype(opts.out_dtype)
    lowered = _lower_trivial(P, opts)
    if lowered is not None:
        return lowered
    batched = P.batched  # local: the plan closure must NOT pin P (and P.x)
    if isinstance(x, jax.core.Tracer):
        return _run_program(x, build_program(P, opts), opts, batched)
    key = _plan_key(P, opts)
    shape, dtname = tuple(x.shape), jnp.dtype(x.dtype).name

    def build():
        # planning (weight composition + separable detection) runs on the
        # cache MISS only — a hit is one dict lookup, like every plan kind
        program = build_program(P, opts)
        return PipePlan(
            ("pipe",) + key, shape, dtname, opts,
            program.steps, program.passes, program.melt_calls,
            lambda t: _run_program(t, program, opts, batched))

    return get_pipe_plan(key, build)(x)


def grad(P: Pipe, method="auto", pad_value="edge"):
    """∂ sum(pipeline(x)) / ∂x for array-valued pipelines."""
    opts = _opts(method, pad_value, None, P.batched)
    if opts.resolved_method == "fused":
        raise ValueError(
            "grad is not supported on the fused path (the Pallas kernels "
            "define no VJP); use method='lax' or 'materialize'")
    from repro.pipe.graph import CovOp, HistOp, MomentsOp

    terminal = P.ops[-1] if P.ops else None
    if isinstance(terminal, (MomentsOp, HistOp, CovOp)):
        kind = terminal.signature()[0]
        raise ValueError(
            f"grad needs an array-valued pipeline; this one ends in "
            f"{kind!r}")
    x = P.x
    batched = P.batched  # local: the plan closure must NOT pin P (and P.x)

    if isinstance(x, jax.core.Tracer):
        program = build_program(P, opts)
        return jax.grad(
            lambda t: jnp.sum(_run_program(t, program, opts, batched)))(x)
    key = ("grad",) + _plan_key(P, opts)
    shape, dtname = tuple(x.shape), jnp.dtype(x.dtype).name

    def build():
        program = build_program(P, opts)

        def scalar(t):
            return jnp.sum(_run_program(t, program, opts, batched))

        return PipePlan(
            ("pipe",) + key, shape, dtname, opts,
            program.steps, program.passes, program.melt_calls,
            jax.grad(scalar))

    return get_pipe_plan(key, build)(x)
