"""Distributed melt engine: row-partition across a mesh axis + halo exchange.

The paper's cluster story (§2.4/§3.1): partition the melt matrix by rows,
allocate row blocks to physical units, compute independently, aggregate.
JAX-native mapping (DESIGN.md §2):

- the *allocation* is a ``shard_map`` over a mesh axis — each device owns a
  contiguous slab of the leading tensor dimension (= a contiguous block of
  melt rows, by construction of ``plan_slab_partition``);
- the *coupling* cost is a **halo exchange**: two ``ppermute`` sends of
  boundary slices (width = operator half-extent), instead of replicating the
  input to every worker as a multiprocessing pool does;
- the aggregation (unmelt) is shard-local — output sharding equals input
  sharding, so chained stencils need no resharding.

Batch × slab sharding (DESIGN.md §3): with ``batch_axis_name`` set,
``sharded_stencil_fn`` expects inputs ``(B, *spatial)`` sharded as
``P(batch_axis, spatial_axis, ...)`` — the batch axis is embarrassingly
parallel (no exchange), the leading spatial dim keeps the halo exchange,
and each device runs one *batched* local stencil over its (batch-slab ×
spatial-slab) block.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.grid import make_quasi_grid, normalize_pad_value
from repro.core.engine import apply_stencil
from repro.core.melt import pad_array

__all__ = [
    "halo_exchange",
    "distributed_stencil",
    "sharded_stencil_fn",
    "sharded_pipe_fn",
    "tree_merge_moments",
    "sharded_moments_fn",
    "sharded_histogram_fn",
    "tile_batch_sharding",
    "put_tile_batch",
]


def _slice_axis(x: jax.Array, lo: int, hi: int, axis: int) -> jax.Array:
    return jax.lax.slice_in_dim(x, lo, hi, axis=axis)


def _edge_block(x_local: jax.Array, width: int, axis: int, first: bool,
                pad_value) -> jax.Array:
    """Edge padding block for a boundary device (constant or edge mode)."""
    pv = normalize_pad_value(pad_value)
    if isinstance(pv, str):
        if pv != "edge":
            raise NotImplementedError(
                f"halo_exchange supports constant or 'edge' padding, "
                f"got {pv!r}")
        n = x_local.shape[axis]
        sl = _slice_axis(x_local, 0, 1, axis) if first else \
            _slice_axis(x_local, n - 1, n, axis)
        return jnp.repeat(sl, width, axis=axis)
    shape = list(x_local.shape)
    shape[axis] = width
    return jnp.full(tuple(shape), pv, x_local.dtype)


def halo_exchange(
    x_local: jax.Array,
    halo_lo: int,
    halo_hi: int,
    axis_name: str,
    pad_value=0.0,
    axis: int = 0,
) -> jax.Array:
    """Extend a device-local slab with neighbour boundary slices along ``axis``.

    Edge devices receive constant/edge padding instead of wrapped data.
    Returns an array whose ``axis`` extent grows by ``halo_lo + halo_hi``.
    """
    idx = jax.lax.axis_index(axis_name)
    num = jax.lax.psum(1, axis_name)  # axis size (portable across jax vers)
    n = x_local.shape[axis]
    parts = []
    if halo_lo > 0:
        # receive the *last* halo_lo rows of the left neighbour
        src = jax.lax.ppermute(
            _slice_axis(x_local, n - halo_lo, n, axis), axis_name,
            perm=[(i, (i + 1) % num) for i in range(num)],
        )
        edge = _edge_block(x_local, halo_lo, axis, True, pad_value)
        parts.append(jnp.where(idx == 0, edge, src))
    parts.append(x_local)
    if halo_hi > 0:
        src = jax.lax.ppermute(
            _slice_axis(x_local, 0, halo_hi, axis), axis_name,
            perm=[(i, (i - 1) % num) for i in range(num)],
        )
        edge = _edge_block(x_local, halo_hi, axis, False, pad_value)
        parts.append(jnp.where(idx == num - 1, edge, src))
    return jnp.concatenate(parts, axis=axis)


def _local_stencil(x_halo, grid_full, weights, pad_value, method,
                   batched: bool = False):
    """Stencil on a halo-extended slab: valid along the sharded spatial dim,
    'same' elsewhere (non-leading spatial dims are pre-padded here)."""
    pads = ([(0, 0)] if batched else []) + [(0, 0)] + [
        (lo, hi) for lo, hi in zip(grid_full.pad_lo[1:], grid_full.pad_hi[1:])
    ]
    xp = pad_array(x_halo, pads, pad_value) \
        if any(p != (0, 0) for p in pads) else x_halo
    return apply_stencil(
        xp, grid_full.op_shape, weights,
        stride=grid_full.stride, padding="valid", dilation=grid_full.dilation,
        pad_value=0.0, method=method, batched=batched,
    )


def sharded_stencil_fn(
    mesh: Mesh,
    axis_name: str,
    in_shape,
    op_shape,
    weights,
    *,
    dilation=1,
    pad_value=0.0,
    method: str = "auto",
    batch_axis_name: Optional[str] = None,
):
    """Build a jit-able distributed stencil for inputs sharded on dim 0.

    stride is fixed to 1 (sharded slab boundaries must align with grid
    slices; production LM uses stride-1 windows).  Returns ``f(x)`` with
    in/out sharding ``P(axis_name, None, ...)``.

    With ``batch_axis_name``, ``in_shape`` is ``(B, *spatial)`` and the
    returned function shards the batch over ``batch_axis_name`` and the
    leading *spatial* dim over ``axis_name`` (batch × spatial-slab).
    """
    pad_value = normalize_pad_value(pad_value)
    batched = batch_axis_name is not None
    in_shape = tuple(int(s) for s in in_shape)
    spatial_shape = in_shape[1:] if batched else in_shape
    grid_full = make_quasi_grid(spatial_shape, op_shape, 1, "same", dilation)
    halo_lo, halo_hi = grid_full.halo()[0]
    n_shards = mesh.shape[axis_name]
    if spatial_shape[0] % n_shards:
        raise ValueError(
            f"leading spatial dim {spatial_shape[0]} not divisible by "
            f"{n_shards} shards"
        )
    if batched and in_shape[0] % mesh.shape[batch_axis_name]:
        raise ValueError(
            f"batch dim {in_shape[0]} not divisible by "
            f"{mesh.shape[batch_axis_name]} batch shards"
        )
    sdim = 1 if batched else 0  # sharded spatial dim in the local block

    def local_fn(x_local):
        x_halo = halo_exchange(x_local, halo_lo, halo_hi, axis_name,
                               pad_value, axis=sdim)
        return _local_stencil(x_halo, grid_full, weights, pad_value, method,
                              batched=batched)

    rank = len(spatial_shape)
    if batched:
        spec = P(batch_axis_name, axis_name, *([None] * (rank - 1)))
    else:
        spec = P(axis_name, *([None] * (rank - 1)))
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False,
    )


# -- distributed pipelines (DESIGN.md §11) ----------------------------------


def sharded_pipe_fn(
    mesh: Mesh,
    axis_name: str,
    graph,
    *,
    method: str = "auto",
    pad_value="edge",
    batch_axis_name: Optional[str] = None,
):
    """Build a jit-able distributed executor for a pipe graph.

    ``graph`` is an un-run :class:`repro.pipe.Pipe` — build it on a
    ``jax.ShapeDtypeStruct`` template (or any array of the global shape).
    The input is sharded ``P(axis_name, ...)`` on the leading *spatial*
    dim (``P(batch_axis_name, axis_name, ...)`` with a batch axis — the
    batch is embarrassingly parallel), and the fused step program runs
    shard-locally with exactly **one halo exchange per fused group**:
    pointwise stages and the terminal reduction ride their group's
    exchange for free.  On its halo-extended slab each group is a
    'valid' pass planned as the one-chip planner plans a 'valid' group:
    per-dim 1-D passes where ``separable_factors`` factors its weights
    and ``separable_profitable`` says they pay, else one dense pass.  A
    terminal ``moments`` tree-merges across the slab axis (per batch item
    — per-item states stay batch-sharded); a terminal ``hist`` psums its
    counts.

    Building records, in ``repro.obs``: the span ``shard/build``
    (``shards``, ``groups``), the counters ``shard/separable_groups`` and
    ``shard/halo_exchanges``, and the gauge ``shard/halo_bytes`` — the
    bytes one call's exchanges send from each device.  A call records
    nothing.

    Restrictions (actionable errors): linear groups must be stride-1
    'same' — slab boundaries must align with grid slices — which also
    means weight-COMPOSED groups (a 'valid'-padding construct) are not
    routeable here: 'valid' slabs are ragged across shards (edge shards
    shrink, interior ones don't), so under shard_map each 'same' group is
    one linear op and composition happens on-device only.  ``zscore`` /
    ``cov`` stages are not yet routed either.
    """
    from repro.obs import TRACER, counter, gauge
    from repro.pipe.compile import (
        _apply_linear, _apply_pointwise, _apply_reduce,
    )
    from repro.pipe.fuse import (
        LinearStep, PointwiseStep, ReduceStep, ZscoreStep, build_program,
    )
    from repro.core.plan import ExecOptions

    t0 = time.perf_counter_ns()
    batched = batch_axis_name is not None
    if bool(graph.batched) != batched:
        raise ValueError(
            f"pipe graph batched={graph.batched} but batch_axis_name="
            f"{batch_axis_name!r}; build the graph with pipe.batched(...) "
            f"iff a batch mesh axis is given")
    opts = ExecOptions.make(method, pad_value, batched)
    # split_same=False: shard routing dispatches stage-by-stage over
    # slab halos; the interior/boundary SplitStep is an on-device
    # single-block rewrite and would defeat the per-stage halo exchange
    program = build_program(graph, opts, split_same=False)
    rank = graph.rank
    sdim = 1 if batched else 0  # sharded spatial dim in the local block
    for s in program.steps:
        if isinstance(s, LinearStep):
            if s.grid.padding != "same" or s.grid.stride != (1,) * rank:
                raise ValueError(
                    "sharded pipelines need stride-1 'same' linear groups "
                    "(slab boundaries must align with grid slices); got "
                    f"padding={s.grid.padding!r} stride={s.grid.stride}")
        elif isinstance(s, ZscoreStep):
            raise NotImplementedError(
                "zscore stages are not routed through shard_map yet; "
                "run them locally or use stats.zscore per shard")
        elif isinstance(s, ReduceStep) and s.kind == "cov":
            raise NotImplementedError(
                "cov reductions are not routed through shard_map yet")
    n_shards = mesh.shape[axis_name]
    if graph.spatial_shape[0] % n_shards:
        raise ValueError(
            f"leading spatial dim {graph.spatial_shape[0]} not divisible "
            f"by {n_shards} shards")
    if batched and graph.x.shape[0] % mesh.shape[batch_axis_name]:
        raise ValueError(
            f"batch dim {graph.x.shape[0]} not divisible by "
            f"{mesh.shape[batch_axis_name]} batch shards")
    # the slab-local steps read no fill: every group runs on a 'valid'
    # grid over its halo-extended, in-plane padded slab
    local_opts = dataclasses.replace(opts, pad_value=0.0)
    lead = (graph.x.shape[0] // mesh.shape[batch_axis_name],) \
        if batched else ()
    slab = ((graph.spatial_shape[0] // n_shards,)
            + tuple(graph.spatial_shape[1:]))
    local, halo_bytes = _slab_steps(program.steps, slab, lead, graph.x.dtype,
                                    local_opts, batched)

    def _local_linear(h, step: LinearStep, lstep: LinearStep):
        """One halo exchange for the whole fused group, then its 'valid'
        pass (or per-dim passes) over the halo-extended slab."""
        grid = step.grid
        halo_lo, halo_hi = grid.halo()[0]
        hh = halo_exchange(h, halo_lo, halo_hi, axis_name, opts.pad_value,
                           axis=sdim)
        pads = (([(0, 0)] if batched else []) + [(0, 0)]
                + [(lo, hi) for lo, hi in zip(grid.pad_lo[1:],
                                              grid.pad_hi[1:])])
        if any(p != (0, 0) for p in pads):
            hh = pad_array(hh, pads, opts.pad_value)
        return _apply_linear(hh, lstep, local_opts, batched)

    out_is_state = program.out_kind != "array"

    def local_fn(x_local):
        h = x_local
        for step, lstep in zip(program.steps, local):
            if isinstance(step, LinearStep):
                h = _local_linear(h, step, lstep)
            elif isinstance(step, PointwiseStep):
                h = _apply_pointwise(h, step, batched, rank)
            elif isinstance(step, ReduceStep):
                if step.kind == "moments":
                    h = _apply_reduce(h, step, opts, batched,
                                      program.channels)
                    h = tree_merge_moments(h, axis_name)
                else:  # hist: counts psum across every mesh axis
                    h = _apply_reduce(h, step, opts, batched,
                                      program.channels)
                    names = ((axis_name, batch_axis_name) if batched
                             else (axis_name,))
                    h = type(h)(jax.lax.psum(h.counts, names), h.lo, h.hi)
        return h

    if batched:
        in_spec = P(batch_axis_name, axis_name, *([None] * (rank - 1)))
    else:
        in_spec = P(axis_name, *([None] * (rank - 1)))
    if out_is_state:
        if program.out_kind == "moments" and batched:
            # per-item states keep the (local) batch dim sharded
            out_spec = P(batch_axis_name)
        else:
            out_spec = P()
    elif program.channels:
        out_spec = P(*(tuple(in_spec) + (None,)))
    else:
        out_spec = in_spec
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
        check_vma=False,
    )
    groups = [s for s in local if isinstance(s, LinearStep)]
    counter("shard/separable_groups").inc(
        sum(1 for s in groups if s.factors is not None))
    counter("shard/halo_exchanges").inc(
        sum(1 for s in program.steps if isinstance(s, LinearStep)
            and sum(s.grid.halo()[0]) > 0))
    gauge("shard/halo_bytes").set(halo_bytes)
    TRACER.record("shard/build", time.perf_counter_ns() - t0,
                  shards=n_shards, groups=len(groups))
    return fn


def _slab_steps(steps, slab, lead, dtype, local_opts, batched):
    """Each step as it runs on one device's slab, and the bytes one
    call's halo exchanges send from that device.

    A linear group's 'same' grid becomes a 'valid' grid over the slab
    extended by its leading-dim halo and padded in-plane, planned as the
    one-chip planner plans a 'valid' group (``fuse._plan_linear``): the
    per-dim rewrite where it is exact and pays.  The value entering each
    step is followed as a shape (``jax.eval_shape`` on the ``lax`` path,
    which runs no kernel), so channels and dtypes count as they are."""
    from repro.pipe.compile import _apply_linear, _apply_pointwise
    from repro.pipe.fuse import LinearStep, PointwiseStep, _plan_linear

    rank = len(slab)
    shape_opts = dataclasses.replace(local_opts, method="lax")
    h = jax.ShapeDtypeStruct(tuple(lead) + tuple(slab), dtype)
    sdim = len(lead)
    out, sent = [], 0
    for step in steps:
        if isinstance(step, LinearStep):
            g = step.grid
            lo, hi = g.halo()[0]
            plane = int(np.prod(h.shape)) // h.shape[sdim]
            sent += (lo + hi) * plane * jnp.dtype(h.dtype).itemsize
            ext = tuple(n + a + b for n, a, b in zip(
                h.shape[sdim:sdim + rank], (lo,) + g.pad_lo[1:],
                (hi,) + g.pad_hi[1:]))
            lstep = dataclasses.replace(
                _plan_linear(g.op_shape, step.weights, step.kind, ext,
                             g.stride, "valid", g.dilation, 0.0,
                             step.fused_from, try_separable=True),
                pointwise=step.pointwise)
            h = jax.eval_shape(
                lambda t, s=lstep: _apply_linear(t, s, shape_opts, batched),
                jax.ShapeDtypeStruct(h.shape[:sdim] + ext
                                     + h.shape[sdim + rank:], h.dtype))
            out.append(lstep)
            continue
        if isinstance(step, PointwiseStep):
            h = jax.eval_shape(
                lambda t, s=step: _apply_pointwise(t, s, batched, rank), h)
        out.append(step)
    return tuple(out), sent


# -- out-of-core tile streams (DESIGN.md §12) --------------------------------
#
# Tiled execution bakes every halo into the tile's own read region, so a
# stacked group of same-class tiles is *embarrassingly parallel*: sharding
# the stack axis over the mesh needs no exchange at all — the one coupling
# cost left is the O(state) reduction merge, which the stats combiners
# above already provide.  ``repro.pipe.tiled`` stacks same-class tiles and
# places them here; XLA partitions the jitted per-class executor along the
# stack axis (batch×slab: a batched graph would additionally shard its own
# batch dim — the tile stream claims the slab-like axis).


def tile_batch_sharding(mesh: Mesh, axis_name: str, ndim: int
                        ) -> NamedSharding:
    """Sharding for a stacked tile batch: dim 0 = tile-stack axis over
    ``axis_name``, everything else replicated per shard."""
    return NamedSharding(mesh, P(axis_name, *([None] * (ndim - 1))))


def put_tile_batch(batch, mesh: Mesh, axis_name: str):
    """Place a host-side stacked tile batch onto the mesh, stack-sharded.

    The stack extent must divide the mesh axis (the tiled scheduler groups
    tiles in multiples of the axis size; ragged remainders run one tile
    per device).
    """
    n = batch.shape[0]
    ways = mesh.shape[axis_name]
    if n % ways:
        raise ValueError(
            f"tile-batch extent {n} not divisible by mesh axis "
            f"{axis_name!r} of size {ways}")
    return jax.device_put(batch, tile_batch_sharding(mesh, axis_name,
                                                     batch.ndim))


# -- distributed statistics (DESIGN.md §10) ---------------------------------
#
# The statistics engine's states are mergeable pytrees, so the cluster
# combiner is psum-shaped: every device contributes its local sufficient
# statistics and receives the global ones.  Moments use an explicit
# all-gather + balanced Chan merge tree (addition is the wrong algebra for
# central moments); histograms over one static grid psum directly.


def tree_merge_moments(state, axis_name: str):
    """All-reduce a MomentState across ``axis_name`` by a balanced merge tree.

    ``all_gather`` stacks every device's state on a new leading axis, then
    the pairwise Chan tree (``merge_along_axis``) folds it — log₂(devices)
    merge depth, identical math to the kernel's tile merge, so device count
    never changes results beyond float rounding.  Every device returns the
    full state (psum-style semantics).
    """
    from repro.stats.moments import merge_along_axis  # deferred: stats→core

    gathered = jax.lax.all_gather(state, axis_name)
    return merge_along_axis(gathered, axis=0)


def sharded_moments_fn(
    mesh: Mesh,
    axis_name: str,
    in_shape,
    *,
    axis=None,
    batch_axis_name: Optional[str] = None,
    method: str = "auto",
    order: int = 4,
):
    """Build a jit-able distributed moments reduction for dim-0-sharded input.

    Matches :func:`sharded_stencil_fn`'s data layout: the input is sharded
    ``P(axis_name, ...)`` — or ``P(batch_axis_name, axis_name, ...)`` with
    a batch axis — each device reduces its local block to a
    ``MomentState`` (any local execution path, including the fused
    no-materialize kernel), and states tree-merge across the slab axis and
    then the batch axis.  No halo: moments have no neighbourhood, the melt
    operator is (1,)*rank, so the partition is embarrassingly parallel —
    the coupling cost is one O(state) collective instead of boundary
    slices.

    Sharded dims must be *reduced* dims (kept axes live whole on every
    device); ``axis`` names the reduced axes of the **global** array, all
    axes by default.  Returns ``f(x) -> MomentState`` with the state
    replicated on every device.
    """
    from repro.core.plan import normalize_axes, resolve_method
    from repro.stats.moments import execute_moments

    batched = batch_axis_name is not None
    in_shape = tuple(int(s) for s in in_shape)
    ndim = len(in_shape)
    axes = normalize_axes(ndim, axis, False)
    sharded_dims = (0, 1) if batched else (0,)
    for d in sharded_dims:
        if d not in axes:
            raise ValueError(
                f"sharded dim {d} must be a reduced axis (got axes={axes}); "
                f"kept axes cannot be split across devices")
    if in_shape[sharded_dims[-1]] % mesh.shape[axis_name]:
        raise ValueError(
            f"sharded dim extent {in_shape[sharded_dims[-1]]} not divisible "
            f"by {mesh.shape[axis_name]} shards")
    if batched and in_shape[0] % mesh.shape[batch_axis_name]:
        raise ValueError(
            f"batch dim {in_shape[0]} not divisible by "
            f"{mesh.shape[batch_axis_name]} batch shards")
    meth = resolve_method(method)

    def local_fn(x_local):
        state = execute_moments(x_local, axes, meth, order)
        state = tree_merge_moments(state, axis_name)
        if batched:
            state = tree_merge_moments(state, batch_axis_name)
        return state

    spec = _stats_in_spec(ndim, axis_name, batch_axis_name)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec,), out_specs=P(),
        check_vma=False,
    )


def sharded_histogram_fn(
    mesh: Mesh,
    axis_name: str,
    in_shape,
    bins: int,
    range,
    *,
    batch_axis_name: Optional[str] = None,
):
    """Distributed fixed-bin histogram over a dim-0-sharded array.

    Every device bins its local block against the same static (lo, hi,
    bins) grid and the counts ``psum`` across the mesh — the histogram
    pytree's merge *is* addition, so the generic combiner degenerates to
    one collective.  Returns ``f(x) -> Histogram`` replicated everywhere.
    """
    from repro.stats.hist import Histogram, histogram_fixed

    lo, hi = float(range[0]), float(range[1])
    in_shape = tuple(int(s) for s in in_shape)
    ndim = len(in_shape)
    batched = batch_axis_name is not None
    names = ((axis_name, batch_axis_name) if batched else (axis_name,))

    def local_fn(x_local):
        h = histogram_fixed(x_local, bins, lo, hi)
        return Histogram(jax.lax.psum(h.counts, names), lo, hi)

    spec = _stats_in_spec(ndim, axis_name, batch_axis_name)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec,), out_specs=P(),
        check_vma=False,
    )


def _stats_in_spec(ndim: int, axis_name: str,
                   batch_axis_name: Optional[str]) -> P:
    if batch_axis_name is not None:
        return P(batch_axis_name, axis_name, *([None] * (ndim - 2)))
    return P(axis_name, *([None] * (ndim - 1)))


def distributed_stencil(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    op_shape,
    weights,
    *,
    batch_axis_name: Optional[str] = None,
    **kw,
) -> jax.Array:
    """One-shot convenience wrapper around :func:`sharded_stencil_fn`."""
    fn = sharded_stencil_fn(mesh, axis_name, x.shape, op_shape, weights,
                            batch_axis_name=batch_axis_name, **kw)
    batched = batch_axis_name is not None
    rank = x.ndim - (1 if batched else 0)
    if batched:
        spec = P(batch_axis_name, axis_name, *([None] * (rank - 1)))
    else:
        spec = P(axis_name, *([None] * (rank - 1)))
    x = jax.device_put(x, NamedSharding(mesh, spec))
    return jax.jit(fn)(x)
