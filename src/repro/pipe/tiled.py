"""Out-of-core tiled execution for pipe graphs (DESIGN.md §12).

The paper's space-completeness argument — high-dimensional arrays
decompose into dimension-independent pieces that can be processed
piecewise and merged exactly — applied to volumes larger than device
memory: a compiled pipe program runs as a stream of halo-padded tiles.

The scheme, per tile of the program's *output* grid:

1. **Backward footprint** — :func:`repro.core.grid.compose_footprints`
   folds every linear stage's reach into one per-dim affine
   ``(α, β, γ)``; the tile's input read region is
   ``[α·a − β, α·(b−1) + γ + 1)`` clamped to the volume
   (:func:`~repro.core.grid.tile_read_region`).  Only the clamped-off
   remainder is ever re-created with the pad mode, and only at true
   volume boundaries — so tiled results match the in-memory run under
   every pad mode (zero / constant / edge / reflect), not just zero.
2. **Forward simulation** — each 'same' stage runs as *pad-if-at-boundary
   + 'valid'* over the shrinking patch (the same rewrite the distributed
   slab engine uses for its halo-exchanged dim, here applied to every
   dim); 'valid' stages run as-is.  Interior halos are real neighbour
   data carried by the read region, never padding.
3. **Crop & merge** — the crop to the tile's output box and the
   ``out_dtype`` cast are fused *inside* the jitted executor, so only
   final bytes ever cross the device→host bus.  Array-valued programs
   assemble tiles into a host-side buffer (optionally a caller-supplied
   arena or an ``np.lib.format.open_memmap`` file, for results larger
   than RAM) through :class:`_WritebackStream` — the output-side mirror
   of the input prefetch: tile i's device→host copy and placement overlap
   tile i+1's compute, with at most 2 results staged at any moment.
   Reduction-terminated programs fold per-tile
   ``MomentState`` / ``Histogram`` / ``CovState`` through the PR-3 merge
   algebra (a streaming binary-counter fold ⇒ balanced merge tree, O(log
   #tiles) live states) — the full intermediate never exists anywhere.

Tiles stream in Hilbert order (:func:`repro.core.hilbert.hilbert_order`)
with a double-buffered ``jax.device_put`` prefetch, and every tile is
served by a :class:`~repro.core.plan.TilePlan` interned per *tile-shape
class* — interior tiles of a uniform tiling share one trace; edge tiles
add at most 3^rank − 1 more.  With ``mesh=``/``axis_name=``, same-class
tiles stack in groups of the mesh-axis size and shard across devices
(:func:`repro.core.distributed.put_tile_batch`): halos are baked into
each patch, so the stream is embarrassingly parallel and the only
coupling cost is the O(state) reduction merge.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.grid import (
    compose_footprints,
    make_quasi_grid,
    tile_read_region,
)
from repro.core.hilbert import hilbert_order
from repro.core.melt import pad_array
from repro.core.partition import plan_tile_partition
from repro.core.plan import (
    ExecOptions,
    TilePlan,
    get_tile_plan,
    plan_fingerprint,
)
from repro.pipe.fuse import (
    LinearStep,
    PipelineProgram,
    PointwiseStep,
    ReduceStep,
    ZscoreStep,
    build_program,
)
from repro.obs import trace_scope as _trace_scope
from repro.obs.metrics import counter as _counter, gauge as _gauge, \
    histogram as _histogram
from repro.obs.trace import instant as _instant, span as _span
from repro.pipe.graph import MomentsOp, Pipe
from repro.runtime.faults import NO_FAULTS, PermanentFault, TransientFault
from repro.runtime.stream_ckpt import StreamCheckpoint

__all__ = ["TileSpec", "TiledProgram", "plan_tiled", "run_tiled",
           "FaultReport", "StreamFaultError"]


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """Static geometry of one tile: placement + the per-stage pad/crop
    schedule the executor needs.

    ``class_key()`` drops the placement — tiles sharing it execute an
    identical trace, which is what lets a stream of many tiles run on a
    handful of interned :class:`~repro.core.plan.TilePlan` executors.
    """

    out_lo: Tuple[int, ...]     # tile's box on the program output grid
    out_hi: Tuple[int, ...]
    read_lo: Tuple[int, ...]    # clamped input region the tile reads
    read_hi: Tuple[int, ...]
    stage_pads: Tuple           # per linear/zscore step: per-dim (lo, hi)
    crop: Tuple                 # per-dim (start, stop) into the final patch

    @property
    def patch_shape(self) -> Tuple[int, ...]:
        return tuple(h - l for l, h in zip(self.read_lo, self.read_hi))

    def class_key(self) -> tuple:
        return (self.patch_shape, self.stage_pads, self.crop)


def _linear_geoms(program: PipelineProgram):
    """The data-traversing steps, in execution order (each consumes one
    entry of a TileSpec's ``stage_pads``)."""
    return [s for s in program.steps
            if isinstance(s, (LinearStep, ZscoreStep))]


def _tile_spec(geoms, footprint, out_lo, out_hi, in_shape, pad_value
               ) -> TileSpec:
    """Forward-simulate one tile's patch through every stage (pure shape
    math): where the patch sits in each intermediate's global coordinates,
    which boundary pads apply, and the final crop."""
    read_lo, read_hi = tile_read_region(footprint, out_lo, out_hi, in_shape)
    c_lo, c_hi = list(read_lo), list(read_hi)
    stage_pads = []
    for step in geoms:
        g = step.grid
        pads, nlo, nhi = [], [], []
        for d in range(g.rank):
            s = g.stride[d]
            eff = (g.op_shape[d] - 1) * g.dilation[d] + 1
            if g.padding == "same":
                at_lo = c_lo[d] == 0
                at_hi = c_hi[d] == g.in_shape[d]
                pad_l = g.pad_lo[d] if at_lo else 0
                pad_r = g.pad_hi[d] if at_hi else 0
                p = 0 if at_lo else c_lo[d] + g.pad_lo[d]
            else:
                pad_l = pad_r = 0
                p = c_lo[d]
            width = c_hi[d] - c_lo[d]
            if pad_value == "reflect" and max(pad_l, pad_r) > width - 1:
                raise ValueError(
                    f"tile patch extent {width} along dim {d} is too small "
                    f"for reflect padding of width {max(pad_l, pad_r)}; "
                    f"use fewer tiles (or a larger memory budget) along "
                    f"this dim")
            if p % s:  # pragma: no cover — the footprint algebra
                raise AssertionError(  # guarantees stride alignment
                    "internal: tile patch misaligned with stage stride")
            plen = width + pad_l + pad_r
            n_out = (plen - eff) // s + 1
            if n_out <= 0:
                raise ValueError(
                    f"tile patch extent {plen} along dim {d} is smaller "
                    f"than the stage's effective operator {eff}; use fewer "
                    f"tiles along this dim")
            pads.append((pad_l, pad_r))
            nlo.append(p // s)
            nhi.append(p // s + n_out)
        stage_pads.append(tuple(pads))
        c_lo, c_hi = nlo, nhi
    for d, (a, b) in enumerate(zip(out_lo, out_hi)):
        if not (c_lo[d] <= a and c_hi[d] >= b):  # pragma: no cover
            raise AssertionError(
                f"internal: tile patch [{c_lo[d]}, {c_hi[d]}) does not "
                f"cover output box [{a}, {b}) along dim {d}")
    crop = tuple((a - cl, b - cl)
                 for a, b, cl in zip(out_lo, out_hi, c_lo))
    return TileSpec(tuple(out_lo), tuple(out_hi), read_lo, read_hi,
                    tuple(stage_pads), crop)


# -- per-tile execution ------------------------------------------------------


def _crop(h, crop, batched: bool, channels: int):
    sl = (([slice(None)] if batched else [])
          + [slice(a, b) for a, b in crop]
          + ([slice(None)] if channels else []))
    return h[tuple(sl)]


def _tile_linear(h, step: LinearStep, dim_pads, opts: ExecOptions,
                 batched: bool):
    """One fused linear group on a patch: boundary pads (real pad mode,
    true volume edges only), then a 'valid' pass — interior halo data is
    already inside the patch."""
    from repro.core import engine

    g = step.grid
    if any(p != (0, 0) for p in dim_pads):
        pads = ([(0, 0)] if batched else []) + list(dim_pads)
        h = pad_array(h, pads, opts.pad_value)
    lshape = h.shape[1:] if batched else h.shape
    lgrid = make_quasi_grid(lshape, g.op_shape, g.stride, "valid",
                            g.dilation)
    meth = opts.resolved_method
    if step.factors is not None:
        out = engine.execute_separable_bank(h, lgrid, step.factors, 0.0,
                                            meth, batched,
                                            pointwise=step.pointwise)
        return out[..., 0] if step.kind == "stencil" else out
    if step.kind == "stencil":
        return engine.execute_stencil(
            h, lgrid, step.weights[:, 0], 0.0, meth, batched)
    return engine.execute_stencil_bank(h, lgrid, step.weights, 0.0, meth,
                                       batched, pointwise=step.pointwise)


def _tile_zscore(h, step: ZscoreStep, dim_pads, opts: ExecOptions,
                 batched: bool):
    """Per-tile local z-score: the [x, x²] pair rides the batch axis of
    one 'valid' window pass over the (boundary-padded) patch."""
    from repro.core import engine

    g = step.grid
    xf = h.astype(jnp.float32)
    if any(p != (0, 0) for p in dim_pads):
        pads = ([(0, 0)] if batched else []) + list(dim_pads)
        xf = pad_array(xf, pads, opts.pad_value)
    lshape = xf.shape[1:] if batched else xf.shape
    lgrid = make_quasi_grid(lshape, g.op_shape, 1, "valid", g.dilation)
    stacked = (jnp.concatenate([xf, xf * xf], axis=0) if batched
               else jnp.stack([xf, xf * xf]))
    col = jnp.asarray(step.window_col)[:, None]
    out = engine.execute_stencil_bank(
        stacked, lgrid, col, 0.0, opts.resolved_method, batched=True)[..., 0]
    b = h.shape[0] if batched else 1
    mean, ex2 = (out[:b], out[b:]) if batched else (out[0], out[1])
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    halos = g.halo()
    csl = (([slice(None)] if batched else [])
           + [slice(halos[d][0], halos[d][0] + lgrid.out_shape[d])
              for d in range(g.rank)])
    xc = xf[tuple(csl)]
    return ((xc - mean) / jnp.sqrt(var + step.eps)).astype(h.dtype)


def _run_tile(patch, program: PipelineProgram, spec: TileSpec,
              opts: ExecOptions, batched: bool):
    from repro.pipe.compile import _apply_pointwise, _apply_reduce

    h = patch
    li = 0
    for step in program.steps:
        if isinstance(step, LinearStep):
            h = _tile_linear(h, step, spec.stage_pads[li], opts, batched)
            li += 1
        elif isinstance(step, ZscoreStep):
            h = _tile_zscore(h, step, spec.stage_pads[li], opts, batched)
            li += 1
        elif isinstance(step, PointwiseStep):
            h = _apply_pointwise(h, step, batched, len(spec.crop))
        elif isinstance(step, ReduceStep):
            # crop BEFORE reducing: the reduction must see exactly the
            # tile's own output box, never halo leftovers
            h = _crop(h, spec.crop, batched, program.channels)
            h = _apply_reduce(h, step, opts, batched, program.channels)
            return h
        else:  # pragma: no cover
            raise TypeError(f"unknown step {step!r}")
    h = _crop(h, spec.crop, batched, program.channels)
    if opts.out_dtype is not None:
        h = h.astype(opts.out_dtype)
    return h


# -- tile-count selection ----------------------------------------------------


def _interior_patch_elems(out_shape, footprint, counts) -> int:
    elems = 1
    for n, (a, b, c), k in zip(out_shape, footprint, counts):
        t = -(-n // k)  # largest tile extent along this dim
        elems *= a * (t - 1) + b + c + 1
    return elems


def _working_set_bytes(out_shape, footprint, counts, itemsize: int,
                       batch: int, channels: int,
                       out_itemsize: int = 0) -> float:
    """One interior tile's estimated working set, in bytes.

    The estimate is deliberately simple and documented: patch bytes ×
    (2 + max(channels, 1)) for the padded copy and the widest
    intermediate, ×2 for the double-buffered prefetch.  Array-output
    programs (``out_itemsize`` > 0) additionally stage the writeback:
    up to 2 cropped result tiles live awaiting their device→host copy
    (the double-buffered D2H mirror of the input prefetch), so the
    estimate adds 2 × output-tile bytes.
    """
    overhead = 2.0 * (2 + max(channels, 1))
    b = (_interior_patch_elems(out_shape, footprint, counts)
         * max(1, batch) * itemsize * overhead)
    if out_itemsize:
        tile_out = 1
        for n, k in zip(out_shape, counts):
            tile_out *= -(-n // k)
        b += (2 * tile_out * max(1, batch) * max(channels, 1)
              * out_itemsize)
    return b


def _budget_tile_counts(out_shape, footprint, itemsize: int, batch: int,
                        channels: int, budget: int,
                        out_itemsize: int = 0) -> Tuple[int, ...]:
    """Pick per-dim tile counts so an interior tile's working set
    (:func:`_working_set_bytes`) fits the byte budget.  Splits always go
    to the dim with the largest current patch extent (keeps tiles chunky
    → fewest shape classes, best halo-to-interior ratio).
    """
    counts = [1] * len(out_shape)

    def bytes_now():
        return _working_set_bytes(out_shape, footprint, counts, itemsize,
                                  batch, channels, out_itemsize)

    while bytes_now() > budget:
        splittable = [d for d in range(len(out_shape))
                      if counts[d] < out_shape[d]]
        if not splittable:
            break  # finest tiling reachable; best effort
        d = max(splittable,
                key=lambda i: -(-out_shape[i] // counts[i]))
        counts[d] = min(out_shape[d], counts[d] * 2)
    return tuple(counts)


# -- the tiled program -------------------------------------------------------


class _FoldStack:
    """Streaming balanced fold: a binary-counter of partial merges, so the
    effective merge tree has log₂(#tiles) depth with O(log #tiles) live
    states (the single-machine face of the distributed merge tree).

    The counter state is exposed (``entries``) and restorable (pass the
    snapshotted entries back in) — a resumed stream that restores the
    stack and keeps pushing reproduces the uninterrupted run's merge
    tree node for node, which is what makes resume bit-identical on the
    lax/materialize paths.
    """

    __slots__ = ("merge", "stack")

    def __init__(self, merge, entries=()):
        self.merge = merge
        self.stack = [(int(lvl), s) for lvl, s in entries]

    def push(self, s):
        level = 0
        while self.stack and self.stack[-1][0] == level:
            _, prev = self.stack.pop()
            s = self.merge(prev, s)
            level += 1
        self.stack.append((level, s))

    @property
    def entries(self):
        return tuple(self.stack)

    def result(self):
        acc = None
        for _, s in reversed(self.stack):
            acc = s if acc is None else self.merge(s, acc)
        return acc


def _fold_merge(merge):
    """``(push, result)`` closures over a fresh :class:`_FoldStack`."""
    fold = _FoldStack(merge)
    return fold.push, fold.result


def _merge_fn(out_kind: str):
    if out_kind == "moments":
        from repro.stats.moments import merge_moments
        return merge_moments
    if out_kind == "hist":
        from repro.stats.hist import merge_histograms
        return merge_histograms
    from repro.stats.cov import merge_cov
    return merge_cov


@dataclasses.dataclass
class FaultReport:
    """What a fault-tolerant stream could not do, and what it cost.

    ``records`` has one dict per quarantined tile — ``tile`` (stream
    index), ``out_lo``/``out_hi`` (its box on the output grid), ``site``
    (read / device / writeback), ``fault`` (transient-exhausted or
    permanent), ``attempts``, ``error``.  ``retried`` counts transient
    faults absorbed by the retry policy (they cost time, not coverage).
    An empty ``records`` means full coverage.
    """

    num_tiles: int
    out_shape: Tuple[int, ...]   # the spatial output grid the boxes tile
    records: list = dataclasses.field(default_factory=list)
    retried: int = 0

    @property
    def quarantined(self) -> Tuple[int, ...]:
        return tuple(r["tile"] for r in self.records)

    def uncovered_mask(self) -> np.ndarray:
        """Boolean mask over the spatial output grid: True where no
        result landed (the union of quarantined tiles' boxes).  Batch
        and channel axes are never partial — a tile covers all of both —
        so the mask is spatial-only."""
        mask = np.zeros(self.out_shape, dtype=bool)
        for r in self.records:
            mask[tuple(slice(a, b)
                       for a, b in zip(r["out_lo"], r["out_hi"]))] = True
        return mask

    def to_json(self) -> str:
        return json.dumps({
            "num_tiles": self.num_tiles,
            "out_shape": list(self.out_shape),
            "retried": self.retried,
            "quarantined": len(self.records),
            "records": self.records,
        }, indent=2)


class StreamFaultError(RuntimeError):
    """Raised at end-of-stream (``strict=True``) when tiles quarantined.

    The stream runs to completion first — every healthy tile's work is
    done, journaled, and (for reductions) snapshotted — so catching this
    and resuming from the checkpoint dir re-attempts only the
    quarantined tiles.  The full :class:`FaultReport` rides on
    ``.report``.
    """

    def __init__(self, report: FaultReport):
        self.report = report
        sites = sorted({r["site"] for r in report.records})
        super().__init__(
            f"{len(report.records)} of {report.num_tiles} tile(s) "
            f"quarantined after retries (sites: {', '.join(sites)}); "
            f"pass strict=False for the partial result + fault report, "
            f"or re-run with the same checkpoint_dir to re-attempt them")


class _WritebackStream:
    """Async double-buffered device→host writeback for array outputs.

    The output-side mirror of the input prefetch: :meth:`stage` is called
    immediately after the *next* tile's compute is dispatched.  It starts
    the device→host copy of this tile's result
    (``jax.Array.copy_to_host_async``) and then drains the *previously*
    staged result into the assembled buffer — so host placement of tile i
    overlaps device compute of tile i+1, and the stream never holds more
    than ``depth`` (= 2) staged results.  ``depth=1`` (``prefetch=False``)
    degrades to the old fully synchronous place-per-tile behaviour.

    Placement prefers a zero-copy DLPack view of the result buffer
    (``np.from_dlpack``; on the CPU backend the "device" buffer is
    host-resident, so no staging allocation happens at all).  Backends
    whose buffers numpy cannot view fall back to one host staging copy
    per tile — already in flight thanks to the async transfer above, and
    dropped as soon as its bytes land in the assembled buffer, so peak
    host memory stays ≤ ``depth`` result tiles either way.

    An entry may also be a same-class tile *group* (a tuple of specs with
    a stack-axis result, the mesh-sharded path): the group drains as one
    staged unit, placing each member from the stacked host view.
    """

    __slots__ = ("buf", "max_staged", "placed", "_batched", "_channels",
                 "_dtype", "_depth", "_staged", "_views", "_copies",
                 "_guard", "_on_placed")

    def __init__(self, buf, batched: bool, channels: int, out_dtype,
                 depth: int = 2, guard=None, on_placed=None):
        self.buf = buf
        self.max_staged = 0
        self.placed = 0
        self._batched = batched
        self._channels = channels
        self._dtype = np.dtype(out_dtype)
        self._depth = max(1, int(depth))
        self._staged = []  # [(spec | tuple-of-specs, device result)]
        self._views = 0    # zero-copy dlpack placements
        self._copies = 0   # staging-copy fallbacks
        # fault/journal hooks around the host placement (the 'writeback'
        # boundary): guard(spec, place_fn) -> placed?; on_placed(spec)
        # fires only after the tile's bytes are in the buffer — that is
        # the durability point the journal's "done" lines mean
        self._guard = guard
        self._on_placed = on_placed

    def _slices(self, spec: TileSpec):
        return (tuple([slice(None)] if self._batched else [])
                + tuple(slice(a, b)
                        for a, b in zip(spec.out_lo, spec.out_hi))
                + (tuple([slice(None)]) if self._channels else ()))

    def _host_view(self, tile):
        """A host-readable array of ``tile``'s bytes: zero-copy when the
        buffer supports DLPack into numpy, else one staging copy."""
        try:
            h = np.from_dlpack(tile)
            self._views += 1
            return h
        except Exception:
            self._copies += 1
            return np.asarray(tile)

    def _place(self, spec, host):
        self.buf[self._slices(spec)] = host
        self.placed += 1

    def _drain_one(self):
        specs, tile, tag = self._staged.pop(0)
        with _span("tile/writeback", tile=tag,
                   staged=len(self._staged) + 1):
            host = self._host_view(tile)
            grouped = isinstance(specs, tuple)  # stacked same-class group
            for j, s in enumerate(specs if grouped else (specs,)):
                h = host[j] if grouped else host
                if self._guard is not None:
                    ok = self._guard(s, lambda s=s, h=h: self._place(s, h))
                else:
                    self._place(s, h)
                    ok = True
                if ok and self._on_placed is not None:
                    self._on_placed(s)

    def stage(self, specs, tile, tag=None):
        """Queue one result (``tag`` labels its trace span — the stream
        index, or None for untagged group drains)."""
        if np.dtype(tile.dtype) != self._dtype:
            raise AssertionError(
                f"internal: tile executor emitted dtype {tile.dtype}, "
                f"but the plan promised {self._dtype} — the fused "
                f"out_dtype cast and the plan metadata disagree")
        try:
            tile.copy_to_host_async()
        except (AttributeError, NotImplementedError):
            pass  # plain arrays (tests) / backends without async D2H
        self._staged.append((specs, tile, tag))
        self.max_staged = max(self.max_staged, len(self._staged))
        while len(self._staged) > self._depth - 1:
            self._drain_one()

    def flush(self):
        while self._staged:
            self._drain_one()
        return self.buf

    def stats(self) -> dict:
        return {"max_staged": self.max_staged, "placed": self.placed,
                "views": self._views, "copies": self._copies,
                "depth": self._depth}


@dataclasses.dataclass
class TiledProgram:
    """A compiled out-of-core schedule: the fused program + tile geometry.

    ``specs`` are in streaming (Hilbert) order; ``classes`` maps each
    tile-shape class key to its member count — ``num_classes`` is the
    exact number of traces a run costs (asserted by the conformance
    tests), and ``num_classes × program.melt_calls`` the exact
    materialize-path melt accounting.
    """

    graph: Pipe
    opts: ExecOptions
    program: PipelineProgram
    footprint: Tuple
    tile_counts: Tuple[int, ...]
    specs: Tuple[TileSpec, ...]
    classes: dict
    #: full assembled shape (batch + out grid + channels) — plan metadata,
    #: derived from the program, never from a computed tile
    out_shape: Tuple[int, ...] = ()
    #: np.dtype of the assembled output (None for reduction programs)
    out_dtype: object = None
    #: last run's :class:`_WritebackStream` counters (array outputs only)
    writeback_stats: dict = dataclasses.field(default_factory=dict)
    #: last run's :class:`FaultReport` (empty records == full coverage)
    fault_report: Optional[FaultReport] = None
    #: last sharded run's heartbeat/straggler counters
    liveness_stats: dict = dataclasses.field(default_factory=dict)

    @property
    def num_tiles(self) -> int:
        return len(self.specs)

    @property
    def num_classes(self) -> int:
        return len(self.classes)

    def describe(self) -> str:
        return (f"{self.program.describe()} | tiles={self.num_tiles} "
                f"({'x'.join(map(str, self.tile_counts))}) "
                f"classes={self.num_classes}")

    def fingerprint(self) -> str:
        """The stream-checkpoint identity: graph signature × exec options
        × input shape/dtype × tiling × tile boxes in stream order.

        Two plans share a fingerprint iff replaying one's journal against
        the other is sound — same tiles, same order, same per-tile math.
        Note anonymous pointwise stages sign by function identity, so
        their fingerprints do not survive a process restart: resume then
        refuses (the safe direction) — use named graph ops for
        checkpointed streams.
        """
        P = self.graph
        return plan_fingerprint(
            "tiled-stream", P.signature(), self.opts.key(), P.batched,
            jnp.dtype(P.x.dtype).name, tuple(P.x.shape), self.tile_counts,
            tuple((s.out_lo, s.out_hi) for s in self.specs))

    def working_set_bytes(self) -> int:
        """This schedule's estimated peak working set (bytes) — the same
        §12 estimate ``memory_budget=`` plans against, evaluated for the
        tile counts this program actually has.  The serving tier's
        admission controller reserves this many bytes from its shared
        :class:`~repro.serve.admission.MemoryBudget` before letting a
        stream start, so concurrent tiled requests cannot collectively
        overshoot the host."""
        P = self.graph
        out_itemsize = (np.dtype(self.out_dtype).itemsize
                        if self.out_dtype is not None else 0)
        return int(_working_set_bytes(
            self.program.out_shape, self.footprint, self.tile_counts,
            jnp.dtype(P.x.dtype).itemsize,
            P.x.shape[0] if P.batched else 1, self.program.channels,
            out_itemsize=out_itemsize))

    # -- execution ---------------------------------------------------------
    def _plan_for(self, spec: TileSpec, stack: int = 0, mesh=None,
                  axis_name: Optional[str] = None) -> TilePlan:
        """The class plan for ``spec``; ``stack`` > 0 runs a stack of
        tiles, split over ``mesh``'s ``axis_name`` when a mesh is given
        (one ``shard_map`` shard per device: a compiled Pallas kernel
        cannot be partitioned automatically)."""
        P, opts, program = self.graph, self.opts, self.program
        batched = P.batched or stack > 0
        dt = jnp.dtype(P.x.dtype).name
        ckey = spec.class_key()
        key = (P.signature(), opts.key(), P.batched, dt,
               tuple(P.x.shape), ckey, stack, mesh, axis_name)
        lead = ((stack,) if stack else
                ((P.x.shape[0],) if P.batched else ()))

        def run_fn(t):
            return _run_tile(t, program, spec, opts, batched)

        if mesh is not None:
            if program.out_kind in ("hist", "cov"):
                # these states fold their stack inside the shard: each
                # shard's state gains a leading axis, merged on the host
                def run_fn(t, one=run_fn):
                    return jax.tree.map(lambda leaf: leaf[None], one(t))

            run_fn = jax.shard_map(run_fn, mesh=mesh,
                                   in_specs=PartitionSpec(axis_name),
                                   out_specs=PartitionSpec(axis_name),
                                   check_vma=False)

        def build():
            if program.out_kind == "array":
                t_out = (lead + tuple(b - a for a, b in spec.crop)
                         + ((program.channels,) if program.channels
                            else ()))
                t_dt = self.out_dtype
            else:
                t_out = t_dt = None  # merge state, not an array
            return TilePlan(
                ("tiled",) + key, lead + spec.patch_shape, dt, opts,
                program.steps, program.passes, program.melt_calls,
                run_fn, spec=ckey, tile_batch=stack, out_shape=t_out,
                out_dtype=t_dt)

        return get_tile_plan(key, build)

    def _read_patch(self, spec: TileSpec):
        sl = (([slice(None)] if self.graph.batched else [])
              + [slice(l, h) for l, h in zip(spec.read_lo, spec.read_hi)])
        return self.graph.x[tuple(sl)]

    def _make_out_buffer(self, out=None, out_path=None, resume=False):
        """The assembled-output buffer, sized from plan metadata (never
        from a computed tile): a fresh array, the caller's ``out=``
        arena, or a ``.npy`` memmap created at ``out_path=`` — the
        latter streams results larger than RAM straight to disk.  A
        resumed run re-opens an existing ``out_path`` read-write
        (``mode='w+'`` would truncate away the completed tiles the
        journal says are durable)."""
        if out is not None and out_path is not None:
            raise ValueError("pass at most one of out= / out_path=")
        if self.program.out_kind != "array":
            if out is not None or out_path is not None:
                raise ValueError(
                    "out=/out_path= assemble array outputs; this program "
                    f"ends in a {self.program.out_kind!r} reduction whose "
                    "result is a merged state, not an array")
            return None
        shape, dtype = self.out_shape, self.out_dtype
        if out_path is not None:
            if resume and os.path.exists(str(out_path)):
                m = np.lib.format.open_memmap(str(out_path), mode="r+")
                if tuple(m.shape) != shape or np.dtype(m.dtype) != dtype:
                    raise ValueError(
                        f"resume target {out_path} holds shape "
                        f"{tuple(m.shape)} dtype {np.dtype(m.dtype).name}; "
                        f"this plan assembles shape {shape} dtype "
                        f"{np.dtype(dtype).name} — the journal matched "
                        f"but the output file was replaced")
                return m
            return np.lib.format.open_memmap(
                str(out_path), mode="w+", dtype=dtype, shape=shape)
        if out is not None:
            if not isinstance(out, np.ndarray):
                raise TypeError(
                    "out= must be a writable np.ndarray (np.memmap "
                    f"included), got {type(out).__name__}")
            if tuple(out.shape) != shape or np.dtype(out.dtype) != dtype:
                raise ValueError(
                    f"out= has shape {tuple(out.shape)} dtype "
                    f"{np.dtype(out.dtype).name}; this program assembles "
                    f"shape {shape} dtype {np.dtype(dtype).name}")
            if not out.flags.writeable:
                raise ValueError("out= array is read-only")
            return out
        return np.empty(shape, dtype)

    def run(self, mesh=None, axis_name: Optional[str] = None,
            prefetch: bool = True, out=None, out_path=None, *,
            checkpoint_dir=None, resume_dir=None, checkpoint_every: int = 8,
            faults=None, max_retries: int = 3, retry_backoff: float = 0.0,
            strict: bool = True, heartbeat=None, straggler=None,
            trace=None, budget=None):
        """Stream every tile; returns the merged reduction state, or the
        assembled output as a host-side ``np.ndarray`` (the out-of-core
        contract: the device only ever holds tiles).

        Array outputs assemble through the async double-buffered
        writeback (:class:`_WritebackStream`); ``prefetch=False``
        disables both the input prefetch and the writeback overlap (one
        fully synchronous tile at a time).  ``out=`` assembles into a
        caller-supplied arena (shape/dtype must match ``out_shape`` /
        ``out_dtype``); ``out_path=`` creates an
        ``np.lib.format.open_memmap`` file and assembles into it, for
        results larger than RAM.  Both return the buffer they filled.

        **Crash-only execution** (DESIGN.md §13).  ``checkpoint_dir=``
        journals per-tile progress and snapshots the reduction fold
        every ``checkpoint_every`` tiles, all keyed by
        :meth:`fingerprint`; re-running with the same directory (or
        ``resume_dir=``, the read-side alias) skips durable tiles and
        continues the fold exactly — bit-identical to the uninterrupted
        run on lax/materialize.  A directory written by a *different*
        plan refuses to load (``ValueError``).  Array-output streams
        need a persistent destination (``out=``/``out_path=``) to be
        checkpointable.

        **Fault policy.**  ``faults=`` takes a
        :class:`~repro.runtime.faults.FaultInjector` (chaos testing) —
        but the policy applies equally to real ``TransientFault`` /
        ``PermanentFault`` raised at the stream's boundaries: transient
        faults retry up to ``max_retries`` times with exponential
        ``retry_backoff`` seconds; permanent (or retry-exhausted) tiles
        are *quarantined* and the stream keeps going.  At end of stream,
        quarantined tiles raise :class:`StreamFaultError` when
        ``strict`` (the default), or — ``strict=False`` — the partial
        result returns and ``self.fault_report`` carries the
        uncovered-region mask.

        ``heartbeat=`` / ``straggler=`` wire the mesh-sharded path's
        tile-group dispatch into the runtime liveness monitors (slow
        groups are flagged and re-dispatched once); see
        ``repro.runtime.fault_tolerance``.

        **Tracing** (DESIGN.md §14).  ``trace=None`` (default) defers to
        the ``REPRO_TRACE`` env var; ``trace=True`` records into the
        global tracer for this run; ``trace="path.json"`` additionally
        exports the Chrome-trace JSON there when the run ends;
        ``trace=False`` is a hard off.  Per-tile read / h2d / execute /
        writeback / journal spans and fault instants land in per-thread
        tracks; counters land in ``repro.obs`` metrics either way
        (``obs.snapshot()`` reads them).

        ``budget=`` arbitrates *concurrent* streams: any object with a
        ``reserve(nbytes)`` context manager (canonically
        ``repro.serve.admission.MemoryBudget``) — the stream holds
        :meth:`working_set_bytes` reserved for its whole duration, so a
        shared budget caps the host's aggregate tiled working set.
        """
        hold = (budget.reserve(self.working_set_bytes())
                if budget is not None else contextlib.nullcontext())
        with hold, _trace_scope(trace):
            return self._run(mesh, axis_name, prefetch, out, out_path,
                             checkpoint_dir, resume_dir, checkpoint_every,
                             faults, max_retries, retry_backoff, strict,
                             heartbeat, straggler)

    def _run(self, mesh, axis_name, prefetch, out, out_path,
             checkpoint_dir, resume_dir, checkpoint_every, faults,
             max_retries, retry_backoff, strict, heartbeat, straggler):
        if (mesh is None) != (axis_name is None):
            raise ValueError("pass mesh= and axis_name= together")
        if mesh is not None and self.graph.batched:
            raise NotImplementedError(
                "mesh-sharded tile streams support unbatched graphs (the "
                "tile stack claims the batch-like axis); run batched "
                "graphs untiled via sharded_pipe_fn, or tiled without a "
                "mesh")
        if resume_dir is not None:
            if (checkpoint_dir is not None
                    and str(checkpoint_dir) != str(resume_dir)):
                raise ValueError(
                    "resume_dir= is an alias for checkpoint_dir= (resume "
                    "IS running with the same journal); pass one of them")
            checkpoint_dir = resume_dir
        if mesh is not None and (checkpoint_dir is not None
                                 or faults is not None):
            raise NotImplementedError(
                "checkpoint/fault-injection cover the single-process "
                "stream; the mesh path's resilience hooks are heartbeat= "
                "and straggler= (DESIGN.md §13)")
        reduce_out = self.program.out_kind != "array"
        inj = faults if faults is not None else NO_FAULTS

        ckpt = resume = None
        if checkpoint_dir is not None:
            if not reduce_out and out is None and out_path is None:
                raise ValueError(
                    "checkpointing an array-output stream needs a "
                    "persistent destination — pass out= (caller-owned "
                    "arena) or out_path= (memmap file) so completed "
                    "tiles survive the process")
            ckpt = StreamCheckpoint(
                str(checkpoint_dir), fingerprint=self.fingerprint(),
                num_tiles=self.num_tiles, out_kind=self.program.out_kind,
                every=max(1, int(checkpoint_every)))
            resume = ckpt.load()

        done = set(resume.done) if resume is not None else set()
        buf = self._make_out_buffer(out, out_path, resume=bool(done))
        records: list = []
        retried = 0

        def quarantine(idx, site, kind, attempts, err):
            spec = self.specs[idx]
            records.append({
                "tile": int(idx), "out_lo": list(spec.out_lo),
                "out_hi": list(spec.out_hi), "site": site, "fault": kind,
                "attempts": int(attempts), "error": err})
            _instant("fault/quarantine", tile=int(idx), site=site,
                     kind=kind, attempts=int(attempts))
            if ckpt is not None:
                ckpt.quarantine(idx, site, kind, attempts, err)

        def attempt(idx, site, fn):
            """Bounded per-tile retry → ``(ok, value)``.  Transient
            faults back off and retry; permanent faults quarantine at
            once; anything else — including ``StreamKilled`` —
            propagates (crash-only: the journal, not a handler, owns
            whole-process recovery)."""
            nonlocal retried
            tries = 0
            while True:
                try:
                    inj.check(site, idx, tries)
                    return True, fn()
                except TransientFault as e:
                    tries += 1
                    retried += 1
                    _instant("fault/transient", tile=int(idx), site=site,
                             attempt=tries)
                    if tries > max_retries:
                        quarantine(idx, site, "transient", tries, str(e))
                        return False, None
                    if retry_backoff:
                        with _span("fault/backoff", tile=int(idx),
                                   attempt=tries):
                            time.sleep(retry_backoff * 2.0 ** (tries - 1))
                except PermanentFault as e:
                    quarantine(idx, site, "permanent", tries + 1, str(e))
                    return False, None

        push = result = sink = fold = None
        if reduce_out:
            fold = _FoldStack(_merge_fn(self.program.out_kind),
                              entries=resume.entries if resume else ())
            push, result = fold.push, fold.result
        else:
            guard = on_placed = None
            if ckpt is not None or faults is not None:
                index_of = {s: i for i, s in enumerate(self.specs)}

                def guard(spec, place):
                    ok, _ = attempt(index_of[spec], "writeback", place)
                    return ok

            if ckpt is not None:
                def on_placed(spec, _n=[0]):
                    ckpt.tile_done(index_of[spec])
                    _n[0] += 1
                    if _n[0] % ckpt.every == 0:
                        if isinstance(buf, np.memmap):
                            buf.flush()
                        ckpt.sync()

            sink = _WritebackStream(
                buf, self.graph.batched, self.program.channels,
                self.out_dtype, depth=2 if prefetch else 1,
                guard=guard, on_placed=on_placed)

        t_run0 = time.perf_counter()
        try:
            with _span("stream/run", tiles=self.num_tiles,
                       classes=self.num_classes,
                       kind=self.program.out_kind,
                       sharded=mesh is not None):
                if mesh is not None:
                    res = self._run_sharded(mesh, axis_name, push, result,
                                            sink, heartbeat=heartbeat,
                                            straggler=straggler)
                else:
                    pending = [i for i in range(self.num_tiles)
                               if i not in done]
                    res = self._run_stream(pending, prefetch, attempt, push,
                                           sink, ckpt, fold, done)
            # end-of-stream durability: on full coverage the completion
            # marker alone is durable truth (resume short-circuits before
            # ever reading a snapshot), so the tail fold state is only
            # snapshotted when quarantines left the stream partial and a
            # resume will need it
            if ckpt is not None:
                if reduce_out and records:
                    ckpt.snapshot(done, fold.entries)
                elif isinstance(buf, np.memmap):
                    buf.flush()
                if not records:
                    ckpt.complete()
        finally:
            if ckpt is not None:
                ckpt.close()

        self.fault_report = FaultReport(
            num_tiles=self.num_tiles, out_shape=self.program.out_shape,
            records=records, retried=retried)
        if sink is not None:
            self.writeback_stats.clear()
            self.writeback_stats.update(sink.stats())
        # counters land in the obs registry whether or not tracing is on
        # — this is what obs.snapshot() unifies
        _counter("stream/runs").inc()
        _counter("stream/tiles").inc(self.num_tiles - len(
            self.fault_report.quarantined))
        if retried:
            _counter("stream/retried").inc(retried)
        if records:
            _counter("stream/quarantined").inc(len(records))
        if sink is not None:
            _gauge("stream/writeback_max_staged").max(sink.max_staged)
        for k, v in self.liveness_stats.items():
            _gauge(f"liveness/{k}").set(v)
        _histogram("stream/run_ms").observe(
            (time.perf_counter() - t_run0) * 1e3)
        if records and strict:
            raise StreamFaultError(self.fault_report)
        return res

    def _run_stream(self, pending, prefetch, attempt, push, sink, ckpt,
                    fold, done):
        """The single-device loop, double-buffered both ways: tile i+1's
        H2D transfer is issued before tile i's compute is dispatched,
        and tile i's D2H writeback drains while tile i+1 computes.
        ``pending`` is the stream order minus resumed-durable tiles."""
        specs = self.specs

        def grab(i):
            # the two halves of a fetch get their own spans: host-side
            # patch slicing vs the H2D transfer dispatch
            with _span("tile/read", tile=int(i)):
                patch = self._read_patch(specs[i])
            with _span("tile/h2d", tile=int(i)):
                return jax.device_put(patch)

        def fetch(k):
            idx = pending[k]
            ok, patch = attempt(idx, "read", lambda i=idx: grab(i))
            return patch if ok else None

        cur = fetch(0) if pending else None
        for k, idx in enumerate(pending):
            spec = specs[idx]
            nxt = (fetch(k + 1)
                   if prefetch and k + 1 < len(pending) else None)
            if cur is not None:  # read not quarantined
                plan = self._plan_for(spec)
                with _span("tile/execute", tile=int(idx)):
                    ok, tile = attempt(idx, "device",
                                       lambda c=cur: plan(c))
                if ok:
                    if push is not None:
                        push(tile)
                        done.add(idx)
                        if ckpt is not None:
                            with _span("tile/journal", tile=int(idx)):
                                ckpt.tile_done(idx)
                                # the final-tile boundary is excluded:
                                # full coverage is about to become a
                                # `complete` marker, partial coverage
                                # gets its tail snapshot from the
                                # quarantine path
                                if (len(done) % ckpt.every == 0
                                        and len(done) < self.num_tiles):
                                    ckpt.snapshot(done, fold.entries)
                    else:
                        sink.stage(spec, tile, tag=int(idx))
            if not prefetch and k + 1 < len(pending):
                nxt = fetch(k + 1)
            cur = nxt
        return fold.result() if push is not None else sink.flush()

    def run_restartable(self, *, checkpoint_dir, max_restarts: int = 3,
                        **kw):
        """Crash-loop driver for whole-stream restarts: :meth:`run` with
        journaling, and any unexpected exception → restart (which
        resumes from the journal, so completed work is never redone) up
        to ``max_restarts`` — the stream-level mirror of
        ``repro.runtime.fault_tolerance.run_restartable``.

        ``KeyboardInterrupt`` passes through (that's the user);
        :class:`StreamFaultError` passes through too — it already *is*
        the end-of-stream verdict, and restarting would re-quarantine
        the same tiles under the same deterministic faults.
        """
        restarts = 0
        while True:
            try:
                return self.run(checkpoint_dir=checkpoint_dir, **kw)
            except (KeyboardInterrupt, StreamFaultError):
                raise
            except Exception:  # noqa: BLE001 — crash-only restart
                restarts += 1
                if restarts > max_restarts:
                    raise

    def _run_sharded(self, mesh, axis_name, push, result, sink,
                     heartbeat=None, straggler=None):
        """Group same-class tiles into mesh-axis-sized stacks; each stack
        is one sharded dispatch (halos are baked in — no exchange).

        Array outputs share the staged writeback with the single-device
        path (a whole stacked group drains as one unit while the next
        group computes), and the stacked reads fill two alternating
        per-class host staging slabs instead of allocating a fresh
        ``np.stack`` per group — ``device_put`` may alias aligned host
        memory, so a slab is only refilled once the group computed from
        it has drained, which the sink's ≤1-pending invariant
        guarantees.  Leftover tiles drain through the same sink.

        ``heartbeat=``/``straggler=`` make each group dispatch a
        *liveness step*: the dispatch blocks until ready (trading the
        async pipeline for a measurable per-group latency), beats the
        heartbeat, and feeds the
        :class:`~repro.runtime.fault_tolerance.StragglerMonitor` — a
        flagged group is re-dispatched once (a fresh executor call over
        the still-resident device patch, the single-host analogue of
        rescheduling a slow rank's shard).  Counters land in
        ``self.liveness_stats``.
        """
        from repro.core.distributed import put_tile_batch
        from repro.stats.moments import merge_along_axis

        ways = int(mesh.shape[axis_name])
        reduce_out = push is not None
        dt = jnp.dtype(self.graph.x.dtype)
        live = heartbeat is not None or straggler is not None
        seq = [0]  # dispatched group count (the liveness "step")
        flagged = redispatched = 0

        def observe(tile, redo):
            nonlocal flagged, redispatched
            if not live:
                return tile
            t0 = time.perf_counter()
            tile = jax.block_until_ready(tile)
            dt_s = time.perf_counter() - t0
            if heartbeat is not None:
                heartbeat.beat(step=seq[0])
            if straggler is not None and straggler.observe(seq[0], dt_s):
                flagged += 1
                tile = jax.block_until_ready(redo())
                redispatched += 1
            seq[0] += 1
            return tile

        by_class = {}
        for spec in self.specs:
            by_class.setdefault(spec.class_key(), []).append(spec)
        slabs = {}  # class key -> two alternating input staging slabs
        leftovers = []
        for ckey, members in by_class.items():
            n_full = (len(members) // ways) * ways
            for i in range(0, n_full, ways):
                group = members[i:i + ways]
                if reduce_out:
                    stacked = np.stack(
                        [np.asarray(self._read_patch(s)) for s in group])
                else:
                    pair = slabs.get(ckey)
                    if pair is None:
                        pair = slabs[ckey] = [
                            np.empty((ways,) + group[0].patch_shape, dt)
                            for _ in range(2)]
                    stacked = pair[(i // ways) % 2]
                    for j, s in enumerate(group):
                        stacked[j] = self._read_patch(s)
                with _span("group/h2d", group=seq[0], size=ways):
                    dev = put_tile_batch(stacked, mesh, axis_name)
                plan = self._plan_for(group[0], stack=ways, mesh=mesh,
                                      axis_name=axis_name)
                with _span("group/execute", group=seq[0], size=ways):
                    tile = observe(plan(dev), lambda p=plan, d=dev: p(d))
                if reduce_out:
                    if self.program.out_kind == "moments":
                        push(merge_along_axis(tile, axis=0))
                    else:  # one folded hist/cov state per shard
                        for j in range(ways):
                            push(jax.tree.map(lambda leaf, j=j: leaf[j],
                                              tile))
                else:
                    sink.stage(tuple(group), tile)
            leftovers.extend(members[n_full:])
        # ragged remainders run one tile per device, round-robin over the
        # mesh (not all on the default device); their small fold states
        # come back to the host together, since committed arrays on
        # different devices cannot meet in one merge
        devices = mesh.devices.flat
        tails = []
        for k, spec in enumerate(leftovers):
            plan = self._plan_for(spec)
            dev = jax.device_put(self._read_patch(spec),
                                 devices[k % mesh.devices.size])
            tile = observe(plan(dev), lambda p=plan, d=dev: p(d))
            if reduce_out:
                tails.append(tile)
            else:
                sink.stage(spec, tile)
        for tile in jax.device_get(tails):
            push(tile)
        if live:
            self.liveness_stats.clear()
            self.liveness_stats.update(
                {"groups": seq[0], "flagged": flagged,
                 "redispatched": redispatched})
        return result() if reduce_out else sink.flush()


# -- planning entry points ---------------------------------------------------


def _validate_tiled(P: Pipe, program: PipelineProgram, opts: ExecOptions):
    if not P.ops:
        raise ValueError("tiled execution needs at least one op; an empty "
                         "pipeline has nothing to stream")
    if isinstance(P.x, jax.core.Tracer):
        raise ValueError(
            "tiled execution schedules host-side reads and cannot run on "
            "a traced input; call it outside jit")
    op0 = P.ops[0]
    if (isinstance(op0, MomentsOp) and op0.axis is not None):
        raise ValueError(
            "tiled moments reduce every spatial axis (tiles partition "
            "space); drop axis= or use stream_moments for custom axes")
    if program.out_kind == "cov" and not program.channels:
        raise ValueError(
            "tiled .cov() needs a bank stage to provide the channel axis "
            "(a standalone .cov() would tile across channels); use "
            "stream_channel_cov for raw channeled data")
    unit_stride = all(
        s.grid.stride == (1,) * s.grid.rank
        for s in program.steps if isinstance(s, LinearStep))
    if opts.resolved_method == "fused" and not unit_stride:
        raise ValueError(
            "the fused path supports stride-1 stages only under tiling "
            "(Pallas kernels lower stride-1 grids); use method='lax' or "
            "'materialize' for strided programs")


def plan_tiled(
    P: Pipe,
    *,
    tiles=None,
    memory_budget: Optional[int] = None,
    method: str = "auto",
    pad_value="edge",
    out_dtype=None,
    order: str = "hilbert",
) -> TiledProgram:
    """Compile a pipe graph into an out-of-core tile schedule.

    ``tiles`` is an int (split the leading spatial dim into that many
    slabs) or a per-dim tuple of tile counts; ``memory_budget`` (bytes)
    derives counts so one tile's working set fits the budget.  ``order``
    is ``'hilbert'`` (locality, the default) or ``'scan'`` (row-major).
    Exactly one of ``tiles``/``memory_budget`` must be given.
    """
    from repro.pipe.compile import _check_out_dtype

    opts = ExecOptions.make(method=method, pad_value=pad_value,
                            batched=P.batched, out_dtype=out_dtype)
    _check_out_dtype(P, opts)
    # split_same=False: the tile executor already pads at true volume
    # edges per stage — nesting a plan-time interior/boundary SplitStep
    # inside per-tile patches would re-split every patch for nothing
    program = build_program(P, opts, split_same=False)
    _validate_tiled(P, program, opts)
    geoms = _linear_geoms(program)
    rank = P.rank
    footprint = (compose_footprints([s.grid for s in geoms])
                 or ((1, 0, 0),) * rank)
    out_shape = program.out_shape

    # plan-time output metadata: abstract-eval the tile executor (shape
    # math only, no compute/compile) on the whole-volume "tile" — the
    # assembled buffer's dtype comes from the program, never from the
    # first computed tile, so mixed-precision programs can't mis-pin it
    out_full: Tuple[int, ...] = ()
    out_dt = None
    out_itemsize = 0
    if program.out_kind == "array":
        lead = (P.x.shape[0],) if P.batched else ()
        spec_all = _tile_spec(geoms, footprint, (0,) * rank,
                              out_shape, P.spatial_shape, opts.pad_value)
        aval = jax.eval_shape(
            lambda t: _run_tile(t, program, spec_all, opts, P.batched),
            jax.ShapeDtypeStruct(lead + spec_all.patch_shape,
                                 jnp.dtype(P.x.dtype)))
        out_dt = np.dtype(aval.dtype)
        out_itemsize = out_dt.itemsize
        out_full = (lead + out_shape
                    + ((program.channels,) if program.channels else ()))

    if (tiles is None) == (memory_budget is None):
        raise ValueError("pass exactly one of tiles= or memory_budget=")
    if tiles is not None:
        if isinstance(tiles, (int, np.integer)):
            counts = (int(tiles),) + (1,) * (rank - 1)
        else:
            counts = tuple(int(t) for t in tiles)
            if len(counts) != rank:
                raise ValueError(f"tiles must be an int or a rank-{rank} "
                                 f"tuple, got {tiles!r}")
        if any(t < 1 for t in counts):
            raise ValueError(f"tile counts must be >= 1, got {counts}")
    else:
        if memory_budget <= 0:
            raise ValueError(f"memory_budget must be positive bytes, got "
                             f"{memory_budget}")
        counts = _budget_tile_counts(
            out_shape, footprint, jnp.dtype(P.x.dtype).itemsize,
            P.x.shape[0] if P.batched else 1, program.channels,
            int(memory_budget), out_itemsize=out_itemsize)

    per_dim, boxes = plan_tile_partition(out_shape, counts)
    grid_counts = tuple(len(r) for r in per_dim)
    if order == "hilbert":
        idx = hilbert_order(grid_counts)
        flat = np.ravel_multi_index(tuple(idx.T), grid_counts)
        boxes = [boxes[int(i)] for i in flat]
    elif order != "scan":
        raise ValueError(f"unknown tile order {order!r}; expected "
                         f"'hilbert' or 'scan'")
    in_shape = P.spatial_shape
    specs = tuple(
        _tile_spec(geoms, footprint, lo, hi, in_shape, opts.pad_value)
        for lo, hi in boxes)
    classes = {}
    for s in specs:
        classes[s.class_key()] = classes.get(s.class_key(), 0) + 1
    return TiledProgram(graph=P, opts=opts, program=program,
                        footprint=footprint, tile_counts=grid_counts,
                        specs=specs, classes=classes,
                        out_shape=out_full, out_dtype=out_dt)


def run_tiled(P: Pipe, *, tiles=None, memory_budget=None, method="auto",
              pad_value="edge", out_dtype=None, order="hilbert",
              mesh=None, axis_name=None, prefetch=True, out=None,
              out_path=None, checkpoint_dir=None, resume_dir=None,
              checkpoint_every=8, faults=None, max_retries=3,
              retry_backoff=0.0, strict=True, heartbeat=None,
              straggler=None, trace=None, budget=None):
    """Plan + run in one call (the ``Pipe.run(tiles=…)`` backend)."""
    with _trace_scope(trace):
        with _span("stream/plan"):
            tp = plan_tiled(P, tiles=tiles, memory_budget=memory_budget,
                            method=method, pad_value=pad_value,
                            out_dtype=out_dtype, order=order)
        return tp.run(mesh=mesh, axis_name=axis_name, prefetch=prefetch,
                      out=out, out_path=out_path,
                      checkpoint_dir=checkpoint_dir, resume_dir=resume_dir,
                      checkpoint_every=checkpoint_every, faults=faults,
                      max_retries=max_retries, retry_backoff=retry_backoff,
                      strict=strict, heartbeat=heartbeat,
                      straggler=straggler, budget=budget)
