"""Out-of-core tiled streaming: fused tile program vs naive per-tile loop.

The tentpole claim (DESIGN.md §12): a reduction-terminated pipe graph
streams a volume through halo-padded tiles — the full intermediate never
exists — and still beats the obvious alternative, a **naive per-tile
eager loop** that runs the 3-call chain (``apply_stencil`` →
``apply_stencil_bank`` → ``moments``) on every tile and merges states.
Both sides see identical tile geometry, so the gated ratio isolates what
tiling *keeps* from PR 4's fusion work: one composed separable pass per
tile instead of three dispatches and two tile-sized intermediates.

- ``tiled/stream-var``  — streaming variance of ``gaussian('valid') →
  gradient('valid') → moments(order=2)`` over a Hilbert-ordered tile
  stream.  **Gated ≥2x** vs the naive per-tile eager loop.
- ``tiled/assemble``    — the *array-output* spelling of the same fused
  pipeline, run in the honest out-of-core setting: host-resident numpy
  volume, slab tiles, async double-buffered D2H writeback into a reused
  ``out=`` arena, vs producing the same host-side ``np.ndarray`` in
  memory.  **Gated ≥1.0x parity** (``GATED_FLOORS`` in
  ``benchmarks.regression``): with the 'valid'-composed program the
  slab decomposition recomputes nothing (each slab's halo is consumed
  by its own separable pass), so assembly itself is the only variable
  and tiling must at least break even.  ('same'-padded programs still
  pay halo-redundant compute per tile — removing that is ROADMAP item
  3's interior-'valid' composition, not a writeback question.)
- ``tiled/memmap-out``  — the same program assembling straight into an
  ``np.lib.format.open_memmap`` file (``out_path=``); context scaling
  row for the larger-than-RAM story.
- ``tiled/ckpt-overhead`` — the stream row's reduction run *with* the
  crash-only journal + fold-state snapshots (``checkpoint_dir=``,
  DESIGN.md §13) vs the same run unjournaled.  **Gated ≥0.95x parity**
  (≤5% overhead): durability is cadence-chunked journal appends/fsyncs
  plus an atomic ``state.npz`` snapshot every ``checkpoint_every``
  tiles, all on the checkpoint's background writer thread while the
  stream's host thread keeps dispatching tiles, so it must be nearly
  free next to the compute.
- ``tiled/trace-overhead`` — the stream row's reduction run with the
  ``repro.obs`` tracer recording per-tile spans (DESIGN.md §14) vs the
  same run with the recorder off.  **Gated ≥0.95x parity** (≤5%
  overhead): a span is two clock reads and one per-thread ring append,
  so tracing must be cheap enough to leave on for real streams.

It also *asserts* (always, not just ``--strict``):

- the tiled stream never materializes ``M`` off the materialize oracle
  (``melt_call_count`` must not move on lax/fused);
- the plan cache traces once per tile-shape *class*, not per tile;
- the streamed volume is ≥4x the per-tile patch working set (the run is
  genuinely out-of-core-shaped, not one big tile);
- streamed variance is allclose to the untiled run;
- the assemble stream never stages more than 2 output tiles, and the
  memmap-out result matches the in-memory run bit-for-bit.

    PYTHONPATH=src python -m benchmarks.tiled [--quick] [--strict]

Prints ``name,us_per_call,derived`` CSV (harness contract).  ``--strict``
exits nonzero when the stream misses the 2x target at the largest shape.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bank_stencil import _time_pair
from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    melt_call_count,
)
from repro.core.filters import difference_stencils, gaussian_weights
from repro.pipe import pipe
from repro.runtime.compile_cache import place_compile_cache
from repro.stats import moments
from repro.stats.moments import merge_moments

TARGET_SPEEDUP = 2.0
SIGMA = 1.5
GAUSS_OP = 5
QUICK_SHAPE = (32, 48, 48)
FULL_SHAPE = (64, 96, 96)
TILES = (4, 2, 2)
#: assembly streams leading-dim slabs: with the 'valid'-composed program
#: a slab's halo is consumed by its own separable pass (zero redundant
#: compute), and slab reads are contiguous host views — the tiling under
#: which the parity claim is exact, not best-effort
ASM_TILES = (2, 1, 1)


def _naive_tile_loop(x, tp, w1, gw):
    """The pre-tiled spelling: per tile, three eager dispatches and two
    tile-sized intermediates, states merged across tiles."""
    state = None
    for spec in tp.specs:
        sl = tuple(slice(l, h) for l, h in zip(spec.read_lo, spec.read_hi))
        patch = x[sl]
        y = apply_stencil(patch, GAUSS_OP, w1, padding="valid",
                          method="auto")
        D = apply_stencil_bank(y, 3, gw, padding="valid", method="auto")
        crop = tuple(slice(a, b) for a, b in spec.crop)
        st = moments(D[crop + (slice(None),)], axis=(0, 1, 2),
                     method="auto", order=2)
        state = st if state is None else merge_moments(state, st)
    return state.variance


def stream_pair(x, reps):
    """Interleaved (t_tiled, t_naive) for the gated stream — shared with
    ``benchmarks.run``'s tiled section so the two never drift."""
    w1 = jnp.asarray(gaussian_weights((GAUSS_OP,) * 3, SIGMA))
    gw = jnp.asarray(difference_stencils(3)[0], jnp.float32)
    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid").moments(order=2))
    tp = P.plan_tiled(tiles=TILES, method="auto")
    return _time_pair(
        lambda: tp.run().variance,
        lambda: _naive_tile_loop(x, tp, w1, gw),
        reps=reps), tp


def ckpt_pair(x, ckpt_root, reps):
    """(t_journaled_us, parity) for the stream row's reduction program.
    Gated ≥0.95x parity: journaling + snapshot-every-8-tiles must cost
    ≤5% vs the unjournaled stream.

    Two quirks vs the other rows' plain ``_time_pair``: each journaled
    rep gets a *fresh* checkpoint dir (re-running into a completed
    journal would resume and compute nothing, timing the no-op instead
    of the durable run), and parity is the median of per-rep
    *bracketed* ratios — each journaled call is sandwiched between two
    plain calls and compared to their mean.  The overhead under test is
    a few percent, below the minute-scale clock drift of shared
    runners; independent medians (what ``_time_pair`` returns) absorb
    that drift into the ratio, bracketing cancels it."""
    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid").moments(order=2))
    tp = P.plan_tiled(tiles=TILES, method="auto")
    n = [0]

    def run_journaled():
        n[0] += 1
        d = os.path.join(ckpt_root, f"rep{n[0]}")
        return tp.run(checkpoint_dir=d, checkpoint_every=8).variance

    def run_plain():
        return tp.run().variance

    def once(f):
        t0 = time.perf_counter()
        np.asarray(f())
        return time.perf_counter() - t0

    for _ in range(2):  # warmup: trace + first-touch of the ckpt dir
        once(run_journaled), once(run_plain)
    ratios, times = [], []
    for _ in range(reps):
        before = once(run_plain)
        t_j = once(run_journaled)
        after = once(run_plain)
        times.append(t_j)
        ratios.append(((before + after) / 2) / t_j)
    return (float(np.median(times)) * 1e6, float(np.median(ratios))), tp


def trace_pair(x, reps):
    """(t_traced_us, parity) for the stream row's reduction program with
    the tracer recording vs off.  Gated ≥0.95x parity: a recorded span
    is two clock reads + one ring append per tile stage, so tracing a
    stream must cost ≤5% next to the compute it measures (DESIGN.md
    §14) — otherwise nobody traces production streams and the timeline
    lies about the untraced run.

    Same bracketing as ``ckpt_pair`` (the overhead under test is below
    shared-runner clock drift).  The enabled flag is forced per rep
    instead of passing ``trace=``: under ``REPRO_TRACE`` (how CI runs
    this benchmark) the env hook has already enabled the global tracer,
    and ``trace=False`` only skips the scope, it does not disable the
    recorder — forcing the flag is what actually isolates the recording
    cost.  The rings are never reset so the spans recorded here (and by
    the earlier rows) survive into the env hook's at-exit export, which
    the CI trace check reads."""
    from repro.obs import TRACER

    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid").moments(order=2))
    tp = P.plan_tiled(tiles=TILES, method="auto")

    def once(enabled):
        was = TRACER.enabled
        TRACER.enabled = enabled
        try:
            t0 = time.perf_counter()
            np.asarray(tp.run(trace=False).variance)
            return time.perf_counter() - t0
        finally:
            TRACER.enabled = was

    for _ in range(2):  # warmup: trace the plan + register the rings
        once(True), once(False)
    ratios, times = [], []
    for _ in range(reps):
        before = once(False)
        t_t = once(True)
        after = once(False)
        times.append(t_t)
        ratios.append(((before + after) / 2) / t_t)
    return (float(np.median(times)) * 1e6, float(np.median(ratios))), tp


def _assemble_setup(x):
    """The honest out-of-core setting: a *host-resident* numpy volume —
    both sides stream it from host memory, the tiled side through the
    async writeback, the in-memory side as one whole-volume H2D → compute
    → full D2H.  The program is the array-output spelling of the stream
    row's fused pipeline (one composed separable 'valid' pass)."""
    xh = np.asarray(x)
    P = (pipe(xh).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid"))
    tp = P.plan_tiled(tiles=ASM_TILES, method="auto")
    return P, tp


def assemble_pair(x, reps):
    """(t_tiled, t_inmemory) for an array-valued program.  Gated ≥1.0x:
    the tiled side assembles into a reused ``out=`` arena (the steady
    state of an out-of-core loop), the in-memory side materializes the
    same host-side ``np.ndarray``."""
    P, tp = _assemble_setup(x)
    arena = np.empty(tp.out_shape, tp.out_dtype)
    return _time_pair(
        lambda: tp.run(out=arena),
        lambda: np.asarray(P.run(method="auto")),
        reps=reps), tp


def memmap_pair(x, out_path, reps):
    """(t_memmap, t_inmemory): same program, assembling straight into an
    ``open_memmap`` file — the larger-than-RAM scaling row (context)."""
    P, tp = _assemble_setup(x)
    return _time_pair(
        lambda: tp.run(out_path=out_path),
        lambda: np.asarray(P.run(method="auto")),
        reps=reps), tp


def headline_rows(x, reps):
    """ONE assembly shared by this CLI and ``benchmarks.run``'s tiled
    section (names/derived strings and the BENCH_tiled.json trajectory
    keyed on them can never drift).  Returns ``(rows, stream_speedup)``.
    """
    tag = "x".join(map(str, x.shape))
    (t_tiled, t_naive), tp = stream_pair(x, reps)
    speedup = t_naive / t_tiled
    rows = [(f"tiled/stream-var/{tag}/t{tp.num_tiles}", t_tiled,
             f"naive-loop={t_naive:.0f}us speedup={speedup:.2f}x")]
    # the assemble rows gate on an *absolute* 1.0x parity floor and their
    # true value sits near 1.0, so the median needs more samples than the
    # 2x-gated stream row; both sides of a pair are ~the same cost, so
    # the extra reps are cheap
    asm_reps = max(reps, 9)
    (t_asm, t_mem), tpa = assemble_pair(x, asm_reps)
    rows.append((f"tiled/assemble/{tag}/t{tpa.num_tiles}", t_asm,
                 f"in-memory={t_mem:.0f}us parity={t_mem / t_asm:.2f}x"))
    with tempfile.TemporaryDirectory() as td:
        (t_mm, t_mem2), _ = memmap_pair(
            x, os.path.join(td, "assemble.npy"), asm_reps)
    rows.append((f"tiled/memmap-out/{tag}/t{tpa.num_tiles}", t_mm,
                 f"in-memory={t_mem2:.0f}us parity={t_mem2 / t_mm:.2f}x"))
    # like the assemble rows, the ckpt row gates on an absolute parity
    # floor near its true value — give the median the extra samples
    with tempfile.TemporaryDirectory() as td:
        (t_ckpt, parity), tpc = ckpt_pair(x, td, asm_reps)
    rows.append((f"tiled/ckpt-overhead/{tag}/t{tpc.num_tiles}", t_ckpt,
                 f"unjournaled={t_ckpt * parity:.0f}us "
                 f"parity={parity:.2f}x"))
    (t_tr, tr_parity), tpt = trace_pair(x, asm_reps)
    rows.append((f"tiled/trace-overhead/{tag}/t{tpt.num_tiles}", t_tr,
                 f"untraced={t_tr * tr_parity:.0f}us "
                 f"parity={tr_parity:.2f}x"))
    return rows, speedup


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensor, fewer reps")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when the tiled stream misses the "
                         "2x target vs the naive per-tile eager loop (off "
                         "by default: wall-clock gates flake on shared "
                         "runners; the contract assertions always exit "
                         "nonzero)")
    args = ap.parse_args(argv)
    place_compile_cache()

    shape = QUICK_SHAPE if args.quick else FULL_SHAPE
    reps = 3 if args.quick else 5
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))

    # -- contract assertions (DESIGN.md §12), always on --------------------
    clear_plan_cache()
    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid").moments(order=2))
    tp = P.plan_tiled(tiles=TILES, method="auto")
    patch_elems = max(int(np.prod(s.patch_shape)) for s in tp.specs)
    if x.size < 4 * patch_elems:
        print(f"FATAL,volume {x.size} not >=4x the tile working set "
              f"{patch_elems} — the benchmark is not out-of-core-shaped")
        return 2
    before = melt_call_count()
    st = tp.run()
    if melt_call_count() != before:
        print(f"FATAL,tiled stream materialized M "
              f"({melt_call_count() - before} melt calls)")
        return 2
    traces = sum(tp._plan_for(s).stats()["traces"]
                 for s in {s.class_key(): s for s in tp.specs}.values())
    if traces != tp.num_classes:
        print(f"FATAL,{traces} traces for {tp.num_classes} tile classes "
              f"({tp.num_tiles} tiles) — per-tile retracing")
        return 2
    ref = P.run(method="auto")
    if not np.allclose(np.asarray(st.variance), np.asarray(ref.variance),
                       rtol=1e-5, atol=1e-7):
        print("FATAL,tiled streamed variance diverged from the untiled run")
        return 2

    # -- assemble-path contract: the async writeback stages at most 2
    # output tiles, and the memmap-out file matches both the in-memory
    # run (allclose) and the in-RAM tiled assembly (bit-for-bit)
    Pa, tpa = _assemble_setup(x)
    ref_a = np.asarray(Pa.run(method="auto"))
    with tempfile.TemporaryDirectory() as td:
        mm = tpa.run(out_path=os.path.join(td, "assemble.npy"))
        if tpa.writeback_stats["max_staged"] > 2:
            print(f"FATAL,assemble stream staged "
                  f"{tpa.writeback_stats['max_staged']} output tiles "
                  f"(working-set bound is 2)")
            return 2
        if not np.array_equal(np.asarray(mm), tpa.run()):
            print("FATAL,memmap-out assembly diverged from the in-RAM "
                  "tiled assembly")
            return 2
        if not np.allclose(np.asarray(mm), ref_a, rtol=1e-5, atol=1e-5):
            print("FATAL,memmap-out assembly diverged from the in-memory "
                  "run")
            return 2
        del mm  # release the mmap before the tempdir goes away

    rows, speedup = headline_rows(x, reps)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"tile_classes,{tp.num_classes},{tp.num_tiles} tiles "
          f"{'x'.join(map(str, tp.tile_counts))}")
    print("melt_free,tiled stream,PASS 0 melt calls")

    ok = speedup >= TARGET_SPEEDUP
    print(f"headline,tiled-stream-vs-naive-loop,"
          f"{'PASS' if ok else 'WARN'} {speedup:.2f}x "
          f"(target {TARGET_SPEEDUP:.1f}x)")
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
