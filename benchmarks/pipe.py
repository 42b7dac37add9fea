"""Pipeline-fusion throughput: one planned pipe vs the eager call chain.

The tentpole claim (DESIGN.md §11): a lazy ``repro.pipe`` graph compiles
to the *minimum* number of melt passes.  The headline pipeline is
``gaussian → gradient → variance``:

- ``pipe/fused-chain``  — the planner merges the 'valid' gaussian and
  gradient stages into ONE composed 7³ K=3 bank by weight composition,
  auto-factors it into separable 1-D passes, and fuses the variance
  reduction into the producing pass (the derivative field never exists as
  a standalone array).  **Gated ≥2x** vs the eager 3-call chain
  (``apply_stencil`` → ``apply_stencil_bank`` → ``moments``).
- ``pipe/same-2pass``   — the same chain under 'same' padding.  The
  planner now SPLITS it (DESIGN.md §11 rule 1b): one composed-'valid'
  interior pass over the full volume plus six thin boundary slabs that
  replay the original stages bit-identically.  The row keeps its
  historical name but is **gated as a speedup** — the split must beat
  the per-stage eager chain.
- ``pipe/strided-compose`` — a stride-2 binomial pyramid (two 'valid'
  stride-2 stages + variance): rule 1a composes the stages into ONE
  7³ stride-4 separable pass.  **Gated** vs the 2-pass eager oracle.

It also *asserts* (always, not just ``--strict``) that the fused pipeline
never materializes ``M`` — the melt-call counter must not move — and that
the materialize-path melt count equals the plan's declared accounting.

    PYTHONPATH=src python -m benchmarks.pipe [--quick] [--strict]

Prints ``name,us_per_call,derived`` CSV (harness contract).  ``--strict``
exits nonzero when the fused pipeline misses the 2x target at the largest
shape.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bank_stencil import _time_pair
from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    melt_call_count,
    plan_cache_stats,
)
from repro.core.filters import difference_stencils, gaussian_weights
from repro.pipe import pipe
from repro.runtime.compile_cache import place_compile_cache
from repro.stats import moments

TARGET_SPEEDUP = 2.0
SIGMA = 1.5
GAUSS_OP = 5
QUICK_SHAPE = (32, 48, 48)
FULL_SHAPE = (64, 96, 96)


def _eager_chain_valid(x, w1, gw):
    """The pre-pipe spelling: three dispatches, two intermediates in HBM."""
    y = apply_stencil(x, GAUSS_OP, w1, padding="valid", method="auto")
    D = apply_stencil_bank(y, 3, gw, padding="valid", method="auto")
    return moments(D, axis=(0, 1, 2), method="auto", order=2).variance


def pipeline_pair(x, reps):
    """Interleaved (t_fused, t_eager) for the gated 'valid' pipeline —
    shared with ``benchmarks.run``'s smoke section so the two never
    drift."""
    w1 = jnp.asarray(gaussian_weights((GAUSS_OP,) * 3, SIGMA))
    gw = jnp.asarray(difference_stencils(3)[0], jnp.float32)
    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP, padding="valid")
         .gradient(padding="valid").moments(order=2))
    return _time_pair(
        lambda: P.run(method="auto").variance,
        lambda: _eager_chain_valid(x, w1, gw),
        reps=reps)


def same_pair(x, reps):
    """(t_pipe, t_eager) for the 'same'-padding pipeline.  The planner
    splits the chain into a composed interior pass + boundary slabs
    (rule 1b) — beating the per-stage eager chain is now the claim."""
    from repro.core import gaussian_filter, gradient

    P = (pipe(x).gaussian(SIGMA, op_shape=GAUSS_OP).gradient()
         .moments(order=2))

    def eager():
        y = gaussian_filter(x, GAUSS_OP, SIGMA, method="auto",
                            pad_value="edge")
        D = gradient(y, method="auto", pad_value="edge")
        return moments(D, axis=(0, 1, 2), method="auto", order=2).variance

    return _time_pair(
        lambda: P.run(method="auto", pad_value="edge").variance,
        eager, reps=reps)


def strided_pair(x, reps):
    """(t_pipe, t_eager) for the strided 'valid' pyramid: two stride-2
    binomial stages + variance compose into ONE 7³ stride-4 separable
    pass (rule 1a) vs the eager 2-pass downsampling chain."""
    b = np.array([1.0, 2.0, 1.0]) / 4.0
    w = jnp.asarray(np.einsum("i,j,k->ijk", b, b, b)
                    .ravel().astype(np.float32))
    P = (pipe(x).stencil(3, w, stride=2, padding="valid")
         .stencil(3, w, stride=2, padding="valid").moments(order=2))

    def eager():
        y = apply_stencil(x, 3, w, stride=2, padding="valid",
                          method="auto")
        z = apply_stencil(y, 3, w, stride=2, padding="valid",
                          method="auto")
        return moments(z, axis=(0, 1, 2), method="auto", order=2).variance

    return _time_pair(
        lambda: P.run(method="auto").variance, eager, reps=reps)


def headline_rows(x, reps):
    """The headline rows — ONE assembly shared by this CLI and
    ``benchmarks.run``'s pipe section (names/derived strings and the
    BENCH_pipe.json trajectory keyed on them can never drift).

    Returns ``(rows, fused_speedup)``; ``fused_speedup`` is the gated
    ratio.
    """
    tag = "x".join(map(str, x.shape))
    t_fused, t_eager = pipeline_pair(x, reps)
    speedup = t_eager / t_fused
    rows = [(f"pipe/fused-chain/{tag}", t_fused,
             f"eager-3call={t_eager:.0f}us speedup={speedup:.2f}x")]
    t_pipe, t_eager2 = same_pair(x, reps)
    rows.append((f"pipe/same-2pass/{tag}", t_pipe,
                 f"eager={t_eager2:.0f}us "
                 f"speedup={t_eager2 / t_pipe:.2f}x"))
    t_str, t_eager3 = strided_pair(x, reps)
    rows.append((f"pipe/strided-compose/{tag}", t_str,
                 f"eager-2pass={t_eager3:.0f}us "
                 f"speedup={t_eager3 / t_str:.2f}x"))
    return rows, speedup


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensor, fewer reps")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when the fused pipeline misses the "
                         "2x target vs the eager 3-call chain (off by "
                         "default: wall-clock gates flake on shared "
                         "runners; the no-materialize assertion and "
                         "crashes always exit nonzero)")
    args = ap.parse_args(argv)
    place_compile_cache()

    shape = QUICK_SHAPE if args.quick else FULL_SHAPE
    reps = 3 if args.quick else 7
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))

    # -- no-materialize + plan-accounting assertions (DESIGN.md §11) -------
    clear_plan_cache()
    small = jnp.asarray(rng.randn(12, 14, 10).astype(np.float32))
    P_small = (pipe(small).gaussian(SIGMA, op_shape=GAUSS_OP,
                                    padding="valid")
               .gradient(padding="valid").moments(order=2))
    prog = P_small.plan(method="auto")
    if prog.passes != 1:
        print(f"FATAL,composed pipeline planned {prog.passes} passes, "
              f"want 1")
        return 2
    before = melt_call_count()
    jax.block_until_ready(P_small.run(method="auto").mean)
    if melt_call_count() != before:
        print(f"FATAL,fused pipeline materialized M "
              f"({melt_call_count() - before} melt calls)")
        return 2
    prog_m = P_small.plan(method="materialize")
    before = melt_call_count()
    jax.block_until_ready(P_small.run(method="materialize").mean)
    got = melt_call_count() - before
    if got != prog_m.melt_calls:
        print(f"FATAL,materialize melt count {got} != planned "
              f"{prog_m.melt_calls}")
        return 2
    # the gated rows' planner claims (DESIGN.md §11 rules 1a/1b)
    prog_same = (pipe(small).gaussian(SIGMA, op_shape=GAUSS_OP).gradient()
                 .moments(order=2).plan(method="auto", pad_value="edge"))
    if prog_same.passes != 1:
        print(f"FATAL,'same' chain planned {prog_same.passes} passes, "
              f"want 1 (split)")
        return 2
    b = np.array([1.0, 2.0, 1.0]) / 4.0
    w3 = jnp.asarray(np.einsum("i,j,k->ijk", b, b, b)
                     .ravel().astype(np.float32))
    prog_str = (pipe(small).stencil(3, w3, stride=2, padding="valid")
                .stencil(3, w3, stride=2, padding="valid").moments(order=2)
                .plan(method="auto"))
    if prog_str.passes != 1:
        print(f"FATAL,strided chain planned {prog_str.passes} passes, "
              f"want 1 (composed stride-4)")
        return 2
    # measured tile autotuning engages on the fused path (DESIGN.md §16):
    # one fused run must intern at least one TunePlan, unless the env
    # opt-out pinned the heuristic
    from repro.kernels.melt_stencil import autotune_enabled

    if autotune_enabled():
        jax.block_until_ready(
            pipe(small).gaussian(SIGMA, op_shape=3).gradient()
            .run(method="fused", pad_value="edge"))
        if plan_cache_stats()["kinds"]["tune"] < 1:
            print("FATAL,fused run interned no TunePlan with autotuning "
                  "enabled")
            return 2

    rows, speedup = headline_rows(x, reps)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    stats = plan_cache_stats()
    print(f"plan_cache,size={stats['size']},"
          f"hits={stats['hits']} misses={stats['misses']}")
    print("melt_free,fused pipeline,PASS 0 melt calls")

    ok = speedup >= TARGET_SPEEDUP
    print(f"headline,pipe-fused-vs-eager-3call,"
          f"{'PASS' if ok else 'WARN'} {speedup:.2f}x "
          f"(target {TARGET_SPEEDUP:.1f}x)")
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
