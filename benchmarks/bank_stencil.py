"""Operator-bank throughput: one fused bank pass vs K sequential stencils.

The tentpole claim (DESIGN.md §9): K operators over the same footprint
share one melt decomposition — the halo slab is loaded once and contracted
against a (numel, K) weight matrix, so the per-operator marginal cost is
one MXU column, not a full pass.  This bench measures the rank-3 curvature
bank (K = rank + rank² = 12, the Eq. 6–7 workload) four ways:

- ``bank/fused``       — one dense bank pass (the headline)
- ``seq/fused``        — K sequential ``apply_stencil`` calls
- ``bank/sep-fused``   — the bank as rank 1-D separable passes
- ``curv/materialized``— paper-faithful: melt ``M`` in HBM, ``M @ W``

plus the same bank/seq pair on the lax path, and end-to-end
``gaussian_curvature``.  It also *asserts* (always, not just ``--strict``)
that the fused bank never materializes ``M`` — the melt-call counter must
not move, even during tracing.

    PYTHONPATH=src python -m benchmarks.bank_stencil [--quick] [--strict]

Prints ``name,us_per_call,derived`` CSV (harness contract).  ``--strict``
exits nonzero when the fused bank is < 2x the K-sequential fused loop.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    curvature_bank,
    gaussian_curvature,
    melt,
    melt_call_count,
    plan_cache_stats,
    unmelt,
)
from repro.runtime.compile_cache import place_compile_cache

TARGET_SPEEDUP = 2.0
RANK = 3
QUICK_SHAPE = (16, 32, 32)
FULL_SHAPE = (24, 48, 48)
PAD = "edge"


def _time(f, reps=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(f())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6  # µs


def _time_pair(f, g, reps=20, warmup=3):
    """Interleave two measurands rep-by-rep so load/thermal drift hits both
    equally — phase-ordered timing makes ratio gates flake."""
    for _ in range(warmup):
        jax.block_until_ready(f())
        jax.block_until_ready(g())
    tf, tg = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        tf.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(g())
        tg.append(time.perf_counter() - t0)
    return float(np.median(tf)) * 1e6, float(np.median(tg)) * 1e6


def _materialized_curvature(x, W):
    """The pre-bank implementation: M really exists, then one matmul."""
    M = melt(x.astype(jnp.float32), (3,) * x.ndim, pad_value=PAD)
    D = M.data @ W
    return unmelt(D, M.grid)


def bank_vs_seq(x, W, method, reps):
    """Interleaved (t_bank, t_seq) for one method — shared with
    ``benchmarks.run``'s smoke section so the two never drift."""
    K = W.shape[1]
    return _time_pair(
        lambda: apply_stencil_bank(x, 3, W, method=method, pad_value=PAD,
                                   separable=False),
        lambda: [apply_stencil(x, 3, W[:, k], method=method, pad_value=PAD)
                 for k in range(K)],
        reps=reps)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensor, fewer reps")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when the fused bank misses the 2x "
                         "target vs K sequential fused calls (off by "
                         "default: wall-clock gates flake on shared "
                         "runners; the no-materialize assertion and "
                         "crashes always exit nonzero)")
    args = ap.parse_args(argv)
    place_compile_cache()

    shape = QUICK_SHAPE if args.quick else FULL_SHAPE
    reps = 5 if args.quick else 15
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    W = jnp.asarray(curvature_bank(RANK))  # (27, 12)
    K = W.shape[1]

    # -- no-materialize assertion (the DESIGN.md §9 memory contract) -------
    clear_plan_cache()
    before = melt_call_count()
    jax.block_until_ready(
        apply_stencil_bank(x, 3, W, method="fused", pad_value=PAD,
                           separable=False))
    fused_melts = melt_call_count() - before
    if fused_melts != 0:
        print(f"FATAL,fused bank materialized M ({fused_melts} melt calls)")
        return 2

    def bank(method, separable):
        return lambda: apply_stencil_bank(
            x, 3, W, method=method, pad_value=PAD, separable=separable)

    rows = []
    tag = "x".join(map(str, shape))
    t_bank_fused, t_seq_fused = bank_vs_seq(x, W, "fused", reps)
    speedup = t_seq_fused / t_bank_fused
    rows.append((f"bank/fused/{tag}/K{K}", t_bank_fused,
                 f"seq={t_seq_fused:.0f}us speedup={speedup:.2f}x"))
    t_sep, t_dense = _time_pair(
        bank("fused", True), bank("fused", False), reps=reps)
    rows.append((f"bank/sep-fused/{tag}/K{K}", t_sep,
                 f"dense={t_dense:.0f}us "
                 f"speedup={t_dense / t_sep:.2f}x"))
    t_bank_lax, t_seq_lax = bank_vs_seq(x, W, "lax", reps)
    rows.append((f"bank/lax/{tag}/K{K}", t_bank_lax,
                 f"seq={t_seq_lax:.0f}us "
                 f"speedup={t_seq_lax / t_bank_lax:.2f}x"))
    t_mat, t_bf = _time_pair(
        lambda: _materialized_curvature(x, W), bank("fused", False),
        reps=reps)
    rows.append((f"curv/materialized/{tag}", t_mat,
                 f"bank-fused={t_bf:.0f}us "
                 f"speedup={t_mat / t_bf:.2f}x"))
    for method in ("fused", "lax"):
        t = _time(lambda m=method: gaussian_curvature(x, method=m),
                  reps=reps)
        rows.append((f"curv/e2e-{method}/{tag}", t, "Eq.6-7 bank pass"))

    # 5³ Gaussian bank: past the Πkᵢ ≈ 4·Σkᵢ crossover, where 'auto'
    # switches to the separable rewrite (O(Σkᵢ) taps per grid point)
    from repro.core import gaussian_weights

    gw = gaussian_weights((5,) * RANK, 1.5)
    Wg = jnp.stack([gw, gw * 2, gw * 3, gw * 4], axis=1)
    t_gs, t_gd = _time_pair(
        lambda: apply_stencil_bank(x, 5, Wg, method="fused",
                                   separable=True),
        lambda: apply_stencil_bank(x, 5, Wg, method="fused",
                                   separable=False),
        reps=reps)
    rows.append((f"gauss/sep-fused/{tag}/op5/K4", t_gs,
                 f"dense={t_gd:.0f}us speedup={t_gd / t_gs:.2f}x"))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    stats = plan_cache_stats()
    print(f"plan_cache,size={stats['size']},"
          f"hits={stats['hits']} misses={stats['misses']}")
    print(f"melt_free,fused bank,PASS 0 melt calls")

    ok = speedup >= TARGET_SPEEDUP
    print(f"headline,bank-vs-{K}-seq fused,"
          f"{'PASS' if ok else 'WARN'} {speedup:.2f}x "
          f"(target {TARGET_SPEEDUP:.1f}x)")
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
