"""Statistics-engine throughput: streaming sufficient statistics vs naive
multi-pass reduction (DESIGN.md §10).

The tentpole claim: summary statistics are ONE streaming pass over the
data — a plan-cached dispatch producing mergeable (count, mean, M2..M4)
states — where the naive baseline pays two eager passes *per tensor*
(``jnp.mean`` then ``jnp.var``), B× over a batch.  Headline rows:

- ``stats/var-streaming``   — batched order-2 streaming variance (one
  dispatch for the whole stack) vs the per-item two-pass
  ``jnp.mean``/``jnp.var`` loop.  This is the gated pair.
- ``stats/summary-full``    — order-4 one-pass (mean/var/skew/kurt) vs the
  four-pass eager baseline.
- ``stats/fused-interp``    — the Pallas tile-reduction kernel (interpret
  mode off-TPU: the memory-contract proof, not a CPU speed claim).
- ``local/zscore``, ``hist/quantiles``, ``cov/pca`` — subsystem ends.

It also *asserts* (always, not just ``--strict``) that the fused moments
path never materializes ``M`` — the melt-call counter must not move, even
during tracing.

    PYTHONPATH=src python -m benchmarks.stats [--quick] [--strict]

Prints ``name,us_per_call,derived`` CSV (harness contract).  ``--strict``
exits nonzero when the streaming variance misses the 2x target against the
per-item two-pass loop at the largest shape.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bank_stencil import _time, _time_pair
from repro.core import clear_plan_cache, melt_call_count, plan_cache_stats
from repro.runtime.compile_cache import place_compile_cache
from repro.stats import (
    channel_cov,
    histogram,
    moments,
    pca,
    quantile,
    zscore,
)

TARGET_SPEEDUP = 2.0
BATCH = 8
QUICK_ITEM = (32, 64, 64)
FULL_ITEM = (64, 96, 96)


def var_streaming_pair(xb, reps):
    """Interleaved (t_streaming, t_loop) for the gated pair — shared with
    ``benchmarks.run``'s smoke section so the two never drift.

    Streaming: one plan-cached batched order-2 pass over the whole stack.
    Baseline: the naive per-item two-pass — eager ``jnp.mean`` then
    ``jnp.var`` per tensor, exactly what the code this subsystem replaces
    looks like.
    """
    B = xb.shape[0]

    def streaming():
        st = moments(xb, batched=True, order=2)
        return st.mean, st.variance

    def loop_twopass():
        return [(jnp.mean(xb[i]), jnp.var(xb[i])) for i in range(B)]

    return _time_pair(streaming, loop_twopass, reps=reps)


def summary_pair(x, reps):
    """(t_onepass, t_fourpass): full order-4 summary vs eager multi-pass."""

    def onepass():
        st = moments(x)
        return st.mean, st.variance, st.skewness, st.kurtosis

    def fourpass():
        mu = jnp.mean(x)
        var = jnp.var(x)
        c = x - mu
        m3 = jnp.mean(c**3)
        m4 = jnp.mean(c**4)
        return mu, var, m3 / var**1.5, m4 / var**2 - 3.0

    return _time_pair(onepass, fourpass, reps=reps)


def headline_rows(xb, reps):
    """The two headline rows — ONE assembly shared by this CLI and
    ``benchmarks.run``'s stats section, so names/derived strings (and the
    BENCH_stats.json trajectory keyed on them) can never drift.

    Returns ``(rows, var_speedup)``; ``var_speedup`` is the gated ratio.
    """
    item = xb.shape[1:]
    tag = f"B{xb.shape[0]}x" + "x".join(map(str, item))
    t_stream, t_loop = var_streaming_pair(xb, reps)
    speedup = t_loop / t_stream
    rows = [(f"stats/var-streaming/{tag}", t_stream,
             f"loop-twopass={t_loop:.0f}us speedup={speedup:.2f}x")]
    t_one, t_four = summary_pair(xb[0], reps)
    rows.append((f"stats/summary-full/{'x'.join(map(str, item))}", t_one,
                 f"fourpass={t_four:.0f}us "
                 f"speedup={t_four / t_one:.2f}x"))
    return rows, speedup


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensors, fewer reps")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when streaming variance misses the "
                         "2x target vs the per-item two-pass loop (off by "
                         "default: wall-clock gates flake on shared "
                         "runners; the no-materialize assertion and "
                         "crashes always exit nonzero)")
    args = ap.parse_args(argv)
    place_compile_cache()

    item = QUICK_ITEM if args.quick else FULL_ITEM
    reps = 5 if args.quick else 15
    rng = np.random.RandomState(0)
    xb = jnp.asarray((rng.randn(BATCH, *item) * 2 + 5).astype(np.float32))
    x1 = xb[0]

    # -- no-materialize assertion (the DESIGN.md §10 memory contract) ------
    clear_plan_cache()
    before = melt_call_count()
    st = moments(x1, method="fused")
    jax.block_until_ready(st.mean)
    fused_melts = melt_call_count() - before
    if fused_melts != 0:
        print(f"FATAL,fused moments materialized M ({fused_melts} melt "
              f"calls)")
        return 2

    rows, speedup = headline_rows(xb, reps)

    t_fused = _time(lambda: jax.block_until_ready(
        moments(x1, method="fused").variance), reps=max(3, reps // 3))
    rows.append((f"stats/fused-interp/{'x'.join(map(str, item))}", t_fused,
                 "tile-reduction kernel (interpret off-TPU)"))

    t_z = _time(lambda: jax.block_until_ready(zscore(x1, 5)), reps=reps)
    rows.append((f"local/zscore/{'x'.join(map(str, item))}/op5", t_z,
                 "windowed (x-mu)/sigma, separable box bank"))

    flat = xb.reshape(-1)
    def hist_quant():
        h = histogram(flat, bins=128, range=(-11.0, 21.0))
        return quantile(h, jnp.asarray([0.25, 0.5, 0.75]))
    t_h = _time(lambda: jax.block_until_ready(hist_quant()), reps=reps)
    rows.append((f"hist/quantiles/{flat.shape[0]}", t_h,
                 "128 bins + q25/50/75"))

    xc = jnp.asarray(rng.randn(4096, 8).astype(np.float32))
    def cov_pca():
        ev, _ = pca(channel_cov(xc), k=3, iters=32)
        return ev
    t_p = _time(lambda: jax.block_until_ready(cov_pca()), reps=reps)
    rows.append(("cov/pca/4096x8/k3", t_p, "streamed cov + subspace iter"))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    stats = plan_cache_stats()
    print(f"plan_cache,size={stats['size']},"
          f"hits={stats['hits']} misses={stats['misses']}")
    print("melt_free,fused moments,PASS 0 melt calls")

    ok = speedup >= TARGET_SPEEDUP
    print(f"headline,streaming-var-vs-{BATCH}x-twopass,"
          f"{'PASS' if ok else 'WARN'} {speedup:.2f}x "
          f"(target {TARGET_SPEEDUP:.1f}x)")
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
