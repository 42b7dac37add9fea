"""Batched melt throughput: one batched dispatch vs a per-item python loop.

The tentpole claim (DESIGN.md §3): every melt row is independent, so a
batch of B tensors is just B× more rows — one plan lookup, one traced
executor, one kernel, instead of B dispatches.  This bench measures
``gaussian_filter`` over a ``(B, *spatial)`` stack against the equivalent
per-item loop, per execution path, and reports the plan-cache counters
that make the amortization visible.

    PYTHONPATH=src python -m benchmarks.batched_stencil [--quick]

Prints ``name,us_per_call,derived`` CSV (harness contract).  The
acceptance target is ≥2× batched throughput on the default config
(materialize path, B=8, CPU); the final line is PASS/FAIL against it.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clear_plan_cache, gaussian_filter, plan_cache_stats
from repro.runtime.compile_cache import place_compile_cache

#: the acceptance config: paper-faithful path, B=8, dispatch-bound tile size
#: (batching amortizes per-call dispatch; tiny tiles are where a serving
#: fleet actually bleeds, and where the loop is most wasteful)
HEADLINE = ("materialize", (32, 32), 5)
TARGET_SPEEDUP = 2.0


def _time(f, reps=30, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(f())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6  # µs


def bench_case(method, spatial, op, batch, sigma=1.5, reps=30):
    rng = np.random.RandomState(0)
    xb = jnp.asarray(rng.randn(batch, *spatial).astype(np.float32))
    items = [xb[i] for i in range(batch)]

    def batched():
        return gaussian_filter(xb, op, sigma, method=method, batched=True)

    def loop():
        return [gaussian_filter(it, op, sigma, method=method)
                for it in items]

    t_batched = _time(batched, reps=reps)
    t_loop = _time(loop, reps=reps)
    return t_batched, t_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="headline config only, fewer reps")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when the headline speedup misses the "
                         "target (off by default: wall-clock gates flake on "
                         "shared runners; crashes always exit nonzero)")
    args = ap.parse_args(argv)
    place_compile_cache()

    reps = 10 if args.quick else 30
    cases = [HEADLINE]
    if not args.quick:
        cases += [
            ("materialize", (64, 64), 5),
            ("materialize", (16, 16, 16), 3),
            ("lax", (32, 32), 5),
            ("lax", (64, 64), 5),
            ("fused", (64, 64), 5),  # interpret mode off-TPU
        ]

    clear_plan_cache()
    rows, headline_speedup = [], None
    for method, spatial, op in cases:
        t_b, t_l = bench_case(method, spatial, op, args.batch, reps=reps)
        speedup = t_l / t_b
        tag = "x".join(map(str, spatial))
        rows.append((f"batched/{method}/{tag}/op{op}/B{args.batch}",
                     t_b, f"loop={t_l:.0f}us speedup={speedup:.2f}x"))
        if (method, spatial, op) == HEADLINE:
            headline_speedup = speedup

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    stats = plan_cache_stats()
    print(f"plan_cache,size={stats['size']},"
          f"hits={stats['hits']} misses={stats['misses']}")

    ok = headline_speedup is not None and headline_speedup >= TARGET_SPEEDUP
    print(f"headline,{HEADLINE[0]} B={args.batch},"
          f"{'PASS' if ok else 'WARN'} {headline_speedup:.2f}x "
          f"(target {TARGET_SPEEDUP:.1f}x)")
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
