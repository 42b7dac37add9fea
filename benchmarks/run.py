"""Benchmark harness: one section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV (harness contract); ``--json PATH``
additionally writes machine-readable results (name, us_per_call, derived,
backend, git rev per row) for the BENCH_*.json trajectory.  A section
that raises still prints (and writes) its ``ERROR`` row, and the run
then exits 1.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
                                           [--sections a,b,...]

Sections:
  fig6/*      — paper Fig 6: melt-matrix row-partition scaling
  fig7/*      — paper Fig 7: ElementWise / VectorWise / MatBroadcast
  stencil/*   — engine path comparison (materialize / lax / pallas-interp)
  filters/*   — bilateral (Eq.3) and curvature (Eq.6-7) end-to-end
  bank/*      — operator-bank fused execution (DESIGN.md §9)
  stats/*     — streaming statistics engine (DESIGN.md §10)
  pipe/*      — lazy pipeline fusion (DESIGN.md §11)
  tiled/*     — out-of-core tiled streaming (DESIGN.md §12)
  model/*     — smoke-config step latencies per architecture family
  serve-lm/*  — LM prefill + decode latency (smoke config)
  serve/*     — analytics serving tier: coalesced batched dispatch
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.compile_cache import place_compile_cache


def _time(f, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(f(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def bench_filters(quick=False):
    from repro.core.filters import bilateral_filter, gaussian_curvature

    rng = np.random.RandomState(0)
    shape = (24, 48, 48) if quick else (32, 64, 64)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    rows = []
    f = jax.jit(lambda t: bilateral_filter(t, 5, 1.5, 0.5))
    rows.append(("filters/bilateral_const", _time(f, x), f"3-D {shape}"))
    f = jax.jit(lambda t: bilateral_filter(t, 5, 1.5, "adaptive"))
    rows.append(("filters/bilateral_adaptive", _time(f, x), "paper Eq.3 σr(x)"))
    f = jax.jit(gaussian_curvature)
    rows.append(("filters/curvature3d", _time(f, x), "paper Eq.6-7"))
    img = jnp.asarray(rng.randn(256, 256), jnp.float32)
    f = jax.jit(gaussian_curvature)
    rows.append(("filters/curvature2d", _time(f, img), "256x256"))
    return rows


def bench_models(quick=False):
    from repro.configs import get_smoke_config, list_archs
    from repro.models import build_model
    from repro.optim import adamw

    rows = []
    archs = ["minitron_4b", "mamba2_370m", "hymba_1p5b"] if quick else list_archs()
    for arch in archs:
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S = 2, 64
        batch = {
            "tokens": jnp.zeros((B, S), jnp.int32),
            "targets": jnp.zeros((B, S), jnp.int32),
        }
        if cfg.n_vis_tokens:
            batch["vis_embed"] = jnp.zeros((B, cfg.n_vis_tokens, cfg.d_model),
                                           jnp.bfloat16)
        if cfg.n_enc_layers:
            batch["enc_embed"] = jnp.zeros((B, 32, cfg.d_model), jnp.bfloat16)
        opt = adamw.init(params)

        @jax.jit
        def step(p, o, b):
            (l, m), g = jax.value_and_grad(
                lambda q: model.loss_fn(q, b), has_aux=True)(p)
            return adamw.update(g, o, p, lr=1e-3)

        rows.append((f"model/{arch}/train_step",
                     _time(step, params, opt, batch, reps=3),
                     f"smoke cfg B{B} S{S}"))
    return rows


def bench_serving(quick=False):
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config("minitron_4b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 64
    toks = jnp.zeros((B, S), jnp.int32)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=S + 32))
    rows = [("serve-lm/prefill",
             _time(prefill, params, {"tokens": toks}, reps=3),
             f"B{B} S{S}")]
    _, caches = prefill(params, {"tokens": toks})
    dec = jax.jit(model.decode_step)
    tok = jnp.zeros((B,), jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    rows.append(("serve-lm/decode_step",
                 _time(lambda: dec(params, tok, pos, caches), reps=5),
                 "one token, cached"))
    return rows


def bench_serve_tier(quick=False):
    """Analytics serving rows: the shared headline + mixed-key rows from
    benchmarks.serve (same service config, warmup and interleaved
    timing — the smoke numbers can't drift from the gated benchmark)."""
    from benchmarks.serve import headline_rows, mixed_key_row, \
        tiled_concurrency_row

    reps = 7 if quick else 11
    rows, _speedup = headline_rows(reps)
    rows.append(mixed_key_row(reps))
    if not quick:
        rows.append(tiled_concurrency_row())
    return rows


def bench_bank(quick=False):
    """Operator-bank rows: the shared ``bank_vs_seq`` pair from
    benchmarks.bank_stencil (same shapes, pad, interleaved timing — the
    smoke numbers can't drift from the gated benchmark)."""
    from benchmarks.bank_stencil import (
        FULL_SHAPE,
        QUICK_SHAPE,
        RANK,
        bank_vs_seq,
    )
    from repro.core import curvature_bank

    rng = np.random.RandomState(0)
    shape = QUICK_SHAPE if quick else FULL_SHAPE
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    W = jnp.asarray(curvature_bank(RANK))
    K = W.shape[1]
    tag = "x".join(map(str, shape))
    rows = []
    for method in ("fused", "lax"):
        t_bank, t_seq = bank_vs_seq(x, W, method, reps=5)
        rows.append((f"bank/{method}/{tag}/K{K}", t_bank,
                     f"seq={t_seq:.0f}us speedup={t_seq / t_bank:.2f}x"))
    return rows


def bench_stats(quick=False):
    """Statistics-engine rows: the shared ``var_streaming_pair`` from
    benchmarks.stats (same shapes, interleaved timing — the smoke numbers
    can't drift from the gated benchmark) plus subsystem end-to-ends."""
    from benchmarks.stats import BATCH, FULL_ITEM, QUICK_ITEM, headline_rows

    rng = np.random.RandomState(0)
    item = QUICK_ITEM if quick else FULL_ITEM
    xb = jnp.asarray((rng.randn(BATCH, *item) * 2 + 5).astype(np.float32))
    rows, _ = headline_rows(xb, reps=5 if quick else 10)
    return rows


def bench_pipe(quick=False):
    """Pipeline-fusion rows: the shared ``headline_rows`` from
    benchmarks.pipe (same shapes, interleaved timing — the smoke numbers
    can't drift from the gated benchmark)."""
    from benchmarks.pipe import FULL_SHAPE, QUICK_SHAPE, headline_rows

    rng = np.random.RandomState(0)
    shape = QUICK_SHAPE if quick else FULL_SHAPE
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    rows, _ = headline_rows(x, reps=3 if quick else 7)
    return rows


def bench_tiled(quick=False):
    """Out-of-core tiled-streaming rows: the shared ``headline_rows`` from
    benchmarks.tiled (same shapes, interleaved timing — the smoke numbers
    can't drift from the gated benchmark)."""
    from benchmarks.tiled import FULL_SHAPE, QUICK_SHAPE, headline_rows

    rng = np.random.RandomState(0)
    shape = QUICK_SHAPE if quick else FULL_SHAPE
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    rows, _ = headline_rows(x, reps=3 if quick else 5)
    return rows


def _git_rev() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            stderr=subprocess.DEVNULL, text=True).strip()
    except Exception:  # noqa: BLE001 — detached/bare env: rev is best-effort
        return "unknown"


def write_json(path: str, rows) -> None:
    """BENCH_*.json contract: one record per row + run metadata."""
    backend = jax.default_backend()
    rev = _git_rev()
    payload = {
        "backend": backend,
        "git_rev": rev,
        "rows": [
            {"name": name, "us_per_call": round(float(us), 1),
             "derived": str(derived), "backend": backend, "git_rev": rev}
            for name, us, derived in rows
        ],
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH",
                    help="also write machine-readable results "
                         "(BENCH_<section>.json trajectory)")
    ap.add_argument("--json-dir", metavar="DIR",
                    help="also write one BENCH_<section>.json per section "
                         "run (the CI artifact layout)")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of "
                         "fig6,fig7,stencil,filters,bank,stats,pipe,"
                         "tiled,model,serve-lm,serve")
    args = ap.parse_args(argv)
    place_compile_cache()

    from benchmarks import paper_figs

    all_rows = []
    sections = {
        "fig6": lambda: paper_figs.fig6_parallel_scaling(
            shape=(16, 48, 48) if args.quick else (32, 64, 64)),
        "fig7": lambda: paper_figs.fig7_abstraction_levels(),
        "stencil": lambda: paper_figs.stencil_paths(
            shape=(16, 48, 48) if args.quick else (32, 64, 64)),
        "filters": lambda: bench_filters(args.quick),
        "bank": lambda: bench_bank(args.quick),
        "stats": lambda: bench_stats(args.quick),
        "pipe": lambda: bench_pipe(args.quick),
        "tiled": lambda: bench_tiled(args.quick),
        "model": lambda: bench_models(args.quick),
        "serve-lm": lambda: bench_serving(args.quick),
        "serve": lambda: bench_serve_tier(args.quick),
    }
    if args.sections:
        wanted = [s.strip() for s in args.sections.split(",") if s.strip()]
        unknown = set(wanted) - set(sections)
        if unknown:
            ap.error(f"unknown sections: {sorted(unknown)}")
        sections = {k: sections[k] for k in wanted}
    print("name,us_per_call,derived")
    per_section = {}
    failed = []
    for name_sec, sec in sections.items():
        try:
            rows = sec()
        except Exception as e:  # noqa: BLE001 — the row records it; exit 1
            import traceback
            traceback.print_exc()
            rows = [("ERROR", 0.0, str(e))]
            failed.append(name_sec)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
        all_rows += rows
        per_section[name_sec] = rows
    if args.json:
        write_json(args.json, all_rows)
    if args.json_dir:
        import os

        os.makedirs(args.json_dir, exist_ok=True)
        for name_sec, rows in per_section.items():
            write_json(os.path.join(args.json_dir,
                                    f"BENCH_{name_sec}.json"), rows)
    if failed:
        sys.exit(f"sections failed: {', '.join(failed)}")
    return all_rows


if __name__ == "__main__":
    main()
