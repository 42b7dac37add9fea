"""Run one cell once: set up, measure a window, check, print one line.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  The run

1. reads ``BENCHMARK.json``, the cell's configuration and traffic files
   (and the modules their names resolve to, see ``bench/manifest.py``),
   and refuses to run without a TPU, with fewer chips than the cell asks
   for, or on a device kind missing from ``peaks.json``;
2. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``
   (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every compile;
3. imports the program, makes the cell's data on the device from the
   seed and warms up the shapes its traffic uses: ``setup_s`` runs from
   the process's start to here;
4. runs the window for ``--seconds`` (under the profiler with
   ``--trace 1``), reads the device's peak memory, frees the program's
   state, and compares what the window produced with the reference;
5. prints each compared number beside its limit as the last lines of
   standard error, and the result as the last line of standard output:
   the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
   metrics, each read by ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

from bench import manifest

__all__ = ["main", "run_cell", "RunRecord", "read_metrics", "result_line"]

PEAKS = manifest.BENCH / "peaks.json"
CACHE_DIR = manifest.ROOT / ".jax_cache"


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""

    cell: str
    chips: int
    setup_s: float
    setup_split: dict
    plan_builds_setup: int
    plan_builds_window: int
    window: dict
    checks: dict
    control_checks: Optional[dict]
    least_bytes_per_call: int
    ops_per_call: int
    peak: dict
    memory_peak_bytes: int
    trace: Optional[dict] = None

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim
                   for v, lim in self.checks.values())


def place_compile_cache() -> str:
    """Cache every compile in one fixed directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_peaks() -> dict:
    return json.loads(PEAKS.read_text())["devices"]


def chip_problem(devices, chips: int, peaks: dict) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        return f"JAX found no TPU (platform {plat!r}); nothing was run"
    if len(devices) < chips:
        return (f"the cell asks for {chips} chips, JAX found "
                f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        return f"device kind {kind!r} is not in {PEAKS.name}"
    return None


def _plan_misses() -> int:
    from repro.core.plan import plan_cache_stats

    return int(plan_cache_stats()["misses"])


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without memory stats
            stats = {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def run_cell(spec: dict, seed: int, seconds: float, *, trace: bool = False,
             t_start: Optional[float] = None, devices=None, shape=None,
             with_control: bool = False) -> RunRecord:
    """Set up, measure and check one cell; no chip check here (``main``
    makes it), so tests can drive a run on the CPU at a small size."""
    import jax

    from bench import trace_reduce, work

    t_start = time.perf_counter() if t_start is None else t_start
    chips = spec["chips"]
    devices = list(devices if devices is not None else jax.devices())[:chips]
    loop = manifest.module("loops", spec["traffic"]["loop"])
    drv = loop.Loop(spec, seed, devices, shape)
    import repro.pipe  # noqa: F401 — the program's import is set-up
    t_import = time.perf_counter()
    misses0 = _plan_misses()
    drv.make_data()
    t_data = time.perf_counter()
    drv.warmup()
    # set-up leaves a large heap (tracing, lowering); collect it now and
    # exempt it from later collections, so that a full pass over it does
    # not stall the host for ~0.1 s at random points of the window
    gc.collect()
    gc.freeze()
    t_warm = time.perf_counter()
    misses1 = _plan_misses()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        try:
            win = drv.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        misses2 = _plan_misses()
        mem = _memory_peak(devices)
        summary = None
        if trace:
            paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            summary = trace_reduce.reduce(trace_reduce.load(paths[0]))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    gc.unfreeze()
    drv.release()
    checks = drv.check()
    control = drv.check(control=True) if with_control else None
    graph = spec["traffic"]["graph"]
    shp = drv.shape
    return RunRecord(
        cell=spec["cell"]["name"], chips=chips, setup_s=t_warm - t_start,
        setup_split={"import_s": t_import - t_start,
                     "data_s": t_data - t_import,
                     "warmup_s": t_warm - t_data},
        plan_builds_setup=misses1 - misses0,
        plan_builds_window=misses2 - misses1,
        window=win, checks=checks, control_checks=control,
        least_bytes_per_call=work.least_bytes(shp, graph),
        ops_per_call=work.ops(shp, graph),
        peak=load_peaks().get(devices[0].device_kind, {}),
        memory_peak_bytes=mem, trace=summary)


def _reader(name: str):
    return manifest.module("metrics", name).read


def read_metrics(man: dict, rec: RunRecord, kind: str) -> dict:
    """The cell's metrics of ``kind`` that have something to read."""
    out = {}
    for m in manifest.metrics_of(man, rec.cell, kind):
        v = _reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(man: dict, rec: RunRecord, devices, traced: bool) -> dict:
    d0 = devices[0]
    line = {
        "correct": rec.correct,
        "attempted": int(rec.window["attempted"]),
        "failed": int(rec.window["failed"]),
        "metrics": read_metrics(man, rec,
                                "per_layer" if traced else "end_to_end"),
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": rec.chips,
                   "memory_peak_bytes": rec.memory_peak_bytes},
    }
    if traced:
        tr = rec.trace
        line["device"]["busy_s"] = sum(tr["busy_s"]) / len(tr["busy_s"])
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["setup_split"] = rec.setup_split
    line["plan_builds_window"] = rec.plan_builds_window
    line["work"] = {"least_bytes_per_call": rec.least_bytes_per_call,
                    "ops_per_call": rec.ops_per_call,
                    "ops_per_byte": rec.ops_per_call
                    / rec.least_bytes_per_call}
    line["window"] = rec.window
    line["checks"] = {k: {"value": v if math.isfinite(v) else None,
                          "limit": lim}
                      for k, (v, lim) in rec.checks.items()}
    return line


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest.load()
    bad = manifest.problems(man)
    if bad:
        print("bench: BENCHMARK.json is not sound: " + "; ".join(bad),
              file=sys.stderr)
        return 2
    spec = manifest.cell(man, args.workload)
    place_compile_cache()
    import jax

    devices = jax.devices()
    why = chip_problem(devices, spec["chips"], load_peaks())
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    rec = run_cell(spec, args.seed, args.seconds, trace=bool(args.trace),
                   t_start=t_start, devices=devices[:spec["chips"]])
    line = result_line(man, rec, devices, bool(args.trace))
    print(f"setup: {json.dumps(rec.setup_split)}; plan builds in set-up "
          f"{rec.plan_builds_setup}, in the window "
          f"{rec.plan_builds_window}", file=sys.stderr)
    for k, (v, lim) in rec.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
