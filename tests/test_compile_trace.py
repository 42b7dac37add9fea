"""Compile accounting and the profiler bridge (repro.runtime.compile_cache,
DESIGN.md §14).

- An owned compile adds one self-time entry to each of
  ``compile/trace_s``, ``compile/lower_s`` and ``compile/backend_s``;
  nested traces count once; compiles with no owner are not counted.
- ``compile/cache_hits`` / ``compile/cache_misses`` follow JAX's
  persistent cache (a miss, then a hit after ``jax.clear_caches()``).
- The tile autotuner's time lands in ``tune/measure_s`` and is taken
  out of the enclosing plan's trace time.
- With the tracer off only the registry moves; with it on each owned
  phase is a span with its attrs.
- Under ``jax.profiler``, a ``repro.obs`` span lands on the host plane
  of the ``.xplane.pb`` and brackets the ops its executor ran.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
import pytest
from jax._src import compilation_cache as jax_cc

from repro import obs
from repro.core import clear_plan_cache
from repro.core.plan import get_plan
from repro.kernels import melt_stencil as ms
from repro.runtime import compile_cache as cc

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
PHASES = ("compile/trace_s", "compile/lower_s", "compile/backend_s")
COUNTERS = ("compile/cache_hits", "compile/cache_misses")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.TRACER.disable()
    obs.TRACER.reset()
    cc.install()  # re-creates the metrics after another test's reset
    yield
    obs.TRACER.disable()
    obs.TRACER.reset()


def _reading():
    snap = obs.REGISTRY.snapshot()
    out = {k: (snap[k]["count"], snap[k]["total"])
           for k in PHASES + (cc.TUNE_HIST,)}
    out.update({k: snap[k] for k in COUNTERS})
    return out


def _delta(before, after):
    return {k: (tuple(a - b for a, b in zip(after[k], before[k]))
                if isinstance(after[k], tuple) else after[k] - before[k])
            for k in after}


def _fresh_fn(scale):
    """A function no earlier test has compiled (its constant differs),
    of lax primitives alone (``jnp`` functions are jits of their own,
    so each would add a nested trace)."""
    c = np.float32(scale)
    return jax.jit(lambda x: lax.add(lax.mul(lax.sin(x), c), c))


def test_owned_compile_adds_one_entry_per_phase():
    x = jnp.ones((16,), jnp.float32)
    f = _fresh_fn(3.25)
    before = _reading()
    with cc.owned("test"):
        f(x).block_until_ready()
    d = _delta(before, _reading())
    for k in PHASES:
        assert d[k][0] == 1 and d[k][1] > 0, (k, d[k])
    assert d[cc.TUNE_HIST] == (0, 0.0)


def test_compile_with_no_owner_is_not_counted():
    x = jnp.ones((16,), jnp.float32)
    before = _reading()
    _fresh_fn(4.5)(x).block_until_ready()
    assert _delta(before, _reading()) == {
        k: ((0, 0.0) if k in PHASES + (cc.TUNE_HIST,) else 0)
        for k in before}


def test_nested_traces_count_once():
    @jax.jit
    def inner(x):
        time.sleep(0.05)  # runs while tracing
        return lax.cos(x)

    @jax.jit
    def outer(x):
        return lax.add(inner(x), inner(lax.neg(x)))

    x = jnp.ones((16,), jnp.float32)
    before = _reading()
    with obs.tracing() as snap, cc.owned("test"):
        outer(x).block_until_ready()
    d = _delta(before, _reading())
    traces = [e for e in snap().named("compile/trace")
              if e.attrs["fun_name"] in ("outer", "inner")]
    outer_ev = [e for e in traces if e.attrs["fun_name"] == "outer"]
    assert len(outer_ev) == 1 and len(traces) >= 2
    spans_total = sum(e.dur for e in traces) / 1e9
    # the registry's sum is the union: the outer trace, once
    assert d["compile/trace_s"][1] == pytest.approx(outer_ev[0].dur / 1e9,
                                                    abs=1e-5)
    assert spans_total > d["compile/trace_s"][1] + 0.04


@pytest.fixture
def temp_persistent_cache(tmp_path):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax_cc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    jax_cc.reset_cache()


def test_cache_miss_then_hit(temp_persistent_cache):
    x = jnp.ones((16,), jnp.float32)
    f = _fresh_fn(5.75)
    before = _reading()
    with obs.tracing() as snap:
        with cc.owned("test"):
            f(x).block_until_ready()
        mid = _reading()
        jax.clear_caches()
        with cc.owned("test"):
            f(x).block_until_ready()
    first, second = _delta(before, mid), _delta(mid, _reading())
    assert (first["compile/cache_misses"], first["compile/cache_hits"]) \
        == (1, 0)
    assert (second["compile/cache_misses"], second["compile/cache_hits"]) \
        == (0, 1)
    # on a hit, compile/backend_s holds the retrieval
    assert second["compile/backend_s"][0] == 1
    caches = [e.attrs["cache"] for e in snap().named("compile/backend")]
    assert caches == ["miss", "hit"]


def _outermost(evs):
    """The spans no other span of ``evs`` contains."""
    return [e for e in evs
            if not any(o is not e and o.ts <= e.ts
                       and e.ts + e.dur <= o.ts + o.dur
                       and o.dur > e.dur for o in evs)]


def test_tuner_time_lands_in_tune_and_leaves_trace(monkeypatch):
    monkeypatch.setenv("REPRO_TILE_AUTOTUNE", "1")
    clear_plan_cache()
    ms._TUNE_MEMO.clear()
    try:
        x = jnp.asarray(np.random.default_rng(3).standard_normal(
            (20, 24)).astype(np.float32))
        w = jnp.full((9,), 1.0 / 9.0, jnp.float32)
        plan = get_plan(x.shape, x.dtype, 3, method="fused")
        before = _reading()
        t0 = time.perf_counter()
        with obs.tracing() as snap:
            plan(x, w).block_until_ready()
        wall = time.perf_counter() - t0
        d = _delta(before, _reading())
    finally:
        clear_plan_cache()
        ms._TUNE_MEMO.clear()
    (tune,) = snap().named("tune/measure")
    assert tune.attrs["family"] == "stencil" and tune.attrs["numel"] == 9
    assert tune.attrs["tile_rows"] in ms._tile_candidates(
        9, 1, 1, jnp.float32)
    assert d[cc.TUNE_HIST][0] == 1
    assert d[cc.TUNE_HIST][1] == pytest.approx(tune.dur / 1e9, abs=1e-3)
    # the plan's trace waited for the tuner; that wait is not trace time
    outer = _outermost([e for e in snap().named("compile/trace")
                        if e.attrs["owner"] == "stencil"])
    assert any(e.attrs["fun_name"] == "run" for e in outer)
    traced = sum(e.dur for e in outer) / 1e9
    assert d["compile/trace_s"][1] <= traced - d[cc.TUNE_HIST][1]
    # the tuner's own compiles count in tune/measure_s only
    owned = [e for e in snap().named("compile/backend")
             if e.attrs["owner"] != "tune"]
    assert d["compile/backend_s"][0] == len(owned)
    assert any(e.attrs["owner"] == "tune"
               for e in snap().named("compile/backend"))
    spent = sum(d[k][1] for k in PHASES + (cc.TUNE_HIST,))
    assert spent <= wall


def test_tracer_off_counts_without_spans():
    x = jnp.ones((8, 8), jnp.float32)
    clear_plan_cache()
    plan = get_plan(x.shape, x.dtype, 3, method="lax")
    before = _reading()
    plan(x, jnp.ones((9,), jnp.float32)).block_until_ready()
    d = _delta(before, _reading())
    assert all(d[k][0] >= 1 for k in PHASES)
    assert obs.TRACER.stats()["events"] == 0
    # a warm call compiles nothing
    before = _reading()
    plan(x, jnp.ones((9,), jnp.float32)).block_until_ready()
    assert all(_delta(before, _reading())[k][0] == 0 for k in PHASES)


def test_tracer_on_records_phase_spans_with_attrs():
    x = jnp.ones((8, 8), jnp.float32)
    clear_plan_cache()
    plan = get_plan(x.shape, x.dtype, 3, method="lax")
    with obs.tracing() as snap:
        plan(x, jnp.ones((9,), jnp.float32)).block_until_ready()
    s = snap()
    (ex,) = s.named("plan/exec")
    assert ex.attrs == {"kind": "stencil", "cold": True}
    for name in ("compile/trace", "compile/lower", "compile/backend"):
        evs = s.named(name)
        assert evs, name
        for e in evs:
            assert e.attrs["owner"] == "stencil"
            assert isinstance(e.attrs["fun_name"], str)
            # each phase lies inside the dispatch that owned it
            assert ex.ts - 1_000_000 <= e.ts
            assert e.ts + e.dur <= ex.ts + ex.dur + 1_000_000
    assert {e.attrs["cache"] for e in s.named("compile/backend")} \
        <= {"hit", "miss"}
    assert any(e.attrs["fun_name"] == "run" for e in s.named("compile/trace"))


PROFILED = """
import glob, json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro import obs
from repro.core.plan import get_plan

# run each executable where it is dispatched (set before the client exists)
jax.config.update("jax_cpu_enable_async_dispatch", False)
x = jnp.asarray(np.random.default_rng(5).standard_normal(
    (64, 64)).astype(np.float32))
w = jnp.full((9,), 1.0 / 9.0, jnp.float32)
plan = get_plan(x.shape, x.dtype, 3, method="lax")
plan(x, w).block_until_ready()  # compiled before the session
jax.profiler.start_trace(sys.argv[1])
plan(x, w).block_until_ready()  # tracer off: no annotation
with obs.tracing():
    plan(x, w).block_until_ready()
jax.profiler.stop_trace()
(path,) = glob.glob(sys.argv[1] + "/**/*.xplane.pb", recursive=True)
print(json.dumps({p.name: [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                           for ln in p.lines for e in ln.events]
                  for p in ProfileData.from_file(path).planes}))
"""


def test_span_lands_on_profiler_host_plane(tmp_path):
    # a process of its own: the CPU client runs each executable where it
    # is dispatched only if it is made so, before its first use
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    p = subprocess.run([sys.executable, "-c", PROFILED, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    planes = json.loads(p.stdout.strip().splitlines()[-1])
    evs = planes["/host:CPU"]
    spans = [e for e in evs if e[0] == "plan/exec"]
    assert len(spans) == 1
    _, s0, s1 = spans[0]
    # the executor's two runs, and the ops they ran: the traced run's
    # inside the span, the other's not
    for name in ("PjRtCpuExecutable::Execute", "convolution"):
        found = [e for e in evs if e[0] == name]
        assert len(found) == 2, (name, found)
        assert sum(s0 <= e[1] and e[2] <= s1 for e in found) == 1, name
