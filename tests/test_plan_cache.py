"""StencilPlan cache: interning, per-plan stats, and no-retrace guarantees."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    apply_stencil,
    clear_plan_cache,
    gaussian_weights,
    get_plan,
    plan_cache_stats,
)
from repro.core import plan as plan_mod


@pytest.fixture(autouse=True)
def fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _x(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def test_same_signature_interns_one_plan():
    p1 = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    p2 = get_plan((8, 9), jnp.float32, (3, 3), (1, 1), "same", 1, 0, "lax",
                  False)
    assert p1 is p2  # scalar/tuple geometry and 0 vs 0.0 normalize equal
    stats = plan_cache_stats()
    assert stats["size"] == 1 and stats["misses"] == 1 and stats["hits"] == 1
    assert p1.stats()["hits"] == 1


def test_distinct_shapes_and_paths_get_distinct_plans():
    p1 = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    p2 = get_plan((8, 10), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    p3 = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "materialize",
                  False)
    p4 = get_plan((2, 8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", True)
    assert len({p1, p2, p3, p4}) == 4
    assert plan_cache_stats()["size"] == 4


def test_apply_stencil_routes_through_cache():
    x = _x((8, 9))
    w = gaussian_weights((3, 3), 1.0)
    apply_stencil(x, 3, w, method="lax")
    apply_stencil(x, 3, w, method="lax")
    apply_stencil(x, 3, w, method="lax")
    stats = plan_cache_stats()
    assert stats["size"] == 1
    assert stats["misses"] == 1 and stats["hits"] == 2


def test_no_retrace_on_repeated_batched_calls():
    """The executor traces once per plan; repeated (and weight-varying)
    batched calls reuse the traced computation."""
    xb = _x((4, 10, 9))
    w1 = gaussian_weights((3, 3), 1.0)
    w2 = gaussian_weights((3, 3), 2.0)
    for w in (w1, w2, w1, w2):
        apply_stencil(xb, 3, w, method="lax", batched=True)
    plan = get_plan((4, 10, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax",
                    True)
    s = plan.stats()
    assert s["calls"] == 4
    assert s["traces"] == 1  # varying weights never retraces
    # a different batch size is a different plan → its own single trace
    xb2 = _x((2, 10, 9))
    apply_stencil(xb2, 3, w1, method="lax", batched=True)
    apply_stencil(xb2, 3, w1, method="lax", batched=True)
    plan2 = get_plan((2, 10, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax",
                     True)
    assert plan2 is not plan
    assert plan2.stats()["traces"] == 1
    assert plan.stats()["traces"] == 1  # untouched by the other plan


def test_plan_execution_matches_direct():
    x = _x((9, 8))
    w = gaussian_weights((3, 3), 1.3)
    plan = get_plan(x.shape, x.dtype, 3, 1, "same", 1, "edge", "lax", False)
    got = plan(x, jnp.asarray(w).reshape(-1))
    want = apply_stencil(x, 3, w, method="materialize", pad_value="edge")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pad_value_normalized_in_key():
    p1 = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0, "lax", False)
    p2 = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    assert p1 is p2
    with pytest.raises(ValueError):
        get_plan((8, 9), jnp.float32, 3, 1, "same", 1, "wrap", "lax", False)


def test_clear_resets_everything():
    get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    clear_plan_cache()
    s = plan_cache_stats()
    assert s == {"size": 0, "hits": 0, "misses": 0, "evictions": 0,
                 "kinds": {"stencil": 0, "bank": 0, "stats": 0, "pipe": 0,
                           "tile": 0}}


def test_lru_eviction_bounds_cache(monkeypatch):
    """The cache never exceeds capacity; LRU plans (and their executors)
    are dropped, and a re-request is just one rebuild miss."""
    monkeypatch.setattr(plan_mod, "PLAN_CACHE_CAPACITY", 3)
    plans = [get_plan((8, 9 + i), jnp.float32, 3, 1, "same", 1, 0.0, "lax",
                      False) for i in range(5)]
    s = plan_cache_stats()
    assert s["size"] == 3 and s["evictions"] == 2
    # oldest two evicted: re-requesting rebuilds (new object, a miss)
    rebuilt = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax",
                       False)
    assert rebuilt is not plans[0]
    # newest survivor still interned
    assert get_plan((8, 13), jnp.float32, 3, 1, "same", 1, 0.0, "lax",
                    False) is plans[4]


def test_traced_inputs_bypass_cache():
    """apply_stencil inside someone else's jit must not intern tracer plans."""
    import jax

    x = _x((8, 9))
    w = gaussian_weights((3, 3), 1.0)
    clear_plan_cache()

    @jax.jit
    def f(x):
        return apply_stencil(x, 3, w, method="lax")

    f(x)
    assert plan_cache_stats()["size"] == 0


def test_concurrent_interning_builds_each_key_once():
    """N threads hammering overlapping keys: the per-key build latch must
    yield exactly one build per distinct key, with hits + misses adding
    up and no counter updates lost (the PR-9 thread-safety contract the
    serving tier depends on)."""
    import threading

    from repro.core.plan import _intern

    n_threads, n_keys, rounds = 8, 4, 25
    builds = {k: 0 for k in range(n_keys)}
    build_lock = threading.Lock()

    class Dummy:
        _hits = 0

    def make_build(k):
        def build():
            with build_lock:
                builds[k] += 1
            return Dummy()
        return build

    start = threading.Barrier(n_threads)
    errors = []

    def worker(tid):
        try:
            start.wait()
            for r in range(rounds):
                k = (tid + r) % n_keys
                _intern(("stress", k), make_build(k))
        except BaseException as e:  # pragma: no cover - diagnostic
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert not errors
    assert all(b == 1 for b in builds.values()), builds
    s = plan_cache_stats()
    assert s["misses"] == n_keys
    assert s["hits"] == n_threads * rounds - n_keys


def test_concurrent_builder_failure_hands_latch_to_waiter():
    """A builder that raises must release the per-key latch so a waiting
    thread retries the build instead of hanging forever."""
    import threading

    from repro.core.plan import _intern

    attempts = []
    gate = threading.Event()

    class Dummy:
        _hits = 0

    def flaky_build():
        attempts.append(threading.current_thread().name)
        if len(attempts) == 1:
            gate.set()           # let the second thread pile on
            raise RuntimeError("injected build failure")
        return Dummy()

    results, errors = [], []

    def first():
        try:
            _intern(("flaky",), flaky_build)
        except RuntimeError as e:
            errors.append(e)

    def second():
        gate.wait(10.0)
        results.append(_intern(("flaky",), flaky_build))

    t1 = threading.Thread(target=first, name="t1")
    t2 = threading.Thread(target=second, name="t2")
    t1.start(); t2.start()
    t1.join(30.0); t2.join(30.0)
    assert len(errors) == 1 and "injected" in str(errors[0])
    assert len(results) == 1 and len(attempts) == 2


def test_plan_cached_probe_does_not_touch_lru_or_counters():
    from repro.core.plan import plan_cached

    p = get_plan((8, 9), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    key = p.key
    before = plan_cache_stats()
    assert plan_cached(key) is p
    assert plan_cached(("nope",)) is None
    assert plan_cache_stats() == before


def test_exec_options_normalize_on_direct_construction():
    """Direct construction must be exactly as validated/canonical as
    ExecOptions.make — a cached plan's stored options can never hold a
    non-normalized value (the PR-9 aliasing fix)."""
    from repro.core.plan import ExecOptions

    a = ExecOptions(pad_value=0)
    b = ExecOptions.make(pad_value=0.0)
    assert a == b and hash(a) == hash(b)
    assert ExecOptions(out_dtype=np.float32).out_dtype == "float32"
    assert ExecOptions(batched=1).batched is True
    with pytest.raises(ValueError, match="unknown method"):
        ExecOptions(method="nope")
    with pytest.raises(ValueError, match="not a dtype"):
        ExecOptions(out_dtype=object())
    with pytest.raises(ValueError):
        ExecOptions(pad_value="not-a-mode")
