"""The unified lazy pipeline API (DESIGN.md §11).

Contracts pinned here:

- **Cross-path oracle** — every fused pipeline equals the eager chain of
  existing calls (ranks 1–3, batched/unbatched, pad modes, K>1 banks) on
  all three execution paths.
- **No-extra-melt** — the materialize-path ``melt_call_count`` delta
  equals the planner's declared pass accounting; lax/fused never melt.
  The acceptance pipeline ``gaussian → gradient → moments`` runs in ONE
  logical pass (split: composed interior + boundary slabs) vs 3 eager.
- **Weight composition** — adjacent 'valid' linear stages merge into one
  operator-bank pass *exactly*, including strided chains (composite
  stride = product); adjacent stride-1 'same' stages split into a
  composed interior pass plus boundary slabs that replay the original
  program (bit-identical at the boundary).  Dilation, K>1 predecessors,
  and mixed padding still decline.
- **Plan cache** — StencilPlan / BankPlan / StatsPlan / PipePlan keys
  intern side by side in the one LRU cache, hit on repeat, and evict
  together under a small capacity.
- **ExecOptions** — misspelled ``method=``/``pad_value=`` reject with the
  valid choices at every entry point; ``out_dtype`` casts array outputs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _prop import given, settings, strategies as st
from conftest import run_with_devices

from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    curvature_bank,
    gaussian_filter,
    gradient,
    melt_call_count,
    plan_cache_reset,
    plan_cache_stats,
)
from repro.core.filters import difference_stencils, gaussian_weights
from repro.core.plan import ExecOptions, PipePlan, get_pipe_plan
from repro.pipe import Pipe, compose_weights, pipe
from repro.stats import histogram, moments, zscore
from repro.stats.cov import channel_cov, covariance

METHODS = ("materialize", "lax", "fused")
PADS = (0.0, 1.5, "edge", "reflect")


@pytest.fixture
def fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _vol(rng, shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


def _eager_chain(x, sigma, op, method, pad_value, batched, order):
    y = gaussian_filter(x, op, sigma, method=method, pad_value=pad_value,
                        batched=batched)
    D = gradient(y, method=method, pad_value=pad_value, batched=batched)
    nd = D.ndim
    axes = tuple(range(1 if batched else 0, nd - 1))
    return moments(D, axis=axes, method=method, order=order)


# -- cross-path oracle -------------------------------------------------------


@pytest.mark.parametrize("shape", [(48,), (14, 11), (8, 9, 7)])
@pytest.mark.parametrize("method", METHODS)
def test_pipeline_matches_eager_chain(shape, method, rng):
    x = _vol(rng, shape)
    st = (pipe(x).gaussian(1.2, op_shape=5).gradient().moments(order=2)
          .run(method=method, pad_value="edge"))
    ref = _eager_chain(x, 1.2, 5, method, "edge", False, 2)
    np.testing.assert_allclose(np.asarray(st.mean), np.asarray(ref.mean),
                               rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(st.variance),
                               np.asarray(ref.variance), rtol=3e-5,
                               atol=3e-6)


@pytest.mark.parametrize("pad", PADS)
def test_pipeline_pad_modes(pad, rng):
    x = _vol(rng, (10, 12))
    for method in METHODS:
        st = (pipe(x).gaussian(1.0, op_shape=3).gradient().moments(order=2)
              .run(method=method, pad_value=pad))
        ref = _eager_chain(x, 1.0, 3, method, pad, False, 2)
        np.testing.assert_allclose(np.asarray(st.variance),
                                   np.asarray(ref.variance), rtol=3e-5,
                                   atol=3e-6)


@pytest.mark.parametrize("method", METHODS)
def test_pipeline_batched(method, rng):
    xb = _vol(rng, (3, 10, 12))
    st = (pipe.batched(xb).gaussian(1.0, op_shape=3).gradient()
          .moments(order=2).run(method=method, pad_value="edge"))
    ref = _eager_chain(xb, 1.0, 3, method, "edge", True, 2)
    assert st.variance.shape == (3, 2)  # per item, per channel
    np.testing.assert_allclose(np.asarray(st.variance),
                               np.asarray(ref.variance), rtol=3e-5,
                               atol=3e-6)


@pytest.mark.parametrize("method", METHODS)
def test_pipeline_k_gt_1_bank(method, rng):
    """A user bank (K = rank + rank²) with a fused moments terminal."""
    x = _vol(rng, (9, 8, 7))
    W = jnp.asarray(curvature_bank(3))
    st = (pipe(x).bank(3, W).moments(order=4)
          .run(method=method, pad_value="edge"))
    D = apply_stencil_bank(x, 3, W, method=method, pad_value="edge")
    ref = moments(D, axis=(0, 1, 2), method=method, order=4)
    assert st.variance.shape == (12,)
    np.testing.assert_allclose(np.asarray(st.variance),
                               np.asarray(ref.variance), rtol=3e-5,
                               atol=3e-6)
    np.testing.assert_allclose(np.asarray(st.kurtosis),
                               np.asarray(ref.kurtosis), rtol=1e-3,
                               atol=1e-4)


def test_trivial_graphs_lower_to_legacy_results(rng):
    x = _vol(rng, (12, 10))
    w = gaussian_weights((3, 3), 0.9)
    np.testing.assert_allclose(
        np.asarray(pipe(x).stencil(3, w).run(method="lax", pad_value=0.0)),
        np.asarray(apply_stencil(x, 3, w, method="lax")), rtol=1e-6)
    grad_w, _ = difference_stencils(2)
    np.testing.assert_allclose(
        np.asarray(pipe(x).bank(3, jnp.asarray(grad_w, jnp.float32))
                   .run(method="lax", pad_value="edge")),
        np.asarray(apply_stencil_bank(x, 3,
                                      jnp.asarray(grad_w, jnp.float32),
                                      method="lax", pad_value="edge")),
        rtol=1e-6)
    st = pipe(x).moments(order=4).run(method="lax")
    ref = moments(x, method="lax")
    np.testing.assert_allclose(float(st.variance), float(ref.variance),
                               rtol=1e-6)


# -- weight composition ------------------------------------------------------


def test_compose_weights_exact_valid(rng):
    """stage2 ∘ stage1 under 'valid' == one composed pass, all paths."""
    x = _vol(rng, (12, 11, 9))
    w1 = np.asarray(gaussian_weights((5, 5, 5), 1.5))
    grad_w, _ = difference_stencils(3)
    for method in METHODS:
        a = apply_stencil(x, 5, w1, padding="valid", method=method)
        ref = apply_stencil_bank(a, 3, jnp.asarray(grad_w, jnp.float32),
                                 padding="valid", method=method)
        out = (pipe(x).gaussian(1.5, op_shape=5, padding="valid")
               .gradient(padding="valid").run(method=method))
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-6)


def test_composition_plan_shape():
    x = jnp.zeros((16, 16, 16), jnp.float32)
    prog = (pipe(x).gaussian(1.5, op_shape=5, padding="valid")
            .gradient(padding="valid").moments(order=2).plan())
    assert prog.passes == 1  # composed into ONE pass + fused reduction
    steps = [s for s in prog.steps]
    assert steps[0].grid.op_shape == (7, 7, 7)  # 5 ⊕ 3 − 1
    assert steps[0].weights.shape == (343, 3)
    assert steps[0].factors is not None  # gaussian ⊛ central-diff is rank-1


def test_composition_same_padding_splits_to_one_pass():
    """'same' chains split: composed interior + boundary slabs = 1 pass."""
    x = jnp.zeros((16, 16), jnp.float32)
    prog = pipe(x).gaussian(1.0, op_shape=3).gradient().plan()
    assert prog.passes == 1
    assert "split[5x5" in prog.describe()


def test_composition_strided_valid_composes():
    """Strided 'valid' chains compose: tap a1 + s1*a2, stride s1*s2."""
    x = jnp.zeros((16, 16), jnp.float32)
    w = np.ones(9, np.float32) / 9.0
    prog = (pipe(x).stencil(3, w, stride=2, padding="valid")
            .stencil(3, w, padding="valid").plan())
    assert prog.passes == 1
    step = prog.steps[0]
    assert step.grid.op_shape == (7, 7)   # 3 + 2*(3-1)
    assert step.grid.stride == (2, 2)
    # composed output count equals the 2-pass chain's exactly
    assert step.grid.out_shape == (5, 5)


def test_composition_still_declined_for_dilation():
    x = jnp.zeros((20, 20), jnp.float32)
    w = np.ones(9, np.float32) / 9.0
    prog = (pipe(x).stencil(3, w, dilation=2, padding="valid")
            .stencil(3, w, padding="valid").plan())
    assert prog.passes == 2


def test_compose_weights_algebra():
    """Direct check of the convolution composition on random operators."""
    rng = np.random.RandomState(5)
    w1 = rng.randn(9, 1)
    W2 = rng.randn(25, 4)
    comp = compose_weights(w1, (3, 3), W2, (5, 5))
    assert comp.shape == (49, 4)
    x = jnp.asarray(rng.randn(20, 18).astype(np.float32))
    a = apply_stencil(x, 3, jnp.asarray(w1[:, 0], jnp.float32),
                      padding="valid", method="materialize")
    ref = apply_stencil_bank(a, 5, jnp.asarray(W2, jnp.float32),
                             padding="valid", method="materialize")
    out = apply_stencil_bank(x, 7, jnp.asarray(comp), padding="valid",
                             method="materialize", separable=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


# -- no-extra-melt accounting ------------------------------------------------


def test_acceptance_pipeline_one_logical_pass(rng):
    """gaussian → gradient → moments: the 'same' chain splits into ONE
    logical pass (composed separable interior + 6 boundary slabs) and the
    materialize melt counter matches the plan's declared accounting."""
    x = _vol(rng, (10, 11, 9))
    P = pipe(x).gaussian(1.5, op_shape=5).gradient().moments(order=2)
    prog = P.plan(method="materialize", pad_value="edge")
    assert prog.passes == 1
    # interior: separable 7³ bank = 3 1-D melts; 6 slabs × (1 + 1) stages
    assert prog.melt_calls == 3 + 6 * 2
    clear_plan_cache()
    before = melt_call_count()
    jax.block_until_ready(
        P.run(method="materialize", pad_value="edge").mean)
    assert melt_call_count() - before == prog.melt_calls
    # the eager chain pays 3 (gaussian + gradient + moments oracle)
    before = melt_call_count()
    jax.block_until_ready(
        _eager_chain(x, 1.5, 5, "materialize", "edge", False, 2).mean)
    assert melt_call_count() - before == 3


@pytest.mark.parametrize("method", ("lax", "fused"))
def test_pipeline_never_melts_off_oracle(method, rng):
    x = _vol(rng, (9, 9, 9))
    clear_plan_cache()
    before = melt_call_count()
    st = (pipe(x).gaussian(1.2, op_shape=3).gradient().moments(order=2)
          .run(method=method, pad_value="edge"))
    jax.block_until_ready(st.mean)
    assert melt_call_count() == before


def test_melt_accounting_matches_plan_for_separable_group(rng):
    """A composed separable group pays one 1-D melt per dim — and the
    plan says so."""
    x = _vol(rng, (12, 11, 9))
    P = (pipe(x).gaussian(1.5, op_shape=5, padding="valid")
         .gradient(padding="valid").moments(order=2))
    prog = P.plan(method="materialize")
    assert prog.melt_calls == 3  # separable 7³ bank = 3 × 1-D passes
    clear_plan_cache()
    before = melt_call_count()
    jax.block_until_ready(P.run(method="materialize").mean)
    assert melt_call_count() - before == prog.melt_calls


# -- other ops ---------------------------------------------------------------


def test_zscore_stage_matches_stats(rng):
    x = _vol(rng, (12, 13))
    for method in METHODS:
        out = pipe(x).zscore(5).run(method=method, pad_value="edge")
        ref = zscore(x, 5, method=method, pad_value="edge")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
    prog = pipe(x).zscore(5).gaussian(1.0, op_shape=3).plan()
    assert prog.passes == 2  # window pass + smoothing pass


def test_hist_terminal_matches_eager(rng):
    x = _vol(rng, (14, 9))
    y = gaussian_filter(x, 3, 1.0, method="lax", pad_value="edge")
    href = histogram(y, bins=32, range=(-3.0, 3.0))
    h = (pipe(x).gaussian(1.0, op_shape=3).hist(32, range=(-3.0, 3.0))
         .run(method="lax", pad_value="edge"))
    np.testing.assert_allclose(np.asarray(h.counts),
                               np.asarray(href.counts))
    with pytest.raises(ValueError, match="explicit range"):
        pipe(x).hist(32)


def test_cov_terminal_structure_tensor(rng):
    """gradient → cov is the melt-native structure tensor."""
    x = _vol(rng, (16, 15))
    st = (pipe(x).gradient().cov().run(method="lax", pad_value="edge"))
    D = gradient(x, method="lax", pad_value="edge")
    ref = channel_cov(D)
    np.testing.assert_allclose(np.asarray(covariance(st)),
                               np.asarray(covariance(ref)), rtol=1e-5,
                               atol=1e-6)


def test_pointwise_and_out_dtype(rng):
    x = _vol(rng, (10, 10))
    out = (pipe(x).pointwise(jnp.abs, key="abs")
           .gaussian(1.0, op_shape=3)
           .run(method="lax", pad_value=0.0, out_dtype=jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    ref = gaussian_filter(jnp.abs(x), 3, 1.0, method="lax")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_grad_matches_eager_vjp(rng):
    x = _vol(rng, (9, 8))
    g = pipe(x).gaussian(1.0, op_shape=3).gradient().grad(
        method="lax", pad_value="edge")

    def eager(t):
        y = gaussian_filter(t, 3, 1.0, method="lax", pad_value="edge")
        return jnp.sum(gradient(y, method="lax", pad_value="edge"))

    ref = jax.grad(eager)(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="fused"):
        pipe(x).gaussian(1.0, op_shape=3).grad(method="fused")
    with pytest.raises(ValueError, match="array-valued"):
        pipe(x).moments().grad(method="lax")


# -- graph validation --------------------------------------------------------


def test_graph_validation_errors(rng):
    x = _vol(rng, (8, 8))
    with pytest.raises(ValueError, match="terminal"):
        pipe(x).moments().gaussian(1.0)
    with pytest.raises(ValueError, match="last linear stage"):
        pipe(x).gradient().gaussian(1.0)
    with pytest.raises(ValueError, match="standalone"):
        pipe(x).gaussian(1.0, op_shape=3).moments(axis=(0,)).run()
    with pytest.raises(ValueError, match="order must be 2 or 4"):
        pipe(x).moments(order=3)


def test_exec_options_validation(rng):
    x = _vol(rng, (8, 8))
    for entry in (
        lambda: pipe(x).gaussian(1.0, op_shape=3).run(method="fusd"),
        lambda: apply_stencil(x, 3, jnp.ones(9) / 9, method="fusd"),
        lambda: apply_stencil_bank(x, 3, jnp.ones((9, 2)), method="fusd"),
        lambda: gaussian_filter(x, 3, 1.0, method="fusd"),
        lambda: gradient(x, method="fusd"),
        lambda: moments(x, method="fusd"),
        lambda: zscore(x, 3, method="fusd"),
    ):
        with pytest.raises(ValueError,
                           match="auto, materialize, lax, fused"):
            entry()
    with pytest.raises(ValueError, match="expected a number or one of"):
        pipe(x).gaussian(1.0, op_shape=3).run(pad_value="edgee")
    with pytest.raises(ValueError, match="not a dtype"):
        ExecOptions.make(out_dtype="floaty32")


# -- plan cache --------------------------------------------------------------


def test_mixed_plan_kinds_intern_side_by_side(fresh_cache, rng):
    x = _vol(rng, (12, 10))
    apply_stencil(x, 3, jnp.ones(9) / 9, method="lax")          # StencilPlan
    apply_stencil_bank(x, 3, jnp.ones((9, 2)), method="lax")    # BankPlan
    moments(x, method="lax")                                    # StatsPlan
    P = pipe(x).gaussian(1.0, op_shape=3).gradient().moments(order=2)
    P.run(method="lax", pad_value="edge")                       # PipePlan
    assert plan_cache_stats()["size"] == 4
    assert plan_cache_stats()["kinds"] == {
        "stencil": 1, "bank": 1, "stats": 1, "pipe": 1, "tile": 0}
    plan_cache_reset()  # zero counters, keep the four warm plans
    for _ in range(3):
        P.run(method="lax", pad_value="edge")
    assert plan_cache_stats()["hits"] == 3
    assert plan_cache_stats()["misses"] == 0
    assert plan_cache_stats()["size"] == 4  # no new entries


def test_pipe_plan_no_retrace_on_repeat(fresh_cache, rng):
    x = _vol(rng, (10, 10))
    P = pipe(x).gaussian(1.0, op_shape=3).gradient()
    P.run(method="lax", pad_value="edge")
    key = [k for k in _cache_keys() if k[0] == "pipe"]
    assert len(key) == 1
    plan = get_pipe_plan(key[0][1:], lambda: None)
    assert isinstance(plan, PipePlan)
    t0 = plan.stats()["traces"]
    for _ in range(4):
        P.run(method="lax", pad_value="edge")
    assert plan.stats()["traces"] == t0  # jit cache hit, no retrace
    assert plan.stats()["calls"] >= 5
    # a different pad_value is a different plan
    P.run(method="lax", pad_value=0.0)
    assert len([k for k in _cache_keys() if k[0] == "pipe"]) == 2


def _cache_keys():
    from repro.core import plan as _plan

    with _plan._LOCK:
        return list(_plan._CACHE.keys())


def test_mixed_eviction_under_small_capacity(fresh_cache, rng,
                                             monkeypatch):
    from repro.core import plan as _plan

    monkeypatch.setattr(_plan, "PLAN_CACHE_CAPACITY", 3)
    x = _vol(rng, (10, 10))
    apply_stencil(x, 3, jnp.ones(9) / 9, method="lax")
    moments(x, method="lax")
    pipe(x).gaussian(1.0, op_shape=3).gradient().run(
        method="lax", pad_value="edge")
    apply_stencil_bank(x, 3, jnp.ones((9, 2)), method="lax")
    stats = plan_cache_stats()
    assert stats["size"] == 3
    assert stats["evictions"] == 1
    # evicted (oldest = the stencil plan) rebuilds on demand
    apply_stencil(x, 3, jnp.ones(9) / 9, method="lax")
    assert plan_cache_stats()["evictions"] == 2


def test_traced_pipeline_executes_inline(fresh_cache, rng):
    x = _vol(rng, (10, 10))

    @jax.jit
    def f(t):
        return (pipe(t).gaussian(1.0, op_shape=3).gradient()
                .moments(order=2).run(method="lax", pad_value="edge")
                .variance)

    v = f(x)
    assert plan_cache_stats()["size"] == 0  # tracers never intern
    ref = _eager_chain(x, 1.0, 3, "lax", "edge", False, 2)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ref.variance),
                               rtol=1e-5)


# -- review regressions ------------------------------------------------------


def test_melt_engine_traced_weights_still_differentiable(rng):
    """MeltEngine must keep accepting traced weights (pre-pipe behavior):
    tracers bypass the graph record and hit the plan executor directly."""
    from repro.core import MeltEngine

    x = _vol(rng, (8, 8))
    w = jnp.ones(9, jnp.float32) / 9.0
    eng = MeltEngine((3, 3), method="lax")
    g = jax.grad(lambda w_: jnp.sum(eng(x, w_)))(w)
    assert g.shape == (9,)
    np.testing.assert_allclose(np.asarray(eng(x, w)),
                               np.asarray(apply_stencil(x, 3, w,
                                                        method="lax")),
                               rtol=1e-6)


def test_pipe_plan_does_not_pin_input_array(fresh_cache, rng):
    """The interned executor closure must not keep the first caller's
    input alive in the process-wide cache."""
    import gc
    import weakref

    x = _vol(rng, (10, 10))
    P = pipe(x).gaussian(1.0, op_shape=3).gradient()
    jax.block_until_ready(P.run(method="lax", pad_value="edge"))
    ref = weakref.ref(x)
    del x, P
    gc.collect()
    assert ref() is None  # plan cache holds steps/weights, never the input


def test_plan_inspection_works_for_axis_moments(rng):
    """.plan() must not crash on a graph .run() accepts."""
    x = _vol(rng, (6, 5, 4))
    P = pipe(x).moments(order=2, axis=(0, 1))
    prog = P.plan(method="lax")
    assert prog.out_kind == "moments"
    st = P.run(method="lax")
    np.testing.assert_allclose(
        np.asarray(st.variance),
        np.var(np.asarray(x, np.float64), axis=(0, 1)), rtol=1e-4,
        atol=1e-5)


def test_zscore_sigma_spellings_hash(rng):
    x = _vol(rng, (10, 10))
    for sigma in (1.5, [1.0, 2.0], np.asarray([1.0, 2.0])):
        out = (pipe(x).zscore(5, weights="gaussian", sigma=sigma)
               .pointwise(jnp.abs, key="abs")
               .run(method="lax", pad_value="edge"))
        assert out.shape == x.shape
    # list and array spellings of the same sigma intern one plan
    from repro.pipe.graph import ZscoreOp

    assert (ZscoreOp(5, 2, "gaussian", [1.0, 2.0]).signature()
            == ZscoreOp(5, 2, "gaussian",
                        np.asarray([1.0, 2.0])).signature())


# -- distributed routing -----------------------------------------------------


def test_sharded_pipe_matches_single_device():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.pipe import pipe
from repro.core.distributed import sharded_pipe_fn
from repro.core import gaussian_filter, gradient
from repro.stats import moments

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(16, 9, 5).astype(np.float32))
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
tmpl = jax.ShapeDtypeStruct(x.shape, x.dtype)

G = pipe(tmpl).gaussian(1.2, op_shape=3).gradient().moments(order=2)
st = jax.jit(sharded_pipe_fn(mesh, "data", G, method="lax",
                             pad_value="edge"))(x)
y = gaussian_filter(x, 3, 1.2, method="lax", pad_value="edge")
ref = moments(gradient(y, method="lax", pad_value="edge"),
              axis=(0, 1, 2), method="lax", order=2)
np.testing.assert_allclose(np.asarray(st.variance),
                           np.asarray(ref.variance), rtol=1e-5)

xb = jnp.asarray(rng.randn(4, 16, 9).astype(np.float32))
mesh2 = Mesh(np.array(jax.devices()).reshape(2, 2), ("batch", "data"))
tb = jax.ShapeDtypeStruct(xb.shape, xb.dtype)
G3 = pipe.batched(tb).gaussian(1.0, op_shape=3).moments(order=2)
st3 = jax.jit(sharded_pipe_fn(mesh2, "data", G3, method="lax",
                              pad_value="edge", batch_axis_name="batch"))(xb)
yb = gaussian_filter(xb, 3, 1.0, method="lax", pad_value="edge",
                     batched=True)
ref3 = moments(yb, batched=True, order=2)
np.testing.assert_allclose(np.asarray(st3.variance),
                           np.asarray(ref3.variance), rtol=1e-5)
print("sharded-pipe OK")
""", 4)
    assert "sharded-pipe OK" in out


def test_sharded_pipe_runs_the_planners_per_dim_passes():
    """On each halo-extended slab the README graph's 7³ Gaussian runs as
    the one-chip planner plans a 'valid' group, three 1-D passes; the
    3³ gradient bank stays one dense pass.  Both paths match one-device
    ``Pipe.run``, and the build counts one per-dim group, two exchanges
    and the bytes they send."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.pipe import pipe
from repro.core.distributed import sharded_pipe_fn
from repro.obs import REGISTRY

x = jnp.asarray(np.random.RandomState(3).randn(32, 24, 40), jnp.float32)
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
ref = pipe(x).gaussian(1.5).gradient().moments(order=2).run(
    method="lax", pad_value="edge")


def seen(name):
    return REGISTRY.snapshot().get(name) or 0


for method in ("lax", "fused"):
    sep, ex = seen("shard/separable_groups"), seen("shard/halo_exchanges")
    G = pipe(jax.ShapeDtypeStruct(x.shape, x.dtype)).gaussian(
        1.5).gradient().moments(order=2)
    f = jax.jit(sharded_pipe_fn(mesh, "data", G, method=method,
                                pad_value="edge"))
    assert seen("shard/separable_groups") - sep == 1
    assert seen("shard/halo_exchanges") - ex == 2
    # 3 + 3 planes for the Gaussian, 1 + 1 for the gradient
    assert seen("shard/halo_bytes") == 8 * 24 * 40 * 4
    st = f(x)
    np.testing.assert_allclose(np.asarray(st.mean), np.asarray(ref.mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st.variance),
                               np.asarray(ref.variance), rtol=1e-5)
    if method == "lax":
        # three 1-D Gaussian passes and the gradient bank
        assert f.lower(x).as_text().count("stablehlo.convolution") == 4
print("sharded-separable OK")
""", 4)
    assert "sharded-separable OK" in out


# -- channel-major hand-off (DESIGN.md §11, fusion rule 4) -------------------


def _handoffs():
    from repro.obs import REGISTRY

    return REGISTRY.counter("pipe/channel_major_handoff").value


def test_curvature_plan_has_no_transpose():
    """The bank hands its K channels to the channels-first det combine
    as the convolution computes them: no relayout anywhere in the
    program.  A default-contract stage still gets them trailing."""
    from repro.core.filters import gaussian_curvature

    x = jnp.asarray(np.random.RandomState(7).randn(9, 8, 7), jnp.float32)
    # traced, gaussian_curvature runs the planner's program inline
    hlo = jax.jit(lambda t: gaussian_curvature(t, method="lax")).lower(
        x).as_text()
    assert "stablehlo.transpose" not in hlo
    W = curvature_bank(3)
    last = jax.jit(lambda t: pipe(t).bank((3, 3, 3), W).pointwise(
        lambda D: D[..., 0], key="first").run(method="lax")).lower(
        x).as_text()
    assert "stablehlo.transpose" in last


@pytest.mark.parametrize("method", METHODS)
def test_pointwise_layout_contract(method):
    """Default stages see (..., *spatial, K); channels-first stages see
    the channels leading, and a channel axis they keep trails again."""
    x = jnp.asarray(np.random.RandomState(8).randn(7, 6, 5), jnp.float32)
    W = curvature_bank(3)
    seen = {}

    def last(D):
        seen["last"] = D.shape
        return D * 2.0

    def first(C):
        seen["first"] = C.shape
        return C * 2.0

    a = pipe(x).bank((3, 3, 3), W).pointwise(last, key="l").run(
        method=method)
    b = pipe(x).bank((3, 3, 3), W).pointwise(
        first, key="f", channels_first=True).run(method=method)
    assert seen["last"] == (7, 6, 5, 12)
    assert seen["first"][0] == 12  # the fused path shows its flat rows
    assert a.shape == b.shape == (7, 6, 5, 12)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                               atol=1e-6)


def test_channels_first_tiled_and_sharded_match():
    """The tile stream and the sharded step loop give the in-memory
    answer with a channels-first combine, kept channels included."""
    from repro.core.filters import _curvature_combine, gaussian_curvature

    x = jnp.asarray(np.random.RandomState(9).randn(12, 10, 9), jnp.float32)
    W = curvature_bank(3)
    P = pipe(x).bank((3, 3, 3), W).pointwise(
        _curvature_combine(3), key="curv", channels_first=True)
    ref = np.asarray(gaussian_curvature(x, method="lax"))
    np.testing.assert_allclose(
        np.asarray(P.run(method="lax", tiles=(2, 2, 1))), ref, rtol=1e-6,
        atol=1e-7)
    K = pipe(x).bank((3, 3, 3), W).pointwise(
        lambda C: C[:3] * C[3:6], key="keep", channels_first=True)
    np.testing.assert_allclose(
        np.asarray(K.run(method="lax", tiles=(2, 1, 2))),
        np.asarray(K.run(method="lax")), rtol=1e-6, atol=1e-7)
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.pipe import pipe
from repro.core.distributed import sharded_pipe_fn
from repro.core.filters import (_curvature_combine, curvature_bank,
                                gaussian_curvature)

x = jnp.asarray(np.random.RandomState(9).randn(16, 10, 9), jnp.float32)
mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
G = (pipe(jax.ShapeDtypeStruct(x.shape, x.dtype))
     .bank((3, 3, 3), curvature_bank(3))
     .pointwise(_curvature_combine(3), key="curv", channels_first=True))
got = jax.jit(sharded_pipe_fn(mesh, "data", G, method="lax",
                              pad_value="edge"))(x)
ref = gaussian_curvature(x, method="lax")
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6,
                           atol=1e-7)
print("sharded-curvature OK")
""", 4)
    assert "sharded-curvature OK" in out


def test_channel_major_handoff_counter(fresh_cache):
    """Counted once per plan build that hands a bank's output to a
    channels-first stage: not on a cache hit, not for other graphs."""
    from repro.core.filters import gaussian_curvature

    x = jnp.asarray(np.random.RandomState(10).randn(11, 9, 6), jnp.float32)
    before = _handoffs()
    gaussian_curvature(x, method="lax").block_until_ready()
    assert _handoffs() == before + 1
    gaussian_curvature(x, method="lax").block_until_ready()
    assert _handoffs() == before + 1
    jax.block_until_ready(
        pipe(x).gaussian(1.5).gradient().moments(order=2).run(
            method="lax"))
    assert _handoffs() == before + 1


# -- property-fuzz: the fusion planner (DESIGN.md §11/§12) -------------------


def _expected_groups(stages):
    """Independent replay of the planner's greedy composition rule: how
    many logical passes a chain of (op, stride, padding) stages must
    plan.  'valid' chains compose under any strides; 'same' chains
    compose (as an interior/boundary split) only when both neighbours
    are unit-stride; mixed padding never composes."""
    groups = 0
    last = None  # (padding, stride) of the previous stage
    for op, stride, padding in stages:
        if last is not None:
            lp, ls = last
            mergeable = (
                (padding == "valid" and lp == "valid")
                or (padding == "same" and lp == "same"
                    and stride == 1 and ls == 1))
            if mergeable:
                last = (padding, stride)
                continue
        groups += 1
        last = (padding, stride)
    return groups


@settings(max_examples=20, deadline=None)
@given(
    n_stages=st.integers(1, 3),
    op=st.integers(2, 3),
    paddings=st.lists(st.sampled_from(["same", "valid"]), min_size=3,
                      max_size=3),
    strides=st.lists(st.sampled_from([1, 1, 2]), min_size=3, max_size=3),
    pad=st.sampled_from(PADS),
    seed=st.integers(0, 2**16),
)
def test_fuzz_planner_pass_accounting(n_stages, op, paddings, strides, pad,
                                      seed):
    """Random linear chains: the planner's pass count matches the greedy
    composition rule, the materialize melt counter matches the plan, and
    the fused program equals the eager chain."""
    rng = np.random.RandomState(seed)
    shape = (17, 15)
    x = _vol(rng, shape)
    stages = [((op, op), strides[i], paddings[i]) for i in range(n_stages)]
    # 'valid'/strided chains can exhaust the extent — skip impossible draws
    cur = shape
    ok = True
    for (o, s, p_) in stages:
        try:
            from repro.core.grid import grid_shape
            cur = grid_shape(cur, (o, o) if isinstance(o, int) else o,
                             (s, s), p_, (1, 1))
        except ValueError:
            ok = False
            break
    if not ok or min(cur) < 1:
        return

    P = pipe(x)
    eager = x
    for (o, s, p_) in stages:
        w = rng.randn(int(np.prod(o if not isinstance(o, int)
                                  else (o, o)))).astype(np.float32)
        P = P.stencil(o, w, stride=s, padding=p_)
        eager = apply_stencil(eager, o, jnp.asarray(w), stride=s,
                              padding=p_, pad_value=pad, method="lax")

    program = P.plan(method="lax", pad_value=pad)
    assert program.passes == _expected_groups(stages)
    np.testing.assert_allclose(np.asarray(P.run(method="lax",
                                                pad_value=pad)),
                               np.asarray(eager), rtol=3e-5, atol=3e-5)

    clear_plan_cache()
    prog_m = P.plan(method="materialize", pad_value=pad)
    before = melt_call_count()
    P.run(method="materialize", pad_value=pad)
    assert melt_call_count() - before == prog_m.melt_calls


@settings(max_examples=10, deadline=None)
@given(
    op1=st.integers(2, 4),
    op2=st.integers(2, 4),
    k=st.integers(1, 3),
    seed=st.integers(0, 2**16),
)
def test_fuzz_weight_composition_exact(op1, op2, k, seed):
    """compose_weights is the full N-D convolution: a composed one-pass
    bank equals the two-pass chain exactly for random weights."""
    rng = np.random.RandomState(seed)
    x = _vol(rng, (14, 13))
    w1 = rng.randn(op1 * op1).astype(np.float32)
    W2 = rng.randn(op2 * op2, k).astype(np.float32)
    P = (pipe(x).stencil((op1, op1), w1, padding="valid")
         .bank((op2, op2), W2, padding="valid"))
    assert P.plan(method="lax").passes == 1
    y = apply_stencil(x, (op1, op1), jnp.asarray(w1), padding="valid",
                      method="lax")
    ref = apply_stencil_bank(y, (op2, op2), jnp.asarray(W2),
                             padding="valid", method="lax",
                             separable=False)
    np.testing.assert_allclose(np.asarray(P.run(method="lax")),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_pipe_run_rejects_mesh_without_tiles(rng):
    x = _vol(rng, (8, 8))
    with pytest.raises(ValueError, match="tiled"):
        pipe(x).gaussian(1.0, op_shape=3).run(mesh=object(),
                                              axis_name="t")


def test_linear_op_weights_frozen_copy():
    """Mutating the caller's weight buffer after building a graph must
    not desync a cached plan from the digest it interned under: ops take
    a private read-only copy (PR-9 aliasing fix)."""
    import numpy as np

    from repro.pipe.graph import pipe

    x = np.zeros((8, 8), np.float32)
    src = np.ones((25,), np.float32)
    P = pipe(x).stencil(5, src)
    sig_before = P.signature()
    src[:] = 99.0
    op = P.ops[0]
    assert float(op.weights[0, 0]) == 1.0
    assert not op.weights.flags.writeable
    assert P.signature() == sig_before
    with np.testing.assert_raises(ValueError):
        op.weights[0, 0] = 5.0


def test_zscore_sigma_frozen_copy():
    import numpy as np

    from repro.pipe.graph import pipe

    x = np.zeros((8, 8), np.float32)
    sig = np.array([1.0, 2.0])
    P = pipe(x).zscore(5, weights="gaussian", sigma=sig)
    sig[:] = 7.0
    assert float(P.ops[0].sigma[0]) == 1.0
    assert not P.ops[0].sigma.flags.writeable
