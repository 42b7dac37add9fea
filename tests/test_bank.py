"""Operator-bank execution (DESIGN.md §9) — the tentpole acceptance tests.

Oracle: a bank pass must equal the stacked results of K single-operator
``apply_stencil`` calls (whose semantics are pinned by the materialize
path), on all three execution paths, batched and unbatched, across pad
modes.  Separable execution must be indistinguishable from the dense bank
wherever it engages; the fused path must never materialize ``M``; and bank
signatures must intern in the plan cache.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    curvature_bank,
    difference_stencils,
    gaussian_curvature,
    gaussian_weights,
    get_bank_plan,
    gradient,
    hessian,
    melt_call_count,
    plan_cache_stats,
    separable_factors,
)
from repro.core.plan import separable_eligible, separable_profitable
from repro.pipe import pipe

BATCH = 3
METHODS = ("materialize", "lax", "fused")

# (spatial_shape, op) — ranks 1..3; K sweeps {1, rank + rank²} per case
CASES = [
    ((17,), 3),
    ((11, 9), 3),
    ((12, 10), 5),
    ((7, 6, 5), 3),
]


def _data(shape, seed=0):
    rng = np.random.RandomState(seed + len(shape))
    return (jnp.asarray(rng.randn(*shape).astype(np.float32)),
            jnp.asarray(rng.randn(BATCH, *shape).astype(np.float32)))


def _stacked_oracle(x, op, W, pad_value, batched):
    return np.stack(
        [np.asarray(apply_stencil(x, op, W[:, k], method="materialize",
                                  pad_value=pad_value, batched=batched))
         for k in range(W.shape[1])], axis=-1)


@pytest.mark.parametrize("pad_value", [0.0, "edge"])
@pytest.mark.parametrize("case", CASES,
                         ids=lambda c: f"r{len(c[0])}-op{c[1]}")
def test_bank_matches_stacked_single(case, pad_value):
    """bank(…)[..., k] == apply_stencil(…, W[:, k]) on every path."""
    shape, op = case
    rank = len(shape)
    x, xb = _data(shape)
    for K in (1, rank + rank * rank):
        W = jnp.asarray(
            np.random.RandomState(rank * 10 + K).randn(op ** rank, K),
            jnp.float32)
        want = _stacked_oracle(x, op, W, pad_value, batched=False)
        want_b = _stacked_oracle(xb, op, W, pad_value, batched=True)
        for method in METHODS:
            got = apply_stencil_bank(x, op, W, method=method,
                                     pad_value=pad_value)
            assert got.shape == shape + (K,)
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=2e-4, atol=2e-5)
            got_b = apply_stencil_bank(xb, op, W, method=method,
                                       pad_value=pad_value, batched=True)
            assert got_b.shape == (BATCH,) + shape + (K,)
            np.testing.assert_allclose(np.asarray(got_b), want_b,
                                       rtol=2e-4, atol=2e-5)


def test_bank_1d_weights_are_K1():
    x, _ = _data((10, 8))
    w = gaussian_weights((3, 3), 1.0)
    got = apply_stencil_bank(x, 3, w, method="materialize")
    want = apply_stencil(x, 3, w, method="materialize")
    assert got.shape == x.shape + (1,)
    np.testing.assert_allclose(np.asarray(got[..., 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_bank_weight_shape_validation():
    x, _ = _data((8, 8))
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 3, jnp.ones((8, 2)))  # needs 9 rows
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 3, jnp.ones((3, 3, 2)))  # not a matrix


# -- separable factorization ------------------------------------------------


@pytest.mark.parametrize("pad_value", [0.0, "edge", "reflect"])
@pytest.mark.parametrize("shape,op", [((13, 11), 5), ((8, 7, 6), 5)])
def test_separable_matches_dense_gaussian(shape, op, pad_value):
    """Gaussian banks factor exactly; k 1-D passes ≡ the dense bank."""
    rank = len(shape)
    x, xb = _data(shape)
    sig = [1.0, 2.0, 0.7][:rank]
    gw = gaussian_weights((op,) * rank, sig)
    W = jnp.stack([gw, 2.0 * gw], axis=1)
    assert separable_factors(W, (op,) * rank) is not None
    for method in METHODS:
        dense = apply_stencil_bank(x, op, W, method=method,
                                   pad_value=pad_value, separable=False)
        sep = apply_stencil_bank(x, op, W, method=method,
                                 pad_value=pad_value, separable=True)
        np.testing.assert_allclose(np.asarray(sep), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)
        dense_b = apply_stencil_bank(xb, op, W, method=method,
                                     pad_value=pad_value, separable=False,
                                     batched=True)
        sep_b = apply_stencil_bank(xb, op, W, method=method,
                                   pad_value=pad_value, separable=True,
                                   batched=True)
        np.testing.assert_allclose(np.asarray(sep_b), np.asarray(dense_b),
                                   rtol=2e-4, atol=2e-5)


def test_separable_K1_and_dilation_regression():
    """Regression: the lax depthwise pass with K=1 once fell into the dense
    branch (groups==1 ambiguity) and crashed; and dilation must stay exact
    through the 1-D rewrite (per-dim offset scaling factorizes too)."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(20, 19).astype(np.float32))
    W = gaussian_weights((5, 5), 1.5, dilation=2)[:, None]  # K = 1
    for method in METHODS:
        dense = apply_stencil_bank(x, 5, W, dilation=2, method=method,
                                   separable=False)
        sep = apply_stencil_bank(x, 5, W, dilation=2, method=method,
                                 separable=True)
        np.testing.assert_allclose(np.asarray(sep), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)


def _per_pass_reference(x, grid, factors, pad_value, batched):
    """The separable bank as it ran before rows were kept: pad, lay out,
    filter and crop around every 1-D pass."""
    from repro.core.grid import make_quasi_grid
    from repro.kernels import ops

    rank, h, shape = grid.rank, x, grid.in_shape
    for d, f in enumerate(factors):
        g = make_quasi_grid(shape, [grid.op_shape[d] if i == d else 1
                                    for i in range(rank)], 1, grid.padding)
        run = ops.fused_stencil_bank if d == 0 else ops.fused_stencil_depthwise
        h = run(h, g, jnp.asarray(f), pad_value, interpret=True,
                batched=batched)
        shape = g.out_shape
    return h


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batch"])
@pytest.mark.parametrize("width", [38, 128])
@pytest.mark.parametrize("shape,op", [((9,), (5, 3)), ((6, 5), (3, 5, 3))],
                         ids=["r2", "r3"])
@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("padding,pad_value",
                         [("valid", 0.0), ("same", "edge"), ("same", 0.0),
                          ("same", "reflect")],
                         ids=["valid", "edge", "zero", "reflect"])
def test_separable_rows_kept_is_bitwise_per_pass(padding, pad_value, K, shape,
                                                 op, width, batched):
    """The fused separable bank pads once, keeps the kernels' rows across
    its per-dim passes and crops once — for every 'valid' group, and for
    'same' where the pad keeps the planes' lane alignment (here the
    misaligned width): bit for bit what padding, laying out and cropping
    around each pass gives, and the dense bank's values within the bank
    tolerances."""
    from repro.core.grid import make_quasi_grid
    from repro.kernels import ops

    spatial = shape + (width,)
    rank = len(spatial)
    rng = np.random.RandomState(rank * 100 + width + K)
    x = jnp.asarray(rng.randn(*((BATCH,) * batched + spatial)), jnp.float32)
    factors = tuple(rng.randn(k, K).astype(np.float32) for k in op)
    grid = make_quasi_grid(spatial, op, 1, padding)
    assert ops._keeps_rows(grid) == (padding == "valid" or width == 38)
    got = ops.fused_separable_bank(x, grid, tuple(map(jnp.asarray, factors)),
                                   pad_value, interpret=True, batched=batched)
    want = _per_pass_reference(x, grid, factors, pad_value, batched)
    assert got.shape == want.shape == (
        (BATCH,) * batched + grid.out_shape + (K,))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    W = np.stack([functools.reduce(np.multiply.outer,
                                   [f[:, k] for f in factors]).ravel()
                  for k in range(K)], axis=1)
    dense = apply_stencil_bank(x, op, jnp.asarray(W), padding=padding,
                               method="lax", pad_value=pad_value,
                               separable=False, batched=batched)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_separable_detection():
    # diagonal-covariance Gaussian: exact rank-1 outer product
    assert separable_factors(
        gaussian_weights((5, 5), [1.0, 2.0])[:, None], (5, 5)) is not None
    # full covariance (cross terms): not factorable
    cov = np.array([[1.0, 0.6], [0.6, 1.5]])
    assert separable_factors(
        gaussian_weights((5, 5), cov)[:, None], (5, 5)) is None
    # every central-difference operator is a product of per-dim vectors
    assert separable_factors(jnp.asarray(curvature_bank(3)),
                             (3, 3, 3)) is not None
    # random dense matrices are not
    W = np.random.RandomState(0).randn(9, 3)
    assert separable_factors(W, (3, 3)) is None
    # rank-1 problems have nothing to factor
    assert separable_factors(np.ones((3, 1)), (3,)) is None
    # factors reconstruct the bank column-by-column
    gw = gaussian_weights((5, 3), [1.0, 0.5])
    facs = separable_factors(gw[:, None], (5, 3))
    recon = np.einsum("i,j->ij", np.asarray(facs[0][:, 0]),
                      np.asarray(facs[1][:, 0])).reshape(-1)
    np.testing.assert_allclose(recon, np.asarray(gw), rtol=1e-5, atol=1e-7)


def test_separable_gates():
    assert separable_eligible(2, (1, 1), "same")
    assert not separable_eligible(1, (1,), "same")
    assert not separable_eligible(2, (2, 1), "same")
    assert not separable_eligible(2, (1, 1), "valid")
    # zero/edge/reflect commute with per-dim passes; nonzero constants don't
    assert separable_eligible(2, (1, 1), "same", pad_value="edge")
    assert separable_eligible(2, (1, 1), "same", pad_value=0)
    assert not separable_eligible(2, (1, 1), "same", pad_value=1.0)
    assert separable_profitable((5, 5, 5))
    assert separable_profitable((9, 9))
    assert not separable_profitable((3, 3, 3))
    assert not separable_profitable((5, 5))


def test_nonzero_constant_pad_stays_dense():
    """Regression: with pad_value=c != 0 the 1-D rewrite is NOT exact (the
    second pass re-injects raw c over filtered boundary values), so 'auto'
    must run dense — and still match the stacked single-operator oracle —
    while separable=True refuses."""
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(14, 13).astype(np.float32))
    gw = gaussian_weights((5, 5), [1.0, 2.0])  # profitable + factorable
    W = jnp.stack([gw, 2.0 * gw], axis=1)
    want = _stacked_oracle(x, 5, W, pad_value=1.0, batched=False)
    for method in METHODS:
        got = apply_stencil_bank(x, 5, W, method=method, pad_value=1.0)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 5, W, pad_value=1.0, separable=True)


def test_separable_forced_and_opt_out():
    x, _ = _data((10, 9))
    gw = gaussian_weights((3, 3), 1.0)[:, None]
    # 3x3 is below the profitability gate: auto must run dense, but
    # separable=True can force the rewrite and still agree
    forced = apply_stencil_bank(x, 3, gw, method="materialize",
                                separable=True)
    auto = apply_stencil_bank(x, 3, gw, method="materialize")
    np.testing.assert_allclose(np.asarray(forced), np.asarray(auto),
                               rtol=1e-5, atol=1e-6)
    # non-factorable weights: separable=True raises, auto falls back
    W = jnp.asarray(np.random.RandomState(1).randn(9, 2), jnp.float32)
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 3, W, separable=True)
    apply_stencil_bank(x, 3, W)  # auto: dense, no error
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 3, W, separable="sometimes")
    # geometry gate: strided banks cannot factor
    with pytest.raises(ValueError):
        apply_stencil_bank(x, 3, gw, stride=2, separable=True)


# -- derivative family ------------------------------------------------------


def test_gradient_hessian_exact_on_quadratics():
    ii, jj = np.meshgrid(np.arange(10, dtype=np.float32),
                         np.arange(9, dtype=np.float32), indexing="ij")
    f = jnp.asarray(2 * ii * ii + 3 * ii * jj + jj * jj + 4 * ii + 5 * jj)
    for method in METHODS:
        g = np.asarray(gradient(f, method=method))
        H = np.asarray(hessian(f, method=method))
        assert g.shape == f.shape + (2,)
        assert H.shape == f.shape + (2, 2)
        want_g = np.stack([4 * ii + 3 * jj + 4, 3 * ii + 2 * jj + 5],
                          axis=-1)
        np.testing.assert_allclose(g[2:-2, 2:-2], want_g[2:-2, 2:-2],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            H[2:-2, 2:-2], np.broadcast_to([[4.0, 3.0], [3.0, 2.0]],
                                           H[2:-2, 2:-2].shape),
            rtol=1e-4, atol=1e-4)


#: one volume per rank for the curvature combine (rank 4 runs the bank
#: through its separable factors)
CURVATURE_SHAPES = {1: (23,), 2: (14, 13), 3: (9, 8, 7), 4: (6, 5, 5, 4)}


def _curvature_channels_last(rank):
    """det(H) / (1 + |∇|²)² on channel-last [∇ | vec(H)] values, with
    ``jnp.linalg.det`` — the default pointwise contract."""

    def fn(D):
        g = D[..., :rank]
        H = D[..., rank:].reshape(D.shape[:-1] + (rank, rank))
        return jnp.linalg.det(H) / (1.0 + jnp.sum(g * g, axis=-1)) ** 2

    return fn


@pytest.mark.parametrize("rank", sorted(CURVATURE_SHAPES))
def test_curvature_methods_agree_batched_and_not(rank):
    """The channels-first combine equals the channel-last det formula on
    every path, and the paths agree with each other."""
    rng = np.random.RandomState(5)
    shape = CURVATURE_SHAPES[rank]
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    xb = jnp.asarray(rng.randn(BATCH, *shape).astype(np.float32))
    fn, W = _curvature_channels_last(rank), curvature_bank(rank)
    last = np.asarray(pipe(x).bank((3,) * rank, W).pointwise(fn).run(
        method="materialize"))
    last_b = np.asarray(pipe.batched(xb).bank((3,) * rank, W).pointwise(
        fn).run(method="materialize"))
    ref = np.asarray(gaussian_curvature(x, method="materialize"))
    ref_b = np.asarray(gaussian_curvature(xb, method="materialize",
                                          batched=True))
    for method in METHODS:
        got = np.asarray(gaussian_curvature(x, method=method))
        got_b = np.asarray(gaussian_curvature(xb, method=method,
                                              batched=True))
        np.testing.assert_allclose(got, last, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_b, last_b, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_b, ref_b, rtol=1e-4, atol=1e-5)


def test_curvature_fused_never_materializes():
    """Acceptance: the fused bank path must not call melt, even tracing."""
    clear_plan_cache()
    x = jnp.asarray(np.random.RandomState(6).randn(19, 18), jnp.float32)
    before = melt_call_count()
    jax.block_until_ready(gaussian_curvature(x, method="fused"))
    assert melt_call_count() == before  # fresh shape → fresh trace, 0 melts
    jax.block_until_ready(gaussian_curvature(x, method="materialize"))
    assert melt_call_count() > before  # the oracle path still melts


def _rows_kept():
    from repro.obs import REGISTRY

    return REGISTRY.counter("kernels/separable_rows_kept").value


@pytest.mark.parametrize("shape,kept", [((14, 13), 1), ((9, 7, 5), 2),
                                        ((6, 4, 128), 0)],
                         ids=["r2", "r3", "r3-lane-aligned"])
def test_separable_rows_kept_counter(shape, kept):
    """rank − 1 per traced fused separable group that keeps its rows (each
    pass after the first takes its predecessor's rows), 0 where a 'same'
    pad would misalign a lane-aligned plane; nothing on a re-run of the
    same trace, nor for a dense bank such as the curvature bank."""
    clear_plan_cache()
    rank = len(shape)
    x = jnp.asarray(np.random.RandomState(11).randn(*shape), jnp.float32)
    gw = gaussian_weights((5,) * rank, 1.2)
    W = jnp.stack([gw, -gw], axis=1)
    before = _rows_kept()
    jax.block_until_ready(apply_stencil_bank(x, 5, W, method="fused",
                                             separable=True))
    assert _rows_kept() == before + kept
    jax.block_until_ready(apply_stencil_bank(x, 5, W, method="fused",
                                             separable=True))
    assert _rows_kept() == before + kept
    jax.block_until_ready(apply_stencil_bank(x, 3, curvature_bank(rank),
                                             method="fused"))
    assert _rows_kept() == before + kept


def test_difference_stencils_cached_and_readonly():
    a = difference_stencils(3)
    b = difference_stencils(3)
    assert a[0] is b[0] and a[1] is b[1]  # lru_cache hit
    with pytest.raises(ValueError):
        a[0][0, 0] = 1.0  # read-only: cache cannot be corrupted in place


# -- plan-cache behaviors ---------------------------------------------------


@pytest.fixture
def fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def test_bank_signatures_intern_and_hit(fresh_cache):
    x, _ = _data((12, 11))
    W = jnp.asarray(np.random.RandomState(2).randn(9, 4), jnp.float32)
    for _ in range(3):
        apply_stencil_bank(x, 3, W, method="lax")
    stats = plan_cache_stats()
    assert stats["size"] == 1
    assert stats["misses"] == 1 and stats["hits"] == 2
    plan = get_bank_plan((12, 11), jnp.float32, 3, 1, "same", 1, 0.0,
                         "lax", False, K=4, separable=False)
    assert plan.K == 4 and not plan.separable
    assert plan.stats()["calls"] == 3
    assert plan.stats()["traces"] == 1  # weight-varying calls never retrace


def test_bank_plans_keyed_on_K_and_separable(fresh_cache):
    base = dict(dtype=jnp.float32, op_shape=3, stride=1, padding="same",
                dilation=1, pad_value=0.0, method="lax", batched=False)
    p1 = get_bank_plan((12, 11), K=4, separable=False, **base)
    p2 = get_bank_plan((12, 11), K=5, separable=False, **base)
    p3 = get_bank_plan((12, 11), K=4, separable=True, **base)
    p4 = get_bank_plan((12, 11), K=4, separable=False, **base)
    assert len({p1, p2, p3}) == 3
    assert p4 is p1
    # bank keys never collide with single-operator plans of the same shape
    from repro.core import get_plan
    p5 = get_plan((12, 11), jnp.float32, 3, 1, "same", 1, 0.0, "lax", False)
    assert plan_cache_stats()["size"] == 4
    assert p5 is not p1


def test_bank_traced_inputs_bypass_cache(fresh_cache):
    x, _ = _data((10, 9))
    W = jnp.asarray(np.random.RandomState(3).randn(9, 2), jnp.float32)

    @jax.jit
    def f(x):
        return apply_stencil_bank(x, 3, W, method="lax", separable=False)

    np.testing.assert_allclose(
        np.asarray(f(x)),
        np.asarray(apply_stencil_bank(x, 3, W, method="lax")),
        rtol=1e-5, atol=1e-6)
    assert plan_cache_stats()["size"] == 1  # only the concrete outer call


# -- tile_rows heuristic ----------------------------------------------------


def test_pick_tile_rows_aligned_and_bounded():
    from repro.kernels.melt_stencil import pick_tile_rows

    for numel, c_in, c_out, dtype in [(27, 1, 1, jnp.float32),
                                      (27, 1, 12, jnp.float32),
                                      (125, 4, 4, jnp.bfloat16),
                                      (3, 1, 1, jnp.float32)]:
        t = pick_tile_rows(numel, c_in, c_out, dtype)
        sub = 16 if jnp.dtype(dtype).itemsize == 2 else 8
        assert t % sub == 0
        assert sub <= t <= 1024
    # a tiny budget shrinks the tile; a geometry of many input windows
    # (or wide window spans) can't overflow it
    small = pick_tile_rows(27, 1, 12, jnp.float32, vmem_budget=64 * 1024)
    assert small < pick_tile_rows(27, 1, 12, jnp.float32)
    assert pick_tile_rows(27, 1, 1, jnp.float32, windows=100_000) == 8
    assert pick_tile_rows(27, 1, 1, jnp.float32, span_rows=100_000) == 8
    assert (pick_tile_rows(27, 1, 1, jnp.float32, windows=27)
            < pick_tile_rows(27, 1, 1, jnp.float32, windows=3))


def test_tile_rows_override_changes_nothing_numerically():
    from repro.core.grid import make_quasi_grid
    from repro.kernels import ops

    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(30, 17).astype(np.float32))
    grid = make_quasi_grid(x.shape, (3, 3), 1, "same", 1)
    W = jnp.asarray(rng.randn(9, 3), jnp.float32)
    default = ops.fused_stencil_bank(x, grid, W)
    for tr in (8, 64):
        got = ops.fused_stencil_bank(x, grid, W, tile_rows=tr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(default),
                                   rtol=1e-5, atol=1e-6)
    w = gaussian_weights((3, 3), 1.0)
    d1 = ops.fused_stencil(x, grid, w, tile_rows=16)
    d2 = ops.fused_stencil(x, grid, w)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-6)


def test_bank_mxu_formulations_agree():
    """One window pass feeding K operators is the same math as K
    single-operator passes (the stencil family) and as K per-channel
    passes over a broadcast input (the depthwise family)."""
    from repro.core.grid import make_quasi_grid
    from repro.kernels import ops

    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(13, 12).astype(np.float32))
    grid = make_quasi_grid(x.shape, (3, 3), 1, "same", 1)
    W = jnp.asarray(rng.randn(9, 5), jnp.float32)
    a = ops.fused_stencil_bank(x, grid, W)
    b = jnp.stack([ops.fused_stencil(x, grid, W[:, k]) for k in range(5)],
                  axis=-1)
    c = ops.fused_stencil_depthwise(
        jnp.broadcast_to(x[..., None], x.shape + (5,)), grid, W)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-6)
