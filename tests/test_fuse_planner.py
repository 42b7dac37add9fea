"""Deep fuse-planner contracts (DESIGN.md §11, rules 1a/1b).

Pinned here:

- **Float64 folding** — a multi-stage 'valid' chain folds its operator
  tensors entirely in float64 and quantizes to float32 exactly once at
  plan time; the old per-merge float32 cast double-rounded 3+-stage
  chains.
- **Strided composition (rule 1a)** — 'valid' chains compose under any
  strides: composite tap ``a1 + s1·a2``, extent ``k1 + s1·(k2−1)``,
  stride ``s1·s2``; the one-pass program matches the two-pass eager
  chain and the materialize melt counter matches the plan.
- **'same' split (rule 1b)** — stride-1 'same' chains plan as a
  composed-'valid' interior pass plus boundary slabs that replay the
  original per-stage program through the tile machinery.  The boundary
  region is BIT-IDENTICAL to the unfused chain; the interior is allclose
  (float reassociation).  Melt accounting is declared and exact.
- **Fallbacks** — dilation declines composition; a volume too small to
  have an interior falls back to per-stage passes; the out-of-core tiled
  front end never nests a split and still agrees numerically.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _prop import given, settings, strategies as st

from repro.core import (
    apply_stencil,
    apply_stencil_bank,
    clear_plan_cache,
    gaussian_filter,
    gradient,
    melt_call_count,
)
from repro.pipe import compose_weights, pipe
from repro.pipe.fuse import SplitStep


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _vol(rng, shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


# -- float64 weight folding (the composition-precision bugfix) ---------------


def test_compose_weights_returns_float64():
    w1 = np.ones((9, 1), np.float32)
    W2 = np.ones((9, 2), np.float32)
    comp = compose_weights(w1, (3, 3), W2, (3, 3))
    assert comp.dtype == np.float64
    assert comp.shape == (25, 2)


def test_chain_folds_float64_single_final_cast():
    """A 4-stage 1-D chain quantizes once: the planned weights equal the
    float64 convolution chain cast to float32 at the end — NOT the
    per-merge-cast fold (which double-rounds and lands on different
    float32 values for generic weights)."""
    rng = np.random.RandomState(3)
    ws = [rng.randn(3).astype(np.float32) for _ in range(4)]
    x = jnp.zeros((64,), jnp.float32)
    P = pipe(x)
    for w in ws:
        P = P.stencil(3, w, padding="valid")
    step = P.plan(method="lax").steps[0]
    assert step.grid.op_shape == (9,)  # 3 ⊕ 3 ⊕ 3 ⊕ 3
    # composed tap c[a] = Σ_{a1+a2=a} w1[a1]·w2[a2] == np.convolve
    ref64 = functools.reduce(np.convolve,
                             [w.astype(np.float64) for w in ws])
    np.testing.assert_array_equal(step.weights.ravel(),
                                  ref64.astype(np.float32))
    # the old per-merge float32 fold is measurably different
    folded32 = ws[0].astype(np.float64)
    for w in ws[1:]:
        folded32 = np.convolve(folded32, w).astype(np.float32)
        folded32 = folded32.astype(np.float64)
    assert not np.array_equal(folded32.astype(np.float32),
                              ref64.astype(np.float32))


# -- rule 1a: strided 'valid' composition ------------------------------------


def test_strided_composition_matches_two_pass(rng):
    x = _vol(rng, (20, 18))
    w1 = rng.randn(9).astype(np.float32)
    W2 = rng.randn(25, 3).astype(np.float32)
    P = (pipe(x).stencil(3, w1, stride=2, padding="valid")
         .bank(5, jnp.asarray(W2), stride=3, padding="valid"))
    for method in ("lax", "materialize"):
        prog = P.plan(method=method)
        assert prog.passes == 1
        step = prog.steps[0]
        assert step.grid.op_shape == (11, 11)   # 3 + 2·(5−1)
        assert step.grid.stride == (6, 6)       # 2·3
        y = apply_stencil(x, 3, jnp.asarray(w1), stride=2,
                          padding="valid", method=method)
        ref = apply_stencil_bank(y, 5, jnp.asarray(W2), stride=3,
                                 padding="valid", method=method,
                                 separable=False)
        out = P.run(method=method)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


@settings(max_examples=15, deadline=None)
@given(
    s1=st.integers(1, 3),
    s2=st.integers(1, 3),
    o1=st.integers(2, 4),
    o2=st.integers(2, 4),
    seed=st.integers(0, 2**16),
)
def test_fuzz_strided_valid_chains(s1, s2, o1, o2, seed):
    """Random strided 'valid' 2-stage chains: one pass, exact output
    count, allclose vs the eager oracle, melt accounting exact."""
    rng = np.random.RandomState(seed)
    x = _vol(rng, (23, 19))
    w1 = rng.randn(o1 * o1).astype(np.float32)
    W2 = rng.randn(o2 * o2, 2).astype(np.float32)
    P = (pipe(x).stencil((o1, o1), w1, stride=s1, padding="valid")
         .bank((o2, o2), jnp.asarray(W2), stride=s2, padding="valid"))
    prog = P.plan(method="lax")
    assert prog.passes == 1
    step = prog.steps[0]
    assert step.grid.op_shape == tuple(o1 + s1 * (o2 - 1) for _ in range(2))
    assert step.grid.stride == (s1 * s2, s1 * s2)
    y = apply_stencil(x, (o1, o1), jnp.asarray(w1), stride=s1,
                      padding="valid", method="lax")
    ref = apply_stencil_bank(y, (o2, o2), jnp.asarray(W2), stride=s2,
                             padding="valid", method="lax",
                             separable=False)
    out = P.run(method="lax")
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)

    clear_plan_cache()
    prog_m = P.plan(method="materialize")
    before = melt_call_count()
    jax.block_until_ready(P.run(method="materialize"))
    assert melt_call_count() - before == prog_m.melt_calls


# -- rule 1b: 'same' interior/boundary split ---------------------------------


def _eager_same(x, method, pad):
    y = gaussian_filter(x, 5, 1.5, method=method, pad_value=pad)
    return gradient(y, method=method, pad_value=pad)


def test_same_split_plan_shape(rng):
    x = _vol(rng, (16, 17))
    prog = (pipe(x).gaussian(1.5, op_shape=5).gradient()
            .plan(method="lax", pad_value="edge"))
    assert prog.passes == 1
    (step,) = prog.steps
    assert isinstance(step, SplitStep)
    assert step.interior.grid.op_shape == (7, 7)
    assert step.interior_lo == (3, 3)      # Σ pad_lo = 2 + 1
    assert len(step.specs) == 4            # 2·rank boundary slabs
    assert step.fused_from == 2
    assert "split[7x7,K=2,slabs=4,fused=2]" in prog.describe()
    # 1 logical pass; melt = dense interior + 4 slabs × 2 inner stages
    assert step.melt_calls == step.interior.melt_calls + 4 * 2


#: the one-chip programs of the CT benchmark cells' graphs at their size
#: (256×512×512, 'edge'): the sharded front end plans its own slabs and
#: must leave these, and the plan keys they intern under, as they are
CT_PLANS = {
    "ct-same-variance": (
        "split[9x9x9,K=3,slabs=6,fused=2] -> reduce[moments] | passes=1 "
        "melt_calls(materialize)=15",
        (("stencil", (7, 7, 7), (1, 1, 1), "same", (1, 1, 1), 1,
          "8553cbe791f3b9ec"),
         ("bank", (3, 3, 3), (1, 1, 1), "same", (1, 1, 1), 3,
          "4ba62108e01fb53f"),
         ("moments", 2, None))),
    "ct-curvature": (
        "linear[3x3x3,K=12,dense,fused=1]+pointwise | passes=1 "
        "melt_calls(materialize)=1",
        (("bank", (3, 3, 3), (1, 1, 1), "same", (1, 1, 1), 12,
          "fb70cc1097ab2d27"),
         ("ptw", "curv", True))),
}


def _ct_graph(name):
    from repro.core.filters import _curvature_combine, curvature_bank

    t = jax.ShapeDtypeStruct((256, 512, 512), jnp.float32)
    if name == "ct-same-variance":
        return pipe(t).gaussian(1.5).gradient().moments(order=2)
    return pipe(t).bank((3, 3, 3), curvature_bank(3)).pointwise(
        _curvature_combine(3), key="curv", channels_first=True)


@pytest.mark.parametrize("method", ("lax", "fused"))
@pytest.mark.parametrize("name", sorted(CT_PLANS))
def test_one_chip_plans_of_the_ct_graphs_are_fixed(name, method):
    from repro.core.plan import ExecOptions
    from repro.pipe.compile import plan_key_for
    from repro.pipe.fuse import build_program

    G = _ct_graph(name)
    prog = build_program(G, ExecOptions.make(method, "edge", False))
    described, signature = CT_PLANS[name]
    assert prog.describe() == described
    assert plan_key_for(G, method, "edge")[-1] == signature
    if name == "ct-same-variance":
        (split, _) = prog.steps
        # the composed interior runs per-dim; its slabs replay the
        # stages dense
        assert split.interior.factors is not None
        assert split.interior.grid.padding == "valid"
        assert split.interior_lo == (4, 4, 4)
        assert split.inner.describe() == (
            "linear[7x7x7,K=1,dense,fused=1] -> "
            "linear[3x3x3,K=3,dense,fused=1] | passes=2 "
            "melt_calls(materialize)=2")


@pytest.mark.parametrize("method", ("lax", "materialize"))
def test_same_split_boundary_bit_identical(method, rng):
    """Where the boundary slabs replay the per-stage program, the split
    output is BIT-identical to the unfused chain; the composed interior
    is allclose (one fused sum reassociates the float adds)."""
    x = _vol(rng, (16, 17))
    P = pipe(x).gaussian(1.5, op_shape=5).gradient()
    out = np.asarray(P.run(method=method, pad_value="edge"))
    ref = np.asarray(_eager_same(x, method, "edge"))
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-6)
    boundary = np.ones((16, 17), bool)
    boundary[3:13, 3:14] = False           # interior box [B, n−C)
    np.testing.assert_array_equal(out[boundary], ref[boundary])


def test_same_split_melt_accounting(rng):
    x = _vol(rng, (16, 17))
    P = pipe(x).gaussian(1.5, op_shape=5).gradient()
    prog = P.plan(method="materialize", pad_value="edge")
    assert prog.passes == 1
    assert prog.melt_calls == 1 + 4 * 2    # dense 7×7 interior + 4 slabs
    clear_plan_cache()
    before = melt_call_count()
    jax.block_until_ready(P.run(method="materialize", pad_value="edge"))
    assert melt_call_count() - before == prog.melt_calls


def test_same_split_fused_method_matches_lax(rng):
    x = _vol(rng, (8, 9, 7))
    P = pipe(x).gaussian(1.2, op_shape=3).gradient()
    out_f = P.run(method="fused", pad_value="edge")
    out_l = P.run(method="lax", pad_value="edge")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_l),
                               rtol=3e-5, atol=3e-6)


def test_same_split_batched(rng):
    xb = _vol(rng, (3, 12, 11))
    out = (pipe.batched(xb).gaussian(1.2, op_shape=3).gradient()
           .run(method="lax", pad_value="edge"))
    refs = [np.asarray(_eager_chain_one(xb[i])) for i in range(3)]
    np.testing.assert_allclose(np.asarray(out), np.stack(refs),
                               rtol=3e-5, atol=3e-6)


def _eager_chain_one(x):
    y = gaussian_filter(x, 3, 1.2, method="lax", pad_value="edge")
    return gradient(y, method="lax", pad_value="edge")


def test_same_split_grad_is_finite(rng):
    x = _vol(rng, (9, 8))

    def loss(t):
        return jnp.sum(pipe(t).gaussian(1.0, op_shape=3).gradient()
                       .run(method="lax", pad_value="edge") ** 2)

    g = jax.grad(loss)(x)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_same_split_declines_when_no_interior():
    """All-boundary volumes fall back to the per-stage program."""
    x = jnp.zeros((4, 4), jnp.float32)
    prog = (pipe(x).gaussian(1.5, op_shape=5).gradient()
            .plan(method="lax", pad_value="edge"))
    assert prog.passes == 2
    assert not any(isinstance(s, SplitStep) for s in prog.steps)


def test_split_graph_streams_tiled_consistently(rng):
    """The tiled front end plans per stage (split_same=False) and must
    agree with the in-memory split plan numerically."""
    x = _vol(rng, (18, 16))
    P = pipe(x).gaussian(1.5, op_shape=5).gradient()
    ref = np.asarray(P.run(method="lax", pad_value="edge"))
    out = np.asarray(P.run(method="lax", pad_value="edge", tiles=(3, 2)))
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-6)
