"""Pallas kernels vs ref.py oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from repro.core.filters import bilateral_filter, gaussian_weights
from repro.core.grid import make_quasi_grid
from repro.kernels import ops
from repro.kernels import ref as kref


@pytest.mark.parametrize("shape", [(64,), (17, 23), (9, 12, 11), (5, 6, 4, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_stencil_matches_melt(shape, dtype):
    rng = np.random.RandomState(len(shape))
    x = jnp.asarray(rng.randn(*shape), dtype)
    op = (3,) * len(shape)
    w = gaussian_weights(op, 1.0)
    grid = make_quasi_grid(shape, op, 1, "same", 1)
    got = ops.fused_stencil(x, grid, w)
    want = kref.stencil_ref(x.astype(jnp.float32), op, w).astype(dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("op", [3, 5])
def test_fused_stencil_op_sizes(op):
    rng = np.random.RandomState(op)
    x = jnp.asarray(rng.randn(20, 20), jnp.float32)
    w = gaussian_weights((op, op), 1.3)
    grid = make_quasi_grid(x.shape, (op, op), 1, "same", 1)
    got = ops.fused_stencil(x, grid, w)
    want = kref.stencil_ref(x, (op, op), w)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(8, 40), m=st.integers(8, 40))
def test_fused_stencil_property_sweep(n, m):
    rng = np.random.RandomState(n * 41 + m)
    x = jnp.asarray(rng.randn(n, m), jnp.float32)
    w = jnp.asarray(rng.randn(9), jnp.float32)  # arbitrary operator
    grid = make_quasi_grid((n, m), (3, 3), 1, "same", 1)
    got = ops.fused_stencil(x, grid, w)
    want = kref.stencil_ref(x, (3, 3), w)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("sigma_r", [0.5, "adaptive"])
@pytest.mark.parametrize("shape", [(24, 18), (10, 9, 8)])
def test_bilateral_kernel_matches_core(shape, sigma_r):
    rng = np.random.RandomState(42)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    got = ops.fused_bilateral(x, 3, 1.5, sigma_r)
    want = bilateral_filter(x, 3, 1.5, sigma_r)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window,tile", [(128, 128), (256, 128), (128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_local_attention_matches_dense(window, tile, dtype):
    rng = np.random.RandomState(window + tile)
    B, S, H, dh = 2, 512, 4, 64
    q = jnp.asarray(rng.randn(B, S, H, dh) * 0.3, dtype)
    k = jnp.asarray(rng.randn(B, S, H, dh) * 0.3, dtype)
    v = jnp.asarray(rng.randn(B, S, H, dh), dtype)
    got = ops.sliding_window_attention(q, k, v, window=window, tile=tile)
    want = kref.local_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        window=window)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_local_attention_matches_banded_model_path():
    """Kernel ≡ the model's banded attention (same melt-over-sequence)."""
    from repro.models.attention import banded_attention

    rng = np.random.RandomState(3)
    B, S, H, dh = 1, 256, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, dh) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, dh) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, dh), jnp.float32)
    got = ops.sliding_window_attention(q, k, v, window=64, tile=64)
    want = banded_attention(q, k, v, window=64)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("K", [2, 4])
@pytest.mark.parametrize("C", [8, 64])
def test_depthwise_conv_sweep(K, C):
    rng = np.random.RandomState(K * C)
    x = jnp.asarray(rng.randn(3, 33, C), jnp.float32)
    w = jnp.asarray(rng.randn(K, C), jnp.float32)
    got = ops.depthwise_conv1d(x, w)
    want = kref.depthwise_conv1d_ref(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_depthwise_matches_model_layer():
    from repro.models.layers import causal_depthwise_conv1d

    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 16, 12), jnp.float32)
    w = jnp.asarray(rng.randn(4, 12), jnp.float32)
    got = ops.depthwise_conv1d(x, w)
    want, _ = causal_depthwise_conv1d(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- zero-weight skipping (melt_stencil.weight_support) ----------------------

from repro.core import engine  # noqa: E402
from repro.core.filters import curvature_bank, difference_stencils  # noqa: E402
from repro.kernels import melt_stencil as ms  # noqa: E402
from repro.obs.metrics import counter  # noqa: E402


def _dyadic(rng, shape):
    """Random signed powers of two.  The CPU backend fuses a multiply and
    an add into one FMA where it likes, differently from one program to
    the next; with exact products the fused and unfused sums agree, so a
    bitwise comparison sees only which terms are summed and in what
    order.  The derivative banks' weights are powers of two already."""
    return (np.where(rng.rand(*shape) < 0.5, -1.0, 1.0)
            * 2.0 ** rng.randint(-3, 4, shape)).astype(np.float32)


def _sparse_bank(rng, K=5):
    """A random bank with one zero row and scattered zero entries."""
    W = _dyadic(rng, (27, K))
    W[4] = 0.0
    W[rng.rand(27, K) < 0.4] = 0.0
    return W


def _zero_column(rng):
    W = _dyadic(rng, (27, 4))
    W[:, 2] = 0.0
    return W


SPARSE_BANKS = {
    "curvature2": lambda rng: curvature_bank(2),
    "curvature3": lambda rng: curvature_bank(3),
    "gradient3": lambda rng: difference_stencils(3)[0].astype(np.float32),
    "sparse": _sparse_bank,
    "zero-column": _zero_column,
    "all-zero": lambda rng: np.zeros((27, 3), np.float32),
}


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("batched", [False, True], ids=["one", "batched"])
@pytest.mark.parametrize("padding", ["same", "valid"])
@pytest.mark.parametrize("bank", list(SPARSE_BANKS))
def test_support_is_bitwise_dense(bank, padding, batched):
    """Skipping the zero products leaves the bank's output bitwise equal
    to the dense kernel's on finite input, in every column."""
    rng = np.random.RandomState(19)
    W = SPARSE_BANKS[bank](rng)
    rank = round(np.log(W.shape[0]) / np.log(3))
    spatial = (6, 9, 20)[-rank:]
    x = jnp.asarray(rng.randn(*(((2,) if batched else ()) + spatial)),
                    jnp.float32)
    grid = make_quasi_grid(spatial, (3,) * rank, 1, padding, 1)
    support = ms.weight_support(W)
    assert support is not None
    kw = dict(pad_value="edge" if padding == "same" else 0.0,
              batched=batched)
    dense = ops.fused_stencil_bank(x, grid, W, **kw)
    got = ops.fused_stencil_bank(x, grid, W, support=support, **kw)
    np.testing.assert_array_equal(_bits(got), _bits(dense))
    # the stencil family: one column, its zero taps skipped
    w = W[:, 0]
    dense = ops.fused_stencil(x, grid, w, **kw)
    got = ops.fused_stencil(x, grid, w, support=ms.weight_support(w[:, None]),
                            **kw)
    np.testing.assert_array_equal(_bits(got), _bits(dense))


@pytest.mark.parametrize("batch", [1, 2])
def test_depthwise_support_is_the_union_over_channels(batch):
    """With C > 1 channels a product is kept where any channel's weight
    is nonzero; the kernel then matches the dense one bitwise."""
    rng = np.random.RandomState(23)
    C, shape = 3, (6, 9, 20)
    W = _dyadic(rng, (27, C)) * (rng.rand(27, C) < 0.3)
    support = ms.weight_support(W, channels=C)
    union = np.flatnonzero((W != 0).any(axis=1))
    assert [t for t, _ in support] == list(union)
    assert all(ks == (0,) for _, ks in support)
    offsets = make_quasi_grid(shape, (3, 3, 3), 1, "same", 1).flat_offsets()
    x = jnp.asarray(rng.randn(batch, C, 8 * ms.LANES), jnp.float32)
    dense = ms.fused_melt_rows(x, W, offsets)
    got = ms.fused_melt_rows(x, W, offsets, support=support)
    np.testing.assert_array_equal(_bits(got), _bits(dense))


def test_dense_weights_have_no_support():
    """A bank with no zero product keeps today's kernel: no support."""
    assert ms.weight_support(
        np.asarray(gaussian_weights((3, 3, 3), 1.0)).reshape(27, 1)) is None
    assert ms.weight_support(np.ones((9, 4), np.float32)) is None


@pytest.mark.parametrize("bank,skipped", [
    ("curvature3", 285), ("gradient3", 75), ("gaussian", 0)])
def test_zero_weight_products_counter(bank, skipped):
    """``kernels/zero_weight_products`` counts the products a build
    skips: dense products less kept ones."""
    rng = np.random.RandomState(5)
    W = (np.asarray(gaussian_weights((3, 3, 3), 1.0)).reshape(27, 1)
         if bank == "gaussian" else SPARSE_BANKS[bank](rng))
    x = jnp.asarray(rng.randn(6, 9, 20), jnp.float32)
    grid = make_quasi_grid(x.shape, (3, 3, 3), 1, "same", 1)
    c = counter("kernels/zero_weight_products")
    before = c.value
    got = engine.execute_stencil_bank(x, grid, W, "edge", "fused")
    assert c.value - before == skipped
    want = engine.execute_stencil_bank(x, grid, W, "edge", "lax")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_traced_weights_run_dense():
    """Weights passed as a jit argument are traced: their zeros are not
    static, so the kernel runs every product and still agrees with lax."""
    rng = np.random.RandomState(6)
    W = curvature_bank(3)
    x = jnp.asarray(rng.randn(6, 9, 20), jnp.float32)
    grid = make_quasi_grid(x.shape, (3, 3, 3), 1, "same", 1)
    c = counter("kernels/zero_weight_products")
    before = c.value
    got = jax.jit(lambda w: engine.execute_stencil_bank(
        x, grid, w, "edge", "fused"))(jnp.asarray(W))
    assert c.value == before
    want = engine.execute_stencil_bank(x, grid, W, "edge", "lax")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
