"""Attention paths: chunked==dense, banded==masked, MLA absorption, ring cache."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import banded_attention, chunked_attention
from repro.kernels.ref import local_attention_ref


def _qkv(key, B, S, H, dh, KV=None):
    KV = KV or H
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32) * 0.4
    k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32) * 0.4
    v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
    return q, k, v


def _dense_ref(q, k, v, causal=True, window=None):
    B, S, H, dh = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, 2)
        v = jnp.repeat(v, H // KV, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    qi, kj = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    m = jnp.ones((S, S), bool)
    if causal:
        m = m & (qi >= kj)
    if window:
        m = m & (qi - kj < window)
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("kv_chunk", [16, 64, 1000])
@pytest.mark.parametrize("KV", [4, 2, 1])
def test_chunked_equals_dense(kv_chunk, KV):
    B, S, H, dh = 2, 96, 4, 16
    q, k, v = _qkv(0, B, S, H, dh, KV)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    got = chunked_attention(q, k, v, pos, pos, causal=True, window=None,
                            kv_chunk=kv_chunk)
    want = _dense_ref(q, k, v)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_chunked_bidirectional():
    B, S, H, dh = 1, 80, 2, 8
    q, k, v = _qkv(1, B, S, H, dh)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    got = chunked_attention(q, k, v, pos, pos, causal=False, window=None,
                            kv_chunk=32)
    want = _dense_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("W", [16, 32])
@pytest.mark.parametrize("KV", [4, 2])
def test_banded_equals_masked_dense(W, KV):
    B, S, H, dh = 2, 128, 4, 16
    q, k, v = _qkv(2, B, S, H, dh, KV)
    got = banded_attention(q, k, v, window=W)
    want = _dense_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_banded_unaligned_length():
    B, S, H, dh, W = 1, 100, 2, 8, 32  # S % W != 0 → internal padding
    q, k, v = _qkv(3, B, S, H, dh)
    got = banded_attention(q, k, v, window=W)
    want = _dense_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_banded_equals_chunked_window():
    B, S, H, dh, W = 1, 128, 2, 16, 32
    q, k, v = _qkv(4, B, S, H, dh)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    a = banded_attention(q, k, v, window=W)
    b = chunked_attention(q, k, v, pos, pos, causal=True, window=W,
                          kv_chunk=10_000)
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


def test_windowed_ring_cache_decode():
    """Decode with a W-entry ring buffer == full attention with window mask."""
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config("hymba_1p5b")  # window 16 in group 1
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 40
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    ref, _ = model.prefill(params, {"tokens": toks})
    # prefill S-8, then decode 8 tokens; last logits must match full prefill
    _, caches = model.prefill(params, {"tokens": toks[:, :S - 8]},
                              max_len=S + 2)
    logits = None
    for i in range(8):
        pos = jnp.full((B,), S - 8 + i, jnp.int32)
        logits, caches = model.decode_step(params, toks[:, S - 8 + i], pos,
                                           caches)
    err = float(jnp.max(jnp.abs(logits.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref)))
    assert err < 0.05 * max(scale, 1.0) + 1e-3, (err, scale)


def test_mla_absorbed_decode_matches_prefill():
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config("deepseek_v2_236b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, cfg.vocab)
    ref, _ = model.prefill(params, {"tokens": toks})
    _, caches = model.prefill(params, {"tokens": toks[:, :S - 1]}, max_len=S)
    pos = jnp.full((B,), S - 1, jnp.int32)
    got, _ = model.decode_step(params, toks[:, S - 1], pos, caches)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < 0.05 * float(jnp.max(jnp.abs(ref))) + 1e-3
