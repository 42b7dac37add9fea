"""MoE dispatch semantics: capacity, renormalized gates, no-drop exactness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import moe as moe_mod


def _cfg(**kw):
    base = get_smoke_config("grok1_314b")
    return dataclasses.replace(base, **kw)


def _params_and_x(cfg, B=2, S=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    from repro.models.layers import split_tree

    params, _ = split_tree(moe_mod.moe_params(cfg, ks[0]))
    x = jax.random.normal(ks[1], (B, S, cfg.d_model), jnp.float32) * 0.5
    return params, x


def moe_dense_ref(cfg, p, x):
    """No-capacity reference: every token exactly its top-k experts."""
    logits = x @ p["router"]
    gates = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(gates, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    y_all = jnp.einsum("bsef,efd->bsed", h, p["w_down"])  # (B,S,E,D)
    sel = jax.nn.one_hot(top_i, cfg.n_experts)  # (B,S,K,E)
    w = jnp.einsum("bske,bsk->bse", sel, top_w)
    out = jnp.einsum("bse,bsed->bsd", w, y_all)
    if cfg.n_shared_experts:
        sp = p["shared"]
        out = out + jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"]), sp["w_down"])
    return out


def test_ample_capacity_matches_dense_reference():
    """capacity_factor large enough that nothing drops ⇒ exact equality."""
    cfg = _cfg(capacity_factor=8.0)  # ample
    params, x = _params_and_x(cfg)
    got, aux = moe_mod.moe_apply(cfg, params, x, group_size=16)
    want = moe_dense_ref(cfg, params, x)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    assert np.isfinite(float(aux))


def test_group_size_invariance_with_ample_capacity():
    cfg = _cfg(capacity_factor=8.0)
    params, x = _params_and_x(cfg, B=2, S=32)
    a, _ = moe_mod.moe_apply(cfg, params, x, group_size=16)
    b, _ = moe_mod.moe_apply(cfg, params, x, group_size=64)
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_tight_capacity_drops_but_stays_finite():
    cfg = _cfg(capacity_factor=0.25)
    params, x = _params_and_x(cfg, B=2, S=64)
    got, aux = moe_mod.moe_apply(cfg, params, x, group_size=64)
    assert bool(jnp.isfinite(got).all())
    # dropped tokens get ≤ top_k experts; output norm shrinks vs ample
    ample, _ = moe_mod.moe_apply(
        dataclasses.replace(cfg, capacity_factor=8.0), params, x,
        group_size=64)
    assert float(jnp.linalg.norm(got)) <= float(jnp.linalg.norm(ample)) + 1e-3


def test_capacity_bound_respected():
    """No expert ever receives more than C tokens per group."""
    cfg = _cfg(capacity_factor=1.0)
    params, x = _params_and_x(cfg, B=4, S=32, key=3)
    # instrument: recompute dispatch the same way and check per-expert loads
    g = 32
    C = moe_mod._capacity(cfg, g)
    xt = x.reshape(-1, g, cfg.d_model)
    logits = jnp.einsum("gtd,de->gte", xt, params["router"])
    gates = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(gates, cfg.top_k)
    counts = np.zeros((xt.shape[0], cfg.n_experts), np.int64)
    ti = np.asarray(top_i)
    for gi in range(xt.shape[0]):
        for t in range(g):
            for k in range(cfg.top_k):
                e = ti[gi, t, k]
                counts[gi, e] += 1
    # the dispatch keeps min(count, C):
    kept = np.minimum(counts, C)
    assert (kept <= C).all()


def test_aux_loss_orders_balance():
    """Uniform routing yields lower aux loss than collapsed routing."""
    cfg = _cfg(capacity_factor=2.0)
    params, x = _params_and_x(cfg, B=2, S=64, key=4)
    # collapse: bias router to expert 0
    biased = dict(params)
    biased["router"] = params["router"].at[:, 0].add(10.0)
    _, aux_uniform = moe_mod.moe_apply(cfg, params, x, group_size=64)
    _, aux_collapsed = moe_mod.moe_apply(cfg, biased, x, group_size=64)
    assert float(aux_collapsed) > float(aux_uniform)


def test_shared_experts_always_active():
    cfg = get_smoke_config("deepseek_v2_236b")
    assert cfg.n_shared_experts >= 1
    params, x = _params_and_x(cfg)
    got, _ = moe_mod.moe_apply(cfg, params, x, group_size=16)
    # zeroing shared experts changes the output for every token
    z = dict(params)
    z["shared"] = jax.tree.map(jnp.zeros_like, params["shared"])
    got0, _ = moe_mod.moe_apply(cfg, z, x, group_size=16)
    diff = jnp.abs(got - got0).max(axis=-1)
    assert float(diff.min()) > 0
