"""Per-architecture smoke tests (assignment requirement): reduced configs,
one forward/train step on CPU, shape + finiteness asserts; plus
decode-vs-prefill consistency for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, list_archs
from repro.models import build_model

ARCHS = list_archs()


def _batch_for(cfg, B, S, key=1):
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(key + 1), (B, S), 0, cfg.vocab),
    }
    if cfg.n_vis_tokens:
        batch["vis_embed"] = jax.random.normal(
            jax.random.PRNGKey(7), (B, cfg.n_vis_tokens, cfg.d_model),
            jnp.bfloat16) * 0.02
    if cfg.n_enc_layers:
        batch["enc_embed"] = jax.random.normal(
            jax.random.PRNGKey(8), (B, 16, cfg.d_model), jnp.bfloat16) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_loss(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, 2, 32)
    loss, metrics = model.loss_fn(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch
    assert float(loss) < 2 * np.log(cfg.vocab) + 2


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_updates(arch):
    from repro.optim import adamw

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    batch = _batch_for(cfg, 2, 32)

    @jax.jit
    def step(p, o, b):
        (l, m), g = jax.value_and_grad(
            lambda pp: model.loss_fn(pp, b), has_aux=True)(p)
        p2, o2 = adamw.update(g, o, p, lr=1e-3)
        return p2, o2, l

    p2, o2, l = step(params, opt, batch)
    assert np.isfinite(float(l))
    # params actually moved and stayed finite
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), params, p2)
    assert max(jax.tree.leaves(moved)) > 0
    finite = jax.tree.map(lambda a: bool(jnp.isfinite(a).all()), p2)
    assert all(jax.tree.leaves(finite)), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch_for(cfg, B, S)
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "targets")}
    toks = batch["tokens"]
    ref, _ = model.prefill(params, {"tokens": toks, **extras})
    _, caches = model.prefill(params, {"tokens": toks[:, :S - 1], **extras},
                              max_len=S + 4)
    enc_out = model._encode(params, extras["enc_embed"]) if cfg.n_enc_layers else None
    pos = jnp.full((B,), cfg.n_vis_tokens + S - 1, jnp.int32)
    got, _ = model.decode_step(params, toks[:, S - 1], pos, caches,
                               enc_out=enc_out)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    assert err < 0.05 * max(scale, 1.0) + 1e-3, (arch, err, scale)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    spec = {
        "mamba2_370m": dict(n_layers=48, d_model=1024, d_ff=0, vocab=50280,
                            ssm_state=128),
        "grok1_314b": dict(n_layers=64, d_model=6144, n_heads=48, n_kv=8,
                           d_ff=32768, vocab=131072, n_experts=8, top_k=2),
        "deepseek_v2_236b": dict(n_layers=60, d_model=5120, n_heads=128,
                                 vocab=102400, n_experts=160, top_k=6,
                                 kv_lora=512, expert_ff=1536),
        "internvl2_2b": dict(n_layers=24, d_model=2048, n_heads=16, n_kv=8,
                             d_ff=8192, vocab=92553),
        "minitron_4b": dict(n_layers=32, d_model=3072, n_heads=24, n_kv=8,
                            d_ff=9216, vocab=256000),
        "minicpm3_4b": dict(n_layers=62, d_model=2560, n_heads=40, n_kv=40,
                            d_ff=6400, vocab=73448, use_mla=True),
        "deepseek_coder_33b": dict(n_layers=62, d_model=7168, n_heads=56,
                                   n_kv=8, d_ff=19200, vocab=32256),
        "phi4_mini_3p8b": dict(n_layers=32, d_model=3072, n_heads=24, n_kv=8,
                               d_ff=8192, vocab=200064),
        "whisper_small": dict(n_layers=12, n_enc_layers=12, d_model=768,
                              n_heads=12, n_kv=12, d_ff=3072, vocab=51865),
        "hymba_1p5b": dict(n_layers=32, d_model=1600, n_heads=25, n_kv=5,
                           d_ff=5504, vocab=32001, ssm_state=16),
    }[arch]
    cfg = get_config(arch)
    for k, v in spec.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_count_sanity():
    """Total-parameter estimates land in the advertised ballparks."""
    approx = {
        "mamba2_370m": (0.30e9, 0.50e9),
        "grok1_314b": (280e9, 340e9),
        "deepseek_v2_236b": (200e9, 260e9),
        "internvl2_2b": (1.5e9, 2.6e9),
        "minitron_4b": (3.5e9, 5.3e9),
        "minicpm3_4b": (3.0e9, 5.0e9),
        "deepseek_coder_33b": (30e9, 36e9),
        "phi4_mini_3p8b": (3.2e9, 5.0e9),
        "whisper_small": (0.2e9, 0.35e9),
        "hymba_1p5b": (1.2e9, 2.0e9),
    }
    for arch, (lo, hi) in approx.items():
        n = get_config(arch).total_params()
        assert lo <= n <= hi, (arch, n / 1e9)
